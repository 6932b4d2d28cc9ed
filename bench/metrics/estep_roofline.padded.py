"""Kernels (kernels/lda_estep.py), padded layout: the least time the
window's fixed-point, token-π and scatter kernels need (bench/counts) over
their time in the profiler trace, in %."""
from bench.metrics._common import kernel_roofline

KERNELS = ("_fixed_point_kernel", "_token_pi_kernel",
           "_segment_scatter_kernel")


def read(layer):
    return kernel_roofline(layer, KERNELS, "padded",
                           "estep_roofline.padded")
