"""Pallas TPU kernels for the paper's compute hotspot (the LDA E-step).

Layout per repo convention: ``lda_estep.py`` holds the ``pl.pallas_call``
kernels with explicit BlockSpec VMEM tiling, ``ops.py`` the jitted wrappers
and ``ref.py`` the flash-attention oracle (the E-step references are
``repro.core.estep.estep_gather`` and ``estep_csr_ref``).
"""
from repro.kernels import flash_attention, lda_estep, ops, ref
