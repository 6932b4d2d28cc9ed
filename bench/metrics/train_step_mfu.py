"""Whole training step: the work one chip needs for the window's steps
(bench/counts lda.train_step: the E-step over its live tokens and the
global update), as the larger of its shares of the chip's flops and
bandwidth peaks, over the window's length, in %."""
from bench.metrics._common import note
from bench.counts import lda as counts


def read(layer):
    if layer.peaks is None or not layer.steps or layer.window_s <= 0:
        return None
    f = b = 0.0
    for step in layer.steps:
        df, db = counts.train_step(step, layer.shape)
        f, b = f + df, b + db
    pf = f / layer.peaks["bf16_flops_per_s"] / layer.window_s
    pb = b / layer.peaks["hbm_bytes_per_s"] / layer.window_s
    note(f"train_step_mfu: flops share {100 * pf!r} %, bandwidth share "
         f"{100 * pb!r} % over {len(layer.steps)} steps")
    return 100.0 * max(pf, pb)
