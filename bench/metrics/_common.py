"""Helpers shared by the per-layer readers in this directory."""
from __future__ import annotations

import sys
from typing import Iterable, Optional

from bench.counts import lda as counts


def note(msg: str) -> None:
    """A reader's remark for the run's standard error."""
    print(msg, file=sys.stderr)


def idle_pct(layer) -> Optional[float]:
    tr = layer.trace
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)


def per_step_ms(layer, names: Iterable[str]) -> Optional[float]:
    if not layer.steps:
        return None
    total = sum(sum(layer.span_durations(n)) for n in names)
    if total == 0.0:
        return None
    return 1e3 * total / len(layer.steps)


def kernel_roofline(layer, kernels, layout: str, name: str):
    """Needed time of the window's E-step kernels over their trace time."""
    if layer.trace is None or layer.peaks is None or not layer.steps \
            or layer.shape.get("layout") != layout:
        return None
    t, calls = layer.trace.kernel_s(kernels)
    if t <= 0.0:
        return None
    need = {"flops": 0.0, "bytes": 0.0}
    total = 0.0
    for step in layer.steps:
        f, b = counts.estep_kernels(step, layer.shape)
        s, bound = counts.roofline_s(f, b, layer.peaks)
        total += s
        need[bound] += s
    binds = max(need, key=need.get)
    note(f"{name}: kernels {t!r} s over {calls} calls, needed {total!r} s "
         f"({binds} bound binds)")
    return 100.0 * total / t
