"""What a runner hands back, and what the per-layer readers read.

A runner (``bench/traffic/<runner>.py``) gets a ``Context`` and returns an
``Outcome``. ``Outcome.layer`` is the ``LayerData`` that every reader in
``bench/metrics/`` receives: the program's spans from the traced window, the reduced profiler trace, the shapes and sweep counts of
each timed step (for the work counts in ``bench/counts/``) and the chip's
peaks. A reader returns ``None`` where it finds nothing to read, and the
harness then leaves its metric out of the line.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict, List, Optional, Tuple

BENCH = Path(__file__).resolve().parent


@dataclasses.dataclass
class Context:
    cell: dict
    cfg: dict
    mix: dict
    seed: int
    seconds: float
    trace: bool
    variant: Optional[str]
    t_start: float


@dataclasses.dataclass
class LayerData:
    spans: List[dict] = dataclasses.field(default_factory=list)
    steps: List[dict] = dataclasses.field(default_factory=list)
    shape: Dict[str, object] = dataclasses.field(default_factory=dict)
    requests: List[dict] = dataclasses.field(default_factory=list)
    trace: Optional[object] = None        # bench.trace.TraceSummary
    window_s: float = 0.0
    chips: int = 1
    peaks: Optional[dict] = None

    def span_durations(self, name: str) -> List[float]:
        """Durations in seconds of the program's spans called ``name``."""
        return [r["dur_us"] * 1e-6 for r in self.spans
                if r.get("type") == "span" and r["name"] == name]


@dataclasses.dataclass
class Outcome:
    e2e: Dict[str, float]
    attempted: int
    failed: int
    checks: List[Tuple[str, float, float]]
    memory_peak_bytes: int
    layer: LayerData


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip, as the backend reports it."""
    import jax
    return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.devices()))


def peaks_for(device_kind: str) -> dict:
    """The peak table's entry for this device; an unknown device is an
    error, never a default."""
    with open(BENCH / "peaks.json") as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (have {sorted(table)})")
    return table[device_kind]
