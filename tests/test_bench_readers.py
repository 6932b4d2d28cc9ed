"""The benchmark's readers of the serving loop's and the host runtime's
spans (``bench/metrics``), on hand-built window records."""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.layer import LayerData  # noqa: E402


def reader(name: str):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def span(name, dur_us, depth=0, **attrs):
    return {"type": "span", "name": name, "ts_us": 0.0, "dur_us": dur_us,
            "tid": 0, "depth": depth, "attrs": attrs}


SPANS = [
    span("serve/admit", 10.0, lag_ms=2.0),
    span("serve/admit", 30.0, lag_ms=4.0),
    span("serve/admit", 20.0, lag_ms=100.0, shed=True),
    span("serve/request_batch", 2000.0, docs=2),
    span("serve/respond", 500.0, docs=2),
    span("serve/respond", 700.0, docs=1),
    span("py/gc", 1000.0, depth=1, generation=0, collected=3),
    span("py/gc", 3000.0, generation=2, collected=40),
    {"type": "event", "name": "serve/admit", "ts_us": 0.0, "tid": 0,
     "attrs": {}},
]
SERVE = {"requests": [{"arrival_s": 0.0, "done_s": 0.01, "batch": 0}]}
TRAIN = {"steps": [{"rows": 256}]}
# metric -> (the cell's kind, the expected reading)
EXPECTED = {
    "serve_admit_ms": (SERVE, 0.02),
    "serve_respond_ms": (SERVE, 0.6),
    "serve_intake_ms": (SERVE, 4.0),
    "gc_pause_ms.serve": (SERVE, 4.0),
    "gc_pause_ms.train": (TRAIN, 4.0),
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_reads_its_spans_and_nothing_else(name):
    kind, want = EXPECTED[name]
    read = reader(name)
    assert read(LayerData(spans=SPANS, **kind)) == pytest.approx(want)
    # a program without these spans (the parent's), or no spans at all
    others = [r for r in SPANS if r["name"] == "serve/request_batch"]
    assert read(LayerData(spans=others, **kind)) is None
    assert read(LayerData(**kind)) is None
    # the other kind of cell reads nothing for a cell-specific metric
    if name.startswith("gc_pause_ms"):
        other = TRAIN if kind is SERVE else SERVE
        assert read(LayerData(spans=SPANS, **other)) is None
