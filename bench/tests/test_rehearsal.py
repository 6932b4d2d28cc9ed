"""The harness end to end on a CPU at a tiny size: the result line's schema,
a correct run, and the check failing on the control and on every fault."""
from __future__ import annotations

import math

import pytest

from bench.faults import FAULTS
from bench.run import run_cell
from bench.tests.tiny import TRAIN_SECONDS, tiny_cell

TRAIN = ("arxiv-ivi-train",)
FAULTS_OF = {"arxiv-ivi-train": ("state_unchanged", "half_batch",
                                 "token_altered", "memo_rows"),
             "arxiv-serve-burst": ("half_batch", "token_altered")}


def run(workload, variant=None, trace=False, seed=2**31 + 11):
    bench, cell, cfg, mix = tiny_cell(workload)
    seconds = TRAIN_SECONDS if workload in TRAIN else 1.0
    return run_cell(bench, cell, cfg, mix, seed=seed, seconds=seconds,
                    trace=trace, variant=variant, t_start=0.0)


def check_schema(res, trace):
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "check"
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"}
        assert math.isfinite(m["value"])
    dev = res["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    for c in res["check"].values():
        assert set(c) == {"value", "limit"}


@pytest.mark.parametrize("workload", TRAIN + ("arxiv-serve-burst",))
def test_sound_run_is_correct(workload):
    res = run(workload)
    check_schema(res, trace=False)
    assert res["correct"], res["check"]
    e2e = set(res["metrics"])
    assert "setup_s" in e2e and len(e2e) == 2


@pytest.mark.parametrize("workload", TRAIN + ("arxiv-serve-burst",))
def test_control_fails(workload):
    res = run(workload, variant="control")
    assert not res["correct"], res["check"]


@pytest.mark.parametrize("workload,fault",
                         [(w, f) for w, fs in FAULTS_OF.items() for f in fs])
def test_fault_fails(workload, fault):
    assert fault in FAULTS
    res = run(workload, variant=fault)
    assert not res["correct"], res["check"]
