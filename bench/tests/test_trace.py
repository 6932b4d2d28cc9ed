"""The trace reduction on small hand-made and recorded traces."""
from __future__ import annotations

from pathlib import Path

import pytest

from bench import trace as T

MS = 1_000_000


def test_busy_union_idle_and_kernels():
    lines = {0: [("_fixed_point_kernel", 0, 4 * MS),
                 ("fusion.1", 2 * MS, 4 * MS),          # overlaps: union
                 ("_fixed_point_kernel", 10 * MS, 2 * MS),
                 ("fusion.2", 30 * MS, 20 * MS)]}       # clipped at 40 ms
    spans = [("train/update", 0, 0, 40 * MS),
             ("train/memo_gather", 1, 6 * MS, 10 * MS)]
    s = T.reduce_lines(lines, (0, 40 * MS), spans)
    assert s.window_s == pytest.approx(0.040)
    assert s.busy_s == pytest.approx(0.018)             # 6 + 2 + 10 ms
    t, calls = s.kernel_s(["_fixed_point_kernel"])
    assert t == pytest.approx(0.006) and calls == 2
    assert s.ops["fusion.2"][0] == pytest.approx(0.010)
    # gaps: 6-10 ms under memo_gather, 12-30 ms under train/update only
    assert s.idle_by_span["train/memo_gather"] == pytest.approx(0.004)
    assert s.idle_by_span["train/update"] == pytest.approx(0.018)
    assert s.collective_s == 0.0
    bd = s.breakdown()
    assert bd["device_ops"][0][0] == "fusion.2"
    assert bd["idle_gaps"][0] == ["train/update", pytest.approx(0.018)]


def test_collectives_exposed_part_and_chip_mean():
    lines = {0: [("all-reduce.3", 0, 10 * MS), ("fusion.1", 0, 4 * MS)],
             1: [("all-reduce.3", 0, 10 * MS)]}
    s = T.reduce_lines(lines, (0, 20 * MS))
    assert s.chips == 2
    assert s.collective_s == pytest.approx(0.010)
    # chip 0: 6 ms exposed, chip 1: 10 ms → mean 8 ms
    assert s.collective_exposed_s == pytest.approx(0.008)
    assert s.busy_s == pytest.approx(0.010)
    assert s.idle_by_span[T.NO_SPAN] == pytest.approx(0.010)


RECORDED = Path(__file__).parent / "data" / "probe.xplane.pb"


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded chip trace")
def test_recorded_chip_trace():
    """A v5e trace of three flat E-steps (bench/tools/probe_trace.py)."""
    s = T.reduce(str(RECORDED))
    assert s.chips == 1 and 0 < s.busy_s <= s.window_s
    t, calls = s.kernel_s(["_csr_fixed_point_kernel"])
    assert calls == 3 and t > 0
    for k in ("_csr_token_pi_kernel", "_segment_scatter_kernel"):
        assert s.kernel_s([k])[1] == 3
