"""Span aggregation and the v5e hardware table.

* ``spans_by_name`` aggregates a ``SpanRecorder``'s records per span name
  (count, total, min, mean seconds). For kernel timings the recorder
  should run with ``device_sync=True`` so a span measures compute, not
  dispatch.
* ``HW`` holds one v5e chip's peak flops and HBM and ICI bandwidths:
  ``repro.tune``'s cost model prices bytes against it and
  ``repro.launch.dryrun_lda`` reports roofline terms from it.

Speed is measured on the chip by the benchmark in ``bench/``, whose own
peak table is ``bench/peaks.json``.
"""
from __future__ import annotations

import math
from typing import Dict, Iterable

HW = {"peak_flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9}


def spans_by_name(records: Iterable[dict]) -> Dict[str, dict]:
    """Aggregate trace records per span name → measured-seconds summary.

    Accepts the raw record dicts of a ``SpanRecorder`` (or a loaded trace
    JSONL); non-span records are ignored. Durations convert from the
    trace's microseconds to seconds.
    """
    out: Dict[str, dict] = {}
    for r in records:
        if r.get("type") != "span":
            continue
        agg = out.setdefault(r["name"], {"count": 0, "total_s": 0.0,
                                         "min_s": math.inf})
        dur_s = r["dur_us"] / 1e6
        agg["count"] += 1
        agg["total_s"] += dur_s
        if dur_s < agg["min_s"]:
            agg["min_s"] = dur_s
    for agg in out.values():
        agg["mean_s"] = agg["total_s"] / agg["count"]
    return out
