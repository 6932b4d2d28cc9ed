"""Loading by name, the generators' determinism and BENCHMARK.json's form."""
from __future__ import annotations

import json
import re
import statistics

import numpy as np
import pytest

from bench.run import (BENCH, ROOT, cell_metrics, runner_for, load_cell,
                       load_module)
from bench.traffic import arrivals, corpus

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("workload", CELLS)
def test_cell_loads_by_name(workload):
    bench, cell, cfg, mix = load_cell(workload)
    assert cfg["name"] == cell["config"]
    assert hasattr(runner_for(mix), "run")
    e2e = {m["name"] for m in cell_metrics(bench, cell, "end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = cell_metrics(bench, cell, "per_layer")
    assert layer
    for m in layer:
        assert hasattr(load_module(BENCH / "metrics" / f"{m['name']}.py",
                                   "m"), "read")
        assert m["moves"] in e2e


def test_benchmark_json_form():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    names = set()
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert set(c["reduced"]) <= set(cfg["reduced"])
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(
        1, len(SPEC["workloads"]) // 2)
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    for entry in (SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"]
                  + SPEC["per_layer"]):
        assert NAME.match(entry["name"]), entry["name"]
        assert entry["name"] not in names
        names.add(entry["name"])
        for key in ("why", "layer", "source"):
            if key in entry:
                assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key]
        if "unit" in entry:
            assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", entry["unit"])
            assert entry["better"] in ("lower", "higher")
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024


def test_corpus_is_seeded_with_the_same_work_for_every_seed():
    cfg = {"vocab_size": 500, "gen_topics": 6, "gen_alpha": 0.1,
           "gen_beta": 0.05, "mean_doc_len": 60, "length_dist": "lognormal",
           "length_sigma": 1.0, "max_unique": 64}
    a = corpus.make_corpus(cfg, corpus.topics(cfg, 2**31 + 5), n_docs=80,
                           seed=2**31 + 5)
    b = corpus.make_corpus(cfg, corpus.topics(cfg, 2**31 + 5), n_docs=80,
                           seed=2**31 + 5)
    c = corpus.make_corpus(cfg, corpus.topics(cfg, 6), n_docs=80, seed=6)
    assert np.array_equal(a.token_ids, b.token_ids)
    assert np.array_equal(a.counts, b.counts)
    assert not np.array_equal(a.token_ids, c.token_ids)
    assert a.token_ids.shape == c.token_ids.shape == (80, 64)
    assert (a.counts > 0).sum(1).max() <= 64
    # the same multiset of lengths (before clipping to the width)
    q = corpus.length_quantiles(80, 60, "lognormal", 1.0)
    assert statistics.mean(q) == pytest.approx(60, rel=0.1)
    # ids ascending within a document, counts as stored
    live = a.counts > 0
    for d in range(80):
        ids = a.token_ids[d, live[d]]
        assert np.all(np.diff(ids) > 0)
    assert np.allclose(a.doc_tokens, a.counts.sum(1))


def test_poisson_lengths_are_the_quantiles():
    q = corpus.length_quantiles(1000, 116, "poisson")
    assert abs(q.mean() - 116) < 1 and q.min() >= corpus.MIN_LEN


def test_arrivals_are_seeded():
    s1 = arrivals.schedule("poisson_stratified", 10.0, 50.0, seed=3)
    s2 = arrivals.schedule("poisson_stratified", 10.0, 50.0, seed=3)
    s3 = arrivals.schedule("poisson_stratified", 10.0, 50.0, seed=4)
    assert np.array_equal(s1, s2) and not np.array_equal(s1, s3)
    assert len(s1) == len(s3) == 500
    assert np.all(np.diff(s1) >= 0) and s1[-1] < 10.0
    p = arrivals.schedule("poisson", 10.0, 50.0, seed=3)
    assert np.array_equal(p, arrivals.schedule("poisson", 10.0, 50.0,
                                               seed=3))
    o = arrivals.schedule("onoff", 10.0, 50.0, seed=3, on_s=1.0, off_s=1.0)
    assert np.all(np.diff(o) >= 0) and o[-1] < 10.0
