"""repro.obs: tracing, metrics, watchdog, and the no-op-when-off contract."""
import dataclasses
import gc
import json
import os
import subprocess
import sys
import warnings
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import LDAConfig
from repro.core.engines import LDAEngine
from repro.core.metrics import _npmi_coherence_loop, npmi_coherence
from repro.data import PAPER_CORPORA, make_corpus
from repro.lda import LDA
from repro.obs import (NULL_TELEMETRY, BoundMonotonicityError, ElboWatchdog,
                       ElboMonotonicityWarning, MetricsRegistry, SpanRecorder,
                       Telemetry, as_telemetry, chrome_trace_from_jsonl,
                       load_jsonl, spans_by_name, validate_jsonl)


@pytest.fixture(scope="module")
def tiny_corpus():
    spec = PAPER_CORPORA["tiny"]
    return make_corpus(spec, split="train", seed=0), spec


# ---------------------------------------------------------------------------
# trace
# ---------------------------------------------------------------------------

def test_span_recorder_nesting_and_roundtrip(tmp_path):
    rec = SpanRecorder()
    with rec.span("outer", phase="a"):
        with rec.span("inner"):
            pass
        rec.event("marker", n=3)
    tok = rec.begin("manual")
    rec.end(tok)
    assert rec.num_records == 4
    by_name = {r["name"]: r for r in rec.records}
    assert by_name["inner"]["depth"] == 1
    assert by_name["outer"]["depth"] == 0
    assert by_name["outer"]["dur_us"] >= by_name["inner"]["dur_us"]
    assert by_name["marker"]["type"] == "event"

    jsonl = str(tmp_path / "t.jsonl")
    chrome = str(tmp_path / "t.chrome.json")
    assert rec.dump_jsonl(jsonl) == 4
    assert validate_jsonl(jsonl) == 4
    # Chrome conversion is count-exact: 1 record -> 1 traceEvent
    assert chrome_trace_from_jsonl(jsonl, chrome) == 4
    with open(chrome) as f:
        ct = json.load(f)
    assert len(ct["traceEvents"]) == 4
    assert {e["ph"] for e in ct["traceEvents"]} == {"X", "i"}


def test_validate_rejects_malformed(tmp_path):
    rec = SpanRecorder()
    rec.event("ok")
    jsonl = str(tmp_path / "bad.jsonl")
    rec.dump_jsonl(jsonl)
    meta, records = load_jsonl(jsonl)
    records[0].pop("ts_us")
    with open(jsonl, "w") as f:
        f.write(json.dumps(meta) + "\n")
        for r in records:
            f.write(json.dumps(r) + "\n")
    with pytest.raises(ValueError, match="missing 'ts_us'"):
        validate_jsonl(jsonl)


def test_spans_by_name_aggregates():
    rec = SpanRecorder()
    for _ in range(3):
        with rec.span("train/solve"):
            pass
    agg = spans_by_name(rec.records)
    assert agg["train/solve"]["count"] == 3
    assert agg["train/solve"]["min_s"] <= agg["train/solve"]["mean_s"]


def _run_child(code: str, *args: str) -> dict:
    """Run ``code`` in a fresh interpreter (no recorder of this process
    alive there) and return the JSON object it prints last."""
    src = str(Path(__file__).resolve().parents[1] / "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [src, os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run([sys.executable, "-c", code, *args], env=env,
                         capture_output=True, text=True, timeout=240)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_gc_collection_is_a_py_gc_span():
    """A collection under a live recorder is a ``py/gc`` span nested in
    the span it interrupted, with the collector's own numbers."""
    rec = SpanRecorder()
    with rec.span("outer"):
        gc.collect()
    outer = next(r for r in rec.records if r["name"] == "outer")
    gcs = [r for r in rec.records if r["name"] == "py/gc"]
    assert gcs, rec.records
    full = gcs[-1]
    assert full["attrs"]["generation"] == 2
    assert full["attrs"]["collected"] >= 0
    assert full["depth"] == outer["depth"] + 1
    assert outer["ts_us"] <= full["ts_us"]
    assert full["ts_us"] + full["dur_us"] <= outer["ts_us"] \
        + outer["dur_us"]


def test_null_telemetry_installs_no_stall_hooks():
    """The disabled bundle records no ``py/gc`` and installs no hook; the
    first live recorder installs each hook once, a second adds none, and
    a jit compile under it is a ``jax/compile`` span."""
    got = _run_child(r"""
import gc, json
import jax, jax.numpy as jnp
from jax._src import monitoring
from repro.obs import NULL_TELEMETRY, SpanRecorder
from repro.obs import trace as T
tr = NULL_TELEMETRY.trace
tok = tr.begin("x")
gc.collect()
tr.end(tok)
off = {"installed": T._gc_hook in gc.callbacks,
       "listener": T._compile_hook
       in monitoring._event_duration_secs_listeners,
       "records": len(tr.records)}
rec, rec2 = SpanRecorder(), SpanRecorder()
with rec.span("outer"):
    jax.jit(lambda x: x * 3.0 + 1.0)(jnp.ones(5)).block_until_ready()
    gc.collect()
names = [r["name"] for r in rec.records]
comp = [r for r in rec.records if r["name"] == "jax/compile"]
print(json.dumps({"off": off,
    "gc_hooks": gc.callbacks.count(T._gc_hook),
    "listeners": monitoring._event_duration_secs_listeners.count(
        T._compile_hook),
    "names": names, "compile_depths": [r["depth"] for r in comp],
    "compile_events": [r["attrs"]["event"] for r in comp],
    "second": sorted({r["name"] for r in rec2.records})}))
""")
    assert got["off"] == {"installed": False, "listener": False,
                          "records": 0}
    assert got["gc_hooks"] == 1 and got["listeners"] == 1
    assert "py/gc" in got["names"] and "jax/compile" in got["names"]
    assert set(got["compile_depths"]) <= {1, 2}
    assert "backend_compile" in got["compile_events"]
    assert got["second"] == ["jax/compile", "py/gc"]


def test_spans_stand_on_the_profilers_host_plane():
    """Under ``jax.profiler.trace``, every span name the recorder holds
    (``jax/compile`` aside, recorded after the fact) appears on a host
    plane of the ``.xplane.pb`` as many times as the recorder has it."""
    got = _run_child(r"""
import gc, glob, json, os, sys, tempfile
import jax, jax.numpy as jnp
from jax.profiler import ProfileData
from repro.obs import SpanRecorder
gc.disable()                 # the one collection is the forced one
rec = SpanRecorder()
d = tempfile.mkdtemp()
with jax.profiler.trace(d):
    with rec.span("test/outer", k=1):
        for i in range(3):
            tok = rec.begin("test/inner", i=i)
            jax.jit(lambda x: x * 2.0 + i)(jnp.ones(4)).block_until_ready()
            rec.end(tok, done=True)
        gc.collect()
path = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
host = {}
stats = {}
for plane in ProfileData.from_file(path).planes:
    if plane.name.startswith("/device:"):
        continue
    for line in plane.lines:
        for ev in line.events:
            host[ev.name] = host.get(ev.name, 0) + 1
            if ev.name == "test/inner":
                stats = {k: str(v) for k, v in dict(ev.stats).items()}
mine = {}
for r in rec.records:
    mine[r["name"]] = mine.get(r["name"], 0) + 1
print(json.dumps({"host": host, "mine": mine, "stats": stats}))
""")
    mine, host = got["mine"], got["host"]
    assert mine["test/inner"] == 3 and mine["py/gc"] == 1
    assert mine.get("jax/compile", 0) >= 1
    for name, n in mine.items():
        if name != "jax/compile":
            assert host.get(name) == n, (name, n, host.get(name))
    assert "jax/compile" not in host
    # attrs from begin and from end both reach the annotation
    assert {"i", "done"} <= set(got["stats"])


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_metrics_counters_gauges_labels():
    m = MetricsRegistry()
    m.inc("train.batches", width=64)
    m.inc("train.batches", width=64)
    m.inc("train.batches", width=128)
    assert m.value("train.batches", width=64) == 2.0
    assert m.total("train.batches") == 3.0
    m.set_gauge("pack.pad_frac", 0.25, width=64)
    m.set_gauge("pack.pad_frac", 0.5, width=64)       # gauges overwrite
    assert m.value("pack.pad_frac", width=64) == 0.5
    snap = m.snapshot()
    assert any(c["name"] == "train.batches" and c["labels"] == {"width": 128}
               for c in snap["counters"])


def test_metrics_percentiles_and_empty():
    m = MetricsRegistry()
    for v in range(1, 101):
        m.observe("lat", float(v))
    pct = m.percentiles("lat")
    assert pct["p50"] == pytest.approx(50.5)
    assert pct["p99"] == pytest.approx(np.percentile(np.arange(1, 101), 99))
    empty = m.percentiles("nothing")
    assert all(np.isnan(v) for v in empty.values())
    assert m.histogram_values("nothing") == []


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------

def test_watchdog_warns_then_raises_on_injected_decrease():
    wd = ElboWatchdog(policy="warn", tol=1e-6)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert not wd.observe(-100.0, step=1)
        assert not wd.observe(-99.0, step=2)          # increase: fine
        assert wd.observe(-99.5, step=3)              # injected decrease
    assert len(w) == 1 and issubclass(w[0].category, ElboMonotonicityWarning)
    assert wd.status()["violations"] == 1 and not wd.status()["ok"]

    hard = ElboWatchdog(policy="raise", tol=1e-6)
    hard.observe(-100.0, step=1)
    with pytest.raises(BoundMonotonicityError, match="monotonicity"):
        hard.observe(-101.0, step=2)


def test_watchdog_unarmed_and_slack():
    wd = ElboWatchdog(policy="raise", tol=1e-6)
    # unarmed readings (random-init mass still retiring) never enforce
    wd.observe(-100.0, armed=False)
    assert not wd.observe(-200.0, armed=False)
    # an armed reading right after an unarmed one has no armed baseline
    assert not wd.observe(-300.0, armed=True)
    # within-slack jitter passes: slack = max(tol, rel_tol * |prev|)
    loose = ElboWatchdog(policy="raise", tol=5e-3)
    loose.observe(-100.0)
    assert not loose.observe(-100.004)
    assert wd.status()["armed_checks"] == 1


def test_watchdog_counts_into_metrics_and_cadence():
    m = MetricsRegistry()
    wd = ElboWatchdog(policy="warn", tol=1e-6, check_every=4, metrics=m)
    assert not wd.should_check(3)
    assert wd.should_check(8)
    assert not ElboWatchdog(check_every=0).should_check(7)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        wd.observe(-1.0)
        wd.observe(-2.0)
    assert m.value("watchdog.violations") == 1.0
    assert wd.bound_tail(1) == [-2.0]


# ---------------------------------------------------------------------------
# the bundle and the null object
# ---------------------------------------------------------------------------

def test_as_telemetry_coercions():
    assert as_telemetry(None) is NULL_TELEMETRY
    assert as_telemetry(False) is NULL_TELEMETRY
    t = as_telemetry(True)
    assert isinstance(t, Telemetry) and t.enabled
    assert t.watchdog.check_every == 0     # default: observe at evaluate()
    assert t.watchdog.metrics is t.metrics  # bundle wires them together
    assert as_telemetry(t) is t
    with pytest.raises(TypeError):
        as_telemetry("yes")


def test_null_telemetry_is_inert():
    assert not NULL_TELEMETRY.enabled
    assert NULL_TELEMETRY.trace.begin("x") is None
    NULL_TELEMETRY.trace.end(None)
    NULL_TELEMETRY.metrics.inc("x")
    assert NULL_TELEMETRY.trace.num_records == 0
    assert NULL_TELEMETRY.trace.records == []
    assert NULL_TELEMETRY.metrics.snapshot() == {"counters": [], "gauges": [],
                                                 "histograms": []}
    assert not NULL_TELEMETRY.watchdog.observe(-1e9)


# ---------------------------------------------------------------------------
# integration: telemetry off is a true no-op; on catches real regressions
# ---------------------------------------------------------------------------

def test_disabled_telemetry_is_noop_bit_identical(tiny_corpus):
    corpus, spec = tiny_corpus
    cfg = LDAConfig(num_topics=4, vocab_size=spec.vocab_size,
                    estep_max_iters=15)
    plain = LDAEngine(cfg, corpus, algo="ivi", batch_size=16, seed=0)
    nulled = LDAEngine(cfg, corpus, algo="ivi", batch_size=16, seed=0,
                       telemetry=None)
    for _ in range(2):
        plain.run_epoch()
        nulled.run_epoch()
    assert np.array_equal(np.asarray(plain.state.lam),
                          np.asarray(nulled.state.lam))
    assert nulled.tel is NULL_TELEMETRY
    assert nulled.tel.trace.num_records == 0


def test_enabled_telemetry_matches_and_records(tiny_corpus):
    corpus, spec = tiny_corpus
    cfg = LDAConfig(num_topics=4, vocab_size=spec.vocab_size,
                    estep_max_iters=15)
    plain = LDAEngine(cfg, corpus, algo="ivi", batch_size=16, seed=0)
    tel = Telemetry()
    traced = LDAEngine(cfg, corpus, algo="ivi", batch_size=16, seed=0,
                       telemetry=tel)
    plain.run_epoch()
    traced.run_epoch()
    assert np.array_equal(np.asarray(plain.state.lam),
                          np.asarray(traced.state.lam))
    n_batches = -(-corpus.num_docs // 16)
    assert tel.metrics.total("train.docs") == corpus.num_docs
    assert tel.metrics.total("train.batches") == n_batches
    assert tel.metrics.total("train.tokens") > 0
    assert tel.metrics.value("train.memo_resident_bytes") > 0
    agg = spans_by_name(tel.trace.records)
    for name in ("train/update", "train/memo_gather", "train/solve",
                 "train/memo_update"):
        assert agg[name]["count"] == n_batches, name
    # evaluate() feeds the watchdog at the free cadence + the topic gauge
    traced.evaluate()
    assert tel.watchdog.status()["checks"] == 1
    assert tel.metrics.value("train.effective_topics") > 0


def test_stream_fit_telemetry_bit_identical(tiny_corpus):
    """A streamed facade run with the default live bundle (spans, metrics,
    watchdog) ends on the λ of the same run with telemetry off, bit for
    bit."""
    from repro.data.stream import CorpusDocStream
    corpus, spec = tiny_corpus
    lams = []
    for telemetry in (None, True):
        lda = LDA(num_topics=4, vocab_size=spec.vocab_size,
                  estep_max_iters=15, algo="ivi", batch_size=16, seed=0,
                  telemetry=telemetry)
        lda.fit(CorpusDocStream(corpus), epochs=2)
        lams.append(np.asarray(lda.lam))
    np.testing.assert_array_equal(*lams)


@pytest.mark.parametrize("layout", ["padded", "csr"])
def test_device_synced_solve_spans_round_trip(tiny_corpus, tmp_path,
                                              layout):
    """A Pallas-backend run under a device-synced recorder records one
    ``train/solve`` span per step in either layout, and its trace survives
    JSONL → validation → Chrome conversion record for record."""
    from repro.data.stream import CorpusDocStream
    corpus, spec = tiny_corpus
    tel = Telemetry(trace=SpanRecorder(device_sync=True))
    lda = LDA(num_topics=spec.num_topics, vocab_size=spec.vocab_size,
              estep_max_iters=15, estep_backend="pallas", algo="ivi",
              batch_size=32, seed=0, layout=layout, telemetry=tel)
    lda.fit(CorpusDocStream(corpus), epochs=1)
    solve = spans_by_name(tel.trace.records)["train/solve"]
    assert solve["count"] == tel.metrics.total("train.batches") > 0
    assert solve["min_s"] > 0
    jsonl = str(tmp_path / "t.jsonl")
    n = tel.trace.dump_jsonl(jsonl)
    assert n == tel.trace.num_records == validate_jsonl(jsonl)
    assert chrome_trace_from_jsonl(jsonl, str(tmp_path / "t.json")) == n


@pytest.mark.parametrize("backend,layout,block_v,steps", [
    ("pallas", "padded", None, (5, 6)),   # V = 250 in one chunk: 5 + 1
    ("pallas", "padded", 128, (10, 7)),   # 2 chunks × 5 row tiles → 5 + 2
    ("csr", "padded", 128, (6, 5)),       # 16 × 17 flat tokens, 3 tiles
    ("csr", "csr", 128, (16, 10)),        # token budget 1,024: 8 tiles
    ("gather", "padded", None, None),     # no scatter kernel: no gauges
])
def test_scatter_step_gauges(tiny_corpus, backend, layout, block_v, steps):
    """An IVI step records the memo correction's scatter grid, dense
    against run, from the batch's static shape."""
    from repro.core.types import KernelPolicy
    from repro.data.stream import as_doc_stream
    corpus, spec = tiny_corpus
    cfg = LDAConfig(num_topics=4, vocab_size=spec.vocab_size,
                    estep_max_iters=5, estep_backend=backend,
                    kernel_policy=(KernelPolicy(delta_block_v=block_v)
                                   if block_v else None))
    tel = Telemetry()
    if layout == "csr":
        eng = LDAEngine(cfg, as_doc_stream(corpus), algo="ivi",
                        batch_size=16, seed=0, telemetry=tel, layout="csr",
                        memo_store="chunked")
        assert eng.stream_step()
    else:
        eng = LDAEngine(cfg, corpus, algo="ivi", batch_size=16, seed=0,
                        telemetry=tel)
        eng.run_minibatch(np.arange(16))
    gauges = {g["name"]: g["value"]
              for g in tel.metrics.snapshot()["gauges"]}
    got = (gauges.get("train.scatter_dense_steps"),
           gauges.get("train.scatter_grid_steps"))
    assert got == (steps or (None, None))


@pytest.mark.parametrize("backend,layout,block_b,tiles", [
    ("pallas", "padded", 8, 2),           # 16 docs in two B-tiles
    ("gather", "padded", None, 1),        # the jnp fixed point: one tile
    ("csr", "csr", None, 1),              # the flat kernel: one tile
])
def test_estep_sweep_gauges(tiny_corpus, backend, layout, block_b, tiles):
    """An IVI step records the fixed-point sweeps it ran, summed over its
    tiles, against tiles × ``estep_max_iters``."""
    from repro.core.types import KernelPolicy
    from repro.data.stream import as_doc_stream
    corpus, spec = tiny_corpus
    cfg = LDAConfig(num_topics=4, vocab_size=spec.vocab_size,
                    estep_max_iters=5, estep_backend=backend,
                    kernel_policy=(KernelPolicy(block_b=block_b)
                                   if block_b else None))
    tel = Telemetry()
    if layout == "csr":
        eng = LDAEngine(cfg, as_doc_stream(corpus), algo="ivi",
                        batch_size=16, seed=0, telemetry=tel, layout="csr",
                        memo_store="chunked")
        assert eng.stream_step()
    else:
        eng = LDAEngine(cfg, corpus, algo="ivi", batch_size=16, seed=0,
                        telemetry=tel)
        eng.run_minibatch(np.arange(16))
    gauges = {g["name"]: g["value"]
              for g in tel.metrics.snapshot()["gauges"]}
    cap = gauges["train.estep_sweep_cap"]
    assert cap == tiles * cfg.estep_max_iters
    assert tiles <= gauges["train.estep_sweeps"] <= cap


def test_watchdog_catches_real_bound_decrease(tiny_corpus):
    """Corrupting the memo mid-run breaks eq. 4's subtract-old bookkeeping —
    exactly the failure class the watchdog exists for — and the next armed
    per-update check must raise."""
    corpus, spec = tiny_corpus
    cfg = LDAConfig(num_topics=4, vocab_size=spec.vocab_size,
                    estep_max_iters=15)
    tel = Telemetry(watchdog=ElboWatchdog(policy="raise", check_every=1))
    eng = LDAEngine(cfg, corpus, algo="ivi", batch_size=16, seed=0,
                    telemetry=tel)
    eng.run_epoch()                       # retires init mass -> armed
    eng.run_epoch()                       # a full armed epoch: no violation
    assert float(jax.device_get(eng.state.init_frac)) == 0.0
    assert tel.watchdog.status()["armed_checks"] > 0
    assert tel.watchdog.status()["ok"]
    # corrupt λ out from under the memoized statistics
    eng.state = dataclasses.replace(
        eng.state, lam=eng.state.lam[:, ::-1] * 7.0 + 11.0)
    with pytest.raises(BoundMonotonicityError):
        eng.run_epoch()
    assert tel.watchdog.status()["violations"] >= 1


# ---------------------------------------------------------------------------
# facade surface + vectorized coherence
# ---------------------------------------------------------------------------

def test_lda_facade_telemetry_and_metrics(tiny_corpus):
    corpus, spec = tiny_corpus
    lda = LDA(num_topics=4, vocab_size=spec.vocab_size, estep_max_iters=15,
              algo="ivi", batch_size=16, seed=0, telemetry=True)
    lda.fit(corpus, epochs=1)
    assert lda.telemetry.metrics.total("train.docs") == corpus.num_docs
    assert lda.telemetry.summary()["trace_records"] > 0
    assert lda.effective_topics() > 1.0
    c = lda.coherence(corpus, k=5)
    assert -1.0 <= c <= 1.0


def test_npmi_vectorized_equals_loop(tiny_corpus):
    corpus, spec = tiny_corpus
    rng = np.random.default_rng(3)
    lam = rng.gamma(2.0, 1.0, size=(spec.vocab_size, 6)).astype(np.float32)
    fast = npmi_coherence(lam, corpus, k=6)
    slow = _npmi_coherence_loop(lam, corpus, k=6)
    assert fast == pytest.approx(slow, abs=1e-12)
