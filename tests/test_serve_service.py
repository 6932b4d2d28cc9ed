"""The `repro.serve` subsystem: admission edge cases, snapshot swaps,
online learning, and the serving loop end to end (`docs/serving.md`).

The controller is clock-free (every method takes an explicit ``now``),
so every admission edge case here is deterministic — no sleeps, no
real-clock races. The served-vs-offline equality tests are the bit-level
contract the admission packer rides on: a batch formed from the request
stream is the SAME batch ``posterior_docs`` would have packed.
"""
from __future__ import annotations

import math
import threading

import numpy as np
import pytest

from repro.core.math import exp_dirichlet_expectation
from repro.data import PAPER_CORPORA, make_corpus
from repro.data.stream import BatchPacker, CorpusDocStream, QueueDocStream
from repro.lda import LDA
from repro.obs import ElboWatchdog, Telemetry
from repro.serve import (
    AdmissionController,
    OnlineLearner,
    Request,
    ServiceConfig,
    ServingService,
    SnapshotStore,
    onoff_arrivals,
    poisson_arrivals,
    replay_arrivals,
    requests_from_docs,
    validate_slo_report,
)

SPEC = PAPER_CORPORA["tiny"]


def _ragged(n_docs, *, vocab=SPEC.vocab_size, max_n=24, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_docs):
        n = int(rng.integers(2, max_n))
        ids = np.sort(rng.choice(vocab, size=n, replace=False)).astype(
            np.int32)
        cnts = (rng.poisson(1.0, n) + 1).astype(np.float32)
        out.append((ids, cnts))
    return out


@pytest.fixture(scope="module")
def tiny_lda():
    train = make_corpus(SPEC, split="train", seed=0, scale=0.25)
    lda = LDA(num_topics=4, vocab_size=SPEC.vocab_size, estep_max_iters=10,
              algo="ivi", seed=0)
    lda.fit(train, epochs=1)
    return lda


@pytest.fixture()
def inf(tiny_lda):
    return tiny_lda.inferencer(batch_size=8)


# ---------------------------------------------------------------------------
# traffic generators
# ---------------------------------------------------------------------------

def test_poisson_arrivals_seeded_and_sorted():
    a = poisson_arrivals(64, 100.0, seed=3)
    b = poisson_arrivals(64, 100.0, seed=3)
    c = poisson_arrivals(64, 100.0, seed=4)
    assert len(a) == 64
    assert np.array_equal(a, b)                  # seeded: reproducible
    assert not np.array_equal(a, c)
    assert np.all(np.diff(a) >= 0)               # a schedule, sorted
    # mean gap ~ 1/rate (loose: 64 samples)
    assert 0.3 / 100.0 < np.diff(a).mean() < 3.0 / 100.0


def test_onoff_arrivals_burst_structure():
    a = onoff_arrivals(80, 200.0, on_s=0.02, off_s=1.0, seed=0)
    assert len(a) == 80 and np.all(np.diff(a) >= 0)
    assert np.array_equal(a, onoff_arrivals(80, 200.0, on_s=0.02,
                                            off_s=1.0, seed=0))
    # the OFF gaps are visible: some inter-arrival jumps span a full
    # silent period, while within a burst gaps stay Poisson-small
    gaps = np.diff(a)
    assert gaps.max() >= 1.0
    assert gaps.min() < 0.02


def test_replay_arrivals():
    assert np.all(np.asarray(replay_arrivals(5)) == 0.0)
    spaced = np.asarray(replay_arrivals(5, 10.0))
    assert np.allclose(np.diff(spaced), 0.1)


def test_requests_from_docs_cycles_and_deadlines():
    docs = _ragged(3, seed=1)
    arr = [0.0, 0.1, 0.2, 0.3, 0.4]
    reqs = requests_from_docs(docs, arr, deadline_s=0.5, start_id=7)
    assert [r.rid for r in reqs] == [7, 8, 9, 10, 11]
    assert np.array_equal(reqs[3].ids, docs[0][0])      # cycles
    assert all(r.deadline_s == pytest.approx(r.arrival_s + 0.5)
               for r in reqs)
    inf_reqs = requests_from_docs(docs, arr[:2])
    assert all(math.isinf(r.deadline_s) for r in inf_reqs)


# ---------------------------------------------------------------------------
# admission control (clock-free: all edge cases deterministic)
# ---------------------------------------------------------------------------

_KW = dict(batch_size=4, vocab_size=SPEC.vocab_size, layout="padded",
           token_budget=None)


def _req(rid, doc, arrival=0.0, deadline=math.inf):
    ids, cnts = doc
    return Request(rid=rid, ids=ids, cnts=cnts, arrival_s=arrival,
                   deadline_s=deadline)


def test_empty_flush_window_never_flushes():
    ac = AdmissionController(_KW, flush_timeout_s=0.01)
    assert ac.poll(now=1e9) == []            # nothing pending: no flush
    assert ac.next_due(now=0.0) is None
    assert ac.close(now=0.0) == []
    assert ac.pending == 0


def test_full_bucket_emits_on_offer():
    ac = AdmissionController(_KW, flush_timeout_s=10.0)
    docs = [( np.arange(6, dtype=np.int32),
              np.ones(6, np.float32)) for _ in range(4)]
    batches = []
    for i, d in enumerate(docs):
        admitted, batch = ac.offer(_req(i, d), now=0.0)
        assert admitted
        if batch is not None:
            batches.append(batch)
    assert len(batches) == 1                 # emitted the moment it filled
    assert len(batches[0].rows) == 4
    taken = ac.take(batches[0].rows, now=0.0)
    assert [r.rid for r, _ in taken] == [0, 1, 2, 3]
    assert [t for _, t in taken] == [0.0] * 4     # their admit times
    assert ac.pending == 0


def test_timeout_partial_flush():
    ac = AdmissionController(_KW, flush_timeout_s=0.05)
    admitted, batch = ac.offer(_req(0, _ragged(1, seed=2)[0]), now=0.0)
    assert admitted and batch is None
    assert ac.poll(now=0.049) == []          # not due yet
    out = ac.poll(now=0.05)                  # oldest waited the timeout
    assert len(out) == 1 and len(out[0].rows) == 1
    assert [(r.rid, t) for r, t in ac.take(out[0].rows, now=0.05)] \
        == [(0, 0.0)]
    assert ac.poll(now=1.0) == []            # window empty again


def test_over_deadline_request_is_shed():
    ac = AdmissionController(_KW, shed_margin_s=0.01)
    doc = _ragged(1, seed=3)[0]
    admitted, batch = ac.offer(_req(0, doc, deadline=1.0), now=0.995)
    assert not admitted and batch is None    # inside the shed margin
    assert [r.rid for r in ac.shed] == [0]
    assert ac.pending == 0 and ac.offered == 1
    admitted, _ = ac.offer(_req(1, doc, deadline=1.0), now=0.5)
    assert admitted                          # plenty of budget left


def test_deadline_headroom_flushes_before_timeout():
    ac = AdmissionController(_KW, flush_timeout_s=10.0,
                             deadline_headroom_s=0.02)
    ac.offer(_req(0, _ragged(1, seed=4)[0], deadline=1.0), now=0.0)
    assert ac.poll(now=0.5) == []            # deadline still far
    assert len(ac.poll(now=0.985)) == 1      # within the headroom
    assert ac.next_due(now=0.0) == pytest.approx(0.98)  # deadline-driven


def test_next_due_is_sleep_horizon():
    ac = AdmissionController(_KW, flush_timeout_s=0.05)
    ac.offer(_req(0, _ragged(1, seed=5)[0]), now=1.0)
    assert ac.next_due(now=1.0) == pytest.approx(1.05)
    assert ac.next_due(now=2.0) == 2.0       # already due: clamped to now


def test_csr_over_budget_doc_at_head_of_flush_serves_clipped():
    kw = dict(batch_size=4, vocab_size=SPEC.vocab_size, layout="csr",
              token_budget=16)
    ac = AdmissionController(kw, flush_timeout_s=0.05)
    ids = np.arange(40, dtype=np.int32)          # 40 uniques > budget 16
    cnts = np.arange(1, 41, dtype=np.float32)
    admitted, batch = ac.offer(_req(0, (ids, cnts)), now=0.0)
    assert admitted and batch is None            # clipped, filed — no wedge
    out = ac.poll(now=0.05)
    assert len(out) == 1
    b = out[0]
    live = int((b.counts > 0).sum())
    assert live == 16                            # clipped to the budget
    # the clip keeps the most frequent tokens (corpus_from_docs rule)
    assert set(np.asarray(b.token_ids)[np.asarray(b.counts) > 0]) \
        == set(range(24, 40))
    assert [r.rid for r, _ in ac.take(b.rows, now=0.05)] == [0]
    assert ac.pending == 0


# ---------------------------------------------------------------------------
# snapshot swaps (satellite: thread-safe swap_model, in-flight semantics)
# ---------------------------------------------------------------------------

def _uniform_docs(n_docs, *, n_tokens=6, seed=0):
    """Same-width docs: they all file into ONE ladder bucket, so a flush
    yields exactly one batch (what `_pack_one_batch` requires)."""
    rng = np.random.default_rng(seed)
    return [(np.sort(rng.choice(SPEC.vocab_size, size=n_tokens,
                                replace=False)).astype(np.int32),
             (rng.poisson(1.0, n_tokens) + 1).astype(np.float32))
            for _ in range(n_docs)]


def _pack_one_batch(inf, docs):
    kw = inf.packer_kwargs()
    packer = BatchPacker(kw["batch_size"], vocab_size=kw["vocab_size"],
                         layout=kw["layout"], token_budget=kw["token_budget"])
    batches = []
    for pos, (ids, cnts) in enumerate(docs):
        b = packer.add(pos, ids, cnts)
        if b is not None:
            batches.append(b)
    batches.extend(packer.flush())
    assert len(batches) == 1
    return batches[0]


def test_swap_model_validation(inf, tiny_lda):
    lam = np.asarray(tiny_lda.lam)
    with pytest.raises(ValueError):
        inf.swap_model()                         # neither lam nor eb
    with pytest.raises(ValueError):
        inf.swap_model(lam, exp_elog_beta=inf.exp_elog_beta)   # both
    with pytest.raises(ValueError):
        inf.swap_model(lam[:-1])                 # shape change
    v1 = inf.swap_model(lam * 1.5)
    assert v1 == 1 and inf.model_version == 1
    with pytest.raises(ValueError):
        inf.swap_model(lam, version=1)           # version must advance
    eb = np.asarray(exp_dirichlet_expectation(lam * 1.5, axis=0))
    assert np.allclose(np.asarray(inf.exp_elog_beta), eb)


def test_in_flight_batch_completes_on_old_snapshot(tiny_lda, monkeypatch):
    """A swap landing mid-dispatch must NOT leak into the running batch:
    `_dispatch` reads the (version, Eφ) tuple exactly once, so the batch
    completes — and reports — the snapshot it started on."""
    import repro.lda.infer as infer_mod

    lam1 = np.asarray(tiny_lda.lam)
    lam2 = lam1 * 2.0
    docs = _uniform_docs(5, seed=6)
    inf = tiny_lda.inferencer(batch_size=8)
    ref_old = tiny_lda.inferencer(batch_size=8)      # frozen at lam1
    batch = _pack_one_batch(inf, docs)
    _, g_old, n, v_old = ref_old.posterior_packed(batch)
    g_old = np.asarray(g_old)

    real = infer_mod._posterior_batch
    fired = []

    def swap_mid_dispatch(cfg, eb, ids, cnts):
        if not fired:                        # swap lands mid-flight, once
            fired.append(inf.swap_model(lam2))
        return real(cfg, eb, ids, cnts)

    monkeypatch.setattr(infer_mod, "_posterior_batch", swap_mid_dispatch)
    _, gamma, n2, version = inf.posterior_packed(batch)
    assert fired == [1]                      # the swap really happened
    assert version == v_old == 0             # ...but this batch predates it
    assert n2 == n
    assert np.array_equal(np.asarray(gamma), g_old)   # served on old Eφ
    monkeypatch.undo()
    assert inf.model_version == 1            # the NEXT batch sees the swap
    _, g_new, _, v_new = inf.posterior_packed(batch)
    assert v_new == 1
    assert not np.array_equal(np.asarray(g_new), g_old)


def test_concurrent_swaps_never_tear(tiny_lda):
    """Hammer swap_model from a writer thread while serving: every result's
    γ must be bit-equal to the single published λ its version names —
    a torn read (version from one snapshot, Eφ from another) would fail."""
    lam1 = np.asarray(tiny_lda.lam)
    lams = {0: lam1}
    inf = tiny_lda.inferencer(batch_size=8)
    batch = _pack_one_batch(inf, _uniform_docs(6, seed=7))

    n_swaps = 40
    rng = np.random.default_rng(8)
    for v in range(1, n_swaps + 1):
        lams[v] = lam1 * float(rng.uniform(1.1, 3.0))
    stop = threading.Event()
    seen = []

    def read_one():
        _, gamma, _, version = inf.posterior_packed(batch)
        seen.append((version, np.asarray(gamma)))

    def writer():
        for v in range(1, n_swaps + 1):
            inf.swap_model(lams[v], version=v)
        stop.set()

    read_one()                               # version 0, before any swap
    t = threading.Thread(target=writer)
    t.start()
    while not stop.is_set():
        read_one()                           # racing the swaps
    t.join()
    read_one()                               # final version, after all swaps

    refs = {}
    for version, gamma in seen:
        if version not in refs:
            ref = tiny_lda.inferencer(batch_size=8)
            if version:
                ref.swap_model(lams[version], version=version)
            refs[version] = np.asarray(ref.posterior_packed(batch)[1])
        assert np.array_equal(gamma, refs[version]), \
            f"torn snapshot at version {version}"
    # bracketing reads make ≥ 2 distinct versions deterministic
    assert {0, n_swaps} <= {v for v, _ in seen}


def test_snapshot_store_publish(inf, tiny_lda):
    store = SnapshotStore(inf)
    lam = np.asarray(tiny_lda.lam) * 1.2
    snap = store.publish(lam, docs_trained=17)
    assert snap.version == 1 == inf.model_version
    assert snap.docs_trained == 17
    assert store.current is snap
    assert snap.swap_stall_s >= 0.0
    assert len(store.swap_stalls_ms()) == 1
    unattached = SnapshotStore()
    with pytest.raises(ValueError):
        unattached.publish(lam)


# ---------------------------------------------------------------------------
# QueueDocStream (the request-queue → DocStream bridge)
# ---------------------------------------------------------------------------

def test_queue_stream_capacity_and_positions():
    qs = QueueDocStream(100, capacity=3)
    docs = _ragged(5, vocab=100, seed=9)
    pos = [qs.append(d) for d in docs]
    assert pos == [0, 1, 2, None, None]      # stable slots, then full
    assert qs.num_docs == 3                  # capacity: the memo size
    assert qs.appended == 3 and qs.dropped == 2
    got = list(qs.iter_from(0))
    assert len(got) == 3
    assert np.array_equal(got[1][0], docs[1][0])


def test_queue_stream_iterator_sees_late_appends():
    qs = QueueDocStream(100, capacity=8)
    docs = _ragged(4, vocab=100, seed=10)
    qs.append(docs[0])
    it = qs.iter_from(0)
    assert np.array_equal(next(it)[0], docs[0][0])
    for d in docs[1:]:
        qs.append(d)                          # appended AFTER iter started
    rest = list(it)
    assert len(rest) == 3                     # the open window grew
    assert qs.num_words == pytest.approx(
        sum(float(c.sum()) for _, c in docs))


def test_queue_stream_clips_to_max_unique():
    qs = QueueDocStream(1000, capacity=2, max_unique=4)
    ids = np.arange(10, dtype=np.int32)
    cnts = np.arange(1, 11, dtype=np.float32)
    qs.append((ids, cnts))
    (got_ids, got_cnts), = list(qs.iter_from(0))
    assert len(got_ids) == 4
    assert set(got_ids.tolist()) == {6, 7, 8, 9}   # most frequent kept
    assert qs.num_words == pytest.approx(float(got_cnts.sum()))
    with pytest.raises(ValueError):
        qs.append((np.array([1000], np.int32),
                   np.ones(1, np.float32)))        # vocab check


# ---------------------------------------------------------------------------
# OnlineLearner
# ---------------------------------------------------------------------------

def test_online_learner_gating_and_publish(tiny_lda):
    inf = tiny_lda.inferencer(batch_size=8)
    store = SnapshotStore(inf)
    learner = OnlineLearner(tiny_lda.cfg, store,
                            lam0=np.asarray(tiny_lda.lam),
                            min_new_docs=4, batch_size=8, seed=0)
    assert learner.update_once() is None          # no traffic yet
    assert learner.update_once(force=True) is None
    assert learner.observe(_ragged(2, seed=11)) == 2
    assert learner.update_once() is None          # below min_new_docs
    learner.observe(_ragged(3, seed=12))
    v = learner.update_once()                     # 5 ≥ 4: a pass runs
    assert v == 1 and inf.model_version == 1
    assert learner.docs_trained == 5
    assert learner.update_once() is None          # nothing new again
    assert learner.update_once(force=True) == 2   # drain path still runs


def test_online_learner_drain_arms_watchdog(tiny_lda):
    inf = tiny_lda.inferencer(batch_size=8)
    store = SnapshotStore(inf)
    wd = ElboWatchdog(policy="warn")
    learner = OnlineLearner(tiny_lda.cfg, store,
                            lam0=np.asarray(tiny_lda.lam),
                            min_new_docs=4, batch_size=8, watchdog=wd,
                            seed=0)
    learner.observe(_ragged(12, seed=13))
    versions = learner.drain(passes=3)
    assert versions == [1, 2, 3]
    # pass 1 trains on a fresh window (unarmed); 2 and 3 revisit the SAME
    # window with the init mass retired — the armed monotone readings
    assert learner.armed_observations >= 1
    assert wd.violations == []
    armed = [r for r in wd.history if r["armed"]]
    assert all(r["delta"] is None or r["delta"] >= -wd.tol for r in armed)


def test_online_learner_background_failure_raises_from_stop(tiny_lda,
                                                           monkeypatch):
    """An exception in the background thread is not lost: ``stop`` raises it."""
    inf = tiny_lda.inferencer(batch_size=8)
    learner = OnlineLearner(tiny_lda.cfg, SnapshotStore(inf),
                            cadence_s=0.001, seed=0)

    def boom(**_):
        raise RuntimeError("update failed")

    monkeypatch.setattr(learner, "update_once", boom)
    learner.start()
    learner._thread.join(timeout=10)
    assert not learner._thread.is_alive()
    with pytest.raises(RuntimeError, match="update failed"):
        learner.stop()
    learner.stop()                                # the error is raised once


# ---------------------------------------------------------------------------
# the serving loop end to end
# ---------------------------------------------------------------------------

def test_service_replay_matches_offline_bit_equal(tiny_lda):
    """Served γ == offline ``posterior_docs`` γ, document for document —
    the admission packer forms the SAME batches the offline path packs."""
    inf = tiny_lda.inferencer(batch_size=8)
    docs = _ragged(13, seed=14)               # 13: forces a partial flush
    offline = np.asarray(inf.posterior_docs(docs))
    svc = ServingService(inf, config=ServiceConfig(flush_timeout_s=0.01))
    reqs = requests_from_docs(docs, replay_arrivals(len(docs)))
    responses = svc.run(reqs)
    assert len(responses) == len(docs)
    assert all(r.ok for r in responses)
    for r in responses:
        assert r.model_version == inf.model_version
        assert np.array_equal(r.gamma, offline[r.rid]), \
            f"served γ diverged from offline for rid {r.rid}"
    rep = validate_slo_report(svc.slo_report())
    assert rep["served"] == len(docs) and rep["shed"] == 0
    assert rep["conservation_ok"] and rep["every_response_versioned"]


def test_service_sheds_expired_deadlines(tiny_lda):
    inf = tiny_lda.inferencer(batch_size=8)
    docs = _ragged(6, seed=15)
    # deadline == arrival: by the time the loop offers it, it's expired
    reqs = requests_from_docs(docs, replay_arrivals(len(docs)),
                              deadline_s=0.0)
    svc = ServingService(inf, config=ServiceConfig(flush_timeout_s=0.01))
    responses = svc.run(reqs)
    assert all(r.status == "shed" for r in responses)
    assert all(r.model_version is None and r.gamma is None
               for r in responses)
    rep = validate_slo_report(svc.slo_report())
    assert rep["shed"] == len(docs) and rep["served"] == 0
    assert rep["conservation_ok"]
    assert math.isnan(rep["latency_ms"]["p50"])


def test_service_csr_layout_end_to_end(tiny_lda):
    """The CSR admission path serves — including an over-budget document
    at the head of the stream (clipped, never wedged)."""
    inf = tiny_lda.inferencer(batch_size=8, layout="csr", token_budget=64)
    big_ids = np.sort(np.random.default_rng(16).choice(
        SPEC.vocab_size, size=100, replace=False)).astype(np.int32)
    docs = [(big_ids, np.ones(100, np.float32))] + _ragged(7, seed=17)
    svc = ServingService(inf, config=ServiceConfig(flush_timeout_s=0.01))
    responses = svc.run(requests_from_docs(docs, replay_arrivals(len(docs))))
    assert len(responses) == len(docs) and all(r.ok for r in responses)
    rep = validate_slo_report(svc.slo_report())
    assert rep["conservation_ok"] and rep["served"] == len(docs)


def test_service_online_versions_advance(tiny_lda):
    """End to end with the learner: versions advance mid-stream, every OK
    response is versioned, and served versions ⊆ published versions."""
    inf = tiny_lda.inferencer(batch_size=8)
    store = SnapshotStore(inf)
    learner = OnlineLearner(tiny_lda.cfg, store,
                            lam0=np.asarray(tiny_lda.lam),
                            min_new_docs=4, batch_size=8, seed=0)
    svc = ServingService(inf, config=ServiceConfig(flush_timeout_s=0.005),
                         learner=learner)
    docs = _ragged(24, seed=18)
    reqs = requests_from_docs(docs, poisson_arrivals(len(docs), 400.0,
                                                     seed=0))
    # serve in two waves with a synchronous update in between — the swap
    # lands mid-stream deterministically (no background-thread timing)
    svc.run(reqs[:12])
    assert learner.update_once(force=True) == 1
    svc.run(reqs[12:])
    learner.drain(passes=2)
    rep = validate_slo_report(svc.slo_report())
    assert rep["every_response_versioned"]
    versions = {r.model_version for r in svc.responses if r.ok}
    assert versions >= {0, 1}                # both snapshots served traffic
    assert max(versions) <= inf.model_version
    assert store.current.version == inf.model_version
    assert max(store.swap_stalls_ms()) < 50.0


def test_slo_report_attainment_and_validation(tiny_lda):
    inf = tiny_lda.inferencer(batch_size=8)
    svc = ServingService(inf, config=ServiceConfig(
        flush_timeout_s=0.01, slo_ms={"p95": 1e6}))
    docs = _ragged(5, seed=19)
    svc.run(requests_from_docs(docs, replay_arrivals(len(docs))))
    rep = validate_slo_report(svc.slo_report())
    assert rep["slo"]["p95"]["attained"]          # 1e6 ms: trivially met
    assert rep["slo"]["p95"]["target_ms"] == 1e6

    bad = dict(rep, schema="bogus/v0")
    with pytest.raises(ValueError, match="schema"):
        validate_slo_report(bad)
    bad = dict(rep, served=rep["served"] + 1, conservation_ok=False)
    with pytest.raises(ValueError, match="conservation"):
        validate_slo_report(bad)
    bad = dict(rep, latency_ms={"p50": 1.0})
    with pytest.raises(ValueError, match="p95"):
        validate_slo_report(bad)
    bad = dict(rep, offered="3")
    with pytest.raises(ValueError, match="offered"):
        validate_slo_report(bad)


def test_service_spans_tile_the_loop(tiny_lda):
    """With a live bundle the loop's top-level spans tile ``run``: one
    ``serve/admit`` per offered request (``shed`` on the refused one), one
    ``serve/respond`` per served batch, and the program's own per-request
    times in order: admit ≤ batch start ≤ completion."""
    import time

    inf = tiny_lda.inferencer(batch_size=8)
    tel = Telemetry()
    svc = ServingService(inf, config=ServiceConfig(flush_timeout_s=0.005),
                         telemetry=tel)
    docs = _ragged(40, seed=20)
    reqs = requests_from_docs(docs, poisson_arrivals(len(docs), 800.0,
                                                     seed=1))
    reqs.append(Request(rid=len(docs), ids=docs[0][0], cnts=docs[0][1],
                        arrival_s=reqs[-1].arrival_s, deadline_s=0.0))
    t0 = time.perf_counter_ns()
    responses = svc.run(reqs)
    wall_us = (time.perf_counter_ns() - t0) / 1e3
    spans = [r for r in tel.trace.records if r["type"] == "span"]

    def named(name):
        return [r for r in spans if r["name"] == name]

    admits = named("serve/admit")
    assert len(admits) == len(reqs)
    assert all(r["attrs"]["lag_ms"] >= 0 for r in admits)
    assert [r["attrs"].get("shed", False) for r in admits] \
        == [False] * len(docs) + [True]
    ok = [r for r in responses if r.ok]
    assert len(ok) == len(docs)
    batches = {r.start_s for r in ok}
    assert len(named("serve/respond")) == len(batches) \
        == len(named("serve/request_batch"))
    assert sum(r["attrs"]["docs"] for r in named("serve/respond")) \
        == len(docs)
    for r in ok:
        assert r.admit_s <= r.start_s <= r.done_s
        assert r.admit_s >= r.arrival_s
    shed = [r for r in responses if r.status == "shed"]
    assert len(shed) == 1 and shed[0].admit_s is None \
        and shed[0].start_s is None
    assert {r["attrs"]["until"] for r in named("serve/wait")} \
        <= {"arrival", "flush"}
    assert sum(r["attrs"]["batches"] for r in named("serve/flush")) \
        + sum(1 for r in named("serve/request_batch") if r["depth"] == 0) \
        == len(batches)
    top = sum(r["dur_us"] for r in spans
              if r["depth"] == 0 and r["name"].startswith("serve/"))
    assert top >= 0.8 * wall_us, (top, wall_us)


def test_service_off_keeps_only_the_latency_histogram(tiny_lda):
    """Telemetry off: the private registry holds ``serve.latency_ms`` and
    nothing else; the admission and packer counters go to a live bundle
    only, and the service leaves ``serve.docs``/``serve.batches`` to the
    inferencer's width-labelled copies."""
    docs = _ragged(10, seed=21)
    reqs = requests_from_docs(docs, replay_arrivals(len(docs)))
    svc = ServingService(tiny_lda.inferencer(batch_size=8),
                         config=ServiceConfig(flush_timeout_s=0.005))
    svc.run(reqs)
    snap = svc.metrics.snapshot()
    assert snap["counters"] == [] and snap["gauges"] == []
    assert [h["name"] for h in snap["histograms"]] == ["serve.latency_ms"]
    assert svc.admission.metrics is None

    tel = Telemetry()
    live = ServingService(tiny_lda.inferencer(batch_size=8, telemetry=tel),
                          config=ServiceConfig(flush_timeout_s=0.005),
                          telemetry=tel)
    live.run(reqs)
    assert tel.metrics.total("serve.docs") == len(docs)
    assert tel.metrics.total("admit.admitted") == len(docs)
    assert tel.metrics.total("pack.docs") == len(docs)
    assert tel.metrics.histogram_values("admit.queue_wait_ms")
