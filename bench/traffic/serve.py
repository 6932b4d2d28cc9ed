"""Runner for open-loop serving cells: ``ServingService`` over a schedule.

Set-up samples the topics and the request documents (the test split) from
the seed, builds λ from the generative topics as a trained model would hold
them (β₀ + the full corpus's tokens per topic × φ), builds the
``TopicInferencer`` and the ``ServingService`` the mix describes, and
compiles the serving programs. The window is
``ServingService.run`` over every arrival of the schedule due in
``--seconds`` (requests cycle through the documents in a seeded order),
served to the last. ``serve_docs_per_s`` is the documents whose response
completed within the window, over its length: the measure of a rate above
the service's capacity, where the queue grows all through the window.
A response's latency is its completion minus its scheduled arrival, and
``serve_p95_ms`` is the 95th percentile over all of them, a request that is
shed or never served counting as infinitely late: the measure of a rate
below capacity.

The check solves every served batch again with the plain reference, from
the same λ: the service records which requests shared a batch (they
complete together), and a batch's documents stop their fixed point
together. ``gamma_gap`` is ‖Γ − Γ_ref‖_F / ‖Γ_ref‖_F over every served
γ of the window. The widest gap of one request, max-norm over max-norm,
goes to standard error beside it: it swings from seed to seed with the one
request whose fixed point is the most sensitive, and is not compared.
"""
from __future__ import annotations

import gc
import math
import sys
import time

import numpy as np

from bench import layer as L
from bench.configs import lda_ref
from bench.trace import Profiler, recorder_spans, window_records
from bench.traffic import arrivals as A
from bench.traffic import corpus as C
from bench.traffic.train import _planted, lda_config, telemetry

REF_BATCHES = 256      # request batches per reference call


def topics_lambda(cfg: dict, phi: np.ndarray):
    """λ (V, K) of a model trained on the full corpus: β₀ plus each topic's
    share of the published corpus's tokens, spread by φ."""
    import jax
    import jax.numpy as jnp
    published = cfg["reduced"]["num_train_docs"]["published"] \
        if "num_train_docs" in cfg.get("reduced", {}) \
        else cfg["num_train_docs"]
    scale = published * cfg["mean_doc_len"] / cfg["num_topics"]
    return jax.jit(lambda p: cfg["beta0"] + scale * p.T)(
        jnp.asarray(phi, jnp.float32))


def run(ctx: L.Context) -> L.Outcome:
    import jax
    import jax.numpy as jnp

    from repro.data.stream import BatchPacker
    from repro.lda.infer import TopicInferencer
    from repro.serve import ServiceConfig, ServingService
    from repro.serve.admission import Request

    cfg, mix = ctx.cfg, ctx.mix
    marks = [("start", time.perf_counter())]
    phi = C.topics(cfg, ctx.seed)
    docs = C.make_corpus(cfg, phi, n_docs=mix["docs"], seed=ctx.seed,
                         split="test")
    lam = topics_lambda(cfg, phi)
    del phi
    marks.append(("data", time.perf_counter()))
    tel = telemetry(ctx.trace)
    inf = TopicInferencer(lda_config(cfg, mix, ctx.variant), lam,
                          batch_size=mix["batch_size"], layout=mix["layout"],
                          token_budget=mix.get("token_budget"),
                          telemetry=tel)
    live = [int(n) for n in (docs.counts > 0).sum(1)]
    ragged = [(docs.token_ids[d, :live[d]], docs.counts[d, :live[d]])
              for d in range(docs.num_docs)]
    # compiles the serving program: one entry for every request mix (csr)
    inf.posterior_docs(ragged[: 4 * mix["batch_size"]], double_buffer=False)
    # the service hands a batch's first n rows of γ to its n requests, one
    # sliced program per n, and the token budget makes n vary with the
    # documents: warm every n on a served γ
    packer = BatchPacker(**inf.packer_kwargs())
    for pos, (ids, cnts) in enumerate(ragged[: mix["batch_size"]]):
        packer.add(pos, ids, cnts)
    _, gamma, _, _ = inf.posterior_packed(packer.flush()[0])
    for n in range(1, mix["batch_size"] + 1):
        np.asarray(gamma[:n])
    marks.append(("warm-up", time.perf_counter()))
    svc = ServingService(inf, config=ServiceConfig(
        flush_timeout_s=mix["flush_timeout_s"]), telemetry=tel)
    sched = A.schedule(mix["arrivals"], ctx.seconds, mix["rate_docs_s"],
                       seed=ctx.seed)
    order = np.random.default_rng([ctx.seed, 4]).permutation(docs.num_docs)
    doc_of = order[np.arange(len(sched)) % docs.num_docs]
    reqs = [Request(rid=i, ids=ragged[d][0], cnts=ragged[d][1],
                    arrival_s=float(t))
            for i, (t, d) in enumerate(zip(sched, doc_of))]
    # the whole schedule is built before the window; kept out of the
    # collector's generations, these load-generator objects cost the
    # serving loop no garbage-collection pauses that a server would not see
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - ctx.t_start
    marks.append(("schedule", time.perf_counter()))
    phases = ", ".join(f"{name} {t - t0:.3f} s"
                       for (_, t0), (name, t) in zip(marks, marks[1:]))
    print(f"bench: set-up {phases}, after {marks[0][1] - ctx.t_start:.3f} s"
          " before the runner", file=sys.stderr)
    prof = Profiler(ctx.trace)
    with _planted(ctx.variant if ctx.variant not in (None, "control")
                  else None):
        with prof:
            responses = svc.run(reqs)
    gc.unfreeze()
    peak = L.memory_peak_bytes()
    token_budget = inf.token_budget
    svc_t0 = svc._t0
    spans = recorder_spans(tel.trace) if tel is not None else []
    records = window_records(tel.trace, prof) if tel is not None else []
    summary = prof.summary(spans)
    del svc, inf
    gc.collect()

    ok = [r for r in responses if r.status == "ok"]
    lat = np.full(len(reqs), math.inf)
    for r in ok:
        lat[r.rid] = (r.done_s - r.arrival_s) * 1e3
    p95 = float(np.percentile(lat, 95)) if len(lat) else math.inf
    failed = len(reqs) - len(ok)

    # batches: responses of one batch complete together, in batch order
    batches, cur = [], []
    for r in ok:
        if cur and r.done_s != cur[0].done_s:
            batches.append(cur)
            cur = []
        cur.append(r)
    if cur:
        batches.append(cur)
    gap = _gamma_gap(cfg, mix, lam, batches, doc_of, docs, token_budget)
    # a batch starts where its serve/request_batch span starts (spans and
    # batches are both in serving order)
    starts = [s * 1e-9 - svc_t0 for name, _, s, _ in spans
              if name == "serve/request_batch"]
    requests = [{"arrival_s": r.arrival_s, "done_s": r.done_s, "batch": b,
                 "start_s": starts[b] if len(starts) == len(batches)
                 else None}
                for b, group in enumerate(batches) for r in group]
    ld = L.LayerData(spans=records, requests=requests, trace=summary,
                     window_s=ctx.seconds, chips=1,
                     shape={"batches": len(batches)})
    done = sum(r.done_s <= ctx.seconds for r in ok)
    return L.Outcome(
        e2e={"serve_p95_ms": p95, "serve_docs_per_s": done / ctx.seconds,
             "setup_s": setup_s},
        attempted=len(reqs), failed=failed,
        checks=[("gamma_gap", gap, mix["limits"]["gamma_gap"])],
        memory_peak_bytes=peak, layer=ld)


def _gamma_gap(cfg, mix, lam, batches, doc_of, docs, t_cap) -> float:
    import jax.numpy as jnp
    eb = lda_ref.exp_elog(lam, axis=0)
    flat = lda_ref.FlatCorpus(docs.token_ids, docs.counts)
    b = mix["batch_size"]
    kw = dict(cfg_items=lda_ref.cfg_items(cfg), batch_rows=b, tile_rows=b,
              denominator="all")
    diff = norm = widest = 0.0
    for lo in range(0, len(batches), REF_BATCHES):
        group = batches[lo: lo + REF_BATCHES]
        packed = [flat.batch(doc_of[[r.rid for r in g]], t_cap, b)[:3]
                  for g in group]
        while len(packed) < REF_BATCHES:       # one program for every call
            packed.append(packed[-1])
        ids, cnts, segs = (jnp.asarray(np.stack(x)) for x in zip(*packed))
        n = jnp.asarray([len(g) for g in group]
                        + [len(group[-1])] * (REF_BATCHES - len(group)))
        ref = np.asarray(lda_ref.serve_gamma(eb, ids, cnts, segs, n, **kw))
        for i, g in enumerate(group):
            got = np.stack([r.gamma for r in g]).astype(np.float64)
            want = ref[i, : len(g)].astype(np.float64)
            diff += float(((got - want) ** 2).sum())
            norm += float((want ** 2).sum())
            widest = max(widest, float(
                (np.abs(got - want).max(1) / np.abs(want).max(1)).max()))
    print(f"gamma widest gap of one request = {widest!r} (not compared)",
          file=sys.stderr)
    return (diff / norm) ** 0.5 if norm > 0 else float("nan")
