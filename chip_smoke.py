"""Smoke test of the LDA system on a TPU, at the Arxiv deployment's width.

    python chip_smoke.py             # one chip: IVI training + topic serving
    python chip_smoke.py --chips 4   # four chips: D-IVI shard_map vs vmap

One chip runs, through the normal entry points (``repro.lda.LDA``,
``TopicInferencer``/``ServingService``), on a corpus sampled from the
paper's Arxiv statistics (Table 1: V=141,927, mean length 116) at 1% of its
documents, with K=100:

* train: IVI with the bf16 chunked host memo, padded layout on the fused
  Pallas kernels and CSR layout on the flat-token kernels. λ must stay
  finite, the memoized bound must not decrease once the random-init mass
  has retired (eq. 4), the compiled memo correction must hold Mosaic
  kernels (``tpu_custom_call``, so nothing ran in interpret mode), its
  scatter kernel must take the sorted visit list (an s32 list of
  row_tiles + chunks visits) as its first operand, and one batch's E-step
  (γ, π, correction) must match the jnp ``gather`` reference;
* serve: a few batches of held-out requests through the serving service in
  both layouts, γ compared with the ``gather`` reference.

``--chips 4`` runs only the distributed phase: D-IVI on a (4, 1) mesh
against the single-device vmap simulation on the same stream, with λ,
the worker memos and the batches checked to sit on four distinct devices.

All phases run in this one process. Without a TPU the script exits
non-zero before any phase. On success the last line of standard output is
``{"ok": true, "device": {"platform", "kind", "count"}}``; any failed
check exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

SCALE = 0.01          # share of Arxiv's 782,385 training documents
NUM_TOPICS = 100      # the paper's K
BATCH = 256           # training mini-batch (documents)
STEPS = 4             # timed training steps
SERVE_DOCS = 64       # held-out request documents
SERVE_BATCH = 16      # serving batch (documents)
DIVI_ROUNDS = 4

# the backend-equivalence tolerances of tests/test_estep_backend.py
GAMMA_TOL = dict(rtol=2e-3, atol=2e-3)
PI_TOL = dict(rtol=2e-3, atol=1e-4)
CORR_TOL = dict(rtol=2e-3, atol=2e-3)
WORDS_TOL = dict(rtol=1e-6)
# the relative fp32 slack of tests/test_monotone.py's bound comparison
BOUND_SLACK_REL = 2e-6
# psum vs sum reduce in different orders (tests/test_divi.py)
DIVI_REL_TOL = 5e-4


class SmokeFailure(Exception):
    """A phase's result disagreed with its reference."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def assert_close(phase: str, name: str, got, want, *, rtol, atol=0.0):
    import numpy as np
    got, want = np.asarray(got), np.asarray(want)
    check(got.shape == want.shape,
          f"{phase}: {name} shape {got.shape} != reference {want.shape}")
    check(bool(np.isfinite(got).all()), f"{phase}: {name} is not finite")
    err = np.abs(got - want)
    bound = atol + rtol * np.abs(want)
    worst = float((err - bound).max()) if err.size else 0.0
    log(phase, f"{name}: max|diff|={float(err.max()) if err.size else 0.0!r}"
               f" (rtol={rtol}, atol={atol})")
    check(worst <= 0.0, f"{phase}: {name} differs from the gather reference "
                        f"beyond rtol={rtol}, atol={atol}")


def reference(fn, *args, **kwargs):
    """Run the jnp reference at full fp32 matmul precision (the TPU's
    default f32 matmul takes one bf16 pass)."""
    import jax
    with jax.default_matmul_precision("highest"):
        return fn(*args, **kwargs)


def kernel_calls(jitted, *args, **kwargs):
    """(kernel name, first operand type) of each Mosaic kernel call in the
    compiled program (none = interpret mode), named as the benchmark's
    trace reader names them."""
    from bench.trace import op_name
    from repro.launch.hlo_analysis import mosaic_calls
    compiled = jitted.lower(*args, **kwargs).compile()
    return [(op_name(line), first) for line, first in mosaic_calls(compiled)]


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_device(chips: int):
    import jax
    backend = jax.default_backend()
    devices = jax.devices()
    if backend != "tpu":
        print(f"chip_smoke: no TPU: JAX's backend is {backend!r} "
              f"(devices: {devices}); this smoke runs only on a TPU",
              file=sys.stderr)
        sys.exit(1)
    log("device", f"{devices}")
    if len(devices) < chips:
        print(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
              f"JAX sees {len(devices)}", file=sys.stderr)
        sys.exit(1)
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def make_data():
    from repro.data import PAPER_CORPORA, make_corpus
    spec = PAPER_CORPORA["arxiv"]
    t0 = time.perf_counter()
    train = make_corpus(spec, split="train", seed=0, scale=SCALE)
    test = make_corpus(spec, split="test", seed=0,
                       scale=SERVE_DOCS / spec.num_test)
    log("data", f"arxiv x{SCALE}: {train.num_docs} train docs, "
                f"{test.num_docs} request docs, V={spec.vocab_size}, "
                f"max unique/doc={train.max_unique}, "
                f"{time.perf_counter() - t0:.1f}s to sample")
    return spec, train, test


def phase_train(spec, corpus, layout: str):
    import jax
    import numpy as np

    from repro.core.types import LDAConfig
    from repro.lda import LDA

    phase = f"train/{layout}"
    backend = "pallas" if layout == "padded" else "csr"
    cfg = LDAConfig(num_topics=NUM_TOPICS, vocab_size=spec.vocab_size)
    lda = LDA(cfg, algo="ivi", backend=backend, layout=layout,
              batch_size=BATCH, memo_store="chunked")

    t0 = time.perf_counter()
    lda.partial_fit(corpus, steps=1)
    jax.block_until_ready(lda.lam)
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    lda.partial_fit(steps=STEPS - 1)
    jax.block_until_ready(lda.lam)
    steady = (time.perf_counter() - t0) / (STEPS - 1)
    log(phase, f"backend={backend} memo=chunked: first step {first:.3f}s "
               f"(compile included), then {steady:.3f}s/step over "
               f"{STEPS - 1} steps, docs_seen={lda.docs_seen}")
    check(bool(np.isfinite(np.asarray(lda.lam)).all()),
          f"{phase}: λ is not finite")

    # finish the first pass: the random-init mass retires as every document
    # is visited once, and from then on eq. 4 makes the bound monotone
    steps, t0 = STEPS, time.perf_counter()
    while float(lda.state.init_frac) > 0.0:
        check(steps < corpus.num_docs, f"{phase}: init mass never retired")
        lda.partial_fit(steps=1)
        steps += 1
    log(phase, f"first pass done after {steps} steps "
               f"({time.perf_counter() - t0:.1f}s), init mass retired")
    before = lda.bound()
    lda.partial_fit(steps=STEPS)
    after = lda.bound()
    slack = max(5e-3, BOUND_SLACK_REL * abs(before))
    log(phase, f"memoized bound {before!r} -> {after!r} over {STEPS} steps "
               f"(slack {slack!r})")
    check(bool(np.isfinite(np.asarray(lda.lam)).all()),
          f"{phase}: λ is not finite")
    check(after >= before - slack, f"{phase}: memoized bound decreased")
    return lda


def phase_estep(corpus, lda, layout: str) -> None:
    """One training batch's E-step and memo correction on the kernels,
    against the gather reference, plus proof the kernels are Mosaic."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.estep import BowBatch, get_backend
    from repro.core.math import exp_dirichlet_expectation
    from repro.kernels import ops as kops

    phase = f"estep/{layout}"
    eng = lda.trainer.eng
    cfg, wire = eng.cfg, eng.memo.pi_wire_dtype
    rows = np.arange(BATCH)
    ids = jnp.asarray(corpus.token_ids[rows])
    cnts = jnp.asarray(corpus.counts[rows])
    old_pi, visited = eng.memo.gather(rows)
    eb = exp_dirichlet_expectation(lda.lam, axis=0)

    if layout == "padded":
        backend = "pallas"
        calls = kernel_calls(kops.memo_correction_pallas, cfg, eb, ids, cnts,
                             old_pi, visited, pi_dtype=wire)
        token_shape = ids.shape
    else:
        backend = "csr"
        tok = get_backend("csr").flatten(BowBatch(ids, cnts))
        calls = kernel_calls(kops.memo_correction_pallas_csr, cfg, eb,
                             tok.token_ids, tok.counts, tok.segments,
                             old_pi.reshape(-1, old_pi.shape[-1]), visited,
                             pi_dtype=wire)
        token_shape = tok.token_ids.shape
    log(phase, f"compiled memo correction holds {len(calls)} Mosaic kernel "
               f"calls")
    check(len(calls) > 0,
          f"{phase}: no tpu_custom_call in the compiled correction")
    # V spans many scatter chunks here: the compiled scatter must walk the
    # sorted visit list, not the dense chunks × row_tiles grid
    dense, grid = kops.correction_scatter_steps(cfg, token_shape)
    scatter = [t for name, t in calls if name == "_segment_scatter_kernel"]
    log(phase, f"compiled scatter's first operand {scatter} "
               f"(visit list of {grid} steps; dense grid {dense})")
    check(scatter == [f"s32[{grid}]"] and grid < dense,
          f"{phase}: the scatter does not run the sorted visit list")

    batch = BowBatch(ids, cnts)
    # at the production tolerance the kernel stops per 128-document tile
    # and the reference on the whole batch's mean change, so their γ differ
    # by what the last sweeps move: reported, not checked
    res = get_backend(backend).solve_correction(cfg, eb, batch, old_pi,
                                                visited, wire)[2]
    rres = reference(get_backend("gather").solve_correction, cfg, eb, batch,
                     old_pi, visited, wire)[2]
    gap = float(np.abs(np.asarray(res.gamma) - np.asarray(rres.gamma)).max())
    log(phase, f"batch of {BATCH} docs x {ids.shape[1]} slots, "
               f"{int(np.asarray(visited).sum())} visited, production tol: "
               f"kernel sweeps per tile {np.asarray(res.iters).tolist()}, "
               f"reference sweeps {int(rres.iters[0])}, max|Δγ|={gap!r}")
    # the check: tol 0 makes both run exactly max_iters sweeps, at full fp32
    # matmul precision outside the kernels too, and π stays fp32 (on the
    # bf16 wire a last-bit difference can flip one bf16 rounding, 2^-8), so
    # only the kernels' arithmetic differs
    exact = dataclasses.replace(cfg, estep_tol=0.0)
    corr, words, res = reference(get_backend(backend).solve_correction,
                                 exact, eb, batch, old_pi, visited)
    rcorr, rwords, rres = reference(get_backend("gather").solve_correction,
                                    exact, eb, batch, old_pi, visited)
    log(phase, f"tol 0: kernel sweeps per tile "
               f"{np.asarray(res.iters).tolist()}, reference sweeps "
               f"{int(rres.iters[0])}")
    check(bool(np.all(np.asarray(res.iters) == int(rres.iters[0]))),
          f"{phase}: kernel and reference ran different sweep counts")
    assert_close(phase, "gamma", res.gamma, rres.gamma, **GAMMA_TOL)
    assert_close(phase, "pi", res.pi, rres.pi, **PI_TOL)
    assert_close(phase, "correction", corr, rcorr, **CORR_TOL)
    assert_close(phase, "first-visit words", words, rwords, **WORDS_TOL)


def serve(inf, docs):
    """Serve ``docs`` as one burst through a ``ServingService``; returns
    (γ in request order, SLO report, wall seconds)."""
    import numpy as np

    from repro.serve import (ServiceConfig, ServingService, replay_arrivals,
                             requests_from_docs)

    # no timeout flush: the service forms exactly the batches the offline
    # packer forms, so a reference can solve the same batches (a document's
    # γ depends on its batch-mates through the batch-mean stopping rule)
    svc = ServingService(inf, config=ServiceConfig(flush_timeout_s=600.0))
    t0 = time.perf_counter()
    responses = svc.run(requests_from_docs(docs, replay_arrivals(len(docs))))
    wall = time.perf_counter() - t0
    check(len(responses) == len(docs) and all(r.ok for r in responses),
          f"serve/{inf.layout}: not every request was served")
    gamma = np.stack([r.gamma for r in sorted(responses,
                                              key=lambda r: r.rid)])
    return gamma, svc.slo_report(), wall


def phase_serve(test, lda, layout: str) -> None:
    import numpy as np

    from repro.data.stream import CorpusDocStream
    from repro.lda.infer import TopicInferencer

    phase = f"serve/{layout}"
    docs = list(CorpusDocStream(test).iter_from(0))
    inf = lda.inferencer(batch_size=SERVE_BATCH)
    t0 = time.perf_counter()
    inf.posterior_docs(docs)                  # compiles every serving shape
    warm = time.perf_counter() - t0
    gamma, rep, wall = serve(inf, docs)
    lat = rep["latency_ms"]
    log(phase, f"backend={inf.cfg.estep_backend}: warm-up {warm:.3f}s "
               f"(compile included); {rep['served']}/{len(docs)} served in "
               f"{wall:.3f}s, {rep['throughput_docs_s']!r} docs/s, latency "
               f"ms p50={lat['p50']!r} p95={lat['p95']!r}, jit entries "
               f"{inf.cache_info()['jit_entries']}")
    check(bool(np.isfinite(gamma).all()), f"{phase}: γ is not finite")

    # the check, as in phase_estep: exactly max_iters sweeps on both sides
    exact = dataclasses.replace(lda.cfg, estep_tol=0.0)
    got = serve(TopicInferencer(exact, lda.lam, batch_size=SERVE_BATCH,
                                layout=layout), docs)[0]
    ref = TopicInferencer(exact, lda.lam, backend="gather",
                          batch_size=SERVE_BATCH, layout=layout)
    want = reference(ref.posterior_docs, docs, double_buffer=False)
    assert_close(phase, "gamma", got, want, **GAMMA_TOL)


def phase_divi(spec, corpus) -> None:
    import jax
    import numpy as np

    from repro.core.types import LDAConfig
    from repro.dist import DIVIConfig, DIVIEngine

    phase = "divi/4chips"
    cfg = LDAConfig(num_topics=NUM_TOPICS, vocab_size=spec.vocab_size,
                    estep_backend="pallas")
    dcfg = DIVIConfig(num_workers=4)
    mesh = jax.make_mesh((4, 1), ("data", "model"))
    dist = DIVIEngine(cfg, dcfg, corpus, seed=0, mesh=mesh)
    sim = DIVIEngine(cfg, dcfg, corpus, seed=0)
    for r in range(DIVI_ROUNDS):
        t0 = time.perf_counter()
        dist.run_round()
        jax.block_until_ready(dist.state.lam)
        t_dist = time.perf_counter() - t0
        t0 = time.perf_counter()
        sim.run_round()
        jax.block_until_ready(sim.state.lam)
        t_sim = time.perf_counter() - t0
        log(phase, f"round {r + 1}: shard_map {t_dist:.3f}s, "
                   f"vmap simulation {t_sim:.3f}s")

    lam_d, lam_s = np.asarray(dist.lam), np.asarray(sim.lam)
    check(bool(np.isfinite(lam_d).all()), f"{phase}: λ is not finite")
    rel = float(np.abs(lam_d - lam_s).max() / np.abs(lam_s).max())
    log(phase, f"{DIVI_ROUNDS} rounds x {dcfg.num_workers} workers x "
               f"{dcfg.batch_size} docs: max|Δλ|/max|λ| = {rel!r} "
               f"(tolerance {DIVI_REL_TOL})")
    check(rel <= DIVI_REL_TOL, f"{phase}: shard_map λ drifts from the vmap "
                               "simulation")

    def devices(x):
        return sorted(s.device.id for s in x.addressable_shards)

    lam_devs = devices(dist.state.lam)
    memo = dist.shard.memo
    memo_devs = devices(memo.pi)
    rows = {s.data.shape[0] for s in memo.pi.addressable_shards}
    log(phase, f"λ shards on devices {lam_devs}, memo shards on "
               f"{memo_devs} ({sorted(rows)} worker per shard)")
    check(len(set(lam_devs)) == 4, f"{phase}: λ is not on four devices")
    check(len(set(memo_devs)) == 4 and rows == {1},
          f"{phase}: the worker memos are not one per device")

    w, s, b, l = 4, dcfg.staleness, dcfg.batch_size, dist.max_unique
    batch = [np.zeros((w, s, b, l), np.int32),
             np.zeros((w, s, b, l), np.float32),
             np.zeros((w, s, b), np.int32), np.zeros((w, s), bool)]
    compiled = dist._round.lower(dist.state, dist.shard, *batch,
                                 dist.num_words_total).compile()
    in_shardings = compiled.input_shardings[0][2:6]
    batch_devs = [sorted(d.id for d in sh.device_set) for sh in in_shardings]
    log(phase, f"round batches placed on devices {batch_devs}")
    check(all(len(set(d)) == 4 for d in batch_devs),
          f"{phase}: the batches are not spread over four devices")


# ---------------------------------------------------------------------------

def _cache_counter():
    """Count persistent compilation cache hits/misses from JAX's events."""
    import jax
    counts = {"hits": 0, "misses": 0}
    names = {"/jax/compilation_cache/cache_hits": "hits",
             "/jax/compilation_cache/cache_misses": "misses"}

    def listener(event, **_):
        if event in names:
            counts[names[event]] += 1

    jax.monitoring.register_event_listener(listener)
    return counts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: train + serve on one chip; 4: only the D-IVI "
                         "phase across four chips")
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    cache_dir = enable_compile_cache()
    device = phase_device(args.chips)
    cache = _cache_counter()
    log("cache", f"persistent compilation cache at {cache_dir}")
    spec, train, test = make_data()
    if args.chips == 4:
        phase_divi(spec, train)
    else:
        for layout in ("padded", "csr"):
            t0 = time.perf_counter()
            lda = phase_train(spec, train, layout)
            phase_estep(train, lda, layout)
            phase_serve(test, lda, layout)
            log(layout, f"train + estep + serve took "
                        f"{time.perf_counter() - t0:.1f}s")
    log("cache", f"compilation cache hits={cache['hits']} "
                 f"misses={cache['misses']}")
    log("total", f"{time.perf_counter() - t_start:.1f}s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
