"""LDA serving launcher — a thin client of the ``repro.serve`` service.

Drives the ``ServingService`` (`docs/serving.md`) with scheduled request
traffic: admission control forms batches over the serving width ladder /
CSR token budget, partial batches flush on timeout, every response
records the model version that served it, and the latency report comes
from the service's SLO accounting (``repro.serve.slo/v1``).

Traffic shapes (``--traffic``): ``replay`` (the legacy fixed-replay mode
as a schedule — ``--requests × --batch`` single-document requests, all at
t=0, or spaced at ``--rate``), ``poisson`` and ``onoff`` (the synthetic
open-stream generators, seeded). ``--online`` runs the background
incremental learner on the served documents and publishes λ through the
atomic snapshot swap.

Legacy flags: ``--requests``/``--batch`` keep their old meaning as the
replay volume (N requests of B docs ⇒ N·B single-doc requests);
``--ragged`` and ``--no-double-buffer`` are DEPRECATED no-ops — the
service always consumes ragged requests through the admission packer.

Examples:
  PYTHONPATH=src python -m repro.launch.serve_lda --corpus small \
      --requests 64 --batch 32 --backend gather
  PYTHONPATH=src python -m repro.launch.serve_lda --corpus small \
      --traffic poisson --rate 200 --requests 16 --online
  # Arxiv-scale serving dry-run (lowering + memory, no weights needed):
  PYTHONPATH=src python -m repro.launch.serve_lda --dryrun
"""
from __future__ import annotations

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

# Arxiv (Table 1): V=141,927 padded /16, K=100 → 128 lanes.
ARXIV = dict(vocab=141_952, topics=128)
ARXIV_WIDTHS = (32, 64, 128)            # serving bucket widths at L=128


def run_serve_dryrun(batch: int = 256, widths=ARXIV_WIDTHS,
                     backend: str = "pallas") -> dict:
    """Lower the per-bucket serving step at Arxiv scale, per width.

    No weights are materialised (ShapeDtypeStructs only): this checks the
    serving program compiles at the production shape and reports its
    device-memory needs — the serving analogue of ``dryrun_lda --mode ivi``.
    """
    from repro.core.types import LDAConfig
    from repro.lda.infer import _posterior_batch

    v, k = ARXIV["vocab"], ARXIV["topics"]
    cfg = LDAConfig(num_topics=k, vocab_size=v, estep_max_iters=50,
                    estep_backend=backend, estep_stream_dtype="bfloat16")
    out = {"arch": "lda-serve-arxiv", "mode": "serve", "backend": backend,
           "shape": f"b{batch}", "widths": list(widths)}
    t0 = time.time()
    try:
        sds = jax.ShapeDtypeStruct
        per_width = {}
        for w in widths:
            lowered = _posterior_batch.lower(
                cfg, sds((v, k), jnp.float32),
                sds((batch, w), jnp.int32), sds((batch, w), jnp.float32))
            compiled = lowered.compile()
            mem = compiled.memory_analysis()
            per_width[w] = {
                "temp_gb": mem.temp_size_in_bytes / 1e9,
                "argument_gb": mem.argument_size_in_bytes / 1e9,
            }
        out["compile_s"] = round(time.time() - t0, 1)
        out["memory"] = per_width
        out["jit_cache_entries"] = len(widths)
        out["ok"] = True
    except Exception as e:  # noqa: BLE001
        out["ok"] = False
        out["error"] = f"{type(e).__name__}: {e}"
        out["traceback"] = traceback.format_exc()[-1500:]
    return out


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--ckpt", default=None,
                    help="LDA checkpoint (manifest dir or legacy .npz); "
                         "omit to train a quick model on --corpus")
    ap.add_argument("--corpus", default="small")
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--topics", type=int, default=50)
    ap.add_argument("--estep-iters", type=int, default=50)
    ap.add_argument("--backend", default=None,
                    choices=[None, "gather", "dense", "pallas"],
                    help="serving E-step backend (default: the config's)")
    ap.add_argument("--batch", type=int, default=32,
                    help="request batch size (also the jit pad width)")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--layout", default=None, choices=[None, "padded", "csr"],
                    help="serving batch layout: padded width buckets or "
                         "the flat CSR token stream (one jit entry total); "
                         "default: the estimator's training layout")
    ap.add_argument("--token-budget", type=int, default=None,
                    help="with --layout csr: flat slots per batch")
    ap.add_argument("--ragged", action="store_true",
                    help="DEPRECATED no-op: the service always serves "
                         "ragged requests through the admission packer")
    ap.add_argument("--no-double-buffer", action="store_true",
                    help="DEPRECATED no-op: batching/overlap policy now "
                         "lives in the service loop")
    ap.add_argument("--traffic", default="replay",
                    choices=["replay", "poisson", "onoff"],
                    help="arrival schedule: replay (--requests×--batch "
                         "docs, burst or --rate-spaced), poisson, or "
                         "bursty ON-OFF")
    ap.add_argument("--rate", type=float, default=None,
                    help="arrival rate, docs/s (replay: None = all at "
                         "t=0; poisson/onoff default 200)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request latency budget; admission sheds "
                         "requests that already blew it (default: none)")
    ap.add_argument("--flush-timeout-ms", type=float, default=20.0,
                    help="partial-batch flush timeout")
    ap.add_argument("--slo-p95-ms", type=float, default=None,
                    help="p95 latency SLO target for the report")
    ap.add_argument("--online", action="store_true",
                    help="train the background incremental learner on "
                         "served documents and publish λ via atomic "
                         "snapshot swaps")
    ap.add_argument("--cadence-s", type=float, default=0.25,
                    help="with --online: background update period")
    ap.add_argument("--warm-epochs", type=int, default=1,
                    help="quick-train epochs when no --ckpt is given")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dryrun", action="store_true",
                    help="Arxiv-scale serving lowering, no weights")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record a repro.obs span trace of the serving run "
                         "and write it as JSONL here (docs/observability.md)")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="write the serving metrics-registry snapshot here")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    if args.dryrun:
        res = run_serve_dryrun(batch=args.batch,
                               backend=args.backend or "pallas")
        if res["ok"]:
            worst = max(m["temp_gb"] for m in res["memory"].values())
            print(f"[OK ] lda-serve arxiv  compile={res['compile_s']}s "
                  f"widths={res['widths']} max_temp={worst:.2f}GB "
                  f"jit_entries={res['jit_cache_entries']}")
        else:
            print(f"[FAIL] lda-serve: {res['error'][:200]}")
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(res) + "\n")
        if not res["ok"]:
            raise SystemExit(1)
        return

    from repro.data import PAPER_CORPORA, make_corpus
    from repro.lda import LDA
    from repro.obs import MetricsRegistry, Telemetry

    # the metrics registry IS the latency accounting now (real histogram
    # percentiles replaced the old ad-hoc list); the full bundle (with a
    # span recorder) is only built when a telemetry flag asks for it
    tel = Telemetry() if (args.trace or args.metrics_json) else None
    reg = tel.metrics if tel is not None else MetricsRegistry()

    spec = PAPER_CORPORA[args.corpus]
    test = make_corpus(spec, split="test", seed=args.seed, scale=args.scale)
    if args.ckpt:
        lda = LDA.load(args.ckpt)
        print(f"topics from {args.ckpt}: V={lda.cfg.vocab_size} "
              f"K={lda.cfg.num_topics}")
    else:
        train = make_corpus(spec, split="train", seed=args.seed,
                            scale=args.scale)
        lda = LDA(num_topics=args.topics, vocab_size=spec.vocab_size,
                  estep_max_iters=args.estep_iters, algo="ivi",
                  seed=args.seed)
        lda.fit(train, epochs=args.warm_epochs)
        print(f"quick-trained ivi on {args.corpus}: "
              f"{args.warm_epochs} epoch(s), docs_seen={lda.docs_seen}")

    if args.ragged or args.no_double_buffer:
        print("note: --ragged/--no-double-buffer are deprecated no-ops — "
              "the service always serves ragged requests through the "
              "admission packer (docs/serving.md)")

    from repro.data.stream import CorpusDocStream
    from repro.serve import (OnlineLearner, ServiceConfig, ServingService,
                             SnapshotStore, onoff_arrivals, poisson_arrivals,
                             replay_arrivals, requests_from_docs)

    inf = lda.inferencer(backend=args.backend, batch_size=args.batch,
                         layout=args.layout, token_budget=args.token_budget,
                         telemetry=tel)
    ragged_docs = list(CorpusDocStream(test).iter_from(0))

    # warmup: serve the whole test corpus once — every bucket width
    # compiles here, so the service run measures steady-state latency
    if args.requests:
        inf.posterior_docs(ragged_docs)

    n = args.requests * args.batch        # legacy volume: N requests × B
    rng = np.random.default_rng(args.seed)
    doc_order = [ragged_docs[i] for i in
                 rng.choice(len(ragged_docs), size=max(n, 1))]
    if args.traffic == "poisson":
        arrivals = poisson_arrivals(n, args.rate or 200.0, seed=args.seed)
    elif args.traffic == "onoff":
        r = args.rate or 200.0
        arrivals = onoff_arrivals(n, r, on_s=max(8.0 / r, 1e-3),
                                  off_s=max(8.0 / r, 1e-3), seed=args.seed)
    else:
        arrivals = replay_arrivals(n, args.rate)
    deadline = (args.deadline_ms / 1e3 if args.deadline_ms is not None
                else float("inf"))
    requests = requests_from_docs(doc_order, arrivals, deadline_s=deadline)

    slo = {"p95": args.slo_p95_ms} if args.slo_p95_ms else None
    svc = ServingService(inf, config=ServiceConfig(
        flush_timeout_s=args.flush_timeout_ms / 1e3,
        slo_ms=slo), telemetry=tel)
    learner = None
    if args.online:
        store = SnapshotStore(inf, metrics=svc.metrics)
        learner = OnlineLearner(lda.cfg, store, lam0=np.asarray(lda.lam),
                                cadence_s=args.cadence_s, seed=args.seed)
        svc.learner = learner
        learner.start()
    t0 = time.perf_counter()
    try:
        svc.run(requests)
    finally:
        if learner is not None:
            learner.stop()
    if learner is not None:
        learner.drain()
    wall = time.perf_counter() - t0

    rep = svc.slo_report()
    pct = rep["latency_ms"]
    mode = f"{inf.layout}/service/{args.traffic}"
    if rep["served"]:
        print(f"served {rep['served']}/{rep['offered']} docs "
              f"({rep['shed']} shed) backend={inf.cfg.estep_backend} "
              f"[{mode}]: {rep['throughput_docs_s']:.1f} docs/s "
              f"(wall {wall:.2f}s)")
        print(f"latency ms: p50={pct['p50']:.1f} p95={pct['p95']:.1f} "
              f"p99={pct['p99']:.1f} max={pct['max']:.1f}")
        print(f"model versions served: {rep['model_versions']}"
              + (f" ({learner.updates} online updates)" if learner else ""))
        pad = inf.padding_stats()
        print(f"padding: frac={pad['pad_frac']:.3f} "
              f"wasted={pad['wasted_token_bytes'] / 1e3:.1f}kB staged "
              f"({pad['padded_slots'] - pad['live_slots']} of "
              f"{pad['padded_slots']} slots dead)")
    else:
        print("served 0 requests — skipping the latency report")
    for name, s in rep["slo"].items():
        print(f"SLO {name}: target {s['target_ms']:.0f}ms observed "
              f"{s['observed_ms']:.1f}ms -> "
              f"{'ATTAINED' if s['attained'] else 'MISSED'}")
    cache = inf.cache_info()
    print(f"jit cache: {cache['jit_entries']} compiled widths "
          f"{cache['compiled_widths']} "
          f"(batches per width: {cache['batches_per_width']})")
    if args.trace:
        n_rec = tel.trace.dump_jsonl(args.trace)
        print(f"trace: wrote {n_rec} records to {args.trace}")
    if args.metrics_json:
        reg.dump_json(args.metrics_json)
        print(f"metrics: wrote {args.metrics_json}")
    if args.out:
        rec = {"mode": "serve", "backend": inf.cfg.estep_backend,
               "serve_mode": mode, "traffic": args.traffic,
               "batch": args.batch, "requests": args.requests,
               "docs_per_s": rep["throughput_docs_s"],
               "latency_ms": pct,
               "slo_report": rep,
               "jit_widths": cache["compiled_widths"],
               "batches_per_width": cache["batches_per_width"],
               "layout": inf.layout,
               "online": bool(learner),
               "padding": inf.padding_stats(), "ok": True}
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    main()
