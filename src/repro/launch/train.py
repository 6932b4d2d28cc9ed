"""Training launcher.

Two modes:
  * ``lda``  — the paper's system: train LDA with MVI/SVI/IVI/S-IVI/D-IVI
    on a synthetic paper-shaped corpus, periodic held-out LPP evaluation,
    checkpointing.
  * ``lm``   — transformer training: any assigned arch (reduced or full),
    synthetic token stream, AdamW or IAG, optional mesh.

Examples:
  PYTHONPATH=src python -m repro.launch.train lda --algo ivi --corpus small
  PYTHONPATH=src python -m repro.launch.train lda --algo divi --workers 4
  PYTHONPATH=src python -m repro.launch.train lda --algo divi --workers 4 \
      --stream                     # D-IVI straight off a UCI DocStream
  PYTHONPATH=src python -m repro.launch.train lm --arch yi-9b --reduced \
      --steps 200 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np


def main_lda(args) -> None:
    """LDA training through the ``repro.lda.LDA`` facade.

    The historical flags are thin aliases onto facade kwargs (``--algo
    divi`` ≡ ``distributed=DIVIConfig(...)``, ``--memo-store`` ≡
    ``memo_store=``, …); ``--ckpt`` now writes a versioned manifest
    directory carrying the FULL incremental state (λ-state, memo, rng,
    epoch remainder — `repro.lda.ckpt`), and ``--resume`` continues such a
    run bit-equally. The old ``save_checkpoint(eng.state)`` flat-npz files
    load too, but serve-only (DeprecationWarning: their memo was dropped
    on save, so an IVI/S-IVI run cannot actually continue from them).
    """
    from repro.core import LDAConfig
    from repro.data import PAPER_CORPORA, UCIDocStream, make_corpus, save_uci
    from repro.dist import DIVIConfig
    from repro.lda import LDA

    tel = _build_telemetry(args)

    spec = PAPER_CORPORA[args.corpus]
    test = make_corpus(spec, split="test", seed=args.seed, scale=args.scale)
    if args.stream:
        # ragged streaming ingest: train from a lazily-read UCI docword
        # file through a DocStream — no (D, L) padded corpus resident.
        # With --docword an existing file is streamed; otherwise the
        # synthetic corpus is written out in UCI format once and then
        # streamed back, exercising the exact production ingest path.
        # Works single-host AND distributed (--algo divi shards the stream
        # into per-worker views); only full-batch mvi needs a materialized
        # corpus.
        if args.algo == "mvi":
            raise SystemExit("--stream needs a mini-batch engine; mvi is "
                             "full-batch coordinate ascent")
        docword = args.docword
        if docword is None:
            import tempfile
            mat = make_corpus(spec, split="train", seed=args.seed,
                              scale=args.scale)
            docword = os.path.join(tempfile.mkdtemp(prefix="lda_stream_"),
                                   "docword.txt.gz")
            save_uci(mat, docword)
        train = UCIDocStream(docword)
        print(f"stream={docword} docs={train.num_docs} "
              f"words={train.num_words:.0f} K={args.topics}")
    elif args.docword:
        raise SystemExit("--docword goes with --stream")
    else:
        train = make_corpus(spec, split="train", seed=args.seed,
                            scale=args.scale)
        print(f"corpus={args.corpus} docs={train.num_docs} "
              f"words={float(train.num_words):.0f} K={args.topics}")
    cfg = LDAConfig(num_topics=args.topics, vocab_size=spec.vocab_size,
                    estep_max_iters=args.estep_iters,
                    estep_backend=args.backend)

    if args.resume:
        lda = LDA.load(args.resume, telemetry=tel).resume(train,
                                                          test_corpus=test)
        print(f"resumed {args.resume}: algo={lda.algo} "
              f"docs_seen={lda.docs_seen}")
    elif args.algo == "divi":
        lda = LDA(cfg, algo="divi",
                  distributed=DIVIConfig(num_workers=args.workers,
                                         batch_size=args.batch,
                                         staleness=args.staleness,
                                         delay_prob=args.delay_prob),
                  seed=args.seed, telemetry=tel,
                  tune_store=args.tune_store)
    else:
        lda = LDA(cfg, algo=args.algo, batch_size=args.batch,
                  seed=args.seed, memo_store=args.memo_store,
                  chunk_docs=args.chunk_docs,
                  bucket_by_length=args.bucketed, telemetry=tel,
                  tune_store=args.tune_store)

    # bind the corpus without stepping so the memo footprint is reportable
    lda.partial_fit(train, steps=0, test_corpus=test)
    if lda.cfg.kernel_policy is not None:
        # a tuned (or explicit) policy is part of the run's identity —
        # log it so the trajectory is attributable (docs/tuning.md)
        print(f"kernel_policy={lda.cfg.kernel_policy}")
    memo = (lda.trainer.eng.memo if lda.trainer.kind == "single" else None)
    if memo is not None:
        print(f"memo_store={memo.kind} "
              f"footprint={memo.footprint_bytes() / 1e6:.2f}MB")
    # pad-waste visibility: log the per-bucket pad fractions once per run
    # so a packing/bucketing regression shows up in the training log
    stats = (lda.trainer.eng.bucket_stats
             if lda.trainer.kind == "single" else None)
    if stats is not None:
        per = " ".join(f"w{b['width']}:{b['docs']}d/{b['pad_frac']:.0%}"
                       for b in stats["per_bucket"])
        print(f"bucket_padding_stats slot_ratio={stats['slot_ratio']:.3f} "
              f"[{per}]")

    if lda.distributed is not None:
        lda.fit(rounds=args.rounds, eval_every=args.eval_every,
                verbose=True)
    else:
        lda.fit(epochs=args.epochs, eval_every=1, verbose=True)
        if args.stream:
            st = lda.trainer.eng.stream_padding_stats()
            per = " ".join(f"w{b['width']}:{b['docs']}d/{b['pad_frac']:.0%}"
                           for b in st["per_width"])
            print(f"stream_padding_stats pad_frac={st['pad_frac']:.3f} "
                  f"[{per}]")
        if args.bound:
            print("final exact bound:", lda.bound())
    if tel is not None:
        _report_telemetry(tel, args)
    if args.ckpt:
        print("saved", lda.save(args.ckpt))


def _build_telemetry(args):
    """Construct the run's ``repro.obs`` bundle from the CLI flags
    (None when no telemetry flag is set — the true-no-op path)."""
    if not (args.trace or args.metrics_json or args.watchdog != "off"):
        return None
    from repro.obs import ElboWatchdog, Telemetry
    if args.watchdog != "off":
        return Telemetry(watchdog=ElboWatchdog(
            policy=args.watchdog, check_every=args.watchdog_every))
    return Telemetry()


def _report_telemetry(tel, args) -> None:
    """End-of-run telemetry summary + the --trace/--metrics-json dumps."""
    m, wd = tel.metrics, tel.watchdog
    tokens = m.total("train.tokens")
    wall = sum(r["dur_us"] for r in tel.trace.records
               if r["type"] == "span" and r["name"] == "train/update") / 1e6
    rate = f"{tokens / wall:,.0f} tok/s" if wall > 0 else "n/a"
    st = wd.status()
    wd_line = ("off" if not st["enabled"] else
               f"{st['policy']} checks={st['checks']} "
               f"violations={st['violations']} "
               f"{'OK' if st['ok'] else 'VIOLATED'}")
    print(f"telemetry: tokens={tokens:,.0f} update_time={wall:.2f}s "
          f"({rate}) spans={tel.trace.num_records} watchdog={wd_line}")
    if args.trace:
        n = tel.trace.dump_jsonl(args.trace)
        print(f"trace: wrote {n} records to {args.trace}")
    if args.metrics_json:
        m.dump_json(args.metrics_json)
        print(f"metrics: wrote {args.metrics_json}")


def main_lm(args) -> None:
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.optim import adamw, cosine_schedule, iag
    from repro.training import TrainState, make_train_step

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced(seq_len_hint=args.seq)
    rng = np.random.default_rng(args.seed)
    params = T.init_params(cfg, jax.random.key(args.seed))
    n = sum(x.size for x in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n / 1e6:.2f}M")
    if args.optimizer == "iag":
        opt = iag(args.lr, num_shards=args.iag_shards)
    else:
        opt = adamw(cosine_schedule(args.lr, 10, args.steps))
    state = TrainState(params, opt.init(params), jnp.zeros((), jnp.int32))
    if args.optimizer == "iag":
        def step_fn(state, batch, shard):
            def lfn(p):
                return T.loss_fn(cfg, p, batch)
            (loss, m), g = jax.value_and_grad(lfn, has_aux=True)(state.params)
            upd, os_ = opt.update(g, state.opt_state, state.params,
                                  shard=shard)
            from repro.optim import apply_updates
            return TrainState(apply_updates(state.params, upd), os_,
                              state.step + 1), m
        step = jax.jit(step_fn)
    else:
        step = jax.jit(make_train_step(cfg, opt))

    def sample_batch():
        shape = ((args.batch, args.seq, cfg.num_codebooks)
                 if cfg.modality == "audio" else (args.batch, args.seq))
        toks = rng.integers(0, cfg.vocab_size, shape)
        batch = {"tokens": jnp.asarray(toks)}
        lab_len = args.seq + (cfg.num_patches if cfg.modality == "vision"
                              else 0)
        lab_shape = ((args.batch, lab_len, cfg.num_codebooks)
                     if cfg.modality == "audio" else (args.batch, lab_len))
        batch["labels"] = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                                   lab_shape))
        if cfg.modality == "vision":
            batch["vision_embeds"] = jnp.asarray(rng.normal(
                0, 1, (args.batch, cfg.num_patches, cfg.d_model))
                .astype(np.float32))
        return batch

    t0 = time.perf_counter()
    for s in range(args.steps):
        batch = sample_batch()
        if args.optimizer == "iag":
            state, metrics = step(state, batch,
                                  jnp.asarray(s % args.iag_shards))
        else:
            state, metrics = step(state, batch)
        if (s + 1) % args.log_every == 0:
            dt = time.perf_counter() - t0
            print(f"step={s + 1} loss={float(metrics['loss']):.4f} "
                  f"ce={float(metrics['ce']):.4f} "
                  f"steps_per_s={(s + 1) / dt:.2f}")
    if args.ckpt:
        from repro.checkpoint import save_checkpoint
        save_checkpoint(args.ckpt, state.params, step=args.steps)
        print("saved", args.ckpt)


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="mode", required=True)

    lda = sub.add_parser("lda")
    lda.add_argument("--algo", default="ivi",
                     choices=["mvi", "svi", "ivi", "sivi", "divi"])
    lda.add_argument("--corpus", default="small")
    lda.add_argument("--scale", type=float, default=1.0)
    lda.add_argument("--topics", type=int, default=50)
    lda.add_argument("--batch", type=int, default=32)
    lda.add_argument("--epochs", type=int, default=5)
    lda.add_argument("--rounds", type=int, default=50)
    lda.add_argument("--workers", type=int, default=4)
    lda.add_argument("--staleness", type=int, default=1)
    lda.add_argument("--delay-prob", type=float, default=0.0)
    lda.add_argument("--estep-iters", type=int, default=60)
    lda.add_argument("--backend", default="gather",
                     choices=["gather", "dense", "pallas"])
    lda.add_argument("--memo-store", default="dense",
                     choices=["dense", "chunked", "gamma"],
                     help="π-memo representation for ivi/sivi "
                          "(docs/estep.md)")
    lda.add_argument("--chunk-docs", type=int, default=8192,
                     help="documents per host-store chunk")
    lda.add_argument("--bucketed", action="store_true",
                     help="length-bucketed epoch batching (svi/ivi/sivi)")
    lda.add_argument("--stream", action="store_true",
                     help="ragged streaming ingest through a UCI DocStream "
                          "(no padded corpus resident; docs/streaming.md)")
    lda.add_argument("--docword", default=None,
                     help="existing UCI docword(.gz) file to stream "
                          "(default: write the synthetic corpus out once)")
    lda.add_argument("--eval-every", type=int, default=5)
    lda.add_argument("--bound", action="store_true")
    lda.add_argument("--seed", type=int, default=0)
    lda.add_argument("--ckpt", default=None,
                     help="save a manifest checkpoint directory here "
                          "(full incremental state; repro.lda.ckpt)")
    lda.add_argument("--tune-store", default=None, metavar="PATH",
                     help="repro.tune policy store of autotuned kernel "
                          "policies (docs/tuning.md); a hit replaces the "
                          "built-in tile defaults, a miss changes nothing")
    lda.add_argument("--resume", default=None,
                     help="resume from a --ckpt manifest (bit-equal "
                          "continuation); algo/store flags then come from "
                          "the checkpoint")
    lda.add_argument("--trace", default=None, metavar="PATH",
                     help="record a repro.obs span trace and write it as "
                          "JSONL here (docs/observability.md)")
    lda.add_argument("--metrics-json", default=None, metavar="PATH",
                     help="write the run's metrics-registry snapshot here")
    lda.add_argument("--watchdog", default="off",
                     choices=["off", "warn", "raise"],
                     help="ELBO-monotonicity watchdog policy on the "
                          "incremental path (armed once init mass retires)")
    lda.add_argument("--watchdog-every", type=int, default=0,
                     help="check the memoized bound every N updates "
                          "(O(corpus) each; 0 = only at evaluations)")

    lm = sub.add_parser("lm")
    lm.add_argument("--arch", required=True)
    lm.add_argument("--reduced", action="store_true")
    lm.add_argument("--steps", type=int, default=100)
    lm.add_argument("--batch", type=int, default=4)
    lm.add_argument("--seq", type=int, default=128)
    lm.add_argument("--lr", type=float, default=3e-4)
    lm.add_argument("--optimizer", default="adamw", choices=["adamw", "iag"])
    lm.add_argument("--iag-shards", type=int, default=8)
    lm.add_argument("--log-every", type=int, default=10)
    lm.add_argument("--seed", type=int, default=0)
    lm.add_argument("--ckpt", default=None)

    args = ap.parse_args()
    if args.mode == "lda":
        main_lda(args)
    else:
        main_lm(args)


if __name__ == "__main__":
    main()
