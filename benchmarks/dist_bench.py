"""Distributed streaming D-IVI benchmark: shard ingest + scaling record.

The distributed stack feeds workers from ``ShardedDocStream`` views of one
``DocStream`` — no materialize-then-slice step exists any more. This bench
produces ``BENCH_dist.json``:

* a **stream-equality guard**: a stream-fed ``DIVIEngine`` must be
  BIT-equal to a materialized-corpus engine over several rounds, for both
  partitioners, and a mid-run trainer save→restore must continue
  bit-equally — the CI guard that keeps the streaming ingest path honest
  (these are asserted, not just recorded);
* **measured per-worker ingest throughput** on this host: documents and
  tokens per second through ``WorkerIngest.next_batch`` (shard iteration +
  single-rung packing), per partitioner — the host-side cost the round
  must overlap. Trend tracking only; CPU wall time is not a bar;
* a **modeled scaling record at the Arxiv shape** (Table 1 padded:
  V=141,952, K=128, 782k docs). Like the other benches, the asserted
  quantity is a deterministic structural model, not a flaky timing:

      t_estep(W)  = per-worker batch E-step HBM bytes / HBM_GBPS
                    (fixed S·B docs per worker per round — constant in W)
      t_ingest(W) = S·B docs · PULL_DOC_US   (overlapped with compute:
                    the ingest of round r+1 streams while r runs)
      t_psum(W)   = S · 2(W−1)/W · V·K·4 bytes / ICI_BW
                    (one ring all-reduce of the (V, K) correction per
                    sub-round — the protocol's single message)

      docs/s(W)   = W·S·B / (max(t_estep, t_ingest) + t_psum)

  The bar: modeled scaling efficiency docs/s(8) / (8 · docs/s(1)) ≥ 0.7.
  It holds because the psum term approaches a W-independent constant
  (2(W−1)/W → 2) that is small against the per-worker E-step at the Arxiv
  shape, and breaks if someone makes the round's communication grow with
  W (e.g. per-worker λ broadcasts instead of one reduction).
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.core import LDAConfig
from repro.launch.compile_cache import enable_compile_cache

# ---------------------------------------------------------------------------
# model constants (documented in docs/divi.md §benchmark)
# ---------------------------------------------------------------------------
HBM_GBPS = 1200.0       # TPU-class HBM stream rate for the E-step model
ICI_BW_GBPS = 50.0      # per-link interconnect rate for the psum ring
PULL_DOC_US = 15.0      # host-side pull+pack cost per ragged document

# Arxiv training shape (Table 1 padded)
ARXIV = dict(vocab=141_952, topics=128, width=128, batch=1024,
             staleness=1, iters=50, stream_bytes=2)


def modeled_estep_bytes(b: int, v: int, k: int, width: int, *, iters: int,
                        stream_bytes: int) -> float:
    """HBM bytes of one worker's (B, L) batch E-step + memo correction:
    the Eφ gather block and counts re-stream every fixed-point sweep
    (VMEM cannot hold them at Arxiv V), γ round-trips per sweep, and the
    (V, K) correction scatter streams once at the end."""
    gather = b * width * k * stream_bytes          # Eφ[ids] block
    counts = b * width * 4
    gamma = b * k * 4
    fixed_point = iters * (gather + counts + 2 * gamma)
    scatter = v * k * 4 + b * width * k * stream_bytes
    return float(fixed_point + scatter)


def modeled_scaling(workers: list[int]) -> dict:
    """docs/s vs W under the structural model above (deterministic)."""
    v, k, width = ARXIV["vocab"], ARXIV["topics"], ARXIV["width"]
    b, s = ARXIV["batch"], ARXIV["staleness"]
    t_estep = s * modeled_estep_bytes(b, v, k, width,
                                      iters=ARXIV["iters"],
                                      stream_bytes=ARXIV["stream_bytes"]) \
        / (HBM_GBPS * 1e9)
    t_ingest = s * b * PULL_DOC_US * 1e-6
    rows = []
    for w in workers:
        t_psum = s * (2 * (w - 1) / w) * v * k * 4 / (ICI_BW_GBPS * 1e9) \
            if w > 1 else 0.0
        t_round = max(t_estep, t_ingest) + t_psum
        rows.append({"workers": w, "t_estep_ms": t_estep * 1e3,
                     "t_ingest_ms": t_ingest * 1e3,
                     "t_psum_ms": t_psum * 1e3,
                     "docs_per_s": w * s * b / t_round})
    base = rows[0]["docs_per_s"]
    for r in rows:
        r["scaling_efficiency"] = r["docs_per_s"] / (r["workers"] * base)
    return {"shape": ARXIV, "per_worker_rows": rows,
            "efficiency_at_8": next(r["scaling_efficiency"] for r in rows
                                    if r["workers"] == 8)}


# ---------------------------------------------------------------------------
# guards + measurement (small corpus, CPU)
# ---------------------------------------------------------------------------

def stream_equality_guard() -> dict:
    """Stream-fed == materialized-fed, bit for bit, both partitioners;
    plus a mid-run save→restore continuation check."""
    import jax.numpy as jnp

    from repro.data import PAPER_CORPORA, make_corpus
    from repro.data.stream import CorpusDocStream
    from repro.dist import DIVIConfig, DIVIEngine
    from repro.lda.trainer import DIVITrainer

    train = make_corpus(PAPER_CORPORA["tiny"])
    cfg = LDAConfig(num_topics=8, vocab_size=250, estep_max_iters=30)
    out: dict = {}
    for part in ("range", "hash"):
        dcfg = DIVIConfig(num_workers=4, batch_size=8, staleness=2,
                          delay_prob=0.25, partitioner=part)
        e1 = DIVIEngine(cfg, dcfg, train, seed=2)
        e2 = DIVIEngine(cfg, dcfg, CorpusDocStream(train), seed=2)
        for _ in range(4):
            e1.run_round()
            e2.run_round()
        out[f"bit_equal_{part}"] = bool(
            jnp.array_equal(e1.lam, e2.lam)
            and jnp.array_equal(e1.shard.pi, e2.shard.pi))

    dcfg = DIVIConfig(num_workers=2, batch_size=7, staleness=2)
    a = DIVITrainer(cfg, dcfg, CorpusDocStream(train), seed=1)
    for _ in range(2):
        a.run_pass()
    meta, arrays = a.capture()
    b = DIVITrainer(cfg, dcfg, CorpusDocStream(train), seed=1)
    b.restore(meta, arrays)
    for _ in range(2):
        a.run_pass()
        b.run_pass()
    out["resume_bit_equal"] = bool(jnp.array_equal(a.state.lam, b.state.lam))
    return out


def measured_ingest(timed: bool = True) -> dict:
    """Per-worker ingest throughput through WorkerIngest.next_batch."""
    from repro.data import PAPER_CORPORA, make_corpus
    from repro.data.stream import CorpusDocStream, ShardedDocStream
    from repro.dist import WorkerIngest

    train = make_corpus(PAPER_CORPORA["medium"])
    stream = CorpusDocStream(train)
    out: dict = {"corpus_docs": int(train.num_docs)}
    for part in ("range", "hash"):
        sharded = ShardedDocStream(stream, 4, partitioner=part)
        ing = WorkerIngest(sharded.shard(0), 64)
        ing.next_batch()                       # warm the iterator
        if not timed:
            out[part] = {"warm_ok": True}
            continue
        t0 = time.perf_counter()
        while ing.docs_pulled < sharded.shard_sizes[0]:
            ing.next_batch()
        dt = time.perf_counter() - t0
        pulled = ing.docs_pulled - 64
        out[part] = {"docs_per_s": pulled / dt,
                     "tokens_per_s": ing.tokens_pulled / dt,
                     "pull_doc_us": dt / pulled * 1e6}
    return out


def dist_report(json_path: str | None, *, dryrun: bool = False) -> dict:
    record = {
        "bench": "dist",
        "stream_guard": stream_equality_guard(),
        "measured_ingest": measured_ingest(timed=not dryrun),
        "arxiv_scaling": modeled_scaling([1, 2, 4, 8, 16]),
    }
    if json_path:
        with open(json_path, "w") as f:
            json.dump(record, f, indent=2)
    return record


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="BENCH_dist.json",
                    help="where to write the distributed record")
    ap.add_argument("--dryrun", action="store_true",
                    help="CI mode: equality guards + modeled record only "
                         "(no timed ingest loop)")
    args = ap.parse_args()
    rec = dist_report(args.json, dryrun=args.dryrun)
    g, sc = rec["stream_guard"], rec["arxiv_scaling"]
    print(f"BENCH_dist -> {args.json}")
    print(f"  stream guard: range={g['bit_equal_range']} "
          f"hash={g['bit_equal_hash']} resume={g['resume_bit_equal']}")
    mi = rec["measured_ingest"]
    if "docs_per_s" in mi.get("range", {}):
        for part in ("range", "hash"):
            m = mi[part]
            print(f"  ingest[{part}]: {m['docs_per_s']:.0f} docs/s, "
                  f"{m['tokens_per_s']:.0f} tokens/s "
                  f"({m['pull_doc_us']:.1f} us/doc)")
    for r in sc["per_worker_rows"]:
        print(f"  arxiv model W={r['workers']:>2}: "
              f"{r['docs_per_s']:>9.0f} docs/s "
              f"(eff {r['scaling_efficiency']:.2f}, "
              f"psum {r['t_psum_ms']:.2f}ms)")
    assert g["bit_equal_range"] and g["bit_equal_hash"], \
        "stream-fed D-IVI diverged from the materialized-corpus reference"
    assert g["resume_bit_equal"], \
        "mid-run save->restore diverged from the uninterrupted run"
    assert sc["efficiency_at_8"] >= 0.7, \
        f"modeled 8-worker scaling efficiency {sc['efficiency_at_8']:.2f} " \
        "fell under the 0.7 bar"
