"""Kernel micro-benchmarks: the LDA E-step hotspot.

On this CPU container the Pallas kernels run in interpret mode (Python) —
their timings are NOT the TPU numbers. What we measure and report:
  * the pure-jnp dense sweep (the oracle workload XLA:CPU compiles) as the
    throughput reference;
  * the gather-formulation E-step (engine default);
  * kernel-vs-oracle max error, as a guard.

``estep_report`` (also ``python -m benchmarks.kernel_bench --estep-json``)
compares the OLD per-sweep Pallas path (`ops.estep_pallas_sweeps` + jnp
memo correction) against the FUSED path (`ops.memo_correction_pallas`,
fixed-point kernel + segment-sum memo_delta pair) and emits
``BENCH_estep.json``:

  * tokens/s and fixed-point sweep counts for both paths (interpret-mode
    wall time — a CPU proxy, kept for trend tracking only), plus an
    interpret-mode head-to-head of the segment-sum scatter against the
    retired one-hot kernel (`lda_estep.memo_delta_onehot`);
  * kernel-launch structure from the jaxpr (`hlo_analysis.
    pallas_call_sites`): the fused path must show ``under_loop == 0``
    (one pallas_call per fixed point, not one per sweep) and
    ``blk_intermediates == 0`` (no (B, L, K) jnp math);
  * a structural HBM-traffic model (`modeled_estep_hbm_bytes`, documented
    in docs/estep.md): per-sweep block fetches for the old path vs the
    fused pipeline's fetch-once-per-index-change behaviour plus bf16
    streaming — the CI bar is ≥2× fewer modeled bytes per E-step — and a
    transient-HBM model at the Arxiv shape
    (`modeled_scatter_transient_bytes`): the segment-sum scatter must
    allocate ≥4× less transient HBM than the one-hot partial baseline.

``csr_report`` (``--csr-json``) models the flat CSR token path
(`ops.memo_correction_pallas_csr`) against the bucketed padded path at a
Zipf-like long-tail document-length distribution: both packers consume the
SAME document sequence, each emitted batch is priced by its structural HBM
model, and the CI bar asserts the CSR path's modeled tokens/s advantage.
The record merges into BENCH_estep.json under the ``"csr"`` key.

Roofline expectations for the TPU kernel are in EXPERIMENTS.md §Roofline.
"""
from __future__ import annotations

import argparse
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import time_call
from repro.core import LDAConfig
from repro.core.estep import estep_dense, estep_gather
from repro.core.math import exp_dirichlet_expectation
from repro.data import PAPER_CORPORA, make_corpus
from repro.kernels import lda_estep, ops, ref
from repro.launch.hlo_analysis import pallas_call_sites
from repro.launch.compile_cache import enable_compile_cache


def rows():
    out = []
    rng = np.random.default_rng(0)
    for (b, v, k) in [(64, 4096, 128), (128, 8192, 128)]:
        c = jnp.asarray(rng.poisson(0.05, (b, v)).astype(np.float32))
        et = jnp.asarray(rng.gamma(1.0, 1.0, (b, k)).astype(np.float32))
        eb = jnp.asarray(rng.gamma(1.0, 1.0, (v, k)).astype(np.float32))
        sweep = jax.jit(lambda c_, e_, b_: ref.estep_sweep_ref(c_, e_, b_, 0.5))
        us = time_call(sweep, c, et, eb)
        flops = 2 * 2 * b * v * k
        out.append((f"kernel/sweep_jnp/B{b}_V{v}_K{k}", us,
                    f"gflops={flops / us / 1e3:.2f}"))
        got = lda_estep.estep_sweep(c, et, eb, 0.5)
        err = float(jnp.abs(got - sweep(c, et, eb)).max())
        out.append((f"kernel/sweep_pallas_interpret_err/B{b}_V{v}_K{k}", 0.0,
                    f"max_err={err:.2e}"))

    spec = PAPER_CORPORA["small"]
    corpus = make_corpus(spec, split="train", seed=0)
    cfg = LDAConfig(num_topics=64, vocab_size=spec.vocab_size,
                    estep_max_iters=30)
    lam = jax.random.gamma(jax.random.key(0), 100.0,
                           (spec.vocab_size, 64)) * 0.01
    eb = exp_dirichlet_expectation(lam, axis=0)
    ids, cnts = corpus.token_ids[:64], corpus.counts[:64]
    for name, fn in (("gather", estep_gather), ("dense", estep_dense)):
        us = time_call(lambda: fn(cfg, eb, ids, cnts))
        out.append((f"kernel/estep_{name}/B64", us,
                    f"tokens_per_s={float(cnts.sum()) / (us / 1e6):.0f}"))
    return out + estep_rows()


# ---------------------------------------------------------------------------
# fused vs per-sweep E-step: BENCH_estep.json
# ---------------------------------------------------------------------------

def modeled_estep_hbm_bytes(path: str, b: int, v: int, k: int, l: int,
                            iters: int, *, stream_bytes: int = 4,
                            block_b: int = 128, block_v: int = 512,
                            delta_block_b: int = 32) -> int:
    """Structural HBM traffic of one E-step + memo correction.

    Counts block fetches/stores the way the Pallas TPU pipeline issues
    them — a block is (re-)fetched only when its index-map output changes
    between consecutive grid steps (so with a V-resident layout, nv == 1,
    the fused kernel reads C once per B-tile and Eφ once per call, while
    the per-sweep path re-launches and therefore re-reads both every
    sweep). jnp intermediates count one write + one read each. Worked
    numbers in docs/estep.md.

    ``path``: "sweeps" (per-sweep kernels + jnp correction), "fused"
    (fixed-point kernel + segment-sum memo_delta pair) or "fused_onehot"
    (fixed-point kernel + the retired one-hot-partial memo_delta).
    """
    nb = -(-b // block_b)
    nv = -(-v // block_v)
    bk = b * k * 4
    if path == "sweeps":
        # per sweep: one pallas_call (C + nb·Eφ re-read) + γ out + jnp Eθ
        # recomputation (read γ, write Eθ, kernel reads Eθ)
        per_sweep = (b * v + nb * v * k) * 4 + 4 * bk
        sstats_kernel = (b * v + nb * v * k + v * k) * 4
        # jnp π/correction: ebg write+read×2, π write+read, Δ write+read,
        # old_pi read, scatter out (V, K)
        pi_path = 7 * b * l * k * 4 + 2 * v * k * 4
        return iters * per_sweep + sstats_kernel + pi_path
    if path not in ("fused", "fused_onehot"):
        raise ValueError(path)
    if nv == 1:
        c_elems, eb_elems = b * v, v * k              # fetched once
    else:
        c_elems = iters * b * v                       # re-streamed per sweep
        eb_elems = iters * nb * v * k
    fixed_point = (c_elems + eb_elems) * stream_bytes + 3 * bk
    bp = -(-b // delta_block_b) * delta_block_b       # padded B (ops wrapper)
    cube = bp * l * k * 4
    if path == "fused_onehot":
        # single kernel: ids+cnts+ebtok+old_pi in, π out, and the two
        # one-hot scatters as per-B-tile (nbd, V, K) partials — written
        # once per block by the kernel, then read + reduced to (V, K) by
        # XLA outside it. nbd counts the grid the kernel actually runs
        # (its VMEM guard halves the B-tile for long token axes).
        bb_eff = lda_estep.delta_effective_block_b(bp, l, k,
                                                   block_b=delta_block_b)
        nbd = bp // bb_eff
        delta = (2 * bp * l * 4 + 3 * cube
                 + 2 * (2 * nbd + 1) * v * k * 4 + bk)
        return fixed_point + delta
    # segment-sum pair: token-π kernel reads cnts + the Eφ token cube and
    # writes π once; the scatter fetches the π/old_pi rows (plus ids/cnts)
    # at every row tile it visits and writes each (V, K) mass exactly once
    # from VMEM — no partial spills at all.
    delta = (2 * bp * l * 4 + 2 * cube + bk           # token-π kernel
             + _scatter_row_bytes(bp * l, k, v)       # visited row tiles
             + 2 * v * k * 4)                         # S_new/S_old out
    return fixed_point + delta


def _scatter_row_bytes(rows: int, k: int, v: int) -> int:
    """Token-row HBM traffic of the segment scatter (π, old π, id, count
    per row): one fetch per step of the sorted visit list, plus the sort's
    read and write of every row."""
    _, tb = lda_estep.segment_scatter_blocks(k, v, True)
    _, grid = lda_estep.scatter_grid_steps(rows, k, v, True)
    row = 2 * k * 4 + 2 * 4
    return grid * min(tb, rows) * row + 2 * rows * row


def modeled_scatter_transient_bytes(path: str, b: int, v: int, k: int,
                                    l: int, *, delta_block_b: int = 32
                                    ) -> int:
    """Peak transient HBM the memo-correction scatter allocates: every
    intermediate between the E-step tensors and the (V, K) results, plus
    those results. The one-hot path's per-B-tile (nb, V, K) partial cubes
    dominate it (~2.3 GB at the Arxiv shape); the segment-sum path holds
    only the row-tile padding remainder and its word-sorted copies of the
    rows — the ≥4× Arxiv bar in
    BENCH_estep.json compares exactly these two numbers.
    """
    bp = -(-b // delta_block_b) * delta_block_b
    vp128 = -(-v // 128) * 128
    results = 2 * vp128 * k * 4                       # S_new + S_old
    if path == "onehot":
        bb_eff = lda_estep.delta_effective_block_b(bp, l, k,
                                                   block_b=delta_block_b)
        nbd = bp // bb_eff
        return 2 * nbd * vp128 * k * 4 + results
    if path == "segment":
        _, bl = lda_estep.pi_tile_shape(bp, l, k, block_b=delta_block_b)
        lp = -(-l // bl) * bl
        _, tb = lda_estep.segment_scatter_blocks(k, v, True)
        rows = bp * lp
        rows_p = -(-rows // tb) * tb
        pad_rows = rows_p - rows
        # sorted π/old π copies, plus keys, order and counts
        sort = rows_p * (2 * k + 3) * 4
        return 2 * (bp * (lp - l) + pad_rows) * k * 4 + sort + results
    raise ValueError(path)


def estep_report(json_path: str | None = None):
    """Old per-sweep vs fused Pallas E-step: the BENCH_estep record.

    The shape keeps Eφ V-resident (one V tile) — the regime the fused
    kernel targets; at larger V both paths stream Eφ per sweep and the
    fused win reduces to the removed γ/Eθ round-trips, the removed
    (B, L, K) jnp path and the bf16 streams.
    """
    b, v, k, l = 128, 4096, 128, 64
    block_v = 4096                         # V-resident: Eφ one VMEM block
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, v, (b, l)).astype(np.int32))
    cnts = jnp.asarray((rng.poisson(1.5, (b, l)) + 1).astype(np.float32))
    lam = jax.random.gamma(jax.random.key(0), 100.0, (v, k)) * 0.01
    eb = exp_dirichlet_expectation(lam, axis=0)
    old_pi = jnp.zeros((b, l, k), jnp.float32)
    visited = jnp.zeros((b,), bool)
    cfg = LDAConfig(num_topics=k, vocab_size=v, estep_max_iters=30,
                    estep_backend="pallas")
    cfg_bf16 = dataclasses.replace(cfg, estep_stream_dtype="bfloat16")
    tokens = float(cnts.sum())

    def legacy_correction(cfg_):
        """Pre-fusion path: per-sweep kernel + jnp subtract-old/add-new."""
        from repro.core.estep import scatter_sstats
        res = ops.estep_pallas_sweeps(cfg_, eb, ids, cnts,
                                      block_v=block_v)
        delta = cnts[:, :, None] * (res.pi - old_pi)
        return scatter_sstats(ids, delta, cfg_.vocab_size), res

    def fused_correction(cfg_, pi_dtype="float32"):
        corr, _, res = ops.memo_correction_pallas(cfg_, eb, ids, cnts,
                                                  old_pi, visited,
                                                  pi_dtype=pi_dtype,
                                                  block_v=block_v)
        return corr, res

    def fused_bf16_correction(cfg_):
        # bf16 streams AND the bf16 memo wire (the chunked-store config)
        return fused_correction(cfg_, pi_dtype="bfloat16")

    corr_old, res_old = legacy_correction(cfg)
    corr_new, _ = fused_correction(cfg)
    max_err = float(jnp.abs(corr_old - corr_new).max())

    record = {
        "shape": {"B": b, "V": v, "K": k, "L": l, "block_v": block_v},
        "correction_max_abs_err": max_err,
        "paths": {},
    }
    for name, fn, cfg_, stream in (
            ("sweeps", legacy_correction, cfg, 4),
            ("fused", fused_correction, cfg, 4),
            ("fused_bf16", fused_bf16_correction, cfg_bf16, 2)):
        us = time_call(lambda: fn(cfg_), warmup=1, iters=3)
        sites = pallas_call_sites(lambda: fn(cfg_))
        iters = int(fn(cfg_)[1].iters.max())  # each config's own convergence
        path_kind = "sweeps" if name == "sweeps" else "fused"
        modeled = modeled_estep_hbm_bytes(
            path_kind, b, v, k, l, iters, stream_bytes=stream,
            block_v=block_v)
        record["paths"][name] = {
            "interpret_us": us,
            "tokens_per_s_interpret": tokens / (us / 1e6),
            "sweeps": iters,
            "kernel_sites": sites,
            "modeled_hbm_bytes": modeled,
        }
    # the retired one-hot memo_delta, modeled at the same shape/sweeps —
    # the baseline the segment-sum scatter is measured against
    record["paths"]["fused_onehot_modeled"] = {
        "modeled_hbm_bytes": modeled_estep_hbm_bytes(
            "fused_onehot", b, v, k, l,
            record["paths"]["fused"]["sweeps"], block_v=block_v),
    }
    base = record["paths"]["sweeps"]["modeled_hbm_bytes"]
    for name in ("fused", "fused_bf16", "fused_onehot_modeled"):
        record["paths"][name]["hbm_ratio_vs_sweeps"] = (
            base / record["paths"][name]["modeled_hbm_bytes"])
    record["meets_2x_hbm_bar"] = (
        record["paths"]["fused"]["hbm_ratio_vs_sweeps"] >= 2.0)
    record["fused_single_launch_ok"] = (
        record["paths"]["fused"]["kernel_sites"]["under_loop"] == 0
        and record["paths"]["fused"]["kernel_sites"]["blk_intermediates"] == 0)

    # interpret-mode head-to-head of the two scatter formulations
    eb_tok = eb[ids]
    et = exp_dirichlet_expectation(res_old.gamma)
    record["scatter_interpret_us"] = {
        "segment": time_call(lambda: lda_estep.memo_delta(
            ids, cnts, eb_tok, et, v, old_pi=old_pi), warmup=1, iters=3),
        "onehot": time_call(lambda: lda_estep.memo_delta_onehot(
            ids, cnts, eb_tok, et, v, old_pi=old_pi), warmup=1, iters=3),
    }

    # transient-HBM model at the Arxiv production shape (Table 1): the
    # one-hot partial cubes vs the segment-sum path — the ≥4× bar
    ax = dict(b=256, v=141_952, k=128, l=128)
    one_t = modeled_scatter_transient_bytes("onehot", **ax)
    seg_t = modeled_scatter_transient_bytes("segment", **ax)
    record["arxiv_scatter"] = {
        "shape": {"B": ax["b"], "V": ax["v"], "K": ax["k"], "L": ax["l"]},
        "onehot_transient_bytes": one_t,
        "segment_transient_bytes": seg_t,
        "transient_ratio": one_t / seg_t,
        "meets_4x_transient_bar": one_t / seg_t >= 4.0,
    }
    if json_path:
        with open(json_path, "w") as f:
            json.dump(record, f, indent=2)
    return record


# ---------------------------------------------------------------------------
# CSR flat-token path vs bucketed padded path: the "csr" record
# ---------------------------------------------------------------------------

CSR_TOKENS_PER_S_BAR = 3.0


def modeled_estep_csr_hbm_bytes(t: int, b: int, v: int, k: int, iters: int,
                                *, stream_bytes: int = 4,
                                block_t: int = 512) -> int:
    """Structural HBM traffic of one CSR E-step + memo correction
    (`ops.memo_correction_pallas_csr`) on a (T,)-slot flat token stream.

    Same counting rules as ``modeled_estep_hbm_bytes``: a block is
    re-fetched only when its index map moves between consecutive grid
    steps. The CSR path never materializes the dense (B, V) count matrix
    — its variable cost scales with T, and ``ops.csr_effective_block_t``
    decides whether the Eφ token cube is resident (fetched once per call)
    or streamed once per sweep. Terms:

      * Eφ token gather: Eφ read once + ids read + the (T, Kp) cube write;
      * fixed point: cnts/segs + the cube, once or per-sweep, plus the
        γ0-in/γ-out/Eθ-out block triple;
      * memo pair: the token-π kernel (cnts/segs + cube re-read, Eθ in,
        π out) and the segment-sum scatter fetching the token rows
        (ids/cnts/π/old_pi) at each row tile it visits, S_new/S_old
        written once.
    """
    kp = -(-k // 128) * 128
    bp = -(-b // 8) * 8
    bt = ops.csr_effective_block_t(t, k, stream_bytes, block_t)
    tp = -(-t // bt) * bt
    resident = tp == bt                               # one (T, Kp) tile
    bk = bp * k * 4
    gather = v * k * 4 + tp * 4 + tp * kp * stream_bytes
    tok_fetch = tp * (4 + 4) + tp * kp * stream_bytes
    fixed_point = (1 if resident else iters) * tok_fetch + 3 * bp * kp * 4
    delta = (tp * (4 + 4) + tp * k * stream_bytes + bk + tp * k * 4
             + _scatter_row_bytes(tp, k, v)           # visited row tiles
             + 2 * v * k * 4)                         # S_new/S_old out
    return gather + fixed_point + delta


def _zipf_docs(rng, num_docs: int, vocab_size: int, cap: int):
    """A Zipf-like long-tail unique-token-length corpus: the regime where
    bucketed padding wastes the most (many tiny docs, a heavy tail)."""
    lengths = np.minimum(rng.zipf(1.35, num_docs), cap).astype(int)
    docs = []
    for n in lengths:
        ids = rng.choice(vocab_size, size=int(n), replace=False)
        cnts = 1.0 + rng.poisson(1.0, int(n))
        docs.append((np.sort(ids).astype(np.int32),
                     cnts.astype(np.float32)))
    return docs, lengths


def _csr_interpret_check():
    """Small-shape interpret-mode guard: the fused CSR kernel pair against
    the jnp segment-sum oracle, warm start and old-π subtraction included."""
    from repro.core.estep import (CSRTokenBatch, estep_csr_ref,
                                  scatter_sstats_flat, warm_start_gamma_flat)
    t, b, v, k = 768, 24, 1024, 32
    rng = np.random.default_rng(3)
    lens = np.minimum(rng.zipf(1.5, b), t // b).astype(int)
    segs_l, ids_l, cnts_l = [], [], []
    for d, n in enumerate(lens):
        segs_l += [d] * int(n)
        ids_l += list(rng.choice(v, size=int(n), replace=False))
        cnts_l += list(1.0 + rng.poisson(1.0, int(n)))
    live = len(ids_l)
    pad = t - live
    ids = jnp.asarray(np.asarray(ids_l + [0] * pad, np.int32))
    cnts = jnp.asarray(np.asarray(cnts_l + [0.0] * pad, np.float32))
    segs = jnp.asarray(np.asarray(segs_l + [0] * pad, np.int32))
    lam = jax.random.gamma(jax.random.key(1), 100.0, (v, k)) * 0.01
    eb = exp_dirichlet_expectation(lam, axis=0)
    old_pi = jnp.asarray(rng.dirichlet(np.ones(k), t).astype(np.float32))
    visited = jnp.asarray((np.arange(b) % 2).astype(bool))
    cfg = LDAConfig(num_topics=k, vocab_size=v, estep_max_iters=25,
                    estep_backend="csr")
    corr, _, res = ops.memo_correction_pallas_csr(
        cfg, eb, ids, cnts, segs, old_pi, visited)
    g0 = warm_start_gamma_flat(cfg, CSRTokenBatch(ids, cnts, segs),
                               old_pi, visited)
    ref = estep_csr_ref(cfg, eb, ids, cnts, segs, num_docs=b, gamma0=g0)
    corr_ref = (scatter_sstats_flat(ids, cnts[:, None] * ref.pi, v)
                - scatter_sstats_flat(ids, cnts[:, None] * old_pi, v))
    us = time_call(lambda: ops.memo_correction_pallas_csr(
        cfg, eb, ids, cnts, segs, old_pi, visited), warmup=1, iters=3)
    return {
        "shape": {"T": t, "B": b, "V": v, "K": k, "live_tokens": live},
        "correction_max_abs_err": float(jnp.abs(corr - corr_ref).max()),
        "gamma_max_rel_err": float(
            (jnp.abs(res.gamma - ref.gamma)
             / jnp.abs(ref.gamma)).max()),
        "interpret_us": us,
    }


def csr_report(json_path: str | None = None, *,
               bar: float = CSR_TOKENS_PER_S_BAR) -> dict:
    """CSR flat-token vs bucketed padded E-step at a long-tail length mix.

    Both packers consume the SAME Zipf-drawn document sequence; every
    emitted batch is priced with its path's structural HBM model. The
    asserted comparison runs at the paper's Arxiv production vocabulary
    (Table 1, the ``arxiv_scatter`` shape): there ``V·K·4`` overflows the
    VMEM residency budget, so the padded fixed point re-streams its dense
    (B, V) count matrix AND Eφ once per sweep, while the CSR path gathers
    Eφ once into a budget-sized T-resident token cube and never touches
    (V, K) again until the scatter — the structural win the flat layout
    exists for. A small-vocab entry (V-resident padded kernel, its best
    case) is recorded unasserted for context: zero-padding alone roughly
    breaks even there, which is WHY the bar is pinned to the production
    shape. Modeled tokens/s divides the same live-token total by each
    path's modeled HBM time. Merged into BENCH_estep.json as ``"csr"``.
    """
    from repro.obs.roofline import HW
    from repro.data.stream import BatchPacker

    d, k, batch, cap = 4096, 128, 64, 512
    v_prod, v_small = 141_952, 8192          # Table 1 Arxiv / V-resident
    token_budget = min(batch * 64, 8192)               # engine default
    sweeps = 20                                        # same fixed point
    rng = np.random.default_rng(7)
    docs, lengths = _zipf_docs(rng, d, v_small, cap)

    padded = BatchPacker(batch, max_width=cap, vocab_size=v_small)
    csr = BatchPacker(batch, max_width=cap, vocab_size=v_small,
                      layout="csr", token_budget=token_budget)
    padded_batches, csr_batches = [], []
    for pos, (ids, cnts) in enumerate(docs):
        for pk, out in ((padded, padded_batches), (csr, csr_batches)):
            b = pk.add(pos, ids, cnts)
            if b is not None:
                out.append(b)
    padded_batches += padded.flush()
    csr_batches += csr.flush()

    tokens = int(lengths.sum())                        # live unique slots
    bw = HW["hbm_bw"]

    def _compare(v: int) -> dict:
        # the padded wrapper's own residency promotion (one V tile — Eφ/C
        # fetched once per call — whenever (V, K) fits the budget), asked
        # of the wrapper instead of re-derived here
        _, eff_block_v, v_resident = ops.effective_fixed_point_blocks(
            batch, v, k, block_v=4096)
        padded_bytes = sum(
            modeled_estep_hbm_bytes("fused", pb.token_ids.shape[0], v, k,
                                    pb.width, sweeps, block_v=eff_block_v)
            for pb in padded_batches)
        # the engine pads the CSR doc axis to batch_size; the stream is
        # always exactly token_budget slots
        csr_bytes = sum(
            modeled_estep_csr_hbm_bytes(cb.token_budget, batch, v, k,
                                        sweeps)
            for cb in csr_batches)
        padded_tps = tokens / (padded_bytes / bw)
        csr_tps = tokens / (csr_bytes / bw)
        return {
            "V": v,
            "padded_modeled_hbm_bytes": padded_bytes,
            "csr_modeled_hbm_bytes": csr_bytes,
            "padded_modeled_tokens_per_s": padded_tps,
            "csr_modeled_tokens_per_s": csr_tps,
            "modeled_tokens_per_s_ratio": csr_tps / padded_tps,
            "padded_v_resident": v_resident,
        }

    production = _compare(v_prod)
    record = {
        "shape": {"docs": d, "K": k, "batch_size": batch,
                  "token_budget": token_budget, "sweeps": sweeps,
                  "length_distribution": f"zipf(a=1.35) clipped to {cap}",
                  "live_tokens": tokens},
        "padded": {
            "batches": len(padded_batches),
            "pad_frac": padded.padding_stats()["pad_frac"],
        },
        "csr": {
            "batches": len(csr_batches),
            "pad_frac": csr.padding_stats()["pad_frac"],
            "t_resident": ops.csr_effective_block_t(token_budget, k)
                          >= token_budget,
        },
        "production": production,
        "small_vocab_informational": _compare(v_small),
        "modeled_tokens_per_s_ratio":
            production["modeled_tokens_per_s_ratio"],
        "tokens_per_s_bar": bar,
        "meets_csr_bar":
            production["modeled_tokens_per_s_ratio"] >= bar,
        "interpret_check": _csr_interpret_check(),
    }
    if json_path:
        try:
            with open(json_path) as f:
                full = json.load(f)
        except (FileNotFoundError, json.JSONDecodeError):
            full = {}
        full["csr"] = record
        with open(json_path, "w") as f:
            json.dump(full, f, indent=2)
    return record


def estep_rows():
    rec = estep_report()
    out = []
    for name, p in rec["paths"].items():
        if "interpret_us" not in p:           # modeled-only baselines
            continue
        ratio = p.get("hbm_ratio_vs_sweeps", 1.0)
        out.append((f"kernel/estep_{name}/B128_V4096", p["interpret_us"],
                    f"sweeps={p['sweeps']} hbm_x={ratio:.2f} "
                    f"launches={p['kernel_sites']['total']} "
                    f"under_loop={p['kernel_sites']['under_loop']}"))
    ax = rec["arxiv_scatter"]
    out.append(("kernel/memo_delta_arxiv_transient", 0.0,
                f"onehot={ax['onehot_transient_bytes'] / 1e9:.2f}GB "
                f"segment={ax['segment_transient_bytes'] / 1e9:.2f}GB "
                f"ratio={ax['transient_ratio']:.1f}x"))
    return out


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--estep-json", default="BENCH_estep.json",
                    help="where to write the fused-vs-sweeps record")
    ap.add_argument("--csr-json", default=None, metavar="PATH",
                    help="also run the CSR-vs-bucketed model and merge the "
                         "'csr' record into PATH (usually the same "
                         "BENCH_estep.json)")
    args = ap.parse_args()
    rec = estep_report(args.estep_json)
    f, fb = rec["paths"]["fused"], rec["paths"]["fused_bf16"]
    oh = rec["paths"]["fused_onehot_modeled"]
    ax = rec["arxiv_scatter"]
    print(f"BENCH_estep -> {args.estep_json}")
    print(f"  sweeps path : {rec['paths']['sweeps']['sweeps']} sweeps, "
          f"{rec['paths']['sweeps']['modeled_hbm_bytes'] / 1e6:.1f} MB modeled")
    print(f"  fused (seg) : {f['sweeps']} sweeps, "
          f"{f['modeled_hbm_bytes'] / 1e6:.1f} MB "
          f"({f['hbm_ratio_vs_sweeps']:.2f}x fewer), "
          f"launches={f['kernel_sites']['total']} "
          f"under_loop={f['kernel_sites']['under_loop']} "
          f"blk_jnp={f['kernel_sites']['blk_intermediates']}")
    print(f"  fused bf16  : {fb['hbm_ratio_vs_sweeps']:.2f}x fewer bytes")
    print(f"  one-hot     : {oh['modeled_hbm_bytes'] / 1e6:.1f} MB modeled "
          f"({oh['hbm_ratio_vs_sweeps']:.2f}x vs sweeps, retired baseline)")
    print(f"  arxiv scatter transient: onehot "
          f"{ax['onehot_transient_bytes'] / 1e9:.2f} GB vs segment "
          f"{ax['segment_transient_bytes'] / 1e9:.3f} GB "
          f"({ax['transient_ratio']:.1f}x)")
    print(f"  correction max |Δ| = {rec['correction_max_abs_err']:.2e}")
    assert rec["meets_2x_hbm_bar"], "fused path lost the 2x HBM bar"
    assert rec["fused_single_launch_ok"], "fused path regressed to per-sweep"
    assert ax["meets_4x_transient_bar"], \
        "segment-sum scatter lost the 4x Arxiv transient-HBM bar"

    if args.csr_json:
        crec = csr_report(args.csr_json)
        pd, cs = crec["padded"], crec["csr"]
        pr, sm = crec["production"], crec["small_vocab_informational"]
        chk = crec["interpret_check"]
        print(f"BENCH_estep csr -> {args.csr_json}")
        print(f"  packing : padded {pd['batches']} batches "
              f"(pad_frac={pd['pad_frac']:.3f}) vs csr {cs['batches']} "
              f"batches (pad_frac={cs['pad_frac']:.3f}, "
              f"t_resident={cs['t_resident']})")
        print(f"  arxiv V={pr['V']}: csr "
              f"{pr['csr_modeled_hbm_bytes'] / 1e9:.1f} GB vs padded "
              f"{pr['padded_modeled_hbm_bytes'] / 1e9:.1f} GB modeled -> "
              f"{pr['modeled_tokens_per_s_ratio']:.2f}x tokens/s "
              f"(bar {crec['tokens_per_s_bar']:.1f}x)")
        print(f"  small V={sm['V']} (padded V-resident, informational): "
              f"{sm['modeled_tokens_per_s_ratio']:.2f}x")
        print(f"  interpret check: correction max |Δ| = "
              f"{chk['correction_max_abs_err']:.2e}, "
              f"gamma max rel = {chk['gamma_max_rel_err']:.2e}")
        assert crec["meets_csr_bar"], \
            "CSR flat-token path lost its modeled tokens/s bar vs bucketed"
        assert chk["correction_max_abs_err"] < 1e-2 \
            and chk["gamma_max_rel_err"] < 2e-3, \
            "CSR kernel pair drifted from the segment-sum oracle"
