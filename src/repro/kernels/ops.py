"""Jitted wrappers around the LDA Pallas kernels.

``estep_pallas`` is the fused drop-in replacement for
``repro.core.estep.estep_dense`` (select with
``LDAConfig(estep_backend="pallas")``): it pads (B, V, K) to the kernel
block grid, runs the WHOLE γ fixed point in one ``pallas_call``
(`lda_estep.estep_fixed_point`), and recovers token-aligned π and the
sufficient statistics with the segment-sum ``memo_delta`` pair (token-π
kernel + V-chunk scatter) — three kernel launches per E-step, none of
them inside a ``while`` loop, no (B, L, K) jnp intermediates beyond the
Eφ token gather that feeds the kernels, and no dense (nb, V, K) scatter
partials.

``memo_correction_pallas`` is the IVI hot path behind
``core.estep.PallasBackend.solve_correction``: the same three launches
also emit the subtract-old/add-new correction ``S_new − S_old`` directly.

``estep_pallas_sweeps`` keeps the pre-fusion formulation (one
``pallas_call`` per sweep inside ``lax.while_loop`` + a separate sstats
kernel + jnp π recovery) as the benchmark baseline — see
``benchmarks/kernel_bench.py`` and BENCH_estep.json.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.estep import (CSRTokenBatch, EStepResult, densify,
                              segment_sum_docs, warm_start_gamma,
                              warm_start_gamma_flat)
from repro.core.math import exp_dirichlet_expectation
from repro.core.types import DEFAULT_KERNEL_POLICY, KernelPolicy, LDAConfig
from repro.kernels import lda_estep
from repro.kernels.flash_attention import flash_attention

_EPS = 1e-30  # fp32-safe (1e-100 underflows to 0)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def resolve_policy(cfg: LDAConfig,
                   policy: Optional[KernelPolicy] = None) -> KernelPolicy:
    """The :class:`KernelPolicy` in effect for a kernel call.

    Precedence: an explicit ``policy`` argument wins, then
    ``cfg.kernel_policy`` (the store-resolved policy threaded through the
    engines), then the built-in defaults — which are bit-identical to the
    pre-autotune hard-coded knobs. Per-knob keyword arguments on the ops
    entry points override whatever this returns.
    """
    if policy is not None:
        return policy
    if cfg.kernel_policy is not None:
        return cfg.kernel_policy
    return DEFAULT_KERNEL_POLICY


def pad_inputs(c: jax.Array, eb: jax.Array, block_b: int, block_v: int,
               block_k: int = 128):
    """Pad C (B,V) and Eφ (V,K) to the kernel grid.

    Padding values keep the math exact: padded documents have zero counts
    (contribute nothing), padded vocabulary rows of Eφ are 1.0 so their
    phinorm contribution is harmless (their C is 0), padded topics get
    Eφ = 0 so they never win responsibilities — and padded γ columns are
    stripped before returning.
    """
    b, v = c.shape
    k = eb.shape[1]
    bp, vp, kp = (_round_up(b, block_b), _round_up(v, block_v),
                  _round_up(k, block_k))
    c = jnp.pad(c, ((0, bp - b), (0, vp - v)))
    # padded vocab rows get Eφ = 1.0 (NOT 0: a zero row makes the phinorm
    # P exactly 0 on that tile — the fp32 epsilon underflows — and C/P
    # would be 0/0); their C is 0 so they contribute nothing either way.
    eb = jnp.pad(eb, ((0, vp - v), (0, 0)), constant_values=1.0)
    eb = jnp.pad(eb, ((0, 0), (0, kp - k)))       # padded topics stay 0
    return c, eb, (b, v, k)


def _stream_cast(cfg: LDAConfig, x: jax.Array) -> jax.Array:
    """Cast a streamed kernel input to ``cfg.estep_stream_dtype``.

    bf16 halves the dominant HBM terms (C and Eφ) of the fixed point;
    accumulation stays fp32 in-kernel. Counts are exact in bf16 up to 256
    occurrences of a token in one document.
    """
    if cfg.estep_stream_dtype == "float32":
        return x
    if cfg.estep_stream_dtype == "bfloat16":
        return x.astype(jnp.bfloat16)
    raise ValueError(f"unknown estep_stream_dtype: {cfg.estep_stream_dtype}")


def _pad_rows(x: jax.Array, rows: int) -> jax.Array:
    pad = [(0, rows - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad)


# Eφ blocks at or under this size are made V-resident: one V tile, which the
# fixed point copies into VMEM once per B-tile instead of re-streaming C and
# Eφ every sweep. Chosen well under the 16 MB VMEM with the fp32 working set.
_V_RESIDENT_BYTES = 6 * 1024 * 1024


def effective_fixed_point_blocks(b: int, v: int, k: int, *,
                                 block_b: int = 128, block_v: int = 512,
                                 stream_bytes: int = 4
                                 ) -> Tuple[int, int, bool]:
    """The (block_b, block_v) grid the fused fixed point actually runs.

    ``_run_fixed_point`` promotes ``block_v`` to whole-V whenever the
    lane-aligned Eφ block fits the resident budget — one V tile means the
    kernel copies Eφ once per B-tile instead of once per sweep. The
    promotion used to be silent; this mirror of ``csr_effective_block_t``
    exposes it so tune records, the roofline HBM model, and telemetry
    report the tile that ran, never a requested-but-ignored ``block_v``.

    Returns ``(block_b, block_v, v_resident)``.
    """
    del b  # B only pads the row grid; it never changes the tile choice
    v_aligned = _round_up(v, 128)
    kp = _round_up(k, 128)
    if v_aligned * kp * stream_bytes <= _V_RESIDENT_BYTES:
        return block_b, max(block_v, v_aligned), True
    return block_b, block_v, False


def correction_scatter_steps(cfg: LDAConfig, token_shape: Tuple[int, ...], *,
                             policy: Optional[KernelPolicy] = None,
                             delta_block_v: Optional[int] = None
                             ) -> Tuple[int, int]:
    """(dense, grid) steps of the segment scatter in the memo correction.

    ``token_shape`` is the batch's (B, L) for ``memo_correction_pallas`` or
    (T,) for ``memo_correction_pallas_csr``; the rows, tiles and V chunks
    are those the call runs (``lda_estep.scatter_grid_steps``): dense
    ``chunks × row_tiles`` against the sorted visit list's ``row_tiles +
    chunks``. Host arithmetic on static shapes: nothing is read from the
    device.
    """
    pol = resolve_policy(cfg, policy)
    if len(token_shape) == 2:
        b, l = token_shape
        rows = lda_estep.scatter_rows((_round_up(b, pol.delta_block_b), l),
                                      block_l=pol.pi_block_l)
    else:
        rows = lda_estep.scatter_rows(token_shape, block_l=pol.pi_block_l)
    block_v = pol.delta_block_v if delta_block_v is None else delta_block_v
    return lda_estep.scatter_grid_steps(
        rows, cfg.num_topics, cfg.vocab_size, True, block_v=block_v,
        block_t=pol.scatter_block_t)


def _run_fixed_point(cfg: LDAConfig, exp_elog_beta: jax.Array,
                     token_ids: jax.Array, counts: jax.Array,
                     gamma0: Optional[jax.Array], block_b: int, block_v: int):
    """densify → pad → fused fixed-point kernel. Returns real-shape γ/Eθ
    and the sweeps each B-tile ran, (nb,)."""
    bsz = token_ids.shape[0]
    v = exp_elog_beta.shape[0]
    stream_bytes = 2 if cfg.estep_stream_dtype == "bfloat16" else 4
    # the resident tile must stay lane-aligned: a raw (unrounded) V as the
    # C lane / Eφ sublane dimension breaks the TPU (8, 128) tiling when V
    # is not a multiple of 128 — pad_inputs pads V up to this block size
    block_b, block_v, _ = effective_fixed_point_blocks(
        bsz, v, exp_elog_beta.shape[1], block_b=block_b, block_v=block_v,
        stream_bytes=stream_bytes)
    c = densify(token_ids, counts, v)
    cpad, ebpad, (b, _, k) = pad_inputs(c, exp_elog_beta, block_b, block_v)
    if gamma0 is None:
        gamma0 = jnp.full((bsz, cfg.num_topics), cfg.alpha0 + 1.0, jnp.float32)
    # pad γ topics/rows with α₀ (they stay exactly α₀: zero Eφ column and
    # zero counts respectively, so their update is a no-op)
    gpad = jnp.pad(gamma0, ((0, cpad.shape[0] - b), (0, ebpad.shape[1] - k)),
                   constant_values=cfg.alpha0)
    gamma, et, iters = lda_estep.estep_fixed_point(
        _stream_cast(cfg, cpad), _stream_cast(cfg, ebpad), gpad,
        cfg.alpha0, cfg.estep_tol, cfg.estep_max_iters, k_real=k,
        b_real=bsz, block_b=block_b, block_v=block_v)
    return gamma[:bsz, :k], et[:bsz, :k], iters.reshape(-1)


@partial(jax.jit, static_argnames=("cfg", "policy", "block_b", "block_v",
                                   "delta_block_b", "delta_block_v"))
def estep_pallas(cfg: LDAConfig, exp_elog_beta: jax.Array,
                 token_ids: jax.Array, counts: jax.Array,
                 gamma0: Optional[jax.Array] = None, *,
                 policy: Optional[KernelPolicy] = None,
                 block_b: Optional[int] = None,
                 block_v: Optional[int] = None,
                 delta_block_b: Optional[int] = None,
                 delta_block_v: Optional[int] = None) -> EStepResult:
    """Fused batched E-step: fixed-point kernel + memo_delta pair.

    Tile knobs resolve per ``resolve_policy`` (explicit kwarg > ``policy``
    > ``cfg.kernel_policy`` > defaults). ``delta_block_v`` is the
    scatter's V-chunk (None → the VMEM-budget policy
    ``lda_estep.segment_scatter_blocks``).
    """
    pol = resolve_policy(cfg, policy)
    block_b = pol.block_b if block_b is None else block_b
    block_v = pol.block_v if block_v is None else block_v
    delta_block_b = pol.delta_block_b if delta_block_b is None else delta_block_b
    delta_block_v = pol.delta_block_v if delta_block_v is None else delta_block_v
    bsz = token_ids.shape[0]
    gamma, et, iters = _run_fixed_point(cfg, exp_elog_beta, token_ids,
                                        counts, gamma0, block_b, block_v)
    eb_tok = exp_elog_beta[token_ids]                  # (B, L, K) kernel feed
    bp = _round_up(bsz, delta_block_b)
    pi, snew = lda_estep.memo_delta(
        _pad_rows(token_ids, bp), _pad_rows(counts, bp),
        _pad_rows(eb_tok, bp), _pad_rows(et, bp), exp_elog_beta.shape[0],
        block_b=delta_block_b, block_l=pol.pi_block_l,
        block_v=delta_block_v, block_t=pol.scatter_block_t)
    return EStepResult(gamma=gamma, pi=pi[:bsz], sstats=snew, iters=iters)


@partial(jax.jit, static_argnames=("cfg", "pi_dtype", "policy", "block_b",
                                   "block_v", "delta_block_b",
                                   "delta_block_v"))
def memo_correction_pallas(cfg: LDAConfig, exp_elog_beta: jax.Array,
                           token_ids: jax.Array, counts: jax.Array,
                           old_pi: jax.Array, visited: jax.Array, *,
                           pi_dtype: str = "float32",
                           policy: Optional[KernelPolicy] = None,
                           block_b: Optional[int] = None,
                           block_v: Optional[int] = None,
                           delta_block_b: Optional[int] = None,
                           delta_block_v: Optional[int] = None
                           ) -> Tuple[jax.Array, jax.Array, EStepResult]:
    """Fused IVI hot path: E-step + subtract-old/add-new correction.

    Returns (correction (V, K), first-visit word count, EStepResult) —
    exactly the `EStepBackend.solve_correction` contract. The correction
    is ``S_new − S_old`` from the segment-sum scatters of the
    ``memo_delta`` pair; the only (B, L, K) jnp array in the jaxpr is the
    Eφ token gather feeding the kernels (old_pi is an *input*, not an
    intermediate), and no (nb, V, K) scatter partials exist.
    ``delta_block_v`` is the scatter's V-chunk (None → the VMEM-budget
    policy ``lda_estep.segment_scatter_blocks``).
    """
    if pi_dtype not in ("float32", "bfloat16"):
        # the in-kernel quantize only implements the bf16 wire; refuse
        # rather than silently skip the round-trip and drift ⟨m_vk⟩
        raise ValueError(f"pallas memo correction supports pi_dtype "
                         f"float32|bfloat16, got {pi_dtype!r}")
    pol = resolve_policy(cfg, policy)
    block_b = pol.block_b if block_b is None else block_b
    block_v = pol.block_v if block_v is None else block_v
    delta_block_b = pol.delta_block_b if delta_block_b is None else delta_block_b
    delta_block_v = pol.delta_block_v if delta_block_v is None else delta_block_v
    bsz = token_ids.shape[0]
    gamma0 = warm_start_gamma(cfg, counts, old_pi, visited)
    gamma, et, iters = _run_fixed_point(cfg, exp_elog_beta, token_ids,
                                        counts, gamma0, block_b, block_v)
    eb_tok = exp_elog_beta[token_ids]                  # (B, L, K) kernel feed
    bp = _round_up(bsz, delta_block_b)
    pi, snew, sold = lda_estep.memo_delta(
        _pad_rows(token_ids, bp), _pad_rows(counts, bp),
        _pad_rows(eb_tok, bp), _pad_rows(et, bp), exp_elog_beta.shape[0],
        old_pi=_pad_rows(old_pi, bp), quantize=(pi_dtype == "bfloat16"),
        block_b=delta_block_b, block_l=pol.pi_block_l,
        block_v=delta_block_v, block_t=pol.scatter_block_t)
    correction = snew - sold
    words_first = jnp.sum(jnp.where(~visited, counts.sum(-1), 0.0))
    res = EStepResult(gamma=gamma, pi=pi[:bsz], sstats=snew, iters=iters)
    return correction, words_first, res


# ---------------------------------------------------------------------------
# CSR ragged path: the width-free flat-token E-step
# ---------------------------------------------------------------------------

def csr_effective_block_t(t: int, k: int, stream_bytes: int = 4,
                          block_t: int = 512) -> int:
    """The token tile the CSR fixed point actually runs.

    Mirrors the ``_V_RESIDENT_BYTES`` promotion of the dense path: when
    the whole (T, K) Eφ token stream fits the resident budget it becomes
    ONE tile, so the pipeline fetches it once per call instead of once
    per sweep — the default token budgets are chosen to sit inside this
    regime. Exposed so the BENCH_estep HBM model counts the same grid.
    """
    t_aligned = _round_up(t, 128)
    kp = _round_up(k, 128)
    if t_aligned * kp * stream_bytes <= _V_RESIDENT_BYTES:
        return t_aligned
    return min(block_t, t_aligned)


def _run_fixed_point_csr(cfg: LDAConfig, exp_elog_beta: jax.Array,
                         token_ids: jax.Array, counts: jax.Array,
                         segments: jax.Array, num_docs: int,
                         gamma0: Optional[jax.Array], block_t: int):
    """K-pad → Eφ token gather → fused CSR kernel. Returns real-shape γ/Eθ
    plus the (T, Kp) Eφ token gather the memo pair re-uses."""
    k = exp_elog_beta.shape[1]
    kp = _round_up(k, 128)
    t = token_ids.shape[0]
    stream_bytes = 2 if cfg.estep_stream_dtype == "bfloat16" else 4
    block_t = csr_effective_block_t(t, k, stream_bytes, block_t)
    ebp = jnp.pad(exp_elog_beta, ((0, 0), (0, kp - k)))  # padded topics → 0
    eb_tok = ebp[token_ids]                              # (T, Kp) kernel feed
    if gamma0 is None:
        gamma0 = jnp.full((num_docs, cfg.num_topics), cfg.alpha0 + 1.0,
                          jnp.float32)
    bp = _round_up(num_docs, 8)
    # pad γ topics/rows with α₀: token-free rows and zero-Eφ topics keep
    # exactly α₀ through every sweep (their update is a no-op)
    gpad = jnp.pad(gamma0, ((0, bp - num_docs), (0, kp - k)),
                   constant_values=cfg.alpha0)
    gamma, et, iters = lda_estep.estep_fixed_point_csr(
        counts, segments, _stream_cast(cfg, eb_tok), gpad,
        cfg.alpha0, cfg.estep_tol, cfg.estep_max_iters, k_real=k,
        b_real=num_docs, block_t=block_t)
    return (gamma[:num_docs, :k], et[:num_docs, :k], eb_tok,
            iters.reshape(-1))


@partial(jax.jit, static_argnames=("cfg", "num_docs", "policy", "block_t",
                                   "delta_block_v"))
def estep_pallas_csr(cfg: LDAConfig, exp_elog_beta: jax.Array,
                     token_ids: jax.Array, counts: jax.Array,
                     segments: jax.Array,
                     gamma0: Optional[jax.Array] = None, *,
                     num_docs: int,
                     policy: Optional[KernelPolicy] = None,
                     block_t: Optional[int] = None,
                     delta_block_v: Optional[int] = None) -> EStepResult:
    """Width-free flat-token E-step: CSR fixed point + CSR memo_delta.

    token_ids/counts/segments are the flat (T,) stream (zero-count
    padding tokens carry segment 0); π comes back in the same flat
    (T, K) layout. One compiled entry serves every document-length mix
    with the same (T, B) shape — no width in the jit key.
    """
    pol = resolve_policy(cfg, policy)
    block_t = pol.block_t if block_t is None else block_t
    delta_block_v = pol.delta_block_v if delta_block_v is None else delta_block_v
    gamma, et, eb_tok, iters = _run_fixed_point_csr(
        cfg, exp_elog_beta, token_ids, counts, segments, num_docs,
        gamma0, block_t)
    k = exp_elog_beta.shape[1]
    pi, snew = lda_estep.memo_delta_csr(
        token_ids, counts, segments, eb_tok[:, :k], et,
        exp_elog_beta.shape[0], block_t_pi=pol.pi_block_l,
        block_v=delta_block_v, block_t=pol.scatter_block_t)
    return EStepResult(gamma=gamma, pi=pi, sstats=snew, iters=iters)


@partial(jax.jit, static_argnames=("cfg", "pi_dtype", "policy", "block_t",
                                   "delta_block_v"))
def memo_correction_pallas_csr(cfg: LDAConfig, exp_elog_beta: jax.Array,
                               token_ids: jax.Array, counts: jax.Array,
                               segments: jax.Array, old_pi: jax.Array,
                               visited: jax.Array, *,
                               pi_dtype: str = "float32",
                               policy: Optional[KernelPolicy] = None,
                               block_t: Optional[int] = None,
                               delta_block_v: Optional[int] = None
                               ) -> Tuple[jax.Array, jax.Array, EStepResult]:
    """Fused CSR IVI hot path: flat E-step + subtract-old/add-new.

    The flat twin of ``memo_correction_pallas``: old_pi is (T, K) in the
    SAME flat token layout, and the correction comes from the unchanged
    ``_segment_scatter_kernel`` — flat token rows are its native input.
    """
    if pi_dtype not in ("float32", "bfloat16"):
        # the in-kernel quantize only implements the bf16 wire; refuse
        # rather than silently skip the round-trip and drift ⟨m_vk⟩
        raise ValueError(f"pallas memo correction supports pi_dtype "
                         f"float32|bfloat16, got {pi_dtype!r}")
    pol = resolve_policy(cfg, policy)
    block_t = pol.block_t if block_t is None else block_t
    delta_block_v = pol.delta_block_v if delta_block_v is None else delta_block_v
    num_docs = visited.shape[0]
    tok = CSRTokenBatch(token_ids, counts, segments)
    gamma0 = warm_start_gamma_flat(cfg, tok, old_pi, visited)
    gamma, et, eb_tok, iters = _run_fixed_point_csr(
        cfg, exp_elog_beta, token_ids, counts, segments, num_docs,
        gamma0, block_t)
    k = exp_elog_beta.shape[1]
    pi, snew, sold = lda_estep.memo_delta_csr(
        token_ids, counts, segments, eb_tok[:, :k], et,
        exp_elog_beta.shape[0], old_pi=old_pi,
        quantize=(pi_dtype == "bfloat16"), block_t_pi=pol.pi_block_l,
        block_v=delta_block_v, block_t=pol.scatter_block_t)
    correction = snew - sold
    doc_words = segment_sum_docs(counts, segments, num_docs)
    words_first = jnp.sum(jnp.where(~visited, doc_words, 0.0))
    res = EStepResult(gamma=gamma, pi=pi, sstats=snew, iters=iters)
    return correction, words_first, res


# ---------------------------------------------------------------------------
# legacy per-sweep path (benchmark baseline)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfg", "block_b", "block_v"))
def estep_pallas_sweeps(cfg: LDAConfig, exp_elog_beta: jax.Array,
                        token_ids: jax.Array, counts: jax.Array,
                        gamma0: Optional[jax.Array] = None, *,
                        block_b: int = 128, block_v: int = 512) -> EStepResult:
    """Pre-fusion E-step: one ``pallas_call`` per sweep inside a
    ``lax.while_loop``, jnp Eθ recomputation between sweeps, separate
    sstats kernel, jnp token-π recovery. Kept as the BENCH_estep baseline."""
    bsz = token_ids.shape[0]
    v = exp_elog_beta.shape[0]
    c = densify(token_ids, counts, v)
    cpad, ebpad, (b, _, k) = pad_inputs(c, exp_elog_beta, block_b, block_v)
    if gamma0 is None:
        gamma0 = jnp.full((bsz, cfg.num_topics), cfg.alpha0 + 1.0, jnp.float32)
    gpad = jnp.pad(gamma0, ((0, cpad.shape[0] - b), (0, ebpad.shape[1] - k)),
                   constant_values=cfg.alpha0)

    def elog_theta_exp(g):
        # digamma expectation over the *real* topics only; padded topics
        # carry exactly α₀ and a zero Eφ column, set their Eθ to 0.
        real = jnp.arange(g.shape[1]) < k
        gm = jnp.where(real, g, 0.0)
        s = gm.sum(-1, keepdims=True)
        et = jnp.exp(jax.scipy.special.digamma(jnp.maximum(g, 1e-10))
                     - jax.scipy.special.digamma(s))
        return jnp.where(real, et, 0.0)

    def cond(carry):
        _, delta, it = carry
        return jnp.logical_and(delta > cfg.estep_tol,
                               it < cfg.estep_max_iters)

    def body(carry):
        g, _, it = carry
        et = elog_theta_exp(g)
        g_new = lda_estep.estep_sweep(cpad, et, ebpad, cfg.alpha0,
                                      block_b=block_b, block_v=block_v)
        real = jnp.arange(g.shape[1]) < k
        g_new = jnp.where(real, g_new, cfg.alpha0)
        delta = jnp.abs(g_new - g).mean()
        return g_new, delta, it + 1

    init = (gpad, jnp.asarray(jnp.inf, jnp.float32), jnp.asarray(0, jnp.int32))
    gpad, _, iters = jax.lax.while_loop(cond, body, init)

    et = elog_theta_exp(gpad)
    spad = lda_estep.sstats(cpad, et, ebpad, block_b=block_b, block_v=block_v)
    gamma = gpad[:bsz, :k]
    sstats_out = spad[:v, :k]

    # token-aligned π for the IVI memo (identical to estep_dense)
    etheta = et[:bsz, :k]
    ebg = exp_elog_beta[token_ids]
    p_tok = jnp.einsum("bk,blk->bl", etheta, ebg) + _EPS
    pi = etheta[:, None, :] * ebg / p_tok[:, :, None]
    pi = jnp.where(counts[:, :, None] > 0, pi, 0.0)
    return EStepResult(gamma=gamma, pi=pi, sstats=sstats_out,
                       iters=iters[None])


def flash_mha(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True,
              scale: Optional[float] = None) -> jax.Array:
    """GQA-aware wrapper: q (B, S, H, hd), k/v (B, S, KV, hd) → (B, S, H, hd).

    Repeats KV heads to the query-head count, flattens (B, H) and pads S to
    the 128-block grid before invoking the flash kernel.
    """
    b, s, h, hd = q.shape
    kv = k.shape[2]
    rep = h // kv
    kf = jnp.repeat(k, rep, axis=2)
    vf = jnp.repeat(v, rep, axis=2)

    def flat(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, hd)

    blk = 128 if s >= 128 else s
    s_pad = ((s + blk - 1) // blk) * blk
    qf, kf, vf = flat(q), flat(kf), flat(vf)
    if s_pad != s:
        pad = ((0, 0), (0, s_pad - s), (0, 0))
        qf, kf, vf = jnp.pad(qf, pad), jnp.pad(kf, pad), jnp.pad(vf, pad)
    out = flash_attention(qf, kf, vf, causal=causal, scale=scale,
                          block_q=blk, block_k=blk)
    out = out[:, :s].reshape(b, h, s, hd).transpose(0, 2, 1, 3)
    return out
