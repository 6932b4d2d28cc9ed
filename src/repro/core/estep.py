"""Batched variational E-step for LDA, behind one backend contract.

Every engine (MVI / SVI / IVI / S-IVI / D-IVI) consumes the E-step through
``EStepBackend`` — the single protocol all formulations implement:

* ``solve(cfg, exp_elog_beta, batch, gamma0) -> EStepResult`` — run the
  per-document fixed point (Alg. 1 lines 4–7) on a padded BOW mini-batch.
* ``solve_correction(cfg, exp_elog_beta, batch, old_pi, visited)`` — the
  IVI hot path: E-step **plus** the subtract-old/add-new memo correction
  Σ_d cnt·(π_new − π_old) scattered into (V, K), with γ warm-started from
  the memo for visited documents.

Four backends:

* ``gather`` — token-aligned: gathers rows of exp(E[ln φ]) at the batch's
  token ids, shape (B, L, K). Memory-proportional to batch token count;
  the default on CPU and for the engines' correctness paths.
* ``dense`` — densifies the mini-batch into a count matrix C (B, V) so one
  fixed-point sweep is two MXU matmuls: the pure-jnp oracle of the kernels.
* ``pallas`` — the TPU kernels (`repro.kernels.ops`): the whole γ fixed
  point is ONE fused ``pallas_call`` (γ/Eθ resident in VMEM scratch, Eφ
  streamed once per sweep via the V grid, in-kernel convergence flag), and
  ``solve_correction`` emits token-aligned π and the (V, K) correction
  from the segment-sum ``memo_delta`` pair — a token-π kernel tiling
  (B, L) and a V-chunk scatter — with no (B, L, K) jnp intermediates and
  no dense (nb, V, K) scatter partials.
* ``csr`` — the width-free CSR kernels behind the padded contract (a
  (B, L) batch flattens losslessly to a token stream), so the same
  equivalence tests pin them against gather/dense.

Every backend also implements the **flat-token contract**
(``solve_tokens`` / ``solve_correction_tokens`` over a ``CSRTokenBatch``
— a concatenated (T,) token stream with per-token segment ids): the jnp
``segment_sum`` reference by default, the Pallas CSR kernels on the
``pallas``/``csr`` backends. That is the path the CSR stream pipeline and
ragged serving consume — zero padding, one compiled entry for every
document-length mix.

All backends return the converged document-topic parameter γ and the
memoized responsibilities π in token layout — (B, L, K) on the padded
contract, (T, K) on the flat one; both are the quantity IVI stores.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.math import exp_dirichlet_expectation
from repro.core.types import LDAConfig

_EPS = 1e-30  # fp32-safe (1e-100 underflows to 0)


class BowBatch(NamedTuple):
    """A padded unique-token bag-of-words mini-batch (both (B, L))."""

    token_ids: jax.Array
    counts: jax.Array


class CSRTokenBatch(NamedTuple):
    """A flat CSR mini-batch: every document's tokens concatenated.

    ``segments[t]`` is the local document row owning token ``t``; padding
    tokens carry segment 0 with count 0 (inert in every reduction). The
    zero-padding twin of ``BowBatch`` — same fixed point, token layout
    (T,) instead of (B, L)."""

    token_ids: jax.Array  # (T,) int32
    counts: jax.Array     # (T,) float32
    segments: jax.Array   # (T,) int32 in [0, B)


class EStepResult(NamedTuple):
    gamma: jax.Array      # (B, K)
    pi: jax.Array         # (B, L, K) token-aligned responsibilities
                          # (flat-token paths: (T, K))
    sstats: jax.Array     # (V, K) Σ_d Σ_l cnt·π scattered at token ids
    iters: jax.Array      # (tiles,) int32 fixed-point sweeps each tile
                          # ran: the B-tiles of the padded kernel; the jnp
                          # backends and the CSR kernel run one tile


def _fixed_point(cfg: LDAConfig, update_fn, gamma0: jax.Array):
    """Run γ ← update(γ) until mean |Δγ| < tol or max_iters; the sweeps
    run come back as one tile's (1,) count."""

    def cond(carry):
        _, delta, it = carry
        return jnp.logical_and(delta > cfg.estep_tol, it < cfg.estep_max_iters)

    def body(carry):
        gamma, _, it = carry
        gamma_new = update_fn(gamma)
        delta = jnp.abs(gamma_new - gamma).mean()
        return gamma_new, delta, it + 1

    init = (gamma0, jnp.asarray(jnp.inf, gamma0.dtype), jnp.asarray(0, jnp.int32))
    gamma, _, iters = jax.lax.while_loop(cond, body, init)
    return gamma, iters[None]


def scatter_sstats(token_ids: jax.Array, weighted_pi: jax.Array,
                   vocab_size: int) -> jax.Array:
    """Scatter (B, L, K) token-aligned weighted responsibilities into (V, K)."""
    k = weighted_pi.shape[-1]
    flat_ids = token_ids.reshape(-1)
    flat_vals = weighted_pi.reshape(-1, k)
    return jnp.zeros((vocab_size, k), weighted_pi.dtype).at[flat_ids].add(flat_vals)


def quantize_pi(pi: jax.Array, pi_dtype: str) -> jax.Array:
    """Round π through the memo store's wire dtype (fp32 result)."""
    if pi_dtype == "float32":
        return pi
    return pi.astype(jnp.dtype(pi_dtype)).astype(jnp.float32)


def warm_start_gamma(cfg: LDAConfig, counts: jax.Array, old_pi: jax.Array,
                     visited: jax.Array) -> jax.Array:
    """Memo-derived γ₀ (Alg. 1 line 6) for visited docs, fresh otherwise.

    Coordinate ascent from the memoized point can only improve the bound,
    which is what makes IVI's monotonicity exact (fresh inits could hop to
    a worse local optimum of the per-document subproblem).
    """
    gamma_memo = cfg.alpha0 + jnp.einsum("blk,bl->bk", old_pi, counts)
    fresh = jnp.full_like(gamma_memo, cfg.alpha0 + 1.0)
    return jnp.where(visited[:, None], gamma_memo, fresh)


# ---------------------------------------------------------------------------
# flat-token (CSR) formulation
# ---------------------------------------------------------------------------

def segment_sum_docs(values: jax.Array, segments: jax.Array,
                     num_docs: int) -> jax.Array:
    """Σ over each document's tokens: (T, ...) → (num_docs, ...)."""
    return jax.ops.segment_sum(values, segments, num_segments=num_docs)


def scatter_sstats_flat(token_ids: jax.Array, weighted_pi: jax.Array,
                        vocab_size: int) -> jax.Array:
    """Scatter (T, K) flat weighted responsibilities into (V, K)."""
    k = weighted_pi.shape[-1]
    return jnp.zeros((vocab_size, k),
                     weighted_pi.dtype).at[token_ids].add(weighted_pi)


def warm_start_gamma_flat(cfg: LDAConfig, tok: CSRTokenBatch,
                          old_pi: jax.Array, visited: jax.Array) -> jax.Array:
    """``warm_start_gamma`` on the flat layout: the memo term is a segment
    sum of cnt·π_old over each document's tokens."""
    num_docs = visited.shape[0]
    gamma_memo = cfg.alpha0 + segment_sum_docs(
        tok.counts[:, None] * old_pi, tok.segments, num_docs)
    fresh = jnp.full_like(gamma_memo, cfg.alpha0 + 1.0)
    return jnp.where(visited[:, None], gamma_memo, fresh)


@partial(jax.jit, static_argnames=("cfg", "num_docs"))
def estep_csr_ref(cfg: LDAConfig, exp_elog_beta: jax.Array,
                  token_ids: jax.Array, counts: jax.Array,
                  segments: jax.Array, num_docs: int,
                  gamma0: Optional[jax.Array] = None) -> EStepResult:
    """jnp ``segment_sum`` reference for the CSR layout — the oracle the
    Pallas CSR kernels are pinned against.

    Same fixed point as ``estep_gather`` with the (B, L) einsums replaced
    by per-token gathers + segment sums over the flat stream; zero-count
    padding tokens (segment 0) are exact no-ops. Returns π in the FLAT
    (T, K) layout.
    """
    eb_tok = exp_elog_beta[token_ids]                  # (T, K)
    if gamma0 is None:
        gamma0 = jnp.full((num_docs, cfg.num_topics), cfg.alpha0 + 1.0,
                          jnp.float32)

    def update(gamma):
        etheta = exp_dirichlet_expectation(gamma)      # (B, K)
        p = (etheta[segments] * eb_tok).sum(-1) + _EPS  # (T,)
        acc = segment_sum_docs((counts / p)[:, None] * eb_tok,
                               segments, num_docs)
        return cfg.alpha0 + etheta * acc

    gamma, iters = _fixed_point(cfg, update, gamma0)

    etheta = exp_dirichlet_expectation(gamma)
    et_tok = etheta[segments]                          # (T, K)
    p = (et_tok * eb_tok).sum(-1) + _EPS
    pi = et_tok * eb_tok / p[:, None]                  # (T, K)
    pi = jnp.where(counts[:, None] > 0, pi, 0.0)
    sstats = scatter_sstats_flat(token_ids, counts[:, None] * pi,
                                 exp_elog_beta.shape[0])
    return EStepResult(gamma=gamma, pi=pi, sstats=sstats, iters=iters)


@partial(jax.jit, static_argnames=("cfg",))
def estep_gather(cfg: LDAConfig, exp_elog_beta: jax.Array,
                 token_ids: jax.Array, counts: jax.Array,
                 gamma0: Optional[jax.Array] = None) -> EStepResult:
    """Token-aligned batched E-step (Algorithm 1, lines 4–7).

    Args:
      exp_elog_beta: (V, K) exp(E[ln φ]).
      token_ids / counts: (B, L) padded unique-token BOW batch.
    """
    b = token_ids.shape[0]
    eb = exp_elog_beta[token_ids]                      # (B, L, K)
    if gamma0 is None:
        gamma0 = jnp.full((b, cfg.num_topics), cfg.alpha0 + 1.0, jnp.float32)

    def update(gamma):
        etheta = exp_dirichlet_expectation(gamma)      # (B, K)
        p = jnp.einsum("bk,blk->bl", etheta, eb) + _EPS
        return cfg.alpha0 + etheta * jnp.einsum("bl,blk->bk", counts / p, eb)

    gamma, iters = _fixed_point(cfg, update, gamma0)

    etheta = exp_dirichlet_expectation(gamma)
    p = jnp.einsum("bk,blk->bl", etheta, eb) + _EPS
    pi = etheta[:, None, :] * eb / p[:, :, None]       # (B, L, K)
    pi = jnp.where(counts[:, :, None] > 0, pi, 0.0)
    sstats = scatter_sstats(token_ids, counts[:, :, None] * pi,
                            exp_elog_beta.shape[0])
    return EStepResult(gamma=gamma, pi=pi, sstats=sstats, iters=iters)


def densify(token_ids: jax.Array, counts: jax.Array,
            vocab_size: int) -> jax.Array:
    """(B, L) BOW → dense count matrix C (B, V)."""
    b = token_ids.shape[0]
    c = jnp.zeros((b, vocab_size), counts.dtype)
    rows = jnp.broadcast_to(jnp.arange(b)[:, None], token_ids.shape)
    return c.at[rows.reshape(-1), token_ids.reshape(-1)].add(counts.reshape(-1))


@partial(jax.jit, static_argnames=("cfg",))
def estep_dense(cfg: LDAConfig, exp_elog_beta: jax.Array,
                token_ids: jax.Array, counts: jax.Array,
                gamma0: Optional[jax.Array] = None) -> EStepResult:
    """Dense-count E-step: one sweep = two (B,V)×(V,K) matmuls.

    The TPU-native formulation (DESIGN.md §2): MXU-friendly, no gathers.
    Matches ``estep_gather`` exactly (same fixed point, same π).
    """
    b = token_ids.shape[0]
    v = exp_elog_beta.shape[0]
    c = densify(token_ids, counts, v)                  # (B, V)
    if gamma0 is None:
        gamma0 = jnp.full((b, cfg.num_topics), cfg.alpha0 + 1.0, jnp.float32)

    def update(gamma):
        etheta = exp_dirichlet_expectation(gamma)      # (B, K)
        p = etheta @ exp_elog_beta.T + _EPS            # (B, V)
        return cfg.alpha0 + etheta * ((c / p) @ exp_elog_beta)

    gamma, iters = _fixed_point(cfg, update, gamma0)

    etheta = exp_dirichlet_expectation(gamma)
    p = etheta @ exp_elog_beta.T + _EPS
    sstats = exp_elog_beta * ((c / p).T @ etheta)      # (V, K)
    # token-aligned π for the memo, recovered by gathering the dense solution
    eb = exp_elog_beta[token_ids]
    p_tok = jnp.einsum("bk,blk->bl", etheta, eb) + _EPS
    pi = etheta[:, None, :] * eb / p_tok[:, :, None]
    pi = jnp.where(counts[:, :, None] > 0, pi, 0.0)
    return EStepResult(gamma=gamma, pi=pi, sstats=sstats, iters=iters)


# ---------------------------------------------------------------------------
# The backend contract
# ---------------------------------------------------------------------------

class EStepBackend:
    """One E-step contract for all engines.

    Subclasses implement ``solve``; ``solve_correction`` has a default
    jnp implementation in terms of ``solve`` (token-aligned subtract-old/
    add-new) that the Pallas backend overrides with fused kernels.
    """

    name: str = "abstract"

    def solve(self, cfg: LDAConfig, exp_elog_beta: jax.Array,
              batch: BowBatch,
              gamma0: Optional[jax.Array] = None) -> EStepResult:
        raise NotImplementedError

    def solve_correction(
            self, cfg: LDAConfig, exp_elog_beta: jax.Array, batch: BowBatch,
            old_pi: jax.Array, visited: jax.Array,
            pi_dtype: str = "float32",
    ) -> Tuple[jax.Array, jax.Array, EStepResult]:
        """E-step + memo correction: the hot path of IVI / S-IVI / D-IVI.

        ``pi_dtype`` is the memo store's wire dtype: π is rounded to it
        BEFORE the add-new side of the correction, so what ⟨m_vk⟩ adds is
        bit-identical to what the store holds (and will later subtract) —
        the accumulator-vs-memo identity stays an invariant instead of a
        per-visit rounding drift with low-precision stores.

        Returns (correction (V, K), first-visit word count, EStepResult);
        the result's π is the rounded value the caller must store.
        """
        ids, cnts = batch
        gamma0 = warm_start_gamma(cfg, cnts, old_pi, visited)
        res = self.solve(cfg, exp_elog_beta, batch, gamma0)
        pi = quantize_pi(res.pi, pi_dtype)
        # rebuild sstats from the ROUNDED π so every backend returns the
        # same result: the Pallas path scatters the quantized π into its
        # S_new (which doubles as sstats), and the low-precision invariant
        # above must hold for the sstats field too
        snew = scatter_sstats(ids, cnts[:, :, None] * pi, cfg.vocab_size)
        res = res._replace(pi=pi, sstats=snew)
        sold = scatter_sstats(ids, cnts[:, :, None] * old_pi, cfg.vocab_size)
        correction = snew - sold
        words_first = jnp.sum(jnp.where(~visited, cnts.sum(-1), 0.0))
        return correction, words_first, res

    def scatter_steps(self, cfg: LDAConfig, token_shape: Tuple[int, ...]
                      ) -> Optional[Tuple[int, int]]:
        """(dense, grid) steps of the memo correction's segment scatter
        kernel for a batch of ``token_shape`` ((B, L) padded or (T,)
        flat), or None where the backend runs no such kernel."""
        return None

    # -- flat-token (CSR) contract --------------------------------------
    def solve_tokens(self, cfg: LDAConfig, exp_elog_beta: jax.Array,
                     tok: CSRTokenBatch, num_docs: int,
                     gamma0: Optional[jax.Array] = None) -> EStepResult:
        """``solve`` on a flat CSR token stream; π comes back (T, K).

        Default: the jnp ``segment_sum`` reference. The Pallas backends
        override with the width-free CSR kernels.
        """
        return estep_csr_ref(cfg, exp_elog_beta, tok.token_ids, tok.counts,
                             tok.segments, num_docs, gamma0)

    def solve_correction_tokens(
            self, cfg: LDAConfig, exp_elog_beta: jax.Array,
            tok: CSRTokenBatch, old_pi: jax.Array, visited: jax.Array,
            pi_dtype: str = "float32",
    ) -> Tuple[jax.Array, jax.Array, EStepResult]:
        """``solve_correction`` on the flat layout (old_pi is (T, K)).

        Identical quantize-then-rescatter discipline as the padded
        contract, with the (B, L) scatters replaced by flat ones.
        """
        num_docs = visited.shape[0]
        gamma0 = warm_start_gamma_flat(cfg, tok, old_pi, visited)
        res = self.solve_tokens(cfg, exp_elog_beta, tok, num_docs, gamma0)
        pi = quantize_pi(res.pi, pi_dtype)
        snew = scatter_sstats_flat(tok.token_ids, tok.counts[:, None] * pi,
                                   cfg.vocab_size)
        res = res._replace(pi=pi, sstats=snew)
        sold = scatter_sstats_flat(tok.token_ids,
                                   tok.counts[:, None] * old_pi,
                                   cfg.vocab_size)
        correction = snew - sold
        doc_words = segment_sum_docs(tok.counts, tok.segments, num_docs)
        words_first = jnp.sum(jnp.where(~visited, doc_words, 0.0))
        return correction, words_first, res


class GatherBackend(EStepBackend):
    name = "gather"

    def solve(self, cfg, exp_elog_beta, batch, gamma0=None):
        return estep_gather(cfg, exp_elog_beta, batch.token_ids,
                            batch.counts, gamma0)


class DenseBackend(EStepBackend):
    name = "dense"

    def solve(self, cfg, exp_elog_beta, batch, gamma0=None):
        return estep_dense(cfg, exp_elog_beta, batch.token_ids,
                           batch.counts, gamma0)


class PallasBackend(EStepBackend):
    """Fused-kernel backend (`repro.kernels.ops`): one pallas_call per
    fixed point, memo correction via the segment-sum ``memo_delta`` pair —
    no (B, L, K) jnp intermediates and no dense (nb, V, K) scatter
    partials.

    ``delta_block_v`` is the scatter's second-level V-chunk size. ``None``
    (the default) defers to the VMEM-budget policy
    (`lda_estep.segment_scatter_blocks`): the largest lane-aligned chunk
    whose selector + accumulators fit the kernel's step budget, capped at
    the vocab so small vocabularies run V-resident in a single chunk. The
    rows are sorted by word id and each row tile is fetched only for the
    chunks its ids meet (``lda_estep.scatter_grid_steps``); overriding the
    chunk only makes sense for benchmark sweeps.

    ``policy`` (a ``repro.core.types.KernelPolicy``) pins every tile knob
    for instances constructed by the autotuner. The module singletons in
    ``_BACKENDS`` keep ``policy=None`` so the knobs resolve from
    ``cfg.kernel_policy`` (or the built-in defaults) per call — that is
    what lets one shared backend instance serve differently-tuned
    configs without retrace hazards: the policy rides on ``cfg``, which
    is already a jit static argument everywhere.
    """

    name = "pallas"

    def __init__(self, policy=None, delta_block_v: Optional[int] = None):
        self.policy = policy
        self.delta_block_v = delta_block_v  # None → VMEM-budget policy

    def solve(self, cfg, exp_elog_beta, batch, gamma0=None):
        from repro.kernels import ops as kops
        return kops.estep_pallas(cfg, exp_elog_beta, batch.token_ids,
                                 batch.counts, gamma0, policy=self.policy,
                                 delta_block_v=self.delta_block_v)

    def solve_correction(self, cfg, exp_elog_beta, batch, old_pi, visited,
                         pi_dtype="float32"):
        from repro.kernels import ops as kops
        return kops.memo_correction_pallas(cfg, exp_elog_beta,
                                           batch.token_ids, batch.counts,
                                           old_pi, visited,
                                           pi_dtype=pi_dtype,
                                           policy=self.policy,
                                           delta_block_v=self.delta_block_v)

    def solve_tokens(self, cfg, exp_elog_beta, tok, num_docs, gamma0=None):
        from repro.kernels import ops as kops
        return kops.estep_pallas_csr(cfg, exp_elog_beta, tok.token_ids,
                                     tok.counts, tok.segments,
                                     num_docs=num_docs, gamma0=gamma0,
                                     policy=self.policy,
                                     delta_block_v=self.delta_block_v)

    def scatter_steps(self, cfg, token_shape):
        from repro.kernels import ops as kops
        return kops.correction_scatter_steps(
            cfg, token_shape, policy=self.policy,
            delta_block_v=self.delta_block_v)

    def solve_correction_tokens(self, cfg, exp_elog_beta, tok, old_pi,
                                visited, pi_dtype="float32"):
        from repro.kernels import ops as kops
        return kops.memo_correction_pallas_csr(
            cfg, exp_elog_beta, tok.token_ids, tok.counts, tok.segments,
            old_pi, visited, pi_dtype=pi_dtype, policy=self.policy,
            delta_block_v=self.delta_block_v)


class CSRBackend(PallasBackend):
    """The width-free CSR kernels behind the PADDED ``solve`` /
    ``solve_correction`` contract.

    A (B, L) batch flattens losslessly to a (B·L,) token stream whose
    segment ids are the row indices — so this backend is the bridge that
    lets the existing backend-equivalence tests pin the CSR kernels
    against gather/dense on identical inputs. Flat-token callers (the
    CSR stream path, ragged serving) use the inherited
    ``solve_tokens``/``solve_correction_tokens`` directly.
    """

    name = "csr"

    @staticmethod
    def flatten(batch: BowBatch) -> CSRTokenBatch:
        b, l = batch.token_ids.shape
        segs = jnp.broadcast_to(jnp.arange(b, dtype=jnp.int32)[:, None],
                                (b, l))
        return CSRTokenBatch(batch.token_ids.reshape(-1),
                             batch.counts.reshape(-1), segs.reshape(-1))

    def scatter_steps(self, cfg, token_shape):
        # a padded (B, L) batch runs flattened to B·L tokens
        return super().scatter_steps(cfg, (math.prod(token_shape),))

    def solve(self, cfg, exp_elog_beta, batch, gamma0=None):
        b, l = batch.token_ids.shape
        res = self.solve_tokens(cfg, exp_elog_beta, self.flatten(batch),
                                num_docs=b, gamma0=gamma0)
        return res._replace(pi=res.pi.reshape(b, l, -1))

    def solve_correction(self, cfg, exp_elog_beta, batch, old_pi, visited,
                         pi_dtype="float32"):
        b, l = batch.token_ids.shape
        corr, words_first, res = self.solve_correction_tokens(
            cfg, exp_elog_beta, self.flatten(batch),
            old_pi.reshape(b * l, -1), visited, pi_dtype=pi_dtype)
        return corr, words_first, res._replace(pi=res.pi.reshape(b, l, -1))


_BACKENDS: Dict[str, EStepBackend] = {
    b.name: b for b in (GatherBackend(), DenseBackend(), PallasBackend(),
                        CSRBackend())
}


def get_backend(name: str) -> EStepBackend:
    try:
        return _BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown estep backend: {name!r} (have {sorted(_BACKENDS)})")


def estep(cfg: LDAConfig, exp_elog_beta: jax.Array, token_ids: jax.Array,
          counts: jax.Array, gamma0: Optional[jax.Array] = None) -> EStepResult:
    """Functional shim: dispatch on ``cfg.estep_backend``."""
    return get_backend(cfg.estep_backend).solve(
        cfg, exp_elog_beta, BowBatch(token_ids, counts), gamma0)
