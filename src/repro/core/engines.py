"""Single-host inference engines for LDA: MVI, SVI, IVI, S-IVI.

All four consume the E-step through the ``EStepBackend`` contract
(`repro.core.estep`) and the incremental engines access their π memos
through the pluggable ``MemoStore`` (`repro.core.memo`); they differ only
in how the global topic-word parameter λ is updated — exactly the contrast
the paper draws:

* **MVI**  (batch, Blei et al. 2003): λ = β₀ + Σ_d s_d after a full pass.
* **SVI**  (Hoffman et al. 2013, eq. 3): λ ← (1−ρ_t)λ + ρ_t(β₀ + (D/|B|)·s_B).
* **IVI**  (this paper, eq. 4 / Alg. 1): memoize per-document π; maintain the
  exact accumulator ⟨m_vk⟩ by subtract-old/add-new; λ = β₀ + ⟨m_vk⟩.
  No learning rate; monotone in the (memoized) ELBO once every document
  has been visited.
* **S-IVI** (eq. 5): the IVI correction inside a Robbins–Monro average:
  λ ← (1−ρ_t)λ + ρ_t(β₀ + ⟨m_vk⟩⁺). SAG-like; amenable to distribution.

Random-initialisation mass: the paper initialises β randomly (Alg. 1 l.1).
For the incremental engines we carry that mass explicitly (``init_mass``)
and retire each document's pro-rata share the first time it is visited, so
after one full pass ⟨m_vk⟩ == Σ_d s_d exactly and the monotonicity guarantee
is exact (cf. Neal & Hinton 1998 discussion of incremental-EM start-up).
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bound import (elbo_collapsed, elbo_collapsed_stream,
                              elbo_memoized_store, elbo_memoized_stream)
from repro.core import estep as estep_mod
from repro.core.estep import BowBatch, CSRTokenBatch, estep, get_backend
from repro.core.math import exp_dirichlet_expectation
from repro.core.memo import MemoStore, make_memo_store
from repro.core.metrics import effective_topics
from repro.core.predictive import log_predictive, split_heldout
from repro.core.types import (Corpus, GlobalState, LDAConfig, Memo,
                              init_global_state)
from repro.obs import NULL_TELEMETRY, as_telemetry

# The canonical global-state constructor set lives in ``repro.core.types``;
# these aliases keep the historical engine-level names working everywhere
# (single-host and ``repro.dist`` both build state through them).
EngineState = GlobalState
init_engine_state = init_global_state


# ---------------------------------------------------------------------------
# MVI — batch coordinate ascent
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(5, 6))
def mvi_scan(cfg: LDAConfig, eb: jax.Array, ids_b: jax.Array,
             cnts_b: jax.Array, doc_idx_b: jax.Array, gamma_buf: jax.Array,
             sstats: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Scan the E-step over stacked batches, accumulating Σ_d s_d.

    ids_b/cnts_b/doc_idx_b: (num_batches, B, ...). γ persists across epochs
    in ``gamma_buf`` (D+1, K): each document's E-step resumes from
    α₀ + Σ_l cnt·π of its previous visit — proper batch coordinate ascent
    in the sense of Neal & Hinton (1998), and the *same* warm-start
    reconstruction the incremental engines use. Without this, a
    ``estep_max_iters``-truncated E-step restarts from scratch every epoch
    while IVI resumes from its memo, and the two full-batch trajectories
    drift apart for reasons that have nothing to do with the incremental
    bookkeeping (see test_fullbatch_ivi_equals_mvi). Row D of ``gamma_buf``
    is the sentinel scratch slot the tail batch's padding writes to.
    """

    def body(carry, batch):
        acc, gbuf = carry
        ids, cnts, idx = batch
        res = estep(cfg, eb, ids, cnts, gbuf[idx])
        gbuf = gbuf.at[idx].set(
            cfg.alpha0 + jnp.einsum("blk,bl->bk", res.pi, cnts))
        return (acc + res.sstats, gbuf), None

    (sstats, gamma_buf), _ = jax.lax.scan(
        body, (sstats, gamma_buf), (ids_b, cnts_b, doc_idx_b))
    return sstats, gamma_buf


# ---------------------------------------------------------------------------
# SVI — stochastic natural gradient (eq. 3)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(1,))
def svi_step(cfg: LDAConfig, state: EngineState, ids: jax.Array,
             cnts: jax.Array, num_docs_total: jax.Array) -> EngineState:
    eb = exp_dirichlet_expectation(state.lam, axis=0)
    res = estep(cfg, eb, ids, cnts)
    scale = num_docs_total / ids.shape[0]
    lam_hat = cfg.beta0 + scale * res.sstats
    rho = cfg.rho(state.t + 1)
    lam = (1.0 - rho) * state.lam + rho * lam_hat
    return dataclasses.replace(state, lam=lam, t=state.t + 1)


@partial(jax.jit, static_argnames=("cfg", "num_docs"), donate_argnums=(1,))
def svi_step_csr(cfg: LDAConfig, state: EngineState, ids: jax.Array,
                 cnts: jax.Array, segs: jax.Array, batch_docs: jax.Array,
                 num_docs_total: jax.Array, *,
                 num_docs: int) -> EngineState:
    """Eq. 3 on a flat CSR token batch.

    ``num_docs`` is the static segment-id capacity (the engine pads it to
    ``batch_size``, so every batch — full, pre-emit-short or epoch tail —
    hits one compiled entry); ``batch_docs`` is the traced live-document
    count the natural-gradient scale divides by. Phantom padding docs own
    zero tokens, so they contribute exactly nothing to the sstats.
    """
    eb = exp_dirichlet_expectation(state.lam, axis=0)
    res = get_backend(cfg.estep_backend).solve_tokens(
        cfg, eb, CSRTokenBatch(ids, cnts, segs), num_docs=num_docs)
    scale = num_docs_total / batch_docs
    lam_hat = cfg.beta0 + scale * res.sstats
    rho = cfg.rho(state.t + 1)
    lam = (1.0 - rho) * state.lam + rho * lam_hat
    return dataclasses.replace(state, lam=lam, t=state.t + 1)


# ---------------------------------------------------------------------------
# IVI / S-IVI — incremental updates (eqs. 4 & 5)
# ---------------------------------------------------------------------------

def memo_correction(cfg: LDAConfig, eb: jax.Array, ids: jax.Array,
                    cnts: jax.Array, old_pi: jax.Array,
                    visited_rows: jax.Array, pi_dtype: str = "float32"):
    """E-step + subtract-old/add-new core shared by IVI, S-IVI and D-IVI.

    Dispatches to ``cfg.estep_backend``'s ``solve_correction`` — the jnp
    backends scatter the token-aligned delta, the Pallas backend fuses the
    whole thing into its kernels. The distributed engine (``repro.dist``)
    calls this same function for its workers, which is what keeps the
    single-host and distributed paths numerically interchangeable
    (test_divi_single_worker_round_equals_sivi_step).

    Returns (correction (V, K), first-visit word count, EStepResult).
    """
    return get_backend(cfg.estep_backend).solve_correction(
        cfg, eb, BowBatch(ids, cnts), old_pi, visited_rows, pi_dtype)


def retire_init_frac(init_frac: jax.Array, words_first: jax.Array,
                     num_words_total: jax.Array) -> jax.Array:
    """Retire the first-visit words' pro-rata share of the random-init mass.

    Snaps the fp32 subtraction residue to an exact zero once every document
    has been visited, so λ = β₀ + ⟨m_vk⟩ holds exactly afterwards (eq. 4).
    """
    frac = jnp.maximum(init_frac - words_first / num_words_total, 0.0)
    return jnp.where(frac < 1e-6, 0.0, frac)


def sivi_global_update(cfg: LDAConfig, state, corr: jax.Array,
                       frac: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Eq. 5 global step: λ ← (1−ρ_t)λ + ρ_t(β₀ + ⟨m_vk⟩⁺ + frac·init_mass).

    Elementwise in V, so it applies unchanged to the model-sharded rows of
    ``repro.dist`` — keeping the single-host and distributed master updates
    one code path. Returns (λ, ⟨m_vk⟩⁺); the caller bumps ``t``.
    """
    m_vk = state.m_vk + corr
    lam_hat = cfg.beta0 + m_vk + frac * state.init_mass
    rho = cfg.rho(state.t + 1)
    lam = (1.0 - rho) * state.lam + rho * lam_hat
    return lam, m_vk


def _incremental_core(cfg: LDAConfig, averaged: bool, state: EngineState,
                      ids: jax.Array, cnts: jax.Array, old_pi: jax.Array,
                      visited: jax.Array, num_words_total: jax.Array,
                      pi_dtype: str):
    """THE eq. 4 / eq. 5 update — every incremental entry point wraps it."""
    eb = exp_dirichlet_expectation(state.lam, axis=0)
    corr, words_first, res = memo_correction(cfg, eb, ids, cnts, old_pi,
                                             visited, pi_dtype)
    frac = retire_init_frac(state.init_frac, words_first, num_words_total)
    if averaged:
        lam, m_vk = sivi_global_update(cfg, state, corr, frac)
    else:
        m_vk = state.m_vk + corr
        lam = cfg.beta0 + m_vk + frac * state.init_mass
    state = dataclasses.replace(state, lam=lam, m_vk=m_vk, init_frac=frac,
                                t=state.t + 1)
    return state, res, eb


class UpdateAux(NamedTuple):
    """What an incremental update hands back besides the state and π."""

    exp_elog_beta: jax.Array   # (V, K) Eφ the E-step ran against
    sweeps: jax.Array          # (tiles,) int32 fixed-point sweeps per tile


@partial(jax.jit, static_argnames=("cfg", "averaged", "pi_dtype"),
         donate_argnums=(2, 5))
def incremental_update(cfg: LDAConfig, averaged: bool, state: EngineState,
                       ids: jax.Array, cnts: jax.Array, old_pi: jax.Array,
                       visited: jax.Array, num_words_total: jax.Array,
                       pi_dtype: str = "float32"):
    """One IVI (``averaged=False``, eq. 4) or S-IVI (eq. 5) global update.

    Pure in the memo: takes the gathered (π_old, visited) rows from a
    ``MemoStore`` and returns the new π for the host to write back —
    the store itself never crosses the jit boundary, which is what lets
    the bf16-chunked and γ-only stores live in host RAM. ``pi_dtype`` is
    the store's wire dtype: π is rounded through it before the add-new
    scatter so ⟨m_vk⟩ stays bit-consistent with the store's contents.

    Returns (state, π_new (B, L, K), ``UpdateAux``): Eφ, so γ-only stores
    can snapshot the λ-epoch the E-step ran against, and the sweeps each
    tile of the fixed point ran.
    """
    state, res, eb = _incremental_core(cfg, averaged, state, ids, cnts,
                                       old_pi, visited, num_words_total,
                                       pi_dtype)
    return state, res.pi, UpdateAux(eb, res.iters)


def _csr_gather_flat(old_pi: jax.Array, ix: jax.Array) -> jax.Array:
    """Doc-aligned memo rows (B, W, K) → token-aligned (T, K) via the
    host-built flat index; padding tokens carry the sentinel index B·W,
    which lands on the appended zero row."""
    b, w, k = old_pi.shape
    flat = jnp.concatenate([old_pi.reshape(b * w, k),
                            jnp.zeros((1, k), old_pi.dtype)])
    return flat[ix]


def _csr_scatter_flat(pi: jax.Array, ix: jax.Array, b: int,
                      w: int) -> jax.Array:
    """Inverse of ``_csr_gather_flat``: token-aligned π back onto the
    (B, W, K) memo wire. Padding tokens all target the sentinel row,
    which the slice drops; memo slots no token maps to stay zero —
    inert, since every memo consumer weights π by the (zero) count."""
    k = pi.shape[-1]
    buf = jnp.zeros((b * w + 1, k), pi.dtype)
    return buf.at[ix].set(pi)[: b * w].reshape(b, w, k)


@partial(jax.jit, static_argnames=("cfg", "averaged", "pi_dtype"),
         donate_argnums=(2,))
def incremental_update_csr(cfg: LDAConfig, averaged: bool,
                           state: EngineState, ids: jax.Array,
                           cnts: jax.Array, segs: jax.Array, ix: jax.Array,
                           old_pi: jax.Array, visited: jax.Array,
                           num_words_total: jax.Array,
                           pi_dtype: str = "float32"):
    """``incremental_update`` on a flat CSR token batch.

    Same eq. 4 / eq. 5 algebra, same quantize-then-rescatter memo wire —
    only the (B, L) token axes are replaced by one (T,) stream plus the
    flat index ``ix`` that maps each token slot onto its (doc, position)
    memo cell. The memo stays doc-aligned (B, W, K): old π rows are
    gathered through ``ix`` on the way in and the new π is scattered back
    through it on the way out, so every ``MemoStore`` works unchanged.
    """
    b, w, _ = old_pi.shape
    eb = exp_dirichlet_expectation(state.lam, axis=0)
    old_flat = _csr_gather_flat(old_pi, ix)
    corr, words_first, res = get_backend(
        cfg.estep_backend).solve_correction_tokens(
            cfg, eb, CSRTokenBatch(ids, cnts, segs), old_flat, visited,
            pi_dtype)
    frac = retire_init_frac(state.init_frac, words_first, num_words_total)
    if averaged:
        lam, m_vk = sivi_global_update(cfg, state, corr, frac)
    else:
        m_vk = state.m_vk + corr
        lam = cfg.beta0 + m_vk + frac * state.init_mass
    state = dataclasses.replace(state, lam=lam, m_vk=m_vk, init_frac=frac,
                                t=state.t + 1)
    new_pi = _csr_scatter_flat(res.pi, ix, b, w)
    return state, new_pi, UpdateAux(eb, res.iters)


def _raw_memo_step(cfg: LDAConfig, averaged: bool, state: EngineState,
                   memo: Memo, ids: jax.Array, cnts: jax.Array,
                   doc_idx: jax.Array, num_words_total: jax.Array):
    """Raw-``Memo`` convenience wrapper over the same core."""
    state, res, _ = _incremental_core(
        cfg, averaged, state, ids, cnts, memo.pi[doc_idx],
        memo.visited[doc_idx], num_words_total, "float32")
    memo = Memo(pi=memo.pi.at[doc_idx].set(res.pi),
                visited=memo.visited.at[doc_idx].set(True))
    return state, memo


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(1, 2))
def ivi_step(cfg: LDAConfig, state: EngineState, memo: Memo, ids: jax.Array,
             cnts: jax.Array, doc_idx: jax.Array,
             num_words_total: jax.Array) -> tuple[EngineState, Memo]:
    """Algorithm 1: partial E-step, then exact incremental M-step (eq. 4)."""
    return _raw_memo_step(cfg, False, state, memo, ids, cnts, doc_idx,
                          num_words_total)


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(1, 2))
def sivi_step(cfg: LDAConfig, state: EngineState, memo: Memo, ids: jax.Array,
              cnts: jax.Array, doc_idx: jax.Array,
              num_words_total: jax.Array) -> tuple[EngineState, Memo]:
    """Eq. 5: the incremental estimate inside a Robbins–Monro average."""
    return _raw_memo_step(cfg, True, state, memo, ids, cnts, doc_idx,
                          num_words_total)


# ---------------------------------------------------------------------------
# Host-side driver
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class History:
    docs_seen: List[int] = dataclasses.field(default_factory=list)
    elbo: List[float] = dataclasses.field(default_factory=list)
    lpp: List[float] = dataclasses.field(default_factory=list)
    wall: List[float] = dataclasses.field(default_factory=list)


class LDAEngine:
    """Host driver: shuffling, mini-batching, evaluation, timing.

    ``corpus`` may be a padded ``Corpus`` (the materialized path) or a
    ``repro.data.stream.DocStream`` — ragged documents pulled and packed
    per mini-batch (`repro.data.stream.BatchPacker`), so no ``(D, L)``
    padded corpus is ever resident. One pass over the stream is one epoch
    (stream order — a stream cannot be permuted); packing is
    bit-transparent, so a stream-fed run reproduces the materialized run's
    trajectory exactly under the same batch schedule
    (tests/test_stream_pipeline.py). MVI (full batch) and the γ-only
    store (π reconstructed from resident corpus rows) need the
    materialized corpus.

    ``memo_store`` selects the π-memo representation for the incremental
    engines: ``dense`` (device fp32 oracle), ``chunked`` (bf16 host
    chunks) or ``gamma`` (γ-only reconstruction — S-IVI only, the eq. 4
    exactness needs the true π). ``bucket_by_length=True`` batches each
    epoch inside length buckets (`repro.data.bow.bucket_corpus`), so
    E-step FLOPs and memo traffic scale with each bucket's own padding
    width instead of the corpus-wide maximum; ``bucket_stats`` then holds
    the per-bucket pad fractions (logged once per run by ``train.py``).
    Stream ingest packs by bucket width always.
    """

    def __init__(self, cfg: LDAConfig, corpus, *, algo: str,
                 batch_size: int = 64, seed: int = 0,
                 test_corpus: Optional[Corpus] = None,
                 memo_store: str = "dense", chunk_docs: int = 8192,
                 bucket_by_length: bool = False, layout: str = "padded",
                 token_budget: Optional[int] = None, telemetry=None,
                 tune_store=None):
        assert algo in ("mvi", "svi", "ivi", "sivi")
        if layout not in ("padded", "csr"):
            raise ValueError(f"unknown layout {layout!r} "
                             "(expected 'padded' or 'csr')")
        self.cfg, self.algo = cfg, algo
        self.batch_size = batch_size
        self.layout = layout
        if layout == "csr" and token_budget is None:
            # default budget: enough flat slots that a full batch of
            # median-length documents fits, capped so the token stream
            # stays VMEM-resident in the CSR kernel's T-promotion regime
            token_budget = min(batch_size * 64, 8192)
        self.token_budget = token_budget if layout == "csr" else None
        self.tel = as_telemetry(telemetry)
        self._updates = 0            # host-side global-update counter
        self._doc_tokens = None      # per-doc token totals (telemetry only)
        self.rng = np.random.default_rng(seed)
        self.state = init_engine_state(cfg, jax.random.key(seed))
        self.memo: Optional[MemoStore] = None
        self._gamma_buf = None
        self._buckets = None
        self.bucket_stats: Optional[dict] = None
        self.stream = None
        if isinstance(corpus, Corpus):
            if layout == "csr":
                raise ValueError(
                    "layout='csr' is the flat-token stream path — feed a "
                    "DocStream (data.stream.as_doc_stream(corpus)) instead "
                    "of a padded Corpus")
            self.corpus: Optional[Corpus] = corpus
            self.num_docs = corpus.num_docs
            max_unique = corpus.max_unique
            num_words = float(np.asarray(corpus.counts).sum())
            if self.tel.enabled:
                # per-doc token totals, precomputed once so the hot path's
                # token counter is a host-side fancy-index + sum
                self._doc_tokens = np.asarray(corpus.counts).sum(axis=1)
        else:
            from repro.data.stream import BatchPacker, is_doc_stream
            if not is_doc_stream(corpus):
                raise TypeError(f"corpus must be a Corpus or DocStream, "
                                f"got {type(corpus).__name__}")
            if algo == "mvi":
                raise ValueError(
                    "mvi is full-batch coordinate ascent — it scans the "
                    "materialized corpus every epoch; use "
                    "data.stream.materialize(stream) or a mini-batch algo")
            if memo_store == "gamma":
                raise ValueError(
                    "the γ-only store reconstructs π from resident corpus "
                    "rows — materialize the stream or pick dense/chunked")
            self.stream = corpus
            self.corpus = None
            self.num_docs = corpus.num_docs
            max_unique = corpus.max_unique
            num_words = float(corpus.num_words)
            self._packer = self._make_packer()
            self._stream_cursor = 0          # docs pulled this epoch
            self._stream_iter = None
            self._stream_emitted: List = []  # flushed, not yet processed
        if (tune_store is not None and cfg.kernel_policy is None
                and cfg.estep_backend in ("pallas", "csr")):
            # store-resolved kernel policy, looked up once at construction
            # (the shape key is fully known here). An explicit
            # cfg.kernel_policy always wins over the store; no store (or a
            # miss) leaves the policy None — bit-identical to the built-in
            # defaults. The policy rides on the frozen cfg, which is a jit
            # static arg everywhere, so it keys retraces correctly.
            from repro.tune.resolve import PolicyResolver
            pol = PolicyResolver(tune_store, telemetry=self.tel).resolve(
                backend=cfg.estep_backend, layout=layout,
                b_or_t=(self.token_budget if layout == "csr"
                        else batch_size),
                v=cfg.vocab_size, k=cfg.num_topics,
                w=None if layout == "csr" else max_unique)
            if pol is not None:
                cfg = dataclasses.replace(cfg, kernel_policy=pol)
                self.cfg = cfg
        if algo in ("ivi", "sivi"):
            if memo_store == "gamma" and algo == "ivi":
                raise ValueError(
                    "the γ-only store reconstructs π approximately — it "
                    "breaks IVI's exact eq. 4 accumulator; use it with "
                    "sivi (or divi), or pick dense/chunked for ivi")
            self.memo = make_memo_store(memo_store, cfg, self.num_docs,
                                        max_unique, corpus=self.corpus,
                                        chunk_docs=chunk_docs)
        elif algo == "mvi":
            # per-document warm starts carried across epochs (see mvi_scan);
            # row D is the sentinel slot for the tail batch's padding
            self._gamma_buf = jnp.full((corpus.num_docs + 1, cfg.num_topics),
                                       cfg.alpha0 + 1.0, jnp.float32)
            zrow_i = jnp.zeros((1, corpus.max_unique), jnp.int32)
            zrow_c = jnp.zeros((1, corpus.max_unique), jnp.float32)
            self._mvi_ids = jnp.concatenate([corpus.token_ids, zrow_i])
            self._mvi_cnts = jnp.concatenate([corpus.counts, zrow_c])
        if bucket_by_length and self.stream is None:
            if algo == "mvi":
                raise ValueError("bucket_by_length applies to the "
                                 "mini-batch engines (svi/ivi/sivi)")
            from repro.data.bow import bucket_corpus, bucket_padding_stats
            self._buckets = bucket_corpus(corpus)
            self.bucket_stats = bucket_padding_stats(corpus, self._buckets)
        self.num_words_total = jnp.asarray(num_words)
        self.docs_seen = 0
        self.history = History()
        self._t0 = time.perf_counter()
        if test_corpus is not None:
            self._obs, self._held = split_heldout(test_corpus, seed=seed)
        else:
            self._obs = self._held = None

    def _make_packer(self):
        """A fresh ``BatchPacker`` in this engine's configured layout —
        used at construction and by the Trainer's mid-epoch restore, so
        the two can never drift on packer parameters."""
        from repro.data.stream import BatchPacker
        return BatchPacker(
            self.batch_size, max_width=self.stream.max_unique,
            vocab_size=self.cfg.vocab_size, layout=self.layout,
            token_budget=self.token_budget,
            metrics=self.tel.metrics if self.tel.enabled else None)

    # -- batching ----------------------------------------------------------
    def _epoch_order(self) -> List[np.ndarray]:
        """A full-cover epoch: every document exactly once.

        The ``D % batch_size`` tail documents form a final (smaller) batch
        instead of being dropped — dropping them meant IVI never visited
        them, ``init_frac`` never retired to 0, and the post-pass exactness
        λ = β₀ + ⟨m_vk⟩ (eq. 4) never held.
        """
        d = self.corpus.num_docs
        order = self.rng.permutation(d)
        b = self.batch_size
        if d <= b:
            return [order]
        n = (d // b) * b
        batches = list(order[:n].reshape(-1, b))
        if d % b:
            batches.append(order[n:])
        return batches

    def _bucketed_epoch_order(self) -> List[tuple[np.ndarray, int]]:
        """Per-bucket batches (rows, width), bucket visit order shuffled."""
        out: List[tuple[np.ndarray, int]] = []
        for rows_all, width in zip(self._buckets.doc_idx,
                                   self._buckets.widths):
            order = rows_all[self.rng.permutation(len(rows_all))]
            for lo in range(0, len(order), self.batch_size):
                out.append((order[lo:lo + self.batch_size], width))
        self.rng.shuffle(out)
        return out

    def epoch_batches(self) -> List[tuple[np.ndarray, Optional[int]]]:
        """Draw one epoch's mini-batches: (rows, width|None) pairs.

        This is the exact sequence (and the exact rng consumption)
        ``run_epoch`` processes — exposed so external drivers (the
        ``repro.lda`` Trainer) can step batch-by-batch, persist the
        not-yet-visited remainder mid-epoch, and still be bit-equal to an
        uninterrupted ``run_epoch`` loop.
        """
        if self.algo == "mvi":
            raise ValueError("mvi is full-batch: use run_epoch")
        if self.stream is not None:
            raise ValueError("stream ingest has no materialized epoch "
                             "order: drive it with stream_step/run_epoch")
        if self._buckets is not None:
            return self._bucketed_epoch_order()
        return [(rows, None) for rows in self._epoch_order()]

    # -- steps -------------------------------------------------------------
    def run_epoch(self) -> None:
        if self.stream is not None:
            while self.stream_step():
                pass
            return
        if self.algo == "mvi":
            self._run_mvi_epoch()
            return
        for rows, width in self.epoch_batches():
            self.run_minibatch(rows, width=width)

    def _run_mvi_epoch(self) -> None:
        d = self.corpus.num_docs
        b = min(self.batch_size, d)
        batches = self._epoch_order()
        idx = np.full((len(batches), b), d, np.int64)     # sentinel = row D
        for r, rows in enumerate(batches):
            idx[r, : len(rows)] = rows
        idx = jnp.asarray(idx)
        eb = exp_dirichlet_expectation(self.state.lam, axis=0)
        sstats, self._gamma_buf = mvi_scan(
            self.cfg, eb, self._mvi_ids[idx], self._mvi_cnts[idx], idx,
            self._gamma_buf, jnp.zeros_like(self.state.lam))
        self.state = dataclasses.replace(
            self.state, lam=self.cfg.beta0 + sstats, t=self.state.t + 1)
        self.docs_seen += d

    def run_minibatch(self, rows: Optional[np.ndarray] = None,
                      width: Optional[int] = None) -> None:
        if rows is None:
            rows = self.rng.choice(self.corpus.num_docs, size=self.batch_size,
                                   replace=False)
        idx = jnp.asarray(rows)
        ids, cnts = self.corpus.token_ids[idx], self.corpus.counts[idx]
        if width is not None and width < self.corpus.max_unique:
            ids, cnts = ids[:, :width], cnts[:, :width]
        self._update_batch(rows, ids, cnts)

    def _update_batch(self, rows: np.ndarray, ids: jax.Array,
                      cnts: jax.Array) -> None:
        """One global update on a padded (B', W) batch — the shared core of
        the materialized (`run_minibatch`) and stream (`stream_step`)
        paths; ``W`` is whatever width the batch was packed/sliced to.

        This is the instrumentation hot path: every telemetry touch is
        gated on ``tel.enabled`` (``begin`` returns None otherwise), so
        the disabled run executes the seed instruction sequence modulo
        one branch per site — no recorder allocations, no syncs, and
        therefore bit-identical trajectories (tests/test_obs.py).
        """
        tel = self.tel
        on = tel.enabled
        width = ids.shape[1]
        sp = tel.trace.begin("train/update", algo=self.algo,
                             width=width, docs=len(rows)) if on else None
        if self.algo == "svi":
            self.state = svi_step(self.cfg, self.state, ids, cnts,
                                  jnp.asarray(float(self.num_docs)))
        elif self.algo in ("ivi", "sivi"):
            g = tel.trace.begin("train/memo_gather", width=width) \
                if on else None
            old_pi, visited = self.memo.gather(rows, width=width)
            if g is not None:
                tel.trace.end(g)
            s = tel.trace.begin("train/solve", width=width) if on else None
            self.state, new_pi, aux = incremental_update(
                self.cfg, self.algo == "sivi", self.state, ids, cnts,
                old_pi, visited, self.num_words_total,
                self.memo.pi_wire_dtype)
            if s is not None:
                tel.trace.end(s, sync=self.state.lam)
            u = tel.trace.begin("train/memo_update", width=width) \
                if on else None
            self.memo = self.memo.update(rows, new_pi,
                                         exp_elog_beta=aux.exp_elog_beta)
            if u is not None:
                tel.trace.end(u)
        else:
            raise ValueError(self.algo)
        self.docs_seen += len(rows)
        if sp is not None:
            tel.trace.end(sp, sync=self.state.lam)
            self._updates += 1
            m = tel.metrics
            m.inc("train.docs", len(rows))
            m.inc("train.batches", width=width)
            if self._doc_tokens is not None:
                m.inc("train.tokens", float(self._doc_tokens[rows].sum()))
            else:
                m.inc("train.tokens", float(np.asarray(cnts).sum()))
            if self.memo is not None:
                m.set_gauge("train.memo_resident_bytes",
                            self.memo.footprint_bytes())
                self._scatter_gauges(m, ids.shape)
                self._sweep_gauges(m, aux.sweeps)
            wd = tel.watchdog
            if (self.algo in ("ivi", "sivi") and wd.enabled
                    and wd.should_check(self._updates)):
                # O(corpus) memoized-bound read — priced by check_every
                wd.observe(self.full_bound(), step=self._updates,
                           armed=self._watchdog_armed())

    def _scatter_gauges(self, m, token_shape) -> None:
        """``train.scatter_dense_steps`` / ``train.scatter_grid_steps``: the
        memo correction's scatter grid for this batch shape, dense
        ``chunks × row_tiles`` against the sorted visit list run; host
        arithmetic, set only where the backend runs the scatter kernel."""
        steps = get_backend(self.cfg.estep_backend).scatter_steps(
            self.cfg, token_shape)
        if steps is not None:
            m.set_gauge("train.scatter_dense_steps", steps[0])
            m.set_gauge("train.scatter_grid_steps", steps[1])

    def _sweep_gauges(self, m, sweeps: jax.Array) -> None:
        """``train.estep_sweeps``: the fixed-point sweeps this step ran,
        summed over its tiles; ``train.estep_sweep_cap``: tiles ×
        ``estep_max_iters``. Read after the step's λ sync, so the counts
        are already on hand."""
        m.set_gauge("train.estep_sweeps", int(np.asarray(sweeps).sum()))
        m.set_gauge("train.estep_sweep_cap",
                    sweeps.size * self.cfg.estep_max_iters)

    def _watchdog_armed(self) -> bool:
        """Whether the monotone-ELBO guarantee is in force: IVI (eq. 4 —
        S-IVI's averaging forfeits it) after the random-init mass has
        fully retired, i.e. the first complete pass is done."""
        return (self.algo == "ivi"
                and float(jax.device_get(self.state.init_frac)) == 0.0)

    # -- stream ingest -----------------------------------------------------
    def stream_step(self) -> bool:
        """Pull-and-pack until ONE mini-batch emits, then process it.

        Returns True when a batch was processed; False exactly at an epoch
        boundary (the stream is exhausted and every flushed batch has been
        processed — the cursor resets, so the next call starts a new
        pass). Every document of the stream is processed exactly once per
        epoch: the packer's partial buckets flush at exhaustion, the
        streaming analogue of the ``D % batch_size`` epoch-tail batch.
        """
        assert self.stream is not None, "stream_step needs stream ingest"
        if self._stream_emitted:
            self._run_packed(self._stream_emitted.pop(0))
            return True
        if self._stream_iter is None:
            self._stream_iter = self.stream.iter_from(self._stream_cursor)
        for ids, cnts in self._stream_iter:
            pos = self._stream_cursor
            self._stream_cursor += 1
            batch = self._packer.add(pos, ids, cnts)
            if batch is not None:
                self._run_packed(batch)
                return True
        self._stream_emitted = self._packer.flush()
        if self._stream_emitted:
            self._run_packed(self._stream_emitted.pop(0))
            return True
        self._stream_cursor = 0              # epoch boundary: rewind
        self._stream_iter = None
        return False

    def _run_packed(self, batch) -> None:
        from repro.data.stream import CSRBatch
        if isinstance(batch, CSRBatch):
            self._update_batch_csr(batch)
        else:
            self._update_batch(batch.rows, jnp.asarray(batch.token_ids),
                               jnp.asarray(batch.counts))

    def _csr_flat_index(self, batch, width: int) -> np.ndarray:
        """The token-slot → memo-cell map: ``ix[t] = seg_t·W + pos_in_doc``
        for live tokens, sentinel ``B·W`` for padding slots. Host-built
        from the batch's offsets — O(T) numpy, no device work."""
        segs = batch.segments.astype(np.int64)
        ix = segs * width + (np.arange(batch.token_budget, dtype=np.int64)
                             - batch.offsets[segs])
        ix[batch.live_tokens:] = self.batch_size * width
        return ix

    def _update_batch_csr(self, batch) -> None:
        """One global update on a flat CSR batch (`stream_step`, csr
        layout). The jit keys are (token_budget, batch_size, W): the flat
        token arrays are always ``token_budget`` slots and the doc axis is
        padded to ``batch_size`` (phantom docs own no tokens — inert in
        every segment reduction), so W — the ladder rung covering the
        batch's longest document, which sizes the memo wire — is the only
        per-batch shape degree of freedom left.
        """
        tel = self.tel
        on = tel.enabled
        rows = batch.rows
        b_real, b_pad = len(rows), self.batch_size
        width = self._packer.width_for(
            int(batch.doc_lengths.max()) if b_real else 1)
        sp = tel.trace.begin("train/update", algo=self.algo, width=width,
                             docs=b_real) if on else None
        ids = jnp.asarray(batch.token_ids)
        cnts = jnp.asarray(batch.counts)
        segs = jnp.asarray(batch.segments)
        if self.algo == "svi":
            self.state = svi_step_csr(
                self.cfg, self.state, ids, cnts, segs,
                jnp.asarray(float(b_real)),
                jnp.asarray(float(self.num_docs)), num_docs=b_pad)
        elif self.algo in ("ivi", "sivi"):
            # pad the doc axis by re-reading row 0: phantom docs own zero
            # tokens, so their gathered memo rows are never touched and
            # their visited flags contribute 0 to the first-visit count
            rows_pad = np.concatenate(
                [rows, np.zeros(b_pad - b_real, np.int64)])
            g = tel.trace.begin("train/memo_gather", width=width) \
                if on else None
            old_pi, visited = self.memo.gather(rows_pad, width=width)
            if g is not None:
                tel.trace.end(g)
            ix = jnp.asarray(self._csr_flat_index(batch, width))
            s = tel.trace.begin("train/solve", width=width) if on else None
            self.state, new_pi, aux = incremental_update_csr(
                self.cfg, self.algo == "sivi", self.state, ids, cnts, segs,
                ix, old_pi, visited, self.num_words_total,
                self.memo.pi_wire_dtype)
            if s is not None:
                tel.trace.end(s, sync=self.state.lam)
            u = tel.trace.begin("train/memo_update", width=width) \
                if on else None
            self.memo = self.memo.update(rows, new_pi[:b_real],
                                         exp_elog_beta=aux.exp_elog_beta)
            if u is not None:
                tel.trace.end(u)
        else:
            raise ValueError(self.algo)
        self.docs_seen += b_real
        if sp is not None:
            tel.trace.end(sp, sync=self.state.lam)
            self._updates += 1
            m = tel.metrics
            m.inc("train.docs", b_real)
            m.inc("train.batches", width=width)
            m.inc("train.tokens", float(batch.counts.sum()))
            if self.memo is not None:
                m.set_gauge("train.memo_resident_bytes",
                            self.memo.footprint_bytes())
                self._scatter_gauges(m, ids.shape)
                self._sweep_gauges(m, aux.sweeps)
            wd = tel.watchdog
            if (self.algo in ("ivi", "sivi") and wd.enabled
                    and wd.should_check(self._updates)):
                wd.observe(self.full_bound(), step=self._updates,
                           armed=self._watchdog_armed())

    def stream_padding_stats(self) -> dict:
        """Pad-waste accounting of everything packed so far (stream mode)."""
        return self._packer.padding_stats()

    # -- evaluation --------------------------------------------------------
    def evaluate(self) -> Dict[str, float]:
        """Periodic evaluation snapshot.

        With a test corpus: held-out LPP (the paper's §6 metric). Without
        one: the corpus bound (for the incremental engines the *memoized*
        ELBO — the monotone objective — read through the store). Each
        metric is appended to its own ``History`` column only when actually
        computed; ``lpp`` used to be padded with ``nan`` rows whenever no
        test corpus was set, which poisoned any downstream min/mean.
        """
        out: Dict[str, float] = {}
        if self._obs is not None:
            out["lpp"] = float(log_predictive(self.cfg, self.state.lam,
                                              self._obs, self._held))
            self.history.lpp.append(out["lpp"])
        else:
            out["elbo"] = self.full_bound()
            self.history.elbo.append(out["elbo"])
            if (self.tel.enabled and self.tel.watchdog.enabled
                    and self.algo in ("ivi", "sivi")):
                # a bound computed anyway — feed it to the watchdog even
                # at check_every=0 (the free cadence)
                self.tel.watchdog.observe(out["elbo"], step=self._updates,
                                          armed=self._watchdog_armed())
        if self.tel.enabled:
            self.tel.metrics.set_gauge(
                "train.effective_topics",
                float(effective_topics(np.asarray(self.state.lam))))
        self.history.docs_seen.append(self.docs_seen)
        self.history.wall.append(time.perf_counter() - self._t0)
        return out

    def full_bound(self) -> float:
        """Exact corpus ELBO.

        For the incremental engines this is the *memoized* bound — the exact
        objective at (γ(π_memo), π_memo, λ), the quantity IVI monotonically
        increases — read through the ``MemoStore`` chunk by chunk (γ is
        α₀ + Σ_l cnt·π, Alg. 1 line 6, so it is derived from the memo and
        stays consistent with it). For MVI/SVI we report the collapsed
        bound at freshly fitted γ.
        """
        cfg = self.cfg
        if self.stream is not None:
            # stream ingest: chunk-by-chunk read-through, no (D, L) corpus
            if self.memo is not None:
                return float(elbo_memoized_stream(cfg, self.stream,
                                                  self.memo, self.state.lam))
            return float(elbo_collapsed_stream(cfg, self.stream,
                                               self.state.lam))
        if self.memo is not None:
            return float(elbo_memoized_store(cfg, self.corpus, self.memo,
                                             self.state.lam))
        eb = exp_dirichlet_expectation(self.state.lam, axis=0)
        # deliberately the gather backend regardless of cfg.estep_backend:
        # this is a full-corpus E-step, and the dense/pallas formulations
        # would densify all D documents into a (D, V) matrix at once
        res = estep_mod.estep_gather(cfg, eb, self.corpus.token_ids,
                                     self.corpus.counts)
        return float(elbo_collapsed(cfg, self.corpus, res.gamma,
                                    self.state.lam))
