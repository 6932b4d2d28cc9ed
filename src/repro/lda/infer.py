"""Serving-side inference: topic posteriors for unseen documents.

Training owns λ; serving only needs the per-document E-step against frozen
topics (the same fixed point `predictive.log_predictive` runs before
scoring). This module packages that E-step for request traffic:

* documents are grouped into **length buckets** under the ONE width
  policy of the ragged token pipeline (`repro.data.stream`: the ladder
  rung covering the last live slot — lossless for any slot layout,
  including ``split_heldout`` halves) and each bucket sliced/packed to its
  own width, so E-step FLOPs scale with a request's actual length, not
  the corpus-wide maximum;
* every bucket batch is padded to one fixed ``batch_size``, so the jit
  cache holds exactly **one compiled executable per bucket width** — a
  bounded, enumerable cache (``TopicInferencer.cache_info``) instead of
  one recompile per request shape;
* ragged requests need no padded ``Corpus`` at all: ``posterior_docs``
  consumes a ``DocStream`` / iterable of ragged documents through a
  ``BatchPacker`` and — by default — an **async double-buffered
  pipeline**: a host thread packs and stages request batch *t+1* while
  the device runs the E-step on batch *t* (`docs/streaming.md`);
* the E-step dispatches through ``cfg.estep_backend`` — with ``pallas``
  this is the fused fixed-point kernel (`docs/estep.md`), the production
  serving configuration;
* topics are held as an atomic **versioned model snapshot**: a single
  ``(version, exp_elog_beta)`` tuple attribute. ``swap_model`` publishes
  a new λ with one reference assignment, every dispatched batch reads the
  tuple exactly once, so an online learner can republish topics under
  live traffic with no torn reads — an in-flight batch completes entirely
  on the snapshot it started with (`docs/serving.md`).

``TopicInferencer`` is the reusable handle (λ is preprocessed to
exp(E[ln φ]) once); ``topic_posterior`` is the one-shot convenience the
``LDA.transform`` facade method wraps.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
from functools import partial
from typing import Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.estep import CSRTokenBatch, estep, get_backend
from repro.core.math import exp_dirichlet_expectation, safe_normalize
from repro.core.types import Corpus, LDAConfig
from repro.data.stream import BatchPacker, as_ragged_doc, bucket_rows
from repro.obs import as_telemetry


@partial(jax.jit, static_argnames=("cfg",))
def _posterior_batch(cfg: LDAConfig, exp_elog_beta: jax.Array,
                     token_ids: jax.Array, counts: jax.Array) -> jax.Array:
    """γ for one padded (B, width) batch via the configured backend."""
    return estep(cfg, exp_elog_beta, token_ids, counts).gamma


@partial(jax.jit, static_argnames=("cfg", "num_docs"))
def _posterior_batch_csr(cfg: LDAConfig, exp_elog_beta: jax.Array,
                         token_ids: jax.Array, counts: jax.Array,
                         segments: jax.Array, *,
                         num_docs: int) -> jax.Array:
    """γ for one flat CSR token batch — every request length distribution
    shares this single (token_budget,)-shaped entry."""
    return get_backend(cfg.estep_backend).solve_tokens(
        cfg, exp_elog_beta, CSRTokenBatch(token_ids, counts, segments),
        num_docs=num_docs).gamma


# one staged request batch: (request positions, device ids, device counts,
# bucket width — padded — or device segments — csr —, live row count)
_Staged = Tuple[np.ndarray, jax.Array, jax.Array, object, int]

# one dispatched result: (request positions, device γ, live rows, the
# model version whose snapshot solved the batch)
_Result = Tuple[np.ndarray, jax.Array, int, int]


class TopicInferencer:
    """Frozen-topics E-step server (see module docstring).

    Args:
      cfg: training config; ``backend`` overrides ``cfg.estep_backend``
        for serving (e.g. train with ``gather``, serve with ``pallas``).
      lam: (V, K) topic-word parameter — from a live ``LDA`` facade, a
        checkpoint, or any λ with the right shape.
      batch_size: fixed request batch; shorter batches are padded with
        empty documents (zero counts — they converge to the γ prior in
        one sweep and are dropped before returning).
      telemetry: a ``repro.obs`` bundle (None/False = off). Serving spans
        (``serve/stage``, ``serve/solve``) never device-sync by default,
        so tracing does not serialise the double-buffer overlap; counters
        record docs/batches served, jit-cache hits vs misses per width,
        and the double-buffer queue depth histogram.
      tune_store: a ``repro.tune`` policy store (path or ``PolicyStore``)
        of autotuned kernel policies (`docs/tuning.md`). Padded serving
        resolves a policy PER BUCKET WIDTH, lazily, the first time a
        width is dispatched (each width is its own kernel shape, so each
        can carry its own winner — the per-width cfg variants mirror the
        one-jit-entry-per-width cache). CSR serving resolves once at
        construction (one shape total). A tuned
        ``double_buffer_depth`` sizes ``posterior_docs``'s staging queue.
        An explicit ``cfg.kernel_policy`` always wins; no store (or a
        miss) is bit-identical to the built-in defaults.
    """

    def __init__(self, cfg: LDAConfig, lam: jax.Array, *,
                 backend: Optional[str] = None, batch_size: int = 256,
                 layout: str = "padded", token_budget: Optional[int] = None,
                 telemetry=None, tune_store=None):
        if backend is not None and backend != cfg.estep_backend:
            cfg = dataclasses.replace(cfg, estep_backend=backend)
        if layout not in ("padded", "csr"):
            raise ValueError(f"unknown layout {layout!r} "
                             "(expected 'padded' or 'csr')")
        self.cfg = cfg
        self.batch_size = batch_size
        self.layout = layout
        if layout == "csr" and token_budget is None:
            token_budget = min(batch_size * 64, 8192)
        self.token_budget = token_budget if layout == "csr" else None
        self.tel = as_telemetry(telemetry)
        # the model snapshot is ONE tuple attribute: readers take a local
        # reference once per batch, swap_model replaces the whole tuple in
        # a single assignment — no lock on the read path, no torn
        # (version, topics) pairs under concurrent republish
        self._model: Tuple[int, jax.Array] = (
            0, exp_dirichlet_expectation(jnp.asarray(lam), axis=0))
        self._swap_lock = threading.Lock()
        self._compiled_widths: Dict[int, int] = {}    # width → batches run
        self._live_slots = 0          # staged token slots actually live
        self._padded_slots = 0        # staged token slots incl. padding
        # tuned-policy resolution (docs/tuning.md): per-width cfg variants
        # for padded serving, a one-shot construction-time lookup for csr
        self._resolver = None
        self._cfg_by_width: Dict[int, LDAConfig] = {}
        if (tune_store is not None and self.cfg.kernel_policy is None
                and self.cfg.estep_backend in ("pallas", "csr")):
            from repro.tune.resolve import PolicyResolver
            self._resolver = PolicyResolver(tune_store, telemetry=self.tel)
            if layout == "csr":
                pol = self._resolver.resolve(
                    backend=self.cfg.estep_backend, layout="csr",
                    b_or_t=self.token_budget, v=self.cfg.vocab_size,
                    k=self.cfg.num_topics, w=None)
                if pol is not None:
                    self.cfg = dataclasses.replace(self.cfg,
                                                   kernel_policy=pol)

    def _cfg_for_width(self, width: int) -> LDAConfig:
        """The serving cfg for one bucket width — carrying that width's
        tuned kernel policy when the store has one (padded layout only;
        csr resolved its single shape at construction). Cached so each
        width's lookup — and its ``tune.cache`` hit/miss — happens once,
        like its jit compile."""
        if self._resolver is None or self.layout == "csr":
            return self.cfg
        cfg = self._cfg_by_width.get(width)
        if cfg is None:
            pol = self._resolver.resolve(
                backend=self.cfg.estep_backend, layout="padded",
                b_or_t=self.batch_size, v=self.cfg.vocab_size,
                k=self.cfg.num_topics, w=width)
            cfg = (self.cfg if pol is None
                   else dataclasses.replace(self.cfg, kernel_policy=pol))
            self._cfg_by_width[width] = cfg
        return cfg

    def _buffer_depth(self) -> int:
        """``posterior_docs``'s staging-queue size: the active kernel
        policy's ``double_buffer_depth`` (tuned or explicit), else the
        classic 2 (one in flight + one staged)."""
        pol = self.cfg.kernel_policy
        return pol.double_buffer_depth if pol is not None else 2

    # -- model snapshot ---------------------------------------------------
    @property
    def exp_elog_beta(self) -> jax.Array:
        """The current snapshot's exp(E[ln φ]) (V, K)."""
        return self._model[1]

    @property
    def model_version(self) -> int:
        """Monotone counter of published snapshots (0 = the constructor's)."""
        return self._model[0]

    def swap_model(self, lam: Optional[jax.Array] = None, *,
                   exp_elog_beta: Optional[jax.Array] = None,
                   version: Optional[int] = None) -> int:
        """Atomically publish new topics; returns the new version.

        Thread-safe against concurrent requests AND concurrent swappers:
        the expensive exp(E[ln φ]) preprocessing runs outside the lock
        (on the caller's thread — an online learner pays it, serving does
        not), and the critical section is a single tuple assignment. A
        batch dispatched before the swap completes on the OLD snapshot —
        ``_dispatch`` reads the tuple exactly once — and its response
        reports the old version; the next batch serves the new one.

        Pass ``lam`` (a (V, K) topic-word parameter, preprocessed here) or
        a precomputed ``exp_elog_beta`` directly. ``version`` overrides
        the auto-incremented counter (it must advance monotonically).
        """
        if (lam is None) == (exp_elog_beta is None):
            raise ValueError("pass exactly one of lam / exp_elog_beta")
        eb = (exp_dirichlet_expectation(jnp.asarray(lam), axis=0)
              if lam is not None else jnp.asarray(exp_elog_beta))
        if eb.shape != self._model[1].shape:
            raise ValueError(
                f"snapshot shape {tuple(eb.shape)} != serving "
                f"{tuple(self._model[1].shape)} — a swap cannot change "
                "the (V, K) geometry")
        with self._swap_lock:
            cur = self._model[0]
            v = cur + 1 if version is None else int(version)
            if v <= cur:
                raise ValueError(f"version must advance: {v} <= {cur}")
            self._model = (v, eb)
        if self.tel.enabled:
            self.tel.metrics.inc("serve.model_swaps")
            self.tel.metrics.set_gauge("serve.model_version", v)
        return v

    # -- padded-corpus requests -----------------------------------------
    def posterior(self, corpus: Corpus) -> np.ndarray:
        """γ (D, K) for every document, bucketed + fixed-batch padded.

        Empty documents (all-zero counts) come back at the prior γ = α₀ —
        they ride the smallest bucket (`repro.data.stream.bucket_rows`
        keeps ``last == 0`` rows on the first rung), so no row of the
        result can be the all-zero vector ``transform`` would fail to
        normalise.
        """
        if self.layout == "csr":
            # the flat layout has no width buckets: route padded-corpus
            # requests through the same single-entry ragged path
            from repro.data.stream import CorpusDocStream
            return self.posterior_docs(CorpusDocStream(corpus))
        d = corpus.num_docs
        out = np.zeros((d, self.cfg.num_topics), np.float32)
        ids_all = np.asarray(corpus.token_ids)
        cnts_all = np.asarray(corpus.counts)
        b = self.batch_size
        buckets = bucket_rows(cnts_all)
        covered = sum(len(rows) for rows, _ in buckets)
        assert covered == d, (covered, d)     # every doc in exactly one bucket
        for rows_all, width in buckets:
            for lo in range(0, len(rows_all), b):
                rows = rows_all[lo:lo + b]
                ids = np.zeros((b, width), np.int32)
                cnts = np.zeros((b, width), np.float32)
                ids[: len(rows)] = ids_all[rows, :width]
                cnts[: len(rows)] = cnts_all[rows, :width]
                self._note_padding(int((cnts > 0).sum()), cnts.size)
                gamma = _posterior_batch(self._cfg_for_width(width),
                                         self.exp_elog_beta,
                                         jnp.asarray(ids), jnp.asarray(cnts))
                out[rows] = np.asarray(gamma[: len(rows)])
                self._note_width(width, len(rows))
        return out

    def _note_width(self, width: int, docs: int) -> None:
        """Per-width serving bookkeeping; a width seen for the first time
        is the batch that paid a jit compile (the cache holds one
        executable per width — `cache_info`)."""
        miss = width not in self._compiled_widths
        self._compiled_widths[width] = \
            self._compiled_widths.get(width, 0) + 1
        if self.tel.enabled:
            m = self.tel.metrics
            m.inc("serve.jit_cache_misses" if miss
                  else "serve.jit_cache_hits", width=width)
            m.inc("serve.docs", docs)
            m.inc("serve.batches", width=width)

    def transform(self, corpus: Corpus) -> np.ndarray:
        """θ̄ (D, K): the normalised topic posterior (matches the θ̄ that
        ``predictive.log_predictive`` scores held-out words with)."""
        gamma = self.posterior(corpus)
        return np.asarray(safe_normalize(jnp.asarray(gamma), axis=-1))

    # -- ragged requests -------------------------------------------------
    def _stage(self, batch) -> _Staged:
        """Pad a packed batch to the fixed ``batch_size`` and put it on
        device — the host half of the pipeline (runs on the packer
        thread when double-buffered — the recorder is thread-safe and
        tags spans with a per-thread tid)."""
        tel = self.tel
        n = len(batch.rows)
        if self.layout == "csr":
            # flat arrays are already exactly token_budget slots — nothing
            # to pad; phantom docs exist only as unused segment ids
            sp = tel.trace.begin("serve/stage", width=batch.token_budget,
                                 docs=n) if tel.enabled else None
            self._note_padding(batch.live_tokens, batch.token_budget)
            staged = (batch.rows, jnp.asarray(batch.token_ids),
                      jnp.asarray(batch.counts),
                      jnp.asarray(batch.segments), n)
            if sp is not None:
                tel.trace.end(sp)
            return staged
        sp = tel.trace.begin("serve/stage", width=batch.width,
                             docs=len(batch.rows)) if tel.enabled else None
        ids = np.zeros((self.batch_size, batch.width), np.int32)
        cnts = np.zeros((self.batch_size, batch.width), np.float32)
        ids[:n] = batch.token_ids
        cnts[:n] = batch.counts
        self._note_padding(int((cnts > 0).sum()), cnts.size)
        staged = (batch.rows, jnp.asarray(ids), jnp.asarray(cnts),
                  batch.width, n)
        if sp is not None:
            tel.trace.end(sp)
        return staged

    def _staged_batches(self, docs) -> Iterator[_Staged]:
        """Pack a ragged request iterable into staged device batches.

        The serving packer runs the SAME width policy as training but with
        an open-ended ladder (requests of unseen lengths extend it by
        doubling) — the jit cache stays one executable per width.
        """
        it = (docs.iter_from(0) if hasattr(docs, "iter_from")
              else (as_ragged_doc(d) for d in docs))
        packer = BatchPacker(
            self.batch_size, vocab_size=self.cfg.vocab_size,
            layout=self.layout, token_budget=self.token_budget,
            metrics=self.tel.metrics if self.tel.enabled else None)
        pos = 0
        for ids, cnts in it:
            batch = packer.add(pos, ids, cnts)
            pos += 1
            if batch is not None:
                yield self._stage(batch)
        for batch in packer.flush():
            yield self._stage(batch)

    def posterior_docs(self, docs, *,
                       double_buffer: bool = True) -> np.ndarray:
        """γ (N, K) for RAGGED request documents — no padded ``Corpus``.

        ``docs``: a ``DocStream`` or any iterable of documents (raw token
        arrays with repeats, or unique ``(ids, counts)`` pairs; empty
        documents return the prior γ = α₀). Results come back in request
        order.

        ``double_buffer=True`` (default) overlaps ingest with compute: a
        host thread packs, pads and stages batch *t+1* while the device
        runs the E-step on batch *t* (the consumer dispatches without
        blocking — jax's async dispatch keeps the device queue full — and
        only converts γ to host arrays once every batch is in flight).
        ``double_buffer=False`` is the synchronous reference path: pack →
        run → block, one batch at a time. Both paths run identical batches
        through the same jit entries, so their results are bit-identical.
        """
        results: List[_Result] = []
        if double_buffer:
            q: "queue.Queue" = queue.Queue(maxsize=self._buffer_depth())
            abort = threading.Event()
            err: List[BaseException] = []

            def put(item) -> bool:
                # bounded put that gives up once the consumer aborts, so a
                # consumer-side exception can never leave this thread (and
                # its staged device buffers) blocked on a full queue
                while not abort.is_set():
                    try:
                        q.put(item, timeout=0.05)
                        return True
                    except queue.Full:
                        continue
                return False

            def produce():
                try:
                    for staged in self._staged_batches(docs):
                        if not put(staged):
                            return
                except BaseException as e:  # noqa: BLE001 — re-raised below
                    err.append(e)
                finally:
                    put(None)

            t = threading.Thread(target=produce, name="serve-packer",
                                 daemon=True)
            t.start()
            try:
                while True:
                    staged = q.get()
                    if staged is None:
                        break
                    if self.tel.enabled:
                        # depth AFTER the take: 0 = consumer starved (pack
                        # is the bottleneck), maxsize−1 = producer ahead
                        self.tel.metrics.observe("serve.queue_depth",
                                                 q.qsize())
                    results.append(self._dispatch(staged))
            finally:
                abort.set()
                t.join()
            if err:
                raise err[0]
        else:
            for staged in self._staged_batches(docs):
                res = self._dispatch(staged)
                res[1].block_until_ready()    # the synchronous baseline
                results.append(res)
        total = sum(n for _, _, n, _ in results)
        out = np.zeros((total, self.cfg.num_topics), np.float32)
        for rows, gamma, n, _ in results:
            out[rows] = np.asarray(gamma[:n])
        return out

    def _dispatch(self, staged: _Staged) -> _Result:
        tel = self.tel
        rows, ids, cnts, aux, n = staged
        # ONE read of the snapshot tuple: the whole batch — and the version
        # its response reports — belongs to a single published model even
        # if swap_model lands mid-dispatch
        version, eb = self._model
        # serve/solve is never device-synced: syncing here would serialise
        # the double-buffer overlap the pipeline exists for, so the span
        # measures dispatch (+ compile on a width's first batch)
        if self.layout == "csr":
            width = self.token_budget
            sp = tel.trace.begin("serve/solve", width=width, docs=n) \
                if tel.enabled else None
            gamma = _posterior_batch_csr(self.cfg, eb, ids, cnts, aux,
                                         num_docs=self.batch_size)
        else:
            width = aux
            sp = tel.trace.begin("serve/solve", width=width, docs=n) \
                if tel.enabled else None
            gamma = _posterior_batch(self._cfg_for_width(width), eb, ids,
                                     cnts)
        if sp is not None:
            tel.trace.end(sp)
        self._note_width(width, n)
        return rows, gamma, n, version

    def posterior_packed(self, batch) -> _Result:
        """γ for ONE pre-packed batch — the serving-service entry point.

        ``batch``: a ``PackedBatch``/``CSRBatch`` from a ``BatchPacker``
        configured like this inferencer (`repro.serve.admission` builds
        one from ``packer_kwargs``). Returns ``(rows, gamma_device, n,
        model_version)`` — γ stays on device (callers block when they
        need honest latency), rows are the packer positions, and the
        version identifies the snapshot that solved the batch. Packing
        and staging are identical to ``posterior_docs``'s, so the served
        γ is bit-equal to the offline path on the same document sequence.
        """
        return self._dispatch(self._stage(batch))

    def packer_kwargs(self) -> Dict[str, object]:
        """The ``BatchPacker`` construction kwargs matching this
        inferencer's serving configuration — external batch formation
        (the admission controller) must pack exactly like
        ``_staged_batches`` to stay bit-equal with ``posterior_docs``."""
        return dict(batch_size=self.batch_size,
                    vocab_size=self.cfg.vocab_size, layout=self.layout,
                    token_budget=self.token_budget)

    def transform_docs(self, docs, *, double_buffer: bool = True
                       ) -> np.ndarray:
        """θ̄ (N, K) for ragged request documents (``posterior_docs``
        normalised)."""
        gamma = self.posterior_docs(docs, double_buffer=double_buffer)
        return np.asarray(safe_normalize(jnp.asarray(gamma), axis=-1))

    def _note_padding(self, live: int, padded: int) -> None:
        self._live_slots += int(live)
        self._padded_slots += int(padded)

    def padding_stats(self) -> Dict[str, object]:
        """Pad-waste accounting of everything staged so far: live vs
        total staged token slots and the bytes the padding cost on the
        host→device wire (`repro.data.stream.TOKEN_SLOT_BYTES` per slot).
        Under ``layout='csr'`` the only padding left is the flat batch
        tail below ``token_budget``."""
        from repro.data.stream import TOKEN_SLOT_BYTES
        wasted = self._padded_slots - self._live_slots
        return {"live_slots": self._live_slots,
                "padded_slots": self._padded_slots,
                "pad_frac": 1.0 - self._live_slots
                    / max(self._padded_slots, 1),
                "wasted_token_bytes": wasted * TOKEN_SLOT_BYTES}

    # -- introspection ---------------------------------------------------
    def cache_info(self) -> Dict[str, object]:
        """Serving-cache introspection — counters and compilations apart.

        ``_compiled_widths`` counts *batches served* per width, NOT jit
        entries (a width served twice still holds one compiled
        executable), so the two quantities are reported separately:

        * ``batches_per_width`` — {bucket width: batches served through
          it}, a traffic histogram;
        * ``compiled_widths``   — the sorted set of widths that have
          compiled an executable (the keys above);
        * ``jit_entries``       — its size: the number of compiled
          executables the fixed ``batch_size`` bounds.
        """
        return {
            "batches_per_width": dict(self._compiled_widths),
            "compiled_widths": sorted(self._compiled_widths),
            "jit_entries": len(self._compiled_widths),
        }


def topic_posterior(cfg: LDAConfig, lam: jax.Array, corpus: Corpus, *,
                    backend: Optional[str] = None, batch_size: int = 256
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """One-shot (γ, θ̄) for ``corpus`` under frozen topics ``lam``."""
    inf = TopicInferencer(cfg, lam, backend=backend, batch_size=batch_size)
    gamma = inf.posterior(corpus)
    theta = np.asarray(safe_normalize(jnp.asarray(gamma), axis=-1))
    return gamma, theta
