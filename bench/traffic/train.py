"""Runner for training cells: IVI through ``repro.lda.LDA``.

Set-up samples the corpus from the seed, builds the estimator as the mix
says (layout, E-step backend, batch, memo store) and runs the first pass,
one ``partial_fit`` step at a time: that compiles every shape the corpus
reaches (the epoch's tail batch included) and fills the memo, so the window
runs with the random init mass retired, as a long job does. The window then
calls ``partial_fit(steps=1)`` until ``--seconds`` have passed and ends on
``block_until_ready(λ)``; ``train_tokens_per_s`` is the corpus tokens of
the steps it completed over its length.

The check compares steps of that same estimator, each driven through the
window's own call, with the plain reference (``bench/configs/lda_ref.py``):

* the first ``SEED_CHECKS`` steps from the seed (set-up), which the
  reference follows from the same λ₀ and documents. They visit documents
  for the first time and retire the init mass: ``lam_step_gap`` and
  ``pi_gap``;
* steps on the filled memo: the first ``FILLED_CHECKS`` steps of the
  second pass (set-up) and ``FILLED_CHECKS`` steps right after the window
  closes. Before each, the program's λ and the batch's memo rows, as the
  store gives them, are read; the reference runs one step from that state.
  Which documents were seen before comes from the step log, not from the
  store.

``lam_step_gap`` is ‖Δλ − Δλ_ref‖/‖Δλ_ref‖ over the first steps;
``corr_gap_filled`` is ‖Δλ − Δλ_ref‖/‖Σ cnt·π_ref‖ over the filled-memo
steps, against the mass the step adds: there Δλ = Σ cnt·(π − π_old) is a
difference of two near-equal masses that shrinks as the model converges,
and a gap over it would grow with the run's length. ``pi_gap`` and
``pi_gap_filled`` are ‖π − π_ref‖/‖π_ref‖ over the batch's live entries of
the π the step wrote, read back through the store, on the memo's wire
dtype. Each number is the largest over its steps. The work counts of the
window's steps (``bench/counts``) take the sweeps that the reference ran on
the filled-memo steps.
"""
from __future__ import annotations

import gc
import time
from types import SimpleNamespace

import numpy as np

from bench import layer as L
from bench.configs import lda_ref
from bench.trace import Profiler, recorder_spans, window_records
from bench.traffic import corpus as C

SEED_CHECKS = 3      # compared steps from the seed, on an empty memo
FILLED_CHECKS = 2    # compared steps at the second pass's start, and again
#                      right after the window, on the filled memo


def lda_config(cfg: dict, mix: dict, variant):
    from repro.core.types import LDAConfig
    stream = "bfloat16" if variant == "control" else cfg["estep_stream_dtype"]
    return LDAConfig(
        num_topics=cfg["num_topics"], vocab_size=cfg["vocab_size"],
        alpha0=cfg["alpha0"], beta0=cfg["beta0"], kappa=cfg["kappa"],
        tau=cfg["tau"], estep_max_iters=cfg["estep_max_iters"],
        estep_tol=cfg["estep_tol"], estep_backend=mix["backend"],
        estep_stream_dtype=stream)


def telemetry(on: bool):
    if not on:
        return None
    from repro.obs import MetricsRegistry, SpanRecorder, Telemetry
    return Telemetry(trace=SpanRecorder(device_sync=True),
                     metrics=MetricsRegistry())


def host(x) -> np.ndarray:
    return np.array(x, dtype=np.float32)


def next_rows(lda) -> np.ndarray:
    """The corpus rows the next ``partial_fit`` step trains on: the
    trainer draws an epoch's batches when none are pending, and drawing
    them here first is the same rng consumption."""
    tr = lda.trainer
    if not tr._pending:
        tr._pending = list(tr.eng.epoch_batches())
    return np.asarray(tr._pending[0][0])


def _timed(ctx: L.Context, data, tel) -> SimpleNamespace:
    """Set-up, the measured window and the compared steps on the program;
    returns what the check and the readers need (the estimator is dropped
    on return)."""
    import jax
    import jax.numpy as jnp

    from repro.core.types import Corpus
    from repro.lda import LDA

    mix = ctx.mix
    lda = LDA(lda_config(ctx.cfg, mix, ctx.variant), algo=mix["algo"],
              seed=ctx.seed, telemetry=tel, layout=mix["layout"],
              batch_size=mix["batch_size"], memo_store=mix["memo_store"],
              chunk_docs=mix["chunk_docs"])
    lda.fit(Corpus(jnp.asarray(data.token_ids), jnp.asarray(data.counts)),
            epochs=0)
    log, captured = [], {}

    def memo_rows(rows):
        return host(lda.trainer.eng.memo.gather(rows)[0])

    check_s = 0.0

    def step(kind=None):
        """One ``partial_fit`` step; ``kind`` captures it for the check."""
        nonlocal check_s
        rows = next_rows(lda)
        if kind is not None:
            t = time.perf_counter()
            lam = host(lda.lam)
            old_pi = memo_rows(rows) if kind == "filled" else None
            check_s += time.perf_counter() - t
        lda.partial_fit(steps=1)
        log.append(rows)
        if kind is not None:
            t = time.perf_counter()
            captured[len(log) - 1] = SimpleNamespace(
                kind=kind, lam=lam if kind == "filled" else None,
                d_lam=host(lda.lam) - lam, old_pi=old_pi,
                written=memo_rows(rows))
            check_s += time.perf_counter() - t
        return rows

    # set-up: the first pass retires the random init mass (a step that
    # never retires it is a fault the check will show: set-up then stops
    # after two passes' worth of documents)
    while True:
        step("seed" if len(log) < SEED_CHECKS else None)
        if float(lda.state.init_frac) == 0.0 \
                or lda.docs_seen >= 2 * data.num_docs:
            break
    for _ in range(FILLED_CHECKS):
        step("filled")
    jax.block_until_ready(lda.lam)
    setup_s = time.perf_counter() - ctx.t_start - check_s
    first_window_step = len(log)
    prof = Profiler(ctx.trace)
    tokens = 0.0
    with prof:
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < ctx.seconds:
            tokens += data.doc_tokens[step()].sum()
        jax.block_until_ready(lda.lam)
        window_s = time.perf_counter() - t0
    n_window = len(log) - first_window_step
    peak = L.memory_peak_bytes()
    for _ in range(FILLED_CHECKS):
        step("filled")
    return SimpleNamespace(
        log=log, captured=captured, prof=prof, setup_s=setup_s,
        first_window_step=first_window_step, n_window=n_window,
        window_s=window_s, tokens=tokens, peak=peak)


def run(ctx: L.Context) -> L.Outcome:
    import jax

    cfg, mix = ctx.cfg, ctx.mix
    phi = C.topics(cfg, ctx.seed)
    data = C.make_corpus(cfg, phi, n_docs=cfg["num_train_docs"],
                         seed=ctx.seed)
    del phi
    tel = telemetry(ctx.trace)
    with _planted(ctx.variant if ctx.variant not in (None, "control")
                  else None):
        r = _timed(ctx, data, tel)
    spans = recorder_spans(tel.trace) if tel is not None else []
    records = window_records(tel.trace, r.prof) if tel is not None else []
    summary = r.prof.summary(spans)
    gc.collect()

    batch, tile = mix["batch_size"], mix["kernel_block_b"]
    ref = lda_ref.Reference(
        cfg, data.token_ids, data.counts, ctx.seed, batch_rows=batch,
        tile_rows=tile, denominator="real", t_cap=batch * data.max_unique,
        wire_bf16=mix["memo_store"] == "chunked")
    gaps = {"seed": ([], []), "filled": ([], [])}
    sweeps = []
    for i in sorted(r.captured):
        c, rows = r.captured[i], r.log[i]
        if c.kind == "seed":
            # the reference has followed every step before this one
            lam_before = ref.state["lam"]
            ref_pi, _, _ = ref.step(rows)
            d_ref = host(ref.state["lam"] - lam_before)
        else:
            seen = np.isin(rows, np.concatenate(r.log[:i]))
            d, added, ref_pi, s = ref.step_from(c.lam, c.old_pi, seen,
                                                rows)
            # Δλ as a λ held in fp32 shows it, as the program's does
            d_ref = (c.lam + host(d)) - c.lam
            sweeps.append(s)
        if c.kind == "seed":
            gaps["seed"][0].append(_rel_gap(c.d_lam, d_ref))
        else:
            gaps["filled"][0].append(
                np.linalg.norm(c.d_lam.astype(np.float64) - d_ref) / added)
        gaps[c.kind][1].append(_pi_gap(c.written, ref_pi))
    lim = mix["limits"]
    # np.max, not max: a nan reading stays nan, and nan fails its limit
    checks = [(name, float(np.max(gaps[kind][j])), lim[name])
              for kind, names in (("seed", ("lam_step_gap", "pi_gap")),
                                  ("filled", ("corr_gap_filled",
                                              "pi_gap_filled")))
              for j, name in enumerate(names)]
    # the window's steps take the mean sweeps per tile of the filled-memo
    # steps that the reference ran
    tile_sweeps = np.mean(sweeps, axis=0).tolist()
    steps = []
    for rows in r.log[r.first_window_step:
                      r.first_window_step + r.n_window]:
        steps.append({"docs": len(rows),
                      "live_slots": int(data.counts[rows].astype(bool).sum()),
                      "tokens": float(data.doc_tokens[rows].sum()),
                      "sweeps": tile_sweeps[: -(-len(rows) // tile)]})
    ld = L.LayerData(
        spans=records, steps=steps, trace=summary, window_s=r.window_s,
        chips=ctx.cell["chips"],
        shape={"layout": mix["layout"], "V": cfg["vocab_size"],
               "K": cfg["num_topics"], "B": batch, "L": data.max_unique,
               "block_b": tile})
    if ctx.trace:
        ld.peaks = L.peaks_for(jax.devices()[0].device_kind)
    return L.Outcome(
        e2e={"train_tokens_per_s": r.tokens / r.window_s,
             "setup_s": r.setup_s},
        attempted=r.n_window, failed=0, checks=checks,
        memory_peak_bytes=r.peak, layer=ld)


def _rel_gap(got: np.ndarray, want: np.ndarray) -> float:
    """‖got − want‖_F / ‖want‖_F."""
    return float(np.linalg.norm(got.astype(np.float64) - want)
                 / np.linalg.norm(want.astype(np.float64)))


def _pi_gap(prog_pi: np.ndarray, ref_docs) -> float:
    """‖π − π_ref‖_F / ‖π_ref‖_F over the batch's live memo entries."""
    diff = norm = 0.0
    for d, ref in enumerate(ref_docs):
        got = prog_pi[d, : len(ref)].astype(np.float64)
        diff += float(((got - ref) ** 2).sum())
        norm += float((ref.astype(np.float64) ** 2).sum())
    return (diff / max(norm, 1e-300)) ** 0.5


def _planted(fault):
    """A fault planted under the timed path (``bench/faults.py``), or
    nothing."""
    from contextlib import nullcontext
    if fault is None:
        return nullcontext()
    from bench import faults
    return faults.plant(fault)
