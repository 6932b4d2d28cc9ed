"""D-IVI production path: one global round as a ``shard_map`` program.

Sharding layout on a ``("data", "model")`` (optionally ``("pod", "data",
"model")``) mesh — DESIGN mirrors the paper's master/worker message flow:

* **master state** λ / ⟨m_vk⟩ / init_mass: model-sharded on V
  (``P("model", None)``) — the master is itself distributed over the model
  axis; scalars (init_frac, t) replicated;
* **worker state** (the π-memo shards) and the per-round inputs (the
  streamed token_ids/counts batches, idx, delay): data-sharded on the
  leading worker axis. The corpus is NOT device state — each worker's
  ``WorkerIngest`` streams one ``(S, B, L)`` slab of documents into the
  round, so the argument footprint is per-round batches, not a resident
  ``(W, D_w, L)`` corpus;
* each sub-round reduces the (V, K) corrections with **one psum over the
  data axes** — the same single message the paper's workers send to the
  master — and the λ fetch is one all-gather of the model-sharded rows.

The worker E-step runs on the *full* mini-batch of each worker (replicated
across the model axis). This is deliberate: the E-step's fixed-point stop
criterion couples the documents of a batch, so splitting a worker's batch
over the model axis would change its numerics — and bit-parity with the
single-device vmap simulation (``repro.dist.protocol.divi_round``) is the
correctness contract validated by ``tests/test_divi.py``. The two paths
share ``worker_correction`` / ``master_update`` verbatim; the only
difference is *where* the worker loop runs (vmap axis vs. data-mesh axis)
and how the corrections are reduced (``sum`` vs. ``psum``).
"""
from __future__ import annotations

import math
from functools import partial

import jax
from jax.sharding import PartitionSpec as P

from repro.core.math import exp_dirichlet_expectation
from repro.core.memo import DenseMemoStore
from repro.core.types import LDAConfig
from repro.dist.protocol import (DIVIConfig, DIVIState, WorkerShard,
                                 divi_round, master_update,
                                 worker_correction)

__all__ = ["DIVIConfig", "DIVIState", "WorkerShard", "divi_round",
           "make_divi_round"]


def make_divi_round(cfg: LDAConfig, dcfg: DIVIConfig, mesh,
                    data_axes) -> jax.stages.Wrapped:
    """Build the jitted shard_map round for ``mesh``.

    Returns a callable/lowerable ``round(state, shard, token_ids, counts,
    idx, delay, num_words_total) -> (state, shard)`` with

      state: DIVIState — (V, K) leaves sharded ``P("model", None)``;
      shard: WorkerShard — leading worker axis sharded over ``data_axes``;
      token_ids/counts: (W, S, B, L) streamed batches, idx: (W, S, B)
      int32, delay: (W, S) bool — all data-sharded on the worker axis;
      num_words_total: () float32, replicated.
    """
    data_axes = tuple(data_axes)
    model = "model" if "model" in mesh.axis_names else None
    n_data = math.prod(int(mesh.shape[a]) for a in data_axes)
    if dcfg.num_workers % n_data:
        raise ValueError(
            f"num_workers={dcfg.num_workers} not divisible by the data-mesh "
            f"size {n_data} ({data_axes})")
    if model and cfg.vocab_size % int(mesh.shape[model]):
        raise ValueError(
            f"vocab_size={cfg.vocab_size} not divisible by the model axis "
            f"({int(mesh.shape[model])}) — pad V")

    mrow = P(model, None)
    state_specs = DIVIState(lam=mrow, m_vk=mrow, init_mass=mrow,
                            init_frac=P(), t=P())
    shard_specs = WorkerShard(
        memo=DenseMemoStore(pi=P(data_axes, None, None, None),
                            visited=P(data_axes, None)))
    in_specs = (state_specs, shard_specs,
                P(data_axes, None, None, None),      # token_ids (W, S, B, L)
                P(data_axes, None, None, None),      # counts    (W, S, B, L)
                P(data_axes, None, None),            # idx       (W, S, B)
                P(data_axes, None),                  # delay     (W, S)
                P())
    out_specs = (state_specs, shard_specs)

    def round_body(state, shard, token_ids, counts, idx, delay,
                   num_words_total):
        # "fetch λ from the master": all-gather the model-sharded rows, then
        # compute exp(E[ln φ]) exactly as the simulation does on the full λ.
        lam_full = (jax.lax.all_gather(state.lam, model, axis=0, tiled=True)
                    if model else state.lam)
        eb = exp_dirichlet_expectation(lam_full, axis=0)
        v_local = state.lam.shape[0]
        row0 = (jax.lax.axis_index(model) * v_local) if model else 0

        def substep(carry, xs):
            st, memo = carry
            ids_s, cnts_s, idx_s, delay_s = xs   # (W_loc, B, L) ×2, (W_loc,
            corr_w, words_w, memo = jax.vmap(    # B), (W_loc,)
                partial(worker_correction, cfg, eb))(
                    ids_s, cnts_s, memo, idx_s, delay_s)
            # "send the correction to the master": the round's one message.
            corr = corr_w.sum(0)
            words = words_w.sum()
            if data_axes:
                corr = jax.lax.psum(corr, data_axes)
                words = jax.lax.psum(words, data_axes)
            corr = jax.lax.dynamic_slice_in_dim(corr, row0, v_local, axis=0) \
                if model else corr
            st = master_update(cfg, st, corr, words, num_words_total)
            return (st, memo), None

        (state, memo), _ = jax.lax.scan(
            substep, (state, shard.memo),
            (token_ids.swapaxes(0, 1), counts.swapaxes(0, 1),
             idx.swapaxes(0, 1), delay.swapaxes(0, 1)))
        return state, WorkerShard(memo=memo)

    fn = jax.shard_map(round_body, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return jax.jit(fn, donate_argnums=(0, 1))
