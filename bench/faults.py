"""Faults planted under the timed path, to show that the check catches them.

Used only by ``bench/tools/readings.py`` and ``bench/tests``; a benchmark
run never plants one. Each fault patches the program in this process for
the length of a ``with plant(name):`` block, where the real code produces
the value:

* ``state_unchanged``: the training step returns the state it was given;
* ``half_batch``: the second half of every batch's documents lose their
  counts, so the step or the served batch is computed from the rest;
* ``token_altered``: the first token of every batch gets the next word id;
* ``memo_rows``: the training step gets each document's old π from the
  memo rows of the next document in its batch, as a gather that reads the
  wrong rows would give it (the store itself is untouched).
"""
from __future__ import annotations

import contextlib
from typing import Callable, Iterator, List, Tuple

FAULTS = ("state_unchanged", "half_batch", "token_altered", "memo_rows")


def _copy(tree):
    import jax
    import jax.numpy as jnp
    return jax.tree_util.tree_map(jnp.copy, tree)


def _half_padded(cnts):
    """Zero the counts of the second half of a (B, L) batch's docs."""
    b = cnts.shape[0]
    return cnts.at[b // 2:, :].set(0.0)


def _alter_first(ids, vocab: int):
    flat = ids.reshape(-1)
    return flat.at[0].set((flat[0] + 1) % vocab).reshape(ids.shape)


@contextlib.contextmanager
def plant(fault: str) -> Iterator[None]:
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r} (have {FAULTS})")
    import jax.numpy as jnp

    from repro.core import engines
    from repro.lda import infer

    patches: List[Tuple[object, str, Callable]] = []
    real_upd = engines.incremental_update
    real_packed = infer.TopicInferencer.posterior_packed

    if fault == "state_unchanged":
        def upd(cfg, averaged, state, *a, **k):
            old = _copy(state)
            _, pi, eb = real_upd(cfg, averaged, state, *a, **k)
            return old, pi, eb

        patches.append((engines, "incremental_update", upd))
    elif fault == "memo_rows":
        def upd(cfg, averaged, state, ids, cnts, old_pi, *a, **k):
            return real_upd(cfg, averaged, state, ids, cnts,
                            jnp.roll(old_pi, -1, axis=0), *a, **k)

        patches.append((engines, "incremental_update", upd))
    else:                                   # half_batch, token_altered
        def upd(cfg, averaged, state, ids, cnts, *a, **k):
            if fault == "half_batch":
                cnts = _half_padded(cnts)
            else:
                ids = _alter_first(ids, cfg.vocab_size)
            return real_upd(cfg, averaged, state, ids, cnts, *a, **k)

        def packed(self, batch):
            import numpy as np
            ids, cnts = np.array(batch.token_ids), np.array(batch.counts)
            if fault == "half_batch":
                if hasattr(batch, "segments"):
                    n = len(batch.rows)
                    cnts[batch.segments >= (n + 1) // 2] = 0.0
                else:
                    cnts[cnts.shape[0] // 2:] = 0.0
            else:
                ids.reshape(-1)[0] = (ids.reshape(-1)[0] + 1) \
                    % self.cfg.vocab_size
            return real_packed(self, batch._replace(token_ids=ids,
                                                    counts=cnts))

        patches += [(engines, "incremental_update", upd),
                    (infer.TopicInferencer, "posterior_packed", packed)]

    saved = [(obj, name, getattr(obj, name)) for obj, name, _ in patches]
    try:
        for obj, name, fn in patches:
            setattr(obj, name, fn)
        yield
    finally:
        for obj, name, fn in saved:
            setattr(obj, name, fn)
