"""Versioned model snapshots: the publish side of online serving.

``TopicInferencer`` holds its topics as one atomic ``(version,
exp_elog_beta)`` tuple (`repro.lda.infer.TopicInferencer.swap_model`);
this module is the other half of the contract — the PUBLISHER the online
learner drives:

* ``ModelSnapshot`` is the immutable record of one publication (version,
  the λ it came from, how many documents trained it, when it went live);
* ``SnapshotStore`` owns the expensive part of a swap — preprocessing λ
  to exp(E[ln φ]) and materialising it on device — OUTSIDE the serving
  swap window, then publishes to every attached inferencer with one
  ``swap_model`` call each and **measures the swap stall** (the wall time
  a concurrent request could contend on). That measured window is the
  ``serve.swap_stall_ms`` histogram: inference never blocks on training
  beyond it.

The store is thread-safe: one learner publishing while any number of
serving threads read is the designed case; multiple publishers serialise
on the store lock.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, List, Optional

import jax.numpy as jnp

from repro.core.math import exp_dirichlet_expectation


@dataclasses.dataclass(frozen=True)
class ModelSnapshot:
    """One published model version (immutable)."""

    version: int
    exp_elog_beta: object           # (V, K) device array, ready to serve
    docs_trained: int               # documents the publisher had consumed
    published_s: float              # store-clock time publish() returned
    swap_stall_s: float             # measured swap window (see module doc)


class SnapshotStore:
    """Atomic λ publication to attached inferencers (see module docstring).

    Args:
      inferencer: a ``TopicInferencer`` to publish to (more via
        ``attach`` — e.g. one per serving replica; every attached
        inferencer receives the same version number).
      metrics: optional ``repro.obs`` ``MetricsRegistry`` — each publish
        observes ``serve.swap_stall_ms`` and bumps ``serve.publishes``.
      clock: injectable monotonic clock (tests).
    """

    def __init__(self, inferencer=None, *, metrics=None,
                 clock: Callable[[], float] = time.perf_counter):
        self._infs = [inferencer] if inferencer is not None else []
        self.metrics = metrics
        self._clock = clock
        self._lock = threading.Lock()
        self.history: List[ModelSnapshot] = []

    def attach(self, inferencer) -> None:
        """Add a serving replica; it picks up the NEXT publish (its
        current snapshot is whatever it was constructed with)."""
        with self._lock:
            self._infs.append(inferencer)

    @property
    def current(self) -> Optional[ModelSnapshot]:
        return self.history[-1] if self.history else None

    def publish(self, lam, *, docs_trained: int = 0) -> ModelSnapshot:
        """Preprocess λ and swap it into every attached inferencer.

        The preprocessing (exp(E[ln φ]) + device materialisation via
        ``block_until_ready``) happens on THIS thread before the swap
        window opens, so a serving thread never waits on an
        unmaterialised snapshot; the measured ``swap_stall_s`` covers
        only the ``swap_model`` reference assignments.
        """
        eb = exp_dirichlet_expectation(jnp.asarray(lam), axis=0)
        eb.block_until_ready()
        with self._lock:
            if not self._infs:
                raise ValueError("no inferencer attached — publish() has "
                                 "nowhere to swap the snapshot into")
            t0 = self._clock()
            version = None
            for inf in self._infs:
                v = inf.swap_model(exp_elog_beta=eb)
                version = v if version is None else version
            stall = self._clock() - t0
            snap = ModelSnapshot(version=version, exp_elog_beta=eb,
                                 docs_trained=int(docs_trained),
                                 published_s=self._clock(),
                                 swap_stall_s=stall)
            self.history.append(snap)
        if self.metrics is not None:
            self.metrics.inc("serve.publishes")
            self.metrics.observe("serve.swap_stall_ms", stall * 1e3)
        return snap

    def swap_stalls_ms(self) -> List[float]:
        """Measured swap windows of every publish, in ms (the bench's
        bounded-stall assertion reads this)."""
        return [s.swap_stall_s * 1e3 for s in self.history]
