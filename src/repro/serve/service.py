"""The serving loop: an open request stream under latency SLOs.

``ServingService`` drives one serving replica end to end:

    requests ──► admission control ──► BatchPacker batches ──► E-step
                  (shed / file / flush)        (`repro.serve.admission`)
                                                 │
                          OnlineLearner ◄── served documents
                          (background partial_fit, publishes λ
                           via atomic snapshot swap — `online.py`)

The loop is **open-loop real time**: requests carry scheduled arrival
times (`repro.serve.traffic`), the service sleeps until the next arrival
or the next admission-flush horizon, whichever is earlier, and a
response's latency is completion − *scheduled* arrival — queueing delay
included, the honest client-side number. Batches run through
``TopicInferencer.posterior_packed`` and block per batch, so the latency
histogram measures real device completion, not dispatch.

With a live ``repro.obs`` bundle the loop's top-level spans tile it:
``serve/admit`` per offered request (the offer, the packer's ``add`` and
the flush check after it; attr ``lag_ms``: offer − scheduled arrival,
the intake wait; ``shed`` when refused), ``serve/flush`` around a poll or
close that emits batches (attr ``batches``), ``serve/request_batch`` (the
E-step, blocked on γ) then ``serve/respond`` (the batch's requests out of
admission, γ to the host, the responses, the latency accounting, the
learner) per served batch, and ``serve/wait`` around each sleep (attr
``until``: ``arrival`` or ``flush``). None of them syncs the device.

Every OK response records the ``model_version`` of the snapshot that
served it; under an ``OnlineLearner`` the version advances mid-stream
while in-flight batches complete on the snapshot they started with
(`docs/serving.md` on the swap semantics).

``slo_report`` summarises a run against the config's SLO targets in a
schema-versioned record (``repro.serve.slo/v1``); ``validate_slo_report``
is the schema gate the CI smoke step runs.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from repro.obs import MetricsRegistry, as_telemetry
from repro.serve.admission import AdmissionController, Request, Response

SLO_SCHEMA = "repro.serve.slo/v1"


@dataclasses.dataclass(frozen=True)
class ServiceConfig:
    """Serving-loop policy knobs.

    ``slo_ms`` maps percentile names (``"p50"``/``"p95"``/``"p99"``) to
    latency targets in ms; targets are *reported* against (SLO
    attainment in ``slo_report``), never enforced in the loop.
    """

    flush_timeout_s: float = 0.05
    shed_margin_s: float = 0.0
    deadline_headroom_s: float = 0.0
    slo_ms: Optional[Dict[str, float]] = None


class ServingService:
    """One serving replica over an open request stream (see module doc).

    Args:
      inferencer: the snapshot-aware ``TopicInferencer`` to serve with —
        batch formation copies its ``packer_kwargs()``, so served batches
        are bit-equal to ``posterior_docs`` on the same admitted
        sequence.
      config: a ``ServiceConfig``.
      learner: optional ``repro.serve.online.OnlineLearner`` — every
        served document is fed to it (non-blocking append; training and
        λ publication happen on the learner's own cadence/thread).
      telemetry: ``repro.obs`` bundle. The service ALWAYS keeps a
        metrics registry for ``serve.latency_ms`` (latency accounting is
        the product here, not optional observability): the bundle's when
        enabled, a private one otherwise. The ``admit.*``, ``pack.*`` and
        ``serve.shed`` counters go to a live bundle only.
      clock/sleep: injectable time sources (tests).
    """

    def __init__(self, inferencer, *, config: Optional[ServiceConfig] = None,
                 learner=None, telemetry=None,
                 clock: Callable[[], float] = time.perf_counter,
                 sleep: Callable[[float], None] = time.sleep):
        self.inf = inferencer
        self.config = config or ServiceConfig()
        self.learner = learner
        self.tel = as_telemetry(telemetry)
        self.metrics = (self.tel.metrics if self.tel.enabled
                        else MetricsRegistry())
        self._clock, self._sleep = clock, sleep
        self.admission = AdmissionController(
            inferencer.packer_kwargs(),
            flush_timeout_s=self.config.flush_timeout_s,
            shed_margin_s=self.config.shed_margin_s,
            deadline_headroom_s=self.config.deadline_headroom_s,
            metrics=self.tel.metrics if self.tel.enabled else None)
        self.responses: List[Response] = []
        self._t0: Optional[float] = None
        self._last_done = 0.0

    # -- the loop --------------------------------------------------------
    def _now(self) -> float:
        return self._clock() - self._t0

    def run(self, requests: Sequence[Request]) -> List[Response]:
        """Serve a scheduled request stream to completion.

        ``requests`` must be sorted by ``arrival_s`` (the traffic
        generators emit them sorted). The call blocks for the schedule's
        real duration; at stream end every open bucket is flushed and
        served (the stream is closed — no further traffic justifies
        holding a partial batch). Returns the responses, completion
        order; they accumulate on ``self.responses`` across runs.
        """
        if self._t0 is None:
            self._t0 = self._clock()
        tel = self.tel
        out_start = len(self.responses)
        for req in requests:
            # sleep toward the arrival, waking for due partial flushes
            while True:
                now = self._now()
                if now >= req.arrival_s:
                    break
                due = self.admission.next_due(now)
                if due is not None and due < req.arrival_s:
                    if due > now:
                        self._wait(due - now, "flush")
                    self._poll_flushes()
                else:
                    self._wait(req.arrival_s - now, "arrival")
            sp = tel.trace.begin("serve/admit",
                                 lag_ms=(now - req.arrival_s) * 1e3) \
                if tel.enabled else None
            admitted, batch = self.admission.offer(req, now)
            if not admitted:
                self.responses.append(Response(
                    rid=req.rid, status="shed", gamma=None,
                    model_version=None, arrival_s=req.arrival_s,
                    done_s=now))
            flush = batch is None and self.admission.due(now)
            if sp is not None:
                if admitted:
                    tel.trace.end(sp)
                else:
                    tel.trace.end(sp, shed=True)
                    tel.metrics.inc("serve.shed")
            if batch is not None:
                self._serve_batch(batch)
                now = self._now()
                flush = self.admission.due(now)
            if flush:
                self._flush(self.admission.poll, now)
        self._flush(self.admission.close, self._now())
        return self.responses[out_start:]

    def _wait(self, seconds: float, until: str) -> None:
        tel = self.tel
        sp = tel.trace.begin("serve/wait", until=until) \
            if tel.enabled else None
        self._sleep(seconds)
        if sp is not None:
            tel.trace.end(sp)

    def _poll_flushes(self) -> None:
        now = self._now()
        if self.admission.due(now):
            self._flush(self.admission.poll, now)

    def _flush(self, emit, now: float) -> None:
        """Serve the batches ``emit(now)`` (the admission's ``poll`` or
        ``close``) hands out, under one ``serve/flush`` span."""
        tel = self.tel
        sp = tel.trace.begin("serve/flush") if tel.enabled else None
        batches = emit(now)
        for batch in batches:
            self._serve_batch(batch)
        if sp is not None:
            tel.trace.end(sp, batches=len(batches))

    def _serve_batch(self, batch) -> None:
        tel = self.tel
        start = self._now()
        sp = tel.trace.begin("serve/request_batch",
                             docs=len(batch.rows)) if tel.enabled else None
        _, gamma, n, version = self.inf.posterior_packed(batch)
        gamma.block_until_ready()          # honest completion time
        if sp is not None:
            tel.trace.end(sp)
            sp = tel.trace.begin("serve/respond", docs=n)
        done = self._now()
        self._last_done = max(self._last_done, done)
        taken = self.admission.take(batch.rows, start)
        g = np.asarray(gamma[:n])
        for i, (req, admit_s) in enumerate(taken):
            self.responses.append(Response(
                rid=req.rid, status="ok", gamma=g[i],
                model_version=version, arrival_s=req.arrival_s,
                done_s=done, admit_s=admit_s, start_s=start))
            self.metrics.observe("serve.latency_ms",
                                 (done - req.arrival_s) * 1e3)
        if self.learner is not None:
            self.learner.observe([(r.ids, r.cnts) for r, _ in taken])
        if sp is not None:
            tel.trace.end(sp)

    # -- reporting -------------------------------------------------------
    def slo_report(self) -> dict:
        """The run summary: counts, latency percentiles, throughput,
        model-version coverage, SLO attainment (``repro.serve.slo/v1``)."""
        ok = [r for r in self.responses if r.ok]
        shed = [r for r in self.responses if r.status == "shed"]
        pct = self.metrics.percentiles("serve.latency_ms",
                                       ps=(50, 95, 99))
        lat = self.metrics.histogram_values("serve.latency_ms")
        wall = max(self._last_done, 1e-9)
        versions = sorted({r.model_version for r in ok})
        report = {
            "schema": SLO_SCHEMA,
            "offered": self.admission.offered,
            "served": len(ok),
            "shed": len(shed),
            "pending": self.admission.pending,
            "conservation_ok": (self.admission.offered
                                == len(ok) + len(shed)
                                + self.admission.pending),
            "latency_ms": {"p50": pct["p50"], "p95": pct["p95"],
                           "p99": pct["p99"],
                           "max": max(lat) if lat else float("nan")},
            "throughput_docs_s": len(ok) / wall,
            "wall_s": wall,
            "model_versions": versions,
            "every_response_versioned": all(
                r.model_version is not None for r in ok),
            "slo": {},
        }
        if self.config.slo_ms:
            for name, target in sorted(self.config.slo_ms.items()):
                got = report["latency_ms"].get(name, float("nan"))
                report["slo"][name] = {
                    "target_ms": float(target), "observed_ms": got,
                    "attained": bool(got <= target) if not math.isnan(got)
                    else False,
                }
        return report


def validate_slo_report(report: dict) -> dict:
    """Schema gate for ``slo_report`` output (the CI smoke runs this) —
    raises ``ValueError`` on any shape violation, returns the report."""
    if not isinstance(report, dict):
        raise ValueError("SLO report must be a dict")
    if report.get("schema") != SLO_SCHEMA:
        raise ValueError(f"unknown SLO report schema "
                         f"{report.get('schema')!r} (want {SLO_SCHEMA})")
    for key, typ in (("offered", int), ("served", int), ("shed", int),
                     ("pending", int), ("conservation_ok", bool),
                     ("latency_ms", dict), ("throughput_docs_s", float),
                     ("wall_s", float), ("model_versions", list),
                     ("every_response_versioned", bool), ("slo", dict)):
        if key not in report:
            raise ValueError(f"SLO report missing {key!r}")
        if not isinstance(report[key], typ):
            raise ValueError(f"SLO report field {key!r} must be "
                             f"{typ.__name__}, got "
                             f"{type(report[key]).__name__}")
    for p in ("p50", "p95", "p99", "max"):
        if p not in report["latency_ms"]:
            raise ValueError(f"latency_ms missing {p!r}")
        v = report["latency_ms"][p]
        if not isinstance(v, float) or (not math.isnan(v) and v < 0):
            raise ValueError(f"latency_ms[{p!r}] must be a non-negative "
                             f"float or NaN, got {v!r}")
    if not report["conservation_ok"]:
        raise ValueError(
            f"request conservation violated: offered={report['offered']} "
            f"!= served={report['served']} + shed={report['shed']} + "
            f"pending={report['pending']}")
    for name, slo in report["slo"].items():
        for k in ("target_ms", "observed_ms", "attained"):
            if k not in slo:
                raise ValueError(f"slo[{name!r}] missing {k!r}")
    return report
