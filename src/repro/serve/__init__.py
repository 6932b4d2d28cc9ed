"""repro.serve — the online serving service.

An open request stream served under latency SLOs while the paper's
incremental update trains on the served traffic in the background:

* `repro.serve.traffic` — seeded Poisson / bursty ON-OFF / replay
  arrival schedules;
* `repro.serve.admission` — deadline- and size-aware batch formation
  over the ragged-pipeline ``BatchPacker`` (shedding, timeout-based
  partial flush);
* `repro.serve.service` — the real-time serving loop + SLO reporting
  (``repro.serve.slo/v1`` schema);
* `repro.serve.snapshot` — atomic versioned λ publication with a
  measured swap-stall window;
* `repro.serve.online` — the background IVI learner feeding it.

See ``docs/serving.md`` for the architecture and semantics.
"""
from repro.serve.admission import AdmissionController, Request, Response
from repro.serve.online import OnlineLearner
from repro.serve.service import (
    SLO_SCHEMA,
    ServiceConfig,
    ServingService,
    validate_slo_report,
)
from repro.serve.snapshot import ModelSnapshot, SnapshotStore
from repro.serve.traffic import (
    onoff_arrivals,
    poisson_arrivals,
    replay_arrivals,
    requests_from_docs,
)

__all__ = [
    "Request", "Response", "AdmissionController",
    "ServiceConfig", "ServingService", "SLO_SCHEMA", "validate_slo_report",
    "ModelSnapshot", "SnapshotStore", "OnlineLearner",
    "poisson_arrivals", "onoff_arrivals", "replay_arrivals",
    "requests_from_docs",
]
