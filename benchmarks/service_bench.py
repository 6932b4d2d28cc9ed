"""Serving-service benchmark: SLO attainment + online-vs-frozen.

Produces ``BENCH_service.json`` — the evidence record for the `repro.serve`
subsystem (`docs/serving.md`):

* **slo** — the real-time service run under both synthetic traffic shapes
  (seeded Poisson and bursty ON-OFF, `repro.serve.traffic`), each emitting
  a schema-validated ``repro.serve.slo/v1`` report: p50/p95/p99 latency,
  throughput, request conservation (offered == served + shed), and
  attainment against a deliberately generous CPU-proxy target. The
  asserted bars here are the *structural* ones — conservation and
  every-response-versioned — latency magnitudes on a shared CPU runner
  are recorded for trend, not barred.
* **swap_stall** — the measured atomic-snapshot swap window across every
  online publish in the bench (`repro.serve.snapshot`). The CI bar: max
  stall ≤ ``SWAP_STALL_BOUND_MS``. The swap is two reference assignments
  under a lock (the Eφ preprocessing runs *before* the lock), so 50 ms is
  generous by ~3 orders of magnitude — the bar exists to catch anyone
  moving device work back inside the swap.
* **online_vs_frozen** — the paper's headline at serving time: a
  deliberately undertrained frozen model (one pass over a quarter-scale
  corpus) versus the same model after ``OnlineLearner`` trained on the
  served traffic (warm start + IVI passes + drain). Held-out
  log-predictive delta over several seeds with a Student-t 95% CI; the
  bar: the CI lower bound is > 0 — online serving *provably* beats the
  frozen snapshot, not just on average.
* **watchdog** — the ELBO watchdog must have produced ≥ 1 *armed*
  monotonicity reading per run (the drain passes over the quiet window)
  with zero violations: the swaps never served a bound-degrading λ.

``--dryrun`` is the CI smoke: fewer requests/seeds, same asserted bars.
"""
from __future__ import annotations

import argparse
import json
import math
import time

import numpy as np

from repro.launch.compile_cache import enable_compile_cache

# ---------------------------------------------------------------------------
# bench constants (documented in docs/serving.md §benchmark)
# ---------------------------------------------------------------------------
SWAP_STALL_BOUND_MS = 50.0     # generous bound on the atomic swap window
SLO_TARGET_MS = {"p95": 5000.0, "p99": 10000.0}   # CPU-proxy targets
FULL_SEEDS = [0, 1, 2, 3, 4]
DRY_SEEDS = [0, 1, 2]
FROZEN_SCALE = 0.25            # frozen model sees a quarter-scale corpus
SCORE_SPLIT_SEED = 0           # one held-out split shared by every score

# two-sided 95% Student-t critical values by degrees of freedom
_T_CRIT = {1: 12.71, 2: 4.30, 3: 3.18, 4: 2.78, 5: 2.57, 6: 2.45,
           7: 2.36, 8: 2.31, 9: 2.26}


def _base_model(seed: int, *, corpus: str = "tiny", topics: int = 8,
                estep_iters: int = 20):
    """The deliberately *undertrained* serving model: one IVI pass over a
    quarter-scale train corpus. Returns (lda, full train ragged docs,
    test corpus) — the full train split is the traffic the online learner
    gets to see and the frozen model never did."""
    from repro.data import PAPER_CORPORA, make_corpus
    from repro.data.stream import CorpusDocStream
    from repro.lda import LDA

    spec = PAPER_CORPORA[corpus]
    sub = make_corpus(spec, split="train", seed=seed, scale=FROZEN_SCALE)
    lda = LDA(num_topics=topics, vocab_size=spec.vocab_size,
              estep_max_iters=estep_iters, algo="ivi", seed=seed)
    lda.fit(sub, epochs=1)
    train = make_corpus(spec, split="train", seed=seed)
    test = make_corpus(spec, split="test", seed=seed)
    train_docs = list(CorpusDocStream(train).iter_from(0))
    return lda, train_docs, test


def _arrivals(shape: str, n: int, rate: float, seed: int):
    from repro.serve import onoff_arrivals, poisson_arrivals
    if shape == "poisson":
        return poisson_arrivals(n, rate, seed=seed)
    return onoff_arrivals(n, rate, on_s=8.0 / rate, off_s=8.0 / rate,
                          seed=seed)


def _run_service(lda, docs, *, shape: str, rate: float, seed: int,
                 online: bool, batch: int = 16,
                 flush_timeout_s: float = 0.02,
                 cadence_s: float = 0.05):
    """One end-to-end service run; returns (slo report, learner or None)."""
    from repro.serve import (OnlineLearner, ServiceConfig, ServingService,
                             SnapshotStore, requests_from_docs)

    inf = lda.inferencer(batch_size=batch)
    inf.posterior_docs(docs)               # warm every bucket width
    arrivals = _arrivals(shape, len(docs), rate, seed)
    requests = requests_from_docs(docs, arrivals)
    svc = ServingService(inf, config=ServiceConfig(
        flush_timeout_s=flush_timeout_s, slo_ms=dict(SLO_TARGET_MS)))
    learner = None
    if online:
        store = SnapshotStore(inf, metrics=svc.metrics)
        learner = OnlineLearner(lda.cfg, store, lam0=np.asarray(lda.lam),
                                cadence_s=cadence_s, seed=seed)
        svc.learner = learner
        learner.start()
    try:
        svc.run(requests)
    finally:
        if learner is not None:
            learner.stop()
    if learner is not None:
        learner.drain(passes=2)
    return svc.slo_report(), learner


# ---------------------------------------------------------------------------
# sections
# ---------------------------------------------------------------------------

def slo_section(*, n_requests: int, rate: float, seed: int = 0) -> dict:
    """Both traffic shapes through the service (no learner — pure serving
    latency), each report schema-validated."""
    from repro.serve import validate_slo_report

    from repro.data.stream import CorpusDocStream

    lda, _, test = _base_model(seed)
    docs = list(CorpusDocStream(test).iter_from(0))[:n_requests]
    out = {}
    for shape in ("poisson", "onoff"):
        t0 = time.perf_counter()
        rep, _ = _run_service(lda, docs, shape=shape, rate=rate, seed=seed,
                              online=False)
        validate_slo_report(rep)
        out[shape] = {
            "traffic": {"shape": shape, "rate_docs_s": rate,
                        "n_requests": len(docs), "seed": seed},
            "wall_s": time.perf_counter() - t0,
            "report": rep,
            "validated": True,
        }
    return out


def online_section(seeds, *, rate: float = 400.0) -> dict:
    """Per-seed online-vs-frozen held-out delta + the swap/watchdog
    evidence each run produces (see module docstring)."""
    per_seed, stalls = [], []
    armed_total, violations_total = 0, 0
    versioned_all = True
    for seed in seeds:
        lda, train_docs, test = _base_model(seed)
        frozen = float(lda.score(test, seed=SCORE_SPLIT_SEED))
        rep, learner = _run_service(lda, train_docs, shape="poisson",
                                    rate=rate, seed=seed, online=True)
        online = float(learner.model.score(test, seed=SCORE_SPLIT_SEED))
        run_stalls = learner.store.swap_stalls_ms()
        stalls.extend(run_stalls)
        armed_total += learner.armed_observations
        violations_total += len(learner.watchdog.violations)
        versioned_all &= bool(rep["every_response_versioned"])
        per_seed.append({
            "seed": seed,
            "frozen_lpp": frozen,
            "online_lpp": online,
            "delta_lpp": online - frozen,
            "online_updates": learner.updates,
            "docs_trained": learner.docs_trained,
            "model_versions_served": rep["model_versions"],
            "served": rep["served"],
            "shed": rep["shed"],
            "armed_observations": learner.armed_observations,
            "watchdog_violations": len(learner.watchdog.violations),
            "swap_stalls_ms": run_stalls,
        })
    deltas = np.array([r["delta_lpp"] for r in per_seed])
    n = len(deltas)
    t_crit = _T_CRIT.get(n - 1, 1.96)
    sem = float(deltas.std(ddof=1) / math.sqrt(n)) if n > 1 else math.inf
    mean = float(deltas.mean())
    return {
        "online_vs_frozen": {
            "seeds": list(seeds),
            "frozen_setup": {"scale": FROZEN_SCALE, "epochs": 1},
            "per_seed": per_seed,
            "mean_delta_lpp": mean,
            "sem_delta_lpp": sem,
            "t_crit_95": t_crit,
            "ci95_lo": mean - t_crit * sem,
            "ci95_hi": mean + t_crit * sem,
            "improves_with_ci": mean - t_crit * sem > 0,
        },
        "swap_stall": {
            "n_swaps": len(stalls),
            "max_ms": max(stalls) if stalls else None,
            "mean_ms": float(np.mean(stalls)) if stalls else None,
            "bound_ms": SWAP_STALL_BOUND_MS,
            "meets_bound": bool(stalls) and max(stalls) <= SWAP_STALL_BOUND_MS,
        },
        "watchdog": {
            "armed_observations": armed_total,
            "violations": violations_total,
            "armed_ok": armed_total >= len(seeds) and violations_total == 0,
        },
        "every_response_versioned": versioned_all,
    }


def service_report(json_path=None, *, dryrun: bool = False) -> dict:
    seeds = DRY_SEEDS if dryrun else FULL_SEEDS
    n_req = 24 if dryrun else 32
    record = {
        "schema": "repro.serve.bench/v1",
        "dryrun": dryrun,
        "slo": slo_section(n_requests=n_req, rate=200.0),
    }
    record.update(online_section(seeds))
    if json_path:
        with open(json_path, "w") as f:
            json.dump(record, f, indent=2)
    return record


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="BENCH_service.json",
                    help="where to write the service record")
    ap.add_argument("--dryrun", action="store_true",
                    help="CI mode: fewer requests/seeds, same bars")
    args = ap.parse_args()
    rec = service_report(args.json, dryrun=args.dryrun)
    print(f"BENCH_service -> {args.json}")
    for shape in ("poisson", "onoff"):
        r = rec["slo"][shape]["report"]
        pct = r["latency_ms"]
        att = all(v["attained"] for v in r["slo"].values())
        print(f"  slo/{shape:7s}: {r['served']}/{r['offered']} served "
              f"p50={pct['p50']:.1f}ms p95={pct['p95']:.1f}ms "
              f"p99={pct['p99']:.1f}ms {r['throughput_docs_s']:.0f} docs/s "
              f"attained={att}")
    ov = rec["online_vs_frozen"]
    print(f"  online vs frozen: Δlpp={ov['mean_delta_lpp']:+.4f} "
          f"95% CI [{ov['ci95_lo']:+.4f}, {ov['ci95_hi']:+.4f}] "
          f"over seeds {ov['seeds']}")
    sw, wd = rec["swap_stall"], rec["watchdog"]
    print(f"  swap stall: max={sw['max_ms']:.3f}ms over {sw['n_swaps']} "
          f"swaps (bound {sw['bound_ms']:.0f}ms)")
    print(f"  watchdog: {wd['armed_observations']} armed readings, "
          f"{wd['violations']} violations")
    for shape in ("poisson", "onoff"):
        assert rec["slo"][shape]["report"]["conservation_ok"], \
            f"{shape}: offered != served + shed"
        assert rec["slo"][shape]["validated"]
    assert rec["every_response_versioned"], \
        "a response was served without a model version"
    assert sw["meets_bound"], \
        f"snapshot swap stalled {sw['max_ms']:.1f}ms > {sw['bound_ms']}ms"
    assert wd["armed_ok"], "watchdog never armed (or a swap broke the bound)"
    assert ov["improves_with_ci"], \
        "online serving did not beat the frozen model at 95% CI"
