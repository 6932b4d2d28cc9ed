"""Autotuner benchmark: tuned-vs-default kernel policies -> BENCH_tune.json.

Runs the real `repro.tune` search (`tune_shape`) on a panel of problem
shapes and records, for each, the default policy's cost, the tuned
winner's cost and the winner itself. Two invariants are asserted as CI
bars:

* **tuned never loses**: every record has ``tuned_cost <= default_cost``
  (the search falls back to the default when nothing gated cheaper, so a
  regression here means the search itself is broken);
* **honest objective**: off-TPU the objective is the structural HBM
  model and every record carries ``proxy_regime: true`` — interpret-mode
  wall time is never presented as a measurement (docs/tuning.md).

Panel:

* ``padded_small``  — V-resident serving-ish shape (gate cheap enough to
  run everywhere);
* ``padded_arxiv``  — the paper's Table 1 Arxiv shape (B=256, V=141 952,
  K=128): streaming regime, where halving the B-grid via ``block_b=256``
  halves per-sweep Eφ re-streams — the headline modeled win;
* ``csr``           — the flat-token path at the engine's default budget.

``--dryrun`` tunes only the small shape with a minimal budget (the CI
smoke: exercises search + gate + store round-trip in seconds).
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from repro.tune.search import (TuneShape, measurement_available,  # noqa: E402
                               tune_shape)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.tune.store import current_device_kind, policy_to_dict  # noqa: E402

PANEL = {
    "padded_small": dict(
        shape=TuneShape(task="padded", b_or_t=64, v=4096, k=128, w=64),
        budget=8, gate_candidates=2),
    "padded_arxiv": dict(
        shape=TuneShape(task="padded", b_or_t=256, v=141_952, k=128, w=128),
        budget=12, gate_candidates=3),
    "csr": dict(
        shape=TuneShape(task="csr", b_or_t=4096, v=8192, k=128, num_docs=64,
                        backend="csr", layout="csr"),
        budget=8, gate_candidates=2),
}

DRYRUN_PANEL = {
    "padded_small": dict(
        shape=PANEL["padded_small"]["shape"], budget=2, gate_candidates=1),
}


def _one(name: str, spec: dict, *, seed: int, iters: int,
         verbose: bool) -> dict:
    shape = spec["shape"]
    res = tune_shape(shape, budget=spec["budget"], seed=seed,
                     gate_candidates=spec["gate_candidates"], iters=iters,
                     verbose=verbose)
    return {
        "name": name,
        "shape": {"task": shape.task, "b_or_t": shape.b_or_t, "v": shape.v,
                  "k": shape.k, "w": shape.w, "num_docs": shape.num_docs,
                  "backend": shape.backend, "layout": shape.layout},
        "objective": res.objective,
        "proxy_regime": res.proxy_regime,
        "default_cost_s": res.default_cost,
        "tuned_cost_s": res.tuned_cost,
        "improvement": res.improvement,
        "trials": res.trials,
        "policy": policy_to_dict(res.policy),
        "tuned_is_default": res.improvement == 1.0,
        "effective": res.effective,
        "equality": res.equality,
    }


def tune_report(json_path=None, *, dryrun: bool = False, seed: int = 0,
                iters: int = 20, verbose: bool = False) -> dict:
    panel = DRYRUN_PANEL if dryrun else PANEL
    measured = measurement_available()
    records = [_one(name, spec, seed=seed, iters=iters, verbose=verbose)
               for name, spec in panel.items()]
    record = {
        "device_kind": current_device_kind(),
        "objective": "measured_seconds" if measured else "modeled_seconds",
        "proxy_regime": not measured,
        "dryrun": dryrun,
        "records": records,
        # the CI bars (asserted under __main__)
        "tuned_never_loses": all(r["tuned_cost_s"] <= r["default_cost_s"]
                                 for r in records),
        "proxy_regime_honest": all(r["proxy_regime"] == (not measured)
                                   for r in records),
    }
    if json_path:
        try:
            with open(json_path) as f:
                full = json.load(f)
        except (FileNotFoundError, json.JSONDecodeError):
            full = {}
        full["tune"] = record
        with open(json_path, "w") as f:
            json.dump(full, f, indent=2)
    return record


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="BENCH_tune.json",
                    help="where to write the tuned-vs-default records")
    ap.add_argument("--dryrun", action="store_true",
                    help="minimal budget, small shape only (CI smoke)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--iters", type=int, default=20,
                    help="fixed-point sweeps priced by the model")
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args()
    rec = tune_report(args.json, dryrun=args.dryrun, seed=args.seed,
                      iters=args.iters, verbose=args.verbose)
    tag = " [proxy_regime]" if rec["proxy_regime"] else ""
    print(f"BENCH_tune -> {args.json} on {rec['device_kind']} "
          f"({rec['objective']}{tag})")
    for r in rec["records"]:
        s = r["shape"]
        win = ("default kept" if r["tuned_is_default"]
               else f"{r['improvement']:.2f}x")
        print(f"  {r['name']:<14} B_or_T={s['b_or_t']} V={s['v']} "
              f"K={s['k']} W={s['w']}: default {r['default_cost_s']:.3e}s "
              f"-> tuned {r['tuned_cost_s']:.3e}s ({win}, "
              f"{r['trials']} trials, gate={r['equality']['mode']} "
              f"err={r['equality']['max_abs_err']:.1e})")
    assert rec["tuned_never_loses"], \
        "a tuned record costs MORE than the default — the search's " \
        "default-fallback guarantee is broken"
    assert rec["proxy_regime_honest"], \
        "proxy_regime tag disagrees with measurement availability — a " \
        "modeled number is masquerading as a measurement"
