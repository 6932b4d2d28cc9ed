"""Read a cell's compared numbers on many seeds, for the program, the
control and each planted fault, in one process on the chip.

    python bench/tools/readings.py --workload arxiv-ivi-train \\
        --seeds 12 --control-seeds 3 --faults state_unchanged,half_batch \\
        --seconds 20 --fault-seconds 5

Prints one JSON line per run: variant, seed, correct and every compared
number. The limits in the traffic mixes are set from these readings (the
largest the program gives, the smallest the control and the faults give).
The benchmark's own runs never run the control or a fault.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--fault-seconds", type=float, default=None,
                    help="window of the control's and the faults' runs "
                         "(default: --seconds)")
    ap.add_argument("--first-seed", type=int, default=3_000_000_001)
    args = ap.parse_args(argv)

    from bench.run import pin_allocator
    pin_allocator()
    import jax

    from bench.run import load_cell, require_chips, run_cell, \
        use_compile_cache
    bench, cell, cfg, mix = load_cell(args.workload)
    use_compile_cache(jax)
    require_chips(jax, cell["chips"])
    plan = [(None, i) for i in range(args.seeds)]
    plan += [("control", i) for i in range(args.control_seeds)]
    for f in filter(None, args.faults.split(",")):
        plan += [(f, i) for i in range(args.fault_seeds)]
    for variant, i in plan:
        seed = args.first_seed + 7919 * i
        t = time.perf_counter()
        seconds = args.seconds if variant is None \
            else (args.fault_seconds or args.seconds)
        res = run_cell(bench, cell, cfg, mix, seed=seed, seconds=seconds,
                       trace=False, variant=variant, t_start=t)
        print(json.dumps({
            "variant": variant or "program", "seed": seed,
            "correct": res["correct"], "failed": res["failed"],
            "check": {k: v["value"] for k, v in res["check"].items()},
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "run_s": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
