"""Find a serving cell's knee: the highest open-loop rate whose backlog
does not grow over the window. One process, one chip.

    python bench/tools/knee.py --workload arxiv-serve-burst \\
        --rates 100,200,400,800 --seconds 10

For each rate it serves the cell's traffic at that rate and prints the p95
latency, the docs/s served and the drain: how long after the window's last
arrival the last response came. A rate whose drain stays near the flush
timeout keeps up; one whose drain grows with the window does not. A
serving cell's traffic mix then takes its rate from the knee.
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=3_100_000_003)
    ap.add_argument("--max-drain", type=float, default=0.25)
    args = ap.parse_args(argv)

    from bench.run import pin_allocator
    pin_allocator()
    import jax

    from bench.layer import Context
    from bench.run import runner_for, load_cell, require_chips, \
        use_compile_cache
    bench, cell, cfg, mix = load_cell(args.workload)
    use_compile_cache(jax)
    require_chips(jax, cell["chips"])
    knee = None
    for rate in (float(r) for r in args.rates.split(",")):
        m = dict(mix, rate_docs_s=rate)
        t = time.perf_counter()
        out = runner_for(m).run(Context(
            cell=cell, cfg=cfg, mix=m, seed=args.seed,
            seconds=args.seconds, trace=False, variant=None, t_start=t))
        req = out.layer.requests
        last_arrival = max(r["arrival_s"] for r in req)
        last_done = max(r["done_s"] for r in req)
        print(json.dumps({
            "rate": rate, "attempted": out.attempted, "failed": out.failed,
            "p95_ms": out.e2e["serve_p95_ms"],
            "served_docs_s": len(req) / last_done,
            "drain_s": last_done - last_arrival,
            "batches": out.layer.shape["batches"],
            "checks": out.checks, "run_s": time.perf_counter() - t}),
            flush=True)
        if out.failed == 0 and last_done - last_arrival <= args.max_drain:
            knee = rate
    print(json.dumps({"knee": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
