"""Benchmark driver — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Budget-friendly defaults (the
paper-scale corpora are sampled down); ``--corpus`` picks another of the
synthetic corpora.

Sections:
  fig1   — MVI/SVI/IVI/S-IVI convergence (paper Fig. 1)
  fig2   — IVI mini-batch size sweep (paper Fig. 2)
  table2 — D-IVI LPP + time vs processors × batch (paper Table 2 / Fig. 3)
  fig5   — delay robustness (paper Fig. 5)

These are CPU-scale reproductions of the paper's curves; speed is measured
on the chip by ``bench/run.py``.
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of sections")
    ap.add_argument("--corpus", default="small")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (fig1_convergence, fig2_minibatch, fig5_delays,
                            table2_divi)
    sections = {
        "fig1": lambda: (fig1_convergence.rows(args.corpus)
                         # K = K* regime (paper-consistent final ordering)
                         + fig1_convergence.rows("tiny", epochs=8)),
        "fig2": lambda: fig2_minibatch.rows(args.corpus),
        "table2": lambda: table2_divi.rows(args.corpus),
        "fig5": lambda: fig5_delays.rows(args.corpus),
    }
    chosen = (args.only.split(",") if args.only else list(sections))
    print("name,us_per_call,derived")
    failures = 0
    for name in chosen:
        try:
            for row in sections[name]():
                print(f"{row[0]},{row[1]:.1f},{row[2]}")
        except Exception:
            failures += 1
            print(f"{name},0,ERROR", file=sys.stderr)
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
