"""The chip benchmark: one cell of BENCHMARK.json, one process, one result.

    python bench/run.py --workload arxiv-ivi-train --seed 7 --seconds 20 \\
        --trace 0

Finds the cell by name in ``BENCHMARK.json`` at the checkout root, its
configuration in ``bench/configs/<config>.json`` and its traffic mix in
``bench/traffic/<traffic>.json``. The mix names the runner module under
``bench/traffic/`` that builds the system from the seed, warms up every
shape the cell uses (set-up), measures for ``--seconds`` and then checks
what the timed path produced against the plain reference
(``bench/configs/lda_ref.py``). ``--trace 1`` runs the same cell under the
JAX profiler with the program's spans on and reports the cell's per-layer
metrics (``bench/metrics/<metric>.py``) instead of its end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``), then ``check``: every compared number beside its limit.
Those numbers are also the last lines on standard error. Without a TPU, or
with fewer chips than the cell asks for, it exits 1 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()    # set-up is measured from process start

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoChip(SystemExit):
    """The cell cannot run here: no TPU, or too few chips."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import ``path`` under ``name`` (metric files carry dots in their
    names, so they are loaded by path, not by import name)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, root: Path = ROOT):
    """(BENCHMARK.json, the cell, its configuration, its traffic mix)."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r} "
                         f"(have {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = load_json(root / configs[cell["config"]]["file"])
    mix = load_json(root / "bench" / "traffic" / f"{cell['traffic']}.json")
    return bench, cell, cfg, mix


def runner_for(mix: dict):
    return importlib.import_module(f"bench.traffic.{mix['runner']}")


def cell_metrics(bench: dict, cell: dict, kind: str):
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    e2e_here = {m["name"] for m in bench["end_to_end"]
                if cell["name"] in m.get("workloads", [cell["name"]])}
    out = []
    for m in bench[kind]:
        if "workloads" in m:
            if cell["name"] in m["workloads"]:
                out.append(m)
        elif kind == "end_to_end" or m["moves"] in e2e_here:
            out.append(m)
    return out


def use_compile_cache(jax) -> str:
    """The program's persistent compile cache
    (``repro.launch.compile_cache``: ``$JAX_COMPILATION_CACHE_DIR`` or the
    fixed ``<checkout>/.jax_cache``), with every program cached however
    fast it compiled, so a second run of a cell compiles nothing."""
    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def pin_allocator() -> None:
    """Fix glibc malloc's mmap threshold at 256 MiB and its trim threshold
    at 1 GiB. Left adaptive, the threshold starts at 128 KiB and rises only
    once a large block has been freed, so a fresh process serves the host
    memo's per-step arrays (16 MB fp32) from new mmaps, page faults and all,
    until something happens to raise it: two speeds of one program, chosen
    by allocation history. Pinned, every run has the speed a long-running
    job settles into."""
    import ctypes
    libc = ctypes.CDLL("libc.so.6")
    m_trim_threshold, m_mmap_threshold = -1, -3
    libc.mallopt(m_mmap_threshold, 256 << 20)
    libc.mallopt(m_trim_threshold, 1 << 30)


def require_chips(jax, chips: int) -> dict:
    devices = jax.devices()
    if jax.default_backend() != "tpu" or len(devices) < chips:
        print(f"bench: this cell needs {chips} TPU chip(s); JAX's backend is "
              f"{jax.default_backend()!r} with {len(devices)} device(s)",
              file=sys.stderr)
        raise NoChip(1)
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def run_cell(bench, cell, cfg, mix, *, seed: int, seconds: float,
             trace: bool, variant=None, t_start: float = T_START) -> dict:
    """Drive one run of ``cell`` and reduce it to the result line (no chip
    check: ``main`` does that; tests call this on the CPU)."""
    import jax

    from bench import layer
    drv = runner_for(mix)
    ctx = layer.Context(cell=cell, cfg=cfg, mix=mix,
                        seed=seed, seconds=seconds, trace=trace,
                        variant=variant, t_start=t_start)
    out = drv.run(ctx)
    checks = out.checks
    correct = bool(checks) and all(v <= lim for _, v, lim in checks) \
        and out.failed == 0
    metrics = {}
    kind = "per_layer" if trace else "end_to_end"
    for m in cell_metrics(bench, cell, kind):
        if trace:
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                                 "bench_metric_" + m["name"].replace(".", "_"))
            value = reader.read(out.layer)
        else:
            value = out.e2e.get(m["name"])
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    d = jax.devices()[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": out.memory_peak_bytes}
    result = {"correct": correct, "attempted": out.attempted,
              "failed": out.failed, "metrics": metrics, "device": device}
    if trace and out.layer.trace is not None:
        device["busy_s"] = out.layer.trace.busy_s
        device["window_s"] = out.layer.trace.window_s
        result["breakdown"] = out.layer.trace.breakdown()
    result["check"] = {name: {"value": v, "limit": lim}
                       for name, v, lim in checks}
    # a check that reads inf or nan prints it as a string: the line stays JSON
    return json.loads(json.dumps(result), parse_constant=str)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    pin_allocator()
    bench, cell, cfg, mix = load_cell(args.workload)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    use_compile_cache(jax)
    require_chips(jax, cell["chips"])
    result = run_cell(bench, cell, cfg, mix, seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace))
    for name, c in result["check"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct = {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
