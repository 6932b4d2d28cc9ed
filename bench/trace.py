"""The JAX profiler around a measured window, and its reduction to numbers.

``Profiler`` traces one window into a temporary directory (under
``$TMPDIR``) and marks the window with a host annotation, ``bench/window``,
whose start and end tie the trace's clock to the host's. The chips' clock
runs apart from the host's by a millisecond or so; the first program the
window launches (the host's ``tpu::System::Execute``) and the first module
a chip runs tie the two. ``reduce`` turns the trace into a
``TraceSummary``:

* busy time: the union of the intervals in which an operation ran on a
  chip, inside the window, averaged over the chips used;
* time per device operation, by a stable name: a Pallas kernel by its
  kernel function's name (``KERNELS``), any other op by its HLO name
  without the instruction's counter (``fusion.12`` → ``fusion``);
* collective time (all-reduce, all-gather, ...) per chip, and the part of
  it with no other operation running on that chip;
* idle time by what the host was doing: each gap between busy intervals is
  charged to the innermost program span (``repro.obs`` spans, on the host
  clock) that covers its midpoint, or to ``(no program span)``.

The reduction is checked on a small recorded trace in ``bench/tests``.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
import tempfile
import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW = "bench/window"
NO_SPAN = "(no program span)"
# Pallas kernels of the program, by the kernel function's name
KERNELS = ("_csr_fixed_point_kernel", "_csr_token_pi_kernel",
           "_fixed_point_kernel", "_token_pi_kernel",
           "_segment_scatter_kernel")
# an XLA Ops event's name is its HLO instruction:
#   %name.N = <result types> custom-call(<typed operands>), ...
HLO_OP = re.compile(r"^%?([\w.-]+?)(?:\.\d+)?\s*=\s*(.*?)\s+([\w-]+)\((.*)$")
TYPE = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all|psum", re.I)
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                          # mean over chips
    chips: int
    ops: Dict[str, Tuple[float, int]]      # name -> (s per chip, calls/chip)
    collective_s: float                    # per chip
    collective_exposed_s: float            # per chip, nothing else running
    idle_by_span: Dict[str, float]         # host span -> idle s per chip

    def kernel_s(self, names: Iterable[str]) -> Tuple[float, int]:
        """Summed time and calls of the ops named in ``names``."""
        t = sum(self.ops[n][0] for n in names if n in self.ops)
        c = sum(self.ops[n][1] for n in names if n in self.ops)
        return t, c

    def breakdown(self) -> dict:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1][0])[:10]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[n, s] for n, (s, _) in top],
                "idle_gaps": [[n, s] for n, s in gaps]}


class Profiler:
    """``with Profiler(on):`` traces the block (a no-op when ``on`` is
    false). Finish the window's device work inside the block. Either way
    it counts the programs compiled or read from the compilation cache
    inside the block (``compiles``), which should be none."""

    def __init__(self, on: bool):
        import jax
        self.on = on
        self.dir: Optional[str] = None
        self.t0_ns = self.t1_ns = 0
        self.compiles = 0
        self._active = False

        def count(event, **_):
            if self._active and event in ("/jax/compilation_cache/cache_hits",
                                          "/jax/compilation_cache/cache_misses"):
                self.compiles += 1
        jax.monitoring.register_event_listener(count)

    def __enter__(self):
        self._active = True
        if self.on:
            import jax
            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(self.dir)
            self._ann = jax.profiler.TraceAnnotation(WINDOW)
            self._ann.__enter__()
        self.t0_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.t1_ns = time.perf_counter_ns()
        self._active = False
        if self.compiles:
            import sys
            print(f"bench: {self.compiles} program(s) compiled or loaded "
                  "inside the measured window", file=sys.stderr)
        if self.on:
            import jax
            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
        return False

    def summary(self, host_spans: Sequence[Tuple[str, int, int, int]] = ()
                ) -> Optional[TraceSummary]:
        """Reduce the trace and delete it. ``host_spans``: (name, depth,
        start, end) in ``perf_counter_ns``."""
        if not self.on:
            return None
        try:
            path = xplane_file(self.dir)
            return reduce(path, host_spans=host_spans,
                          window_host_ns=(self.t0_ns, self.t1_ns))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def xplane_file(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def _stats(ev) -> Dict[str, object]:
    try:
        return dict(ev.stats)
    except Exception:  # noqa: BLE001 - a malformed stat must not end a run
        return {}


def pallas_kernel(results: str, operands: str) -> Optional[str]:
    """Which of the program's Pallas kernels a ``tpu_custom_call`` runs.

    The trace names a Pallas call after the jitted function around it
    (``memo_correction_pallas_csr.3``), not after its kernel, so the kernel
    is told by its result and operand types (``kernels/lda_estep.py``):
    the fixed points return (γ, E[θ], s32 sweep counts), the flat one also
    takes s32 segment ids; the padded token-π kernel returns a rank-3 π;
    the scatter's first operand is the s32 word ids; the flat token-π
    kernel takes f32 counts then s32 segment ids."""
    res = TYPE.findall(results)
    ops = TYPE.findall(operands.split("),")[0])
    if not res or not ops:
        return None
    if len(res) == 3 and res[-1][0] == "s32":
        return ("_csr_fixed_point_kernel" if any(t == "s32" for t, _ in ops)
                else "_fixed_point_kernel")
    if len(res) == 1 and res[0][1].count(",") == 2:
        return "_token_pi_kernel"
    if ops[0][0] == "s32":
        return "_segment_scatter_kernel"
    if len(ops) > 1 and ops[0][0] == "f32" and ops[1][0] == "s32":
        return "_csr_token_pi_kernel"
    return None


def op_name(text: str) -> str:
    """A device op's stable name: the Pallas kernel it runs, else the HLO
    instruction's name without its counter."""
    m = HLO_OP.match(text)
    if not m:
        return text
    name, results, opcode, operands = m.groups()
    if opcode == "custom-call" and "tpu_custom_call" in operands:
        return pallas_kernel(results, operands) or name
    return name


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _length(iv: List[Tuple[int, int]]) -> int:
    return sum(b - a for a, b in iv)


def _subtract(a: List[Tuple[int, int]], b: List[Tuple[int, int]]
              ) -> List[Tuple[int, int]]:
    """Parts of union ``a`` not covered by union ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def device_op_lines(pd) -> Dict[int, List[Tuple[str, int, int]]]:
    """Chip index → (stable op name, start ns, duration ns) of the events
    on its ``XLA Ops`` line."""
    out: Dict[int, List[Tuple[str, int, int]]] = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                out[int(m.group(1))] = [
                    (op_name(ev.name), int(ev.start_ns), int(ev.duration_ns))
                    for ev in line.events]
    return out


def window_in_trace(pd) -> Tuple[int, int]:
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW:
                    return int(ev.start_ns), int(ev.start_ns + ev.duration_ns)
    raise ValueError(f"the trace holds no {WINDOW!r} annotation")


def device_offset(pd, w0: int, w1: int) -> int:
    """Chip clock minus host clock: the first module a chip runs starts
    just after the host launched the window's first program."""
    launch = [int(ev.start_ns) for plane in pd.planes
              if not plane.name.startswith("/device:")
              for line in plane.lines for ev in line.events
              if ev.name == "tpu::System::Execute" and w0 <= ev.start_ns < w1]
    first = [int(ev.start_ns) for plane in pd.planes
             if DEVICE_PLANE.match(plane.name)
             for line in plane.lines if line.name == "XLA Modules"
             for ev in line.events]
    if not launch or not first:
        return 0
    return min(first) - min(launch)


def reduce(path: str, *, host_spans: Sequence[Tuple[str, int, int, int]] = (),
           window_host_ns: Tuple[int, int] = (0, 0)) -> TraceSummary:
    """Read an ``.xplane.pb`` and reduce it (``host_spans`` on the host
    clock, tied to the trace by the window annotation's start)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    w0, w1 = window_in_trace(pd)
    lines = device_op_lines(pd)
    if not lines:
        raise ValueError("the trace holds no TPU device plane with XLA Ops")
    off = device_offset(pd, w0, w1)
    shift = w0 - window_host_ns[0] + off
    spans = [(name, depth, s + shift, e + shift)
             for name, depth, s, e in host_spans]
    return reduce_lines(lines, (w0 + off, w1 + off), spans)


def reduce_lines(lines: Dict[int, List[Tuple[str, int, int]]],
                 window: Tuple[int, int],
                 spans: Sequence[Tuple[str, int, int, int]] = ()
                 ) -> TraceSummary:
    """The reduction proper: per chip (op name, start, duration) events,
    the window and the host spans (name, depth, start, end), all in trace
    ns."""
    w0, w1 = window
    spans = sorted(((s, e, depth, name) for name, depth, s, e in spans),
                   key=lambda x: x[0])
    ops: Dict[str, List[float]] = {}
    busy = coll = coll_exposed = 0
    idle: Dict[str, int] = {}
    for _chip, events in sorted(lines.items()):
        all_iv, coll_iv, other_iv = [], [], []
        for name, start, dur in events:
            a, b = max(start, w0), min(start + dur, w1)
            if b <= a:
                continue
            acc = ops.setdefault(name, [0.0, 0])
            acc[0] += (b - a) * 1e-9
            acc[1] += 1
            all_iv.append((a, b))
            (coll_iv if COLLECTIVE.search(name) else other_iv).append((a, b))
        busy_iv = union(all_iv)
        busy += _length(busy_iv)
        c = union(coll_iv)
        coll += _length(c)
        coll_exposed += _length(_subtract(c, union(other_iv)))
        for a, b in _subtract([(w0, w1)], busy_iv):
            mid = (a + b) // 2
            inner = None
            for s, e, depth, name in spans:
                if s > mid:
                    break
                if e >= mid and (inner is None or depth >= inner[0]):
                    inner = (depth, name)
            key = inner[1] if inner else NO_SPAN
            idle[key] = idle.get(key, 0) + (b - a)
    n = max(len(lines), 1)
    return TraceSummary(
        window_s=(w1 - w0) * 1e-9, busy_s=busy * 1e-9 / n, chips=n,
        ops={k: (v[0] / n, int(round(v[1] / n))) for k, v in ops.items()},
        collective_s=coll * 1e-9 / n,
        collective_exposed_s=coll_exposed * 1e-9 / n,
        idle_by_span={k: v * 1e-9 / n for k, v in idle.items()})


def window_records(recorder, prof: "Profiler") -> List[dict]:
    """The recorder's records that start inside the profiled window."""
    t0 = recorder._t0
    return [r for r in recorder.records
            if prof.t0_ns <= t0 + r["ts_us"] * 1e3 <= prof.t1_ns]


def recorder_spans(recorder) -> List[Tuple[str, int, int, int]]:
    """A ``repro.obs.SpanRecorder``'s spans as (name, depth, start, end) in
    ``perf_counter_ns`` (its timestamps are relative to its construction)."""
    t0 = recorder._t0
    return [(r["name"], r["depth"], t0 + int(r["ts_us"] * 1e3),
             t0 + int((r["ts_us"] + r["dur_us"]) * 1e3))
            for r in recorder.records if r.get("type") == "span"]


def dump_structure(path: str, out: str, per_line: int = 5) -> None:
    """Write each plane's lines and a few events with their stats (JSON),
    to look at a trace before reading it in code."""
    import json

    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    doc = []
    for plane in pd.planes:
        p = {"plane": plane.name, "lines": []}
        for line in plane.lines:
            evs = list(line.events)
            p["lines"].append({
                "line": line.name, "events": len(evs),
                "sample": [{"name": e.name, "start_ns": e.start_ns,
                            "dur_ns": e.duration_ns,
                            "stats": {k: str(v)[:300]
                                      for k, v in _stats(e).items()}}
                           for e in evs[:per_line]]})
        doc.append(p)
    with open(out, "w") as f:
        json.dump(doc, f, indent=1)
