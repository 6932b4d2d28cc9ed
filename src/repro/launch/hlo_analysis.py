"""Structural analysis of compiled (SPMD, per-device) HLO text.

``jax``'s ``compiled.cost_analysis()`` counts each ``while`` body **once**,
but every ``lax.scan`` (layer stacks, attention q-chunks, SSD chunk scans)
lowers to a while loop — so raw cost_analysis under-counts FLOPs by the trip
counts. This module parses the HLO text instead:

* builds the computation call graph (while bodies/conditions, fusions,
  calls) and recovers each while loop's **trip count** from the constant in
  its condition's compare;
* multiplies instruction costs by the product of enclosing trip counts;
* FLOPs: every ``dot`` = 2 × numel(result) × Π contracting dims (the MXU
  term — elementwise FLOPs are ignored, they are bandwidth-bound anyway);
* collective bytes: Σ max(result, operand) bytes per all-gather/all-reduce/
  reduce-scatter/all-to-all/collective-permute, trip-multiplied — the
  per-device ICI traffic proxy;
* HBM bytes: Σ (unique operand bytes + result bytes) over dot instructions
  plus entry parameter bytes — a structural upper-ish bound on HBM traffic
  (fusion reuse is invisible in text form; documented in EXPERIMENTS.md).

All quantities are **per device** (SPMD HLO is the per-device program).
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

import jax
from jax.extend import core as jax_core

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
# header like: %wide.region_3 (param: (s32[], bf16[...])) -> (...) {
# params may contain nested parens (tuple types) — match only the name.
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.*)$")


def _shape_bytes(shape_str: str) -> int:
    """Total bytes of possibly-tuple shape text like 'f32[8,128]{1,0}'."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _shape_dims(shape_str: str) -> Tuple[str, List[int]]:
    m = _SHAPE_RE.search(shape_str)
    if not m:
        return "", []
    dims = [int(d) for d in m.group(2).split(",") if d]
    return m.group(1), dims


@dataclasses.dataclass
class Instr:
    name: str
    op: str
    text: str
    comp: str


@dataclasses.dataclass
class HLOModule:
    comps: Dict[str, List[Instr]]
    entry: str
    defs: Dict[str, str]          # instruction name → result shape text


def parse_module(text: str) -> HLOModule:
    comps: Dict[str, List[Instr]] = {}
    defs: Dict[str, str] = {}
    entry = ""
    cur: Optional[str] = None
    for line in text.splitlines():
        stripped = line.strip()
        if not stripped or stripped.startswith("//"):
            continue
        if stripped.startswith("HloModule"):
            continue
        if "->" in stripped and stripped.endswith("{") \
                and not _INSTR_RE.match(stripped):
            m = _COMP_RE.match(stripped)
            if m:
                cur = m.group(1)
                comps[cur] = []
                if stripped.startswith("ENTRY"):
                    entry = cur
            continue
        if stripped == "}":
            continue
        if cur is None:
            continue
        mi = _INSTR_RE.match(stripped)
        if mi:
            rhs = mi.group(2)
            opm = re.search(r"\}?\s*([a-z][\w\-]*)\(", rhs)
            op = opm.group(1) if opm else ""
            name = mi.group(1)
            comps[cur].append(Instr(name, op, stripped, cur))
            sm = _SHAPE_RE.search(rhs)
            if sm:
                # result shape text up to the op token (covers tuples too)
                cut = rhs.find(" " + op + "(") if op else -1
                defs[name] = rhs[:cut] if cut > 0 else sm.group(0)
    return HLOModule(comps=comps, entry=entry, defs=defs)


def _called_comps(instr: Instr) -> List[str]:
    """Computations referenced by this instruction (body/cond/calls/fusion)."""
    out = []
    for key in ("body", "condition", "to_apply", "calls", "branch_computations"):
        for m in re.finditer(key + r"=\{?%?([\w\.\-]+)", instr.text):
            out.append(m.group(1))
        for m in re.finditer(key + r"=\{([^}]*)\}", instr.text):
            out.extend(x.strip().lstrip("%") for x in m.group(1).split(","))
    return out


def _while_trip_count(mod: HLOModule, cond_name: str) -> int:
    """Recover trip count from the condition's compare-with-constant.

    XLA may wrap the compare in a fused computation (``wrapped_compare``);
    the loop-bound constant stays in the condition computation itself, so the
    robust recovery is: largest positive integer constant reachable from the
    condition (conditions are tiny — counter, bound, compare).
    """
    best = 1
    seen = set()
    stack = [cond_name]
    while stack:
        name = stack.pop()
        if name in seen or name not in mod.comps:
            continue
        seen.add(name)
        for ins in mod.comps[name]:
            m = re.search(r"constant\((\d+)\)", ins.text)
            if m:
                best = max(best, int(m.group(1)))
            stack.extend(_called_comps(ins))
    return best


def _edges(mod: HLOModule) -> Dict[str, List[Tuple[str, float]]]:
    """caller → [(callee, weight)]; while bodies weighted by trip count."""
    out: Dict[str, List[Tuple[str, float]]] = defaultdict(list)
    for comp, instrs in mod.comps.items():
        for ins in instrs:
            if ins.op == "while":
                bodym = re.search(r"body=%?([\w\.\-]+)", ins.text)
                condm = re.search(r"condition=%?([\w\.\-]+)", ins.text)
                trip = _while_trip_count(mod, condm.group(1)) if condm else 1
                if bodym:
                    out[comp].append((bodym.group(1), float(trip)))
                if condm:
                    out[comp].append((condm.group(1), float(trip + 1)))
                continue
            for callee in _called_comps(ins):
                if callee in mod.comps:
                    out[comp].append((callee, 1.0))
    return out


def _multipliers(mod: HLOModule) -> Dict[str, float]:
    """Effective execution multiplier per computation.

    The call graph is a DAG; propagate trip-count products in topological
    order (Kahn) so computations with several callers accumulate fully
    before their own callees are visited.
    """
    edges = _edges(mod)
    indeg: Dict[str, int] = defaultdict(int)
    for comp, outs in edges.items():
        for callee, _ in outs:
            indeg[callee] += 1
    mult: Dict[str, float] = defaultdict(float)
    mult[mod.entry] = 1.0
    queue = [c for c in mod.comps if indeg[c] == 0]
    while queue:
        comp = queue.pop()
        for callee, w in edges.get(comp, []):
            mult[callee] += mult[comp] * w
            indeg[callee] -= 1
            if indeg[callee] == 0:
                queue.append(callee)
    return dict(mult)


_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute", "all-gather-start", "all-reduce-start",
                "collective-permute-start")

_LOOP_PRIMS = ("while", "scan")


# jnp-side *compute* on a (B, L, K)-rank array — data staging (gather /
# pad / broadcast / reshape / transpose) feeding a kernel is excluded: XLA
# fuses it into the operand read, and the issue is arithmetic round-trips.
_ARITH_PRIMS = frozenset({
    "add", "sub", "mul", "div", "pow", "integer_pow", "exp", "log", "neg",
    "max", "min", "select_n", "rsqrt", "sqrt", "tanh", "logistic",
    "reduce_sum", "reduce_max", "dot_general",
})


def mosaic_calls(compiled) -> List[Tuple[str, str]]:
    """(HLO line, first operand type) of each Mosaic kernel call in a
    compiled TPU program, e.g. ``("%custom-call.3 = ...", "s32[353]")``.

    The lines are printed with typed operands, the form the profiler's op
    events carry, so ``bench.trace.op_name`` names each line's kernel.
    """
    from jax._src.lib import xla_client
    opts = xla_client._xla.HloPrintOptions()
    opts.print_operand_shape = True
    opts.print_metadata = False
    text = compiled.runtime_executable().hlo_modules()[0].to_string(opts)
    return [(line.strip(), line.split("custom-call(", 1)[1].split("{", 1)[0])
            for line in text.splitlines()
            if "tpu_custom_call" in line and "custom-call(" in line]


def pallas_call_sites(fn, *args, **kwargs) -> Dict[str, int]:
    """Count Pallas kernel-launch sites in ``fn``'s jaxpr.

    Returns ``{"total": n, "under_loop": m, "blk_intermediates": i}``:
    ``under_loop`` counts sites nested inside a ``while``/``scan`` — a
    kernel there launches once per trip (the pre-fusion E-step paid one
    launch per fixed-point sweep; the fused path must report 0) — and
    ``blk_intermediates`` counts rank-≥3 *arithmetic* results outside any
    kernel (the (B, L, K) jnp intermediates the fused memo correction
    eliminates; kernel-internal VMEM math is not walked).

    Structure is counted at jaxpr level rather than in compiled HLO
    because interpret-mode Pallas (CPU CI) inlines kernels into plain HLO
    ops; on TPU each site lowers to exactly one Mosaic custom-call, so the
    count equals the compiled launch-site count there.
    """
    jaxpr = jax.make_jaxpr(fn)(*args, **kwargs) if callable(fn) else fn
    counts = {"total": 0, "under_loop": 0, "blk_intermediates": 0}

    def sub_jaxprs(eqn):
        for v in eqn.params.values():
            if isinstance(v, (jax_core.Jaxpr, jax_core.ClosedJaxpr)):
                yield v
            elif isinstance(v, (tuple, list)):
                for x in v:
                    if isinstance(x, (jax_core.Jaxpr, jax_core.ClosedJaxpr)):
                        yield x

    def walk(jx, in_loop):
        if isinstance(jx, jax_core.ClosedJaxpr):
            jx = jx.jaxpr
        for eqn in jx.eqns:
            name = eqn.primitive.name
            if name == "pallas_call":
                counts["total"] += 1
                if in_loop:
                    counts["under_loop"] += 1
                continue              # kernel-internal math lives in VMEM
            if name in _ARITH_PRIMS and any(
                    getattr(ov.aval, "ndim", 0) >= 3 for ov in eqn.outvars):
                counts["blk_intermediates"] += 1
            for sub in sub_jaxprs(eqn):
                walk(sub, in_loop or name in _LOOP_PRIMS)

    walk(jaxpr, False)
    return counts


def dense_vocab_cubes(fn, vocab_size: int, *args, **kwargs) -> int:
    """Count rank-≥3 jaxpr values carrying a vocab-sized axis.

    The one-hot ``memo_delta`` emitted (nb, V, K) scatter partials — rank-3
    arrays with a (padded) vocab axis that exist only to be reduced. The
    segment-sum path must produce **zero** such values: its (V, K) masses
    are rank 2 and its only rank-3 arrays are (B, L, K) token cubes. An
    axis counts as vocab-sized only inside the lane-padding window
    ``[V, round_up(V, 128)]`` — the extent a vocab axis can actually take
    in the launch structure — NOT for any axis ≥ V, or a long token axis
    (L ≥ V is routine for small-vocab shapes) would trip the guard.
    """
    jaxpr = jax.make_jaxpr(fn)(*args, **kwargs) if callable(fn) else fn
    vocab_pad = ((vocab_size + 127) // 128) * 128
    count = 0

    def sub_jaxprs(eqn):
        for v in eqn.params.values():
            if isinstance(v, (jax_core.Jaxpr, jax_core.ClosedJaxpr)):
                yield v
            elif isinstance(v, (tuple, list)):
                for x in v:
                    if isinstance(x, (jax_core.Jaxpr, jax_core.ClosedJaxpr)):
                        yield x

    def walk(jx):
        nonlocal count
        if isinstance(jx, jax_core.ClosedJaxpr):
            jx = jx.jaxpr
        for eqn in jx.eqns:
            for ov in eqn.outvars:
                shape = getattr(ov.aval, "shape", ())
                if len(shape) >= 3 and any(vocab_size <= d <= vocab_pad
                                           for d in shape):
                    count += 1
            for sub in sub_jaxprs(eqn):
                walk(sub)

    walk(jaxpr)
    return count


def _dot_flops(ins: Instr, defs: Dict[str, str]) -> float:
    """2 × numel(result) × contraction size for a dot instruction.

    Compiled HLO references operands by name only, so the lhs shape is
    resolved through the module-wide symbol table ``defs``.
    """
    lhs_c = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", ins.text)
    shape_part = ins.text.split("=", 1)[1]
    _, res_dims = _shape_dims(shape_part)
    argm = re.search(r"dot\(([^)]*)\)", ins.text)
    if not argm:
        return 0.0
    arg_txt = argm.group(1)
    if _SHAPE_RE.search(arg_txt):
        # operands carry inline shapes (xla in jax<=0.4): first shape = lhs
        lhs_txt = arg_txt
    else:
        # name-only operands: resolve through the module symbol table
        lhs_txt = defs.get(arg_txt.split(",")[0].strip().lstrip("%"), "")
    cdim = 1
    if lhs_c and lhs_txt:
        _, lhs_dims = _shape_dims(lhs_txt)
        for ci in lhs_c.group(1).split(","):
            if ci != "" and int(ci) < len(lhs_dims):
                cdim *= lhs_dims[int(ci)]
    res_n = 1
    for d in res_dims:
        res_n *= d
    return 2.0 * res_n * cdim


def analyze(text: str, top_k: int = 0) -> Dict[str, object]:
    """Roofline inputs from per-device SPMD HLO text.

    ``top_k`` > 0 additionally returns the heaviest individual collectives
    and dots (multiplier-weighted) for bottleneck hunting.
    """
    mod = parse_module(text)
    mult = _multipliers(mod)
    flops = 0.0
    coll_bytes: Dict[str, float] = defaultdict(float)
    dot_bytes = 0.0
    param_bytes = 0.0
    top_coll: List[Tuple[float, str]] = []
    top_dot: List[Tuple[float, str]] = []
    for comp, instrs in mod.comps.items():
        m = mult.get(comp, 0.0)
        if m == 0.0:
            continue
        for ins in instrs:
            if ins.op == "dot":
                fl = m * _dot_flops(ins, mod.defs)
                flops += fl
                dot_bytes += m * _shape_bytes(ins.text)
                if top_k:
                    top_dot.append((fl, f"x{m:g} {ins.text[:140]}"))
            elif ins.op in _COLLECTIVES:
                key = ins.op.replace("-start", "")
                by = m * _shape_bytes(ins.text.split("=", 1)[1])
                coll_bytes[key] += by
                if top_k:
                    top_coll.append((by, f"x{m:g} {ins.text[:140]}"))
            elif ins.op == "parameter" and comp == mod.entry:
                param_bytes += _shape_bytes(ins.text.split("=", 1)[1])
    out: Dict[str, object] = {
        "dot_flops": flops,
        "dot_bytes": dot_bytes,
        "param_bytes": param_bytes,
        "collective_bytes": sum(coll_bytes.values()),
        **{f"coll_{k}": v for k, v in sorted(coll_bytes.items())},
    }
    if top_k:
        out["top_collectives"] = sorted(top_coll, reverse=True)[:top_k]
        out["top_dots"] = sorted(top_dot, reverse=True)[:top_k]
    return out
