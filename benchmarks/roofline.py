"""Roofline tables: the dry-run sweep AND the LDA measured-vs-modeled join.

Two sections share ONE hardware table (``repro.obs.roofline.HW`` — this
module re-exports it for the older callers):

* the seed transformer dry-run renderer: reads results/dryrun.jsonl
  (``python -m repro.launch.dryrun --all --mesh both --out
  results/dryrun.jsonl``) and renders per-(arch × shape × mesh) roofline
  terms, dominant bottleneck, MODEL_FLOPS ratio, and memory fit;
* the LDA stack's roofline records: reads ``BENCH_obs.json`` (written by
  ``python -m benchmarks.obs_bench --json BENCH_obs.json``) and renders
  the measured-vs-modeled kernel verdicts of
  ``repro.obs.roofline.roofline_from_trace`` — the join that flags a
  kernel whose modeled HBM bytes say memory-bound but whose measured
  time disagrees (`docs/observability.md`).
"""
from __future__ import annotations

import json
import math
import os
from typing import Dict, List, Optional

from repro.configs import ARCHS, get_shape
from repro.launch.compile_cache import enable_compile_cache
from repro.obs.roofline import HBM_GB, HW  # the canonical hardware table

__all__ = ["HW", "HBM_GB", "load", "render", "rows", "render_lda",
           "load_obs", "count_params", "active_params", "model_flops"]


def count_params(cfg) -> float:
    """Analytic parameter count (embedding included once if tied)."""
    import jax
    from repro.models import transformer as T
    shapes = jax.eval_shape(lambda: T.init_params(cfg, jax.random.key(0)))
    return float(sum(s.size for s in jax.tree.leaves(
        shapes, is_leaf=lambda x: hasattr(x, "size"))))


def active_params(cfg) -> float:
    """Active parameters per token (MoE: top-k of routed + shared)."""
    total = count_params(cfg)
    if not cfg.num_experts:
        return total
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    n_moe = sum(1 for kk in cfg.pattern if kk == "moe")
    expert_p = n_moe * e * 3 * cfg.d_model * cfg.moe_d_ff
    return total - expert_p * (1 - k / e)


def model_flops(cfg, shape) -> float:
    """6·N_active·D for train, 2·N_active·D for inference (global)."""
    n = active_params(cfg)
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    mult = 6.0 if shape.kind == "train" else 2.0
    return mult * n * tokens


def load(path: str = "results/dryrun.jsonl") -> List[dict]:
    rows = []
    if not os.path.exists(path):
        return rows
    seen = {}
    for line in open(path):
        r = json.loads(line)
        # older rows stored a mesh-shape dict under "mesh"
        if not isinstance(r["mesh"], str):
            r["mesh"] = "multi" if r.get("chips") == 512 else "single"
        seen[(r["arch"], r["shape"], r["mesh"], r.get("seq_shard", False))] = r
    return list(seen.values())


def render(path: str = "results/dryrun.jsonl",
           mesh: str = "single") -> List[str]:
    rows = [r for r in load(path) if r["mesh"] == mesh
            and not r.get("seq_shard")]
    lines = []
    hdr = (f"| arch | shape | ok | compute_s | memory_s | collective_s | "
           f"bottleneck | MODEL_FLOPs/HLO | temp GB (≤{HBM_GB:.0f}) |")
    lines.append(hdr)
    lines.append("|" + "---|" * 9)
    for r in sorted(rows, key=lambda x: (x["arch"], x["shape"])):
        if not r.get("ok"):
            lines.append(f"| {r['arch']} | {r['shape']} | FAIL | | | | | | "
                         f"{r.get('error', '')[:60]} |")
            continue
        rf = r["roofline"]
        terms = {"compute": rf["compute_s"], "memory": rf["memory_s"],
                 "collective": rf["collective_s"]}
        dom = max(terms, key=terms.get)
        cfg = ARCHS[r["arch"]]
        shape = get_shape(r["shape"])
        mf = model_flops(cfg, shape) / r["chips"]
        ratio = mf / max(r["hlo"]["dot_flops"], 1.0)
        temp = r["memory"]["temp_gb"]
        fit = "✓" if temp <= HBM_GB else "✗"
        lines.append(
            f"| {r['arch']} | {r['shape']} | OK | {terms['compute']:.2e} | "
            f"{terms['memory']:.2e} | {terms['collective']:.2e} | {dom} | "
            f"{ratio:.2f} | {temp:.2f} {fit} |")
    return lines


def load_obs(path: str = "BENCH_obs.json") -> List[dict]:
    """The LDA stack's roofline-check sections from ``BENCH_obs.json``:
    ``[(section name, roofline_from_trace output)]`` flattened to dicts.
    Empty when the bench has not run (the renderer prints a hint)."""
    if not os.path.exists(path):
        return []
    with open(path) as f:
        rec = json.load(f)
    out = []
    for section in ("roofline", "roofline_csr"):
        chk = rec.get(section)
        if chk and chk.get("records"):
            out.append({"section": section, **chk})
    return out


def render_lda(path: str = "BENCH_obs.json") -> List[str]:
    """Markdown table of the LDA kernels' measured-vs-modeled verdicts
    (`repro.obs.roofline.roofline_check` output semantics)."""
    checks = load_obs(path)
    if not checks:
        return [f"(no LDA roofline records — run `python -m "
                f"benchmarks.obs_bench --json {path}` first)"]
    lines = ["| section | kernel | measured_s | modeled_s | ratio | "
             "verdict | proxy |", "|" + "---|" * 7]
    for chk in checks:
        proxy = "interpret" if chk.get("proxy_regime") else "device"
        for r in chk["records"]:
            lines.append(
                f"| {chk['section']} | {r['name']} | "
                f"{r['measured_s']:.2e} | {r['modeled_s']:.2e} | "
                f"{r['measured_vs_modeled']:.2f} | {r['verdict']} | "
                f"{proxy} |")
    return lines


def rows():
    """CSV rows for benchmarks/run.py (dry-run sweep + LDA join)."""
    out = []
    for mesh in ("single", "multi"):
        data = [r for r in load() if r["mesh"] == mesh
                and not r.get("seq_shard")]
        ok = sum(1 for r in data if r.get("ok"))
        out.append((f"roofline/dryrun_{mesh}", 0.0,
                    f"pairs_ok={ok}/{len(data)}"))
        for r in data:
            if not r.get("ok"):
                continue
            rf = r["roofline"]
            terms = {"compute": rf["compute_s"], "memory": rf["memory_s"],
                     "collective": rf["collective_s"]}
            dom = max(terms, key=terms.get)
            out.append((f"roofline/{mesh}/{r['arch']}/{r['shape']}",
                        max(terms.values()) * 1e6,
                        f"bottleneck={dom} temp_gb={r['memory']['temp_gb']:.2f}"))
    for chk in load_obs():
        for r in chk["records"]:
            out.append((f"roofline/lda/{chk['section']}/{r['name']}",
                        r["measured_s"] * 1e6,
                        f"ratio={r['measured_vs_modeled']:.2f} "
                        f"verdict={r['verdict']}"))
    return out


if __name__ == "__main__":
    enable_compile_cache()
    for mesh in ("single", "multi"):
        print(f"\n## Roofline — {mesh} pod\n")
        for line in render(mesh=mesh):
            print(line)
    print("\n## Roofline — LDA kernels (measured vs modeled)\n")
    for line in render_lda():
        print(line)
