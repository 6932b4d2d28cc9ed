import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e).

For every (architecture × input shape), build the production step function
(train_step / prefill_step / serve_step), lower it with production shardings
on the 16×16 single-pod mesh AND the 2×16×16 multi-pod mesh, ``compile()``
it, and record memory analysis, cost analysis and the HLO-derived roofline
inputs. The two XLA_FLAGS lines above MUST precede any jax import — jax
locks the device count on first initialisation.

Usage:
  python -m repro.launch.dryrun --arch yi-9b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both          # 40 pairs × 2
  python -m repro.launch.dryrun --all --mesh single --out results/d.jsonl
"""
import argparse
import dataclasses
import json
import subprocess
import sys
import time
import traceback
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, get_config, get_shape
from repro.configs.base import InputShape, ModelConfig, shape_variant
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T
from repro.models.moe import MeshCtx
from repro.optim import adamw
from repro.sharding import batch_specs, cache_specs, fsdp_axes, param_specs
from repro.training import TrainState, make_prefill_step, make_serve_step, \
    make_train_step

# TPU v5e hardware constants (roofline denominators)
HW = {"peak_flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9}


def make_ctx(mesh: Mesh, seq_shard: bool = False,
             profile: str = "tp_fsdp") -> MeshCtx:
    data_axes = fsdp_axes(mesh)
    if profile == "fsdp_only":
        # no tensor parallelism: the model axis carries batch/data too.
        # (Not valid for MoE archs — their expert shard_map needs the model
        # axis; the dryrun rejects that combination.)
        data_axes = data_axes + ("model",)
    return MeshCtx(mesh=mesh, data_axes=data_axes, model_axis="model",
                   seq_shard=seq_shard)


def _sds(tree_shapes, spec_tree, mesh: Mesh):
    """ShapeDtypeStructs carrying NamedShardings (for .lower())."""
    return jax.tree.map(
        lambda s, sp: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, sp)),
        tree_shapes, spec_tree,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def input_specs(cfg: ModelConfig, shape: InputShape, mesh: Mesh,
                profile: str = "tp_fsdp") -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for every model input (no allocation)."""
    b, s = shape.global_batch, shape.seq_len
    fs = fsdp_axes(mesh)
    if profile == "fsdp_only":
        # no tensor parallelism: the model axis carries batch too
        allax = fs + ("model",)
        bspec = allax if b % _size(mesh, allax) == 0 else (
            fs if b % _size(mesh, fs) == 0 else None)
    else:
        bspec = fs if b % _size(mesh, fs) == 0 else None
    sd = lambda shp, dt, sp: jax.ShapeDtypeStruct(
        shp, dt, sharding=NamedSharding(mesh, sp))
    if shape.kind in ("train", "prefill"):
        if cfg.modality == "audio":
            toks = sd((b, s, cfg.num_codebooks), jnp.int32,
                      P(bspec, None, None))
            labs = sd((b, s, cfg.num_codebooks), jnp.int32,
                      P(bspec, None, None))
        elif cfg.modality == "vision":
            toks = sd((b, s - cfg.num_patches), jnp.int32, P(bspec, None))
            labs = sd((b, s), jnp.int32, P(bspec, None))
        else:
            toks = sd((b, s), jnp.int32, P(bspec, None))
            labs = sd((b, s), jnp.int32, P(bspec, None))
        batch = {"tokens": toks}
        if cfg.modality == "vision":
            batch["vision_embeds"] = sd((b, cfg.num_patches, cfg.d_model),
                                        jnp.bfloat16, P(bspec, None, None))
        if shape.kind == "train":
            batch["labels"] = labs
        return batch
    # decode
    tok_shape = (b, cfg.num_codebooks) if cfg.modality == "audio" else (b,)
    return {
        "tokens": sd(tok_shape, jnp.int32,
                     P(bspec, None) if cfg.modality == "audio" else P(bspec)),
        "pos": sd((b,), jnp.int32, P(bspec)),
    }


def _size(mesh: Mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def build_and_lower(arch: str, shape_name: str, mesh: Mesh,
                    donate: bool = True, seq_shard: bool = False,
                    profile: str = "tp_fsdp", microbatches: int = 1,
                    cfg_override: Optional[ModelConfig] = None):
    """Returns (lowered, meta) for the production step of this pair."""
    cfg = cfg_override or get_config(arch)
    shape = get_shape(shape_name)
    cfg, note = shape_variant(cfg, shape)
    if profile == "fsdp_only" and cfg.num_experts:
        raise ValueError("fsdp_only profile is incompatible with MoE archs")
    ctx = make_ctx(mesh, seq_shard=seq_shard, profile=profile)

    params_shapes = jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.key(0)))
    pspecs = param_specs(mesh, params_shapes, profile=profile)
    meta = {"arch": arch, "shape": shape_name, "variant_note": note,
            "mesh_shape": dict(zip(mesh.axis_names, mesh.devices.shape))}

    if shape.kind == "train":
        opt = adamw(3e-4)
        opt_shapes = jax.eval_shape(opt.init, params_shapes)
        ospecs = param_specs(mesh, opt_shapes, profile=profile)
        state_sds = TrainState(
            params=_sds(params_shapes, pspecs, mesh),
            opt_state=_sds(opt_shapes, ospecs, mesh),
            step=jax.ShapeDtypeStruct((), jnp.int32,
                                      sharding=NamedSharding(mesh, P())))
        step = make_train_step(cfg, opt, ctx, microbatches=microbatches)
        jitted = jax.jit(step, donate_argnums=(0,) if donate else ())
        lowered = jitted.lower(state_sds,
                               input_specs(cfg, shape, mesh, profile))
    elif shape.kind == "prefill":
        step = make_prefill_step(cfg, ctx)
        jitted = jax.jit(step)
        lowered = jitted.lower(_sds(params_shapes, pspecs, mesh),
                               input_specs(cfg, shape, mesh, profile))
    else:  # decode
        cache_shapes = jax.eval_shape(
            partial(T.init_caches, cfg, shape.global_batch, shape.seq_len))
        cspecs = cache_specs(mesh, cfg, cache_shapes)
        step = make_serve_step(cfg, ctx)
        jitted = jax.jit(step, donate_argnums=(1,) if donate else ())
        ins = input_specs(cfg, shape, mesh)
        lowered = jitted.lower(_sds(params_shapes, pspecs, mesh),
                               _sds(cache_shapes, cspecs, mesh),
                               ins["tokens"], ins["pos"])
    return lowered, meta


def run_pair(arch: str, shape_name: str, mesh_kind: str,
             seq_shard: bool = False, profile: str = "tp_fsdp",
             microbatches: int = 1) -> Dict[str, Any]:
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = mesh.devices.size
    t0 = time.time()
    out: Dict[str, Any] = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_kind, "chips": n_chips,
                           "seq_shard": seq_shard, "profile": profile,
                           "microbatches": microbatches}
    try:
        lowered, meta = build_and_lower(arch, shape_name, mesh,
                                        seq_shard=seq_shard, profile=profile,
                                        microbatches=microbatches)
        out.update(meta)
        out["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        out["compile_s"] = round(time.time() - t1, 1)
        mem = compiled.memory_analysis()
        out["memory"] = {
            "argument_gb": mem.argument_size_in_bytes / 1e9,
            "output_gb": mem.output_size_in_bytes / 1e9,
            "temp_gb": mem.temp_size_in_bytes / 1e9,
            "alias_gb": mem.alias_size_in_bytes / 1e9,
        }
        ca = compiled.cost_analysis() or {}
        out["cost_analysis"] = {
            "flops_once": float(ca.get("flops", 0.0)),
            "bytes_once": float(ca.get("bytes accessed", 0.0)),
        }
        hlo = hlo_analysis.analyze(compiled.as_text())
        out["hlo"] = hlo
        # roofline terms (per device, seconds)
        out["roofline"] = {
            "compute_s": hlo["dot_flops"] / HW["peak_flops"],
            "memory_s": max(hlo["dot_bytes"], hlo["param_bytes"])
            / HW["hbm_bw"],
            "collective_s": hlo["collective_bytes"] / HW["ici_bw"],
        }
        out["ok"] = True
    except Exception as e:  # noqa: BLE001 — report, don't crash the sweep
        out["ok"] = False
        out["error"] = f"{type(e).__name__}: {e}"
        out["traceback"] = traceback.format_exc()[-2000:]
    out["total_s"] = round(time.time() - t0, 1)
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None, help="append JSONL here")
    ap.add_argument("--seq-shard", action="store_true",
                    help="sequence-parallel residual stream (hillclimb lever)")
    ap.add_argument("--profile", default="tp_fsdp",
                    choices=["tp_fsdp", "fsdp_only"],
                    help="parallelism profile (hillclimb lever)")
    ap.add_argument("--microbatches", type=int, default=1,
                    help="gradient-accumulation microbatches (train shapes)")
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        # isolate each pair in a subprocess: keeps host RAM bounded and one
        # failure cannot poison the rest of the sweep
        from repro.configs.base import INPUT_SHAPES
        for arch in sorted(ARCHS):
            for shape in INPUT_SHAPES:
                for mk in meshes:
                    cmd = [sys.executable, "-m", "repro.launch.dryrun",
                           "--arch", arch, "--shape", shape, "--mesh", mk]
                    if args.seq_shard:
                        cmd.append("--seq-shard")
                    if args.out:
                        cmd += ["--out", args.out]
                    subprocess.run(cmd, check=False)
        return

    assert args.arch and args.shape, "--arch and --shape (or --all) required"
    for mk in meshes:
        res = run_pair(args.arch, args.shape, mk, seq_shard=args.seq_shard,
                       profile=args.profile, microbatches=args.microbatches)
        line = json.dumps(res)
        status = "OK " if res["ok"] else "FAIL"
        print(f"[{status}] {args.arch} × {args.shape} × {mk}  "
              f"compile={res.get('compile_s', '-')}s  "
              f"temp={res.get('memory', {}).get('temp_gb', float('nan')):.3f}GB"
              if res["ok"] else
              f"[{status}] {args.arch} × {args.shape} × {mk}: "
              f"{res.get('error', '')[:300]}")
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")


if __name__ == "__main__":
    main()
