import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Dry-run of the paper's own workload at the Arxiv corpus scale of
Table 1 (V=141,927; K=100 padded to 128; 782k documents).

Three modes:

* ``divi`` — one D-IVI global round on the production mesh: λ / ⟨m_vk⟩
  model-sharded on V (DESIGN.md §5); per-worker memo stores and the
  streamed (W, S, B, L) batch slabs data-sharded — no corpus is device
  state. Reports memory + roofline terms like the transformer dry-run.
* ``ivi`` — the single-host IVI hot step (`engines.incremental_update`)
  lowered with the fused Pallas E-step backend, plus the MemoStore
  footprint math: the device program only ever sees one mini-batch of the
  memo (the store lives in host RAM), and the bf16 chunked store holds the
  full Arxiv memo under the 40 GB single-host budget. Also reports the
  kernel-launch structure (one fused ``pallas_call`` per fixed point, none
  under a loop — docs/estep.md).
* ``serve`` — the ``LDA.transform`` serving step (`repro.lda.infer`)
  lowered per bucket width at Arxiv V with the fused backend: the
  per-width jit cache the `launch/serve_lda.py` request loop runs on.

Usage: python -m repro.launch.dryrun_lda [--mode divi|ivi|serve|all]
       [--mesh single|multi|both] [--batch 1024] [--staleness 1]
       [--out results/lda.jsonl]
"""
import argparse
import json
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.memo import memo_footprint_bytes
from repro.core.types import GlobalState, LDAConfig
from repro.dist.divi import (DIVIConfig, DIVIState, WorkerShard,
                             make_divi_round)
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh

HW = {"peak_flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9}

# Arxiv (Table 1): 782,385 train docs, V=141,927, avg 116 words/doc.
ARXIV = dict(num_docs=782_384, vocab=141_952,       # padded: /16 divisible
             max_unique=128, topics=128)            # K=100 → 128 lanes


def lower_round(mesh, batch: int, staleness: int):
    data_axes = tuple(a for a in mesh.axis_names if a != "model")
    n_workers = 1
    for a in data_axes:
        n_workers *= mesh.shape[a]
    docs_per_worker = ARXIV["num_docs"] // n_workers
    v, k, L = ARXIV["vocab"], ARXIV["topics"], ARXIV["max_unique"]

    cfg = LDAConfig(num_topics=k, vocab_size=v, estep_max_iters=100)
    dcfg = DIVIConfig(num_workers=n_workers, batch_size=batch,
                      staleness=staleness)
    rnd = make_divi_round(cfg, dcfg, mesh, data_axes)

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    dspec = P(data_axes)
    state = DIVIState(
        lam=sds((v, k), jnp.float32, P("model", None)),
        m_vk=sds((v, k), jnp.float32, P("model", None)),
        init_mass=sds((v, k), jnp.float32, P("model", None)),
        init_frac=sds((), jnp.float32, P()),
        t=sds((), jnp.int32, P()),
    )
    from repro.core.memo import DenseMemoStore
    shard = WorkerShard(
        memo=DenseMemoStore(
            pi=sds((n_workers, docs_per_worker, L, k), jnp.float32,
                   P(data_axes, None, None, None)),
            visited=sds((n_workers, docs_per_worker), jnp.bool_,
                        P(data_axes, None))),
    )
    # per-round streamed batches — the argument footprint is (W, S, B, L)
    # slabs pulled by each worker's ingest, not a resident corpus
    ids = sds((n_workers, staleness, batch, L), jnp.int32,
              P(data_axes, None, None, None))
    cnts = sds((n_workers, staleness, batch, L), jnp.float32,
               P(data_axes, None, None, None))
    idx = sds((n_workers, staleness, batch), jnp.int32,
              P(data_axes, None, None))
    delay = sds((n_workers, staleness), jnp.bool_, P(data_axes, None))
    nw = sds((), jnp.float32, P())
    return rnd.lower(state, shard, ids, cnts, idx, delay, nw), n_workers


def run(mesh_kind: str, batch: int, staleness: int):
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    out = {"arch": "lda-divi-arxiv", "shape": f"b{batch}_s{staleness}",
           "mesh": mesh_kind, "chips": mesh.devices.size}
    t0 = time.time()
    try:
        lowered, n_workers = lower_round(mesh, batch, staleness)
        compiled = lowered.compile()
        out["compile_s"] = round(time.time() - t0, 1)
        mem = compiled.memory_analysis()
        out["memory"] = {"temp_gb": mem.temp_size_in_bytes / 1e9,
                         "argument_gb": mem.argument_size_in_bytes / 1e9}
        hlo = hlo_analysis.analyze(compiled.as_text())
        out["hlo"] = hlo
        out["roofline"] = {
            "compute_s": hlo["dot_flops"] / HW["peak_flops"],
            "memory_s": max(hlo["dot_bytes"], hlo["param_bytes"])
            / HW["hbm_bw"],
            "collective_s": hlo["collective_bytes"] / HW["ici_bw"],
        }
        out["workers"] = n_workers
        out["ok"] = True
    except Exception as e:  # noqa: BLE001
        out["ok"] = False
        out["error"] = f"{type(e).__name__}: {e}"
        out["traceback"] = traceback.format_exc()[-1500:]
    return out


def run_ivi(batch: int, estep_iters: int = 50):
    """Lower the single-host IVI hot step at Arxiv scale, fused backend."""
    from repro.core.engines import incremental_update

    v, k, L, D = (ARXIV["vocab"], ARXIV["topics"], ARXIV["max_unique"],
                  ARXIV["num_docs"])
    cfg = LDAConfig(num_topics=k, vocab_size=v, estep_max_iters=estep_iters,
                    estep_backend="pallas", estep_stream_dtype="bfloat16")
    out = {"arch": "lda-ivi-arxiv", "shape": f"b{batch}", "mode": "ivi",
           "memo_store": "chunked-bf16"}
    t0 = time.time()
    try:
        sds = jax.ShapeDtypeStruct
        state = GlobalState(lam=sds((v, k), jnp.float32),
                            m_vk=sds((v, k), jnp.float32),
                            init_mass=sds((v, k), jnp.float32),
                            init_frac=sds((), jnp.float32),
                            t=sds((), jnp.int32))
        args = (state, sds((batch, L), jnp.int32),
                sds((batch, L), jnp.float32),
                sds((batch, L, k), jnp.float32),       # π_old from the store
                sds((batch,), jnp.bool_), sds((), jnp.float32),
                "bfloat16")                  # the chunked store's wire dtype
        out["kernel_sites"] = hlo_analysis.pallas_call_sites(
            lambda *a: incremental_update(cfg, False, *a, "bfloat16"),
            *args[:-1])
        lowered = incremental_update.lower(cfg, False, *args)
        compiled = lowered.compile()
        out["compile_s"] = round(time.time() - t0, 1)
        mem = compiled.memory_analysis()
        out["memory"] = {"temp_gb": mem.temp_size_in_bytes / 1e9,
                         "argument_gb": mem.argument_size_in_bytes / 1e9}
        # the memo itself never enters the device program — footprint math:
        out["memo_gb"] = {
            kind: memo_footprint_bytes(kind, D, L, k, vocab_size=v) / 1e9
            for kind in ("dense", "chunked", "gamma")}
        out["memo_under_40gb"] = out["memo_gb"]["chunked"] < 40.0
        out["ok"] = True
    except Exception as e:  # noqa: BLE001
        out["ok"] = False
        out["error"] = f"{type(e).__name__}: {e}"
        out["traceback"] = traceback.format_exc()[-1500:]
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="all",
                    choices=["divi", "ivi", "serve", "all"])
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--batch", type=int, default=1024)
    ap.add_argument("--staleness", type=int, default=1)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    results = []
    if args.mode in ("divi", "all"):
        meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
        for mk in meshes:
            res = run(mk, args.batch, args.staleness)
            if res["ok"]:
                rf = res["roofline"]
                print(f"[OK ] lda-divi × {mk}  compile={res['compile_s']}s "
                      f"temp={res['memory']['temp_gb']:.2f}GB "
                      f"compute={rf['compute_s']:.2e}s "
                      f"coll={rf['collective_s']:.2e}s")
            else:
                print(f"[FAIL] lda-divi × {mk}: {res['error'][:200]}")
            results.append(res)
    if args.mode in ("ivi", "all"):
        res = run_ivi(args.batch)
        if res["ok"]:
            ks = res["kernel_sites"]
            mg = res["memo_gb"]
            print(f"[OK ] lda-ivi single-host  compile={res['compile_s']}s "
                  f"kernels={ks['total']} under_loop={ks['under_loop']} "
                  f"blk_jnp={ks['blk_intermediates']} "
                  f"memo dense={mg['dense']:.1f}GB "
                  f"chunked={mg['chunked']:.1f}GB "
                  f"gamma={mg['gamma']:.2f}GB "
                  f"(<40GB: {res['memo_under_40gb']})")
        else:
            print(f"[FAIL] lda-ivi: {res['error'][:200]}")
        results.append(res)
    if args.mode in ("serve", "all"):
        from repro.launch.serve_lda import run_serve_dryrun
        res = run_serve_dryrun(batch=min(args.batch, 256))
        if res["ok"]:
            worst = max(m["temp_gb"] for m in res["memory"].values())
            print(f"[OK ] lda-serve single-host  compile={res['compile_s']}s "
                  f"widths={res['widths']} max_temp={worst:.2f}GB "
                  f"jit_entries={res['jit_cache_entries']}")
        else:
            print(f"[FAIL] lda-serve: {res['error'][:200]}")
        results.append(res)
    if args.out:
        with open(args.out, "a") as f:
            for res in results:
                f.write(json.dumps(res) + "\n")
    if not all(res["ok"] for res in results):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
