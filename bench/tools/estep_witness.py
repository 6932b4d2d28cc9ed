"""A second witness for the training check: one IVI step of the program's
E-step backends against the plain reference, and the Pallas kernels'
in-kernel E[θ] against jnp and float64. One process, one chip.

    python bench/tools/estep_witness.py --seed 3000000001

From λ₀ of the seed, on the first ``batch_size`` documents of the cell's
corpus, it runs the program's ``incremental_update`` with the ``pallas``
backend and with the jnp ``gather`` backend at ``Precision.HIGHEST``, each
with the memo's bf16 wire and with an fp32 wire, and the reference's step
with and without the bf16 rounding. It prints, per path, the relative
Frobenius gaps of Δλ and of π against the reference, and the share of π
entries whose bf16 value differs. It then evaluates the kernels'
``_exp_elog_theta`` in a Pallas call on the reference's γ of that batch and
prints its largest relative error against ``jnp`` (digamma, exp) on the
chip and against float64 on the host. Nothing of this runs in a benchmark
run.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="arxiv-ivi-train")
    ap.add_argument("--seed", type=int, default=3_000_000_001)
    args = ap.parse_args(argv)

    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl
    from scipy.special import digamma as digamma64

    from bench.configs import lda_ref
    from bench.run import load_cell, require_chips, use_compile_cache
    from bench.traffic import corpus as C
    from bench.traffic.train import lda_config
    from repro.core import engines
    from repro.core.types import GlobalState
    from repro.kernels import lda_estep

    _, cell, cfg, mix = load_cell(args.workload)
    use_compile_cache(jax)
    require_chips(jax, cell["chips"])
    data = C.make_corpus(cfg, C.topics(cfg, args.seed),
                         n_docs=cfg["num_train_docs"], seed=args.seed)
    b = mix["batch_size"]
    rows = np.arange(b)
    ids, cnts = data.token_ids[rows], data.counts[rows]
    out = {"seed": args.seed}

    refs = {}
    for wire in (True, False):
        ref = lda_ref.Reference(
            cfg, data.token_ids, data.counts, args.seed, batch_rows=b,
            tile_rows=mix["kernel_block_b"], denominator="real",
            t_cap=b * data.max_unique, wire_bf16=wire)
        lam0 = np.asarray(ref.state["lam"])
        pi, gamma, sweeps = ref.step(rows)
        refs[wire] = (np.asarray(ref.state["lam"]) - lam0, pi, gamma)
    out["ref_sweeps"] = sweeps.tolist()

    def state():
        lam = jnp.asarray(lam0)
        return GlobalState(lam=lam, m_vk=jnp.zeros_like(lam),
                           init_mass=lam - cfg["beta0"],
                           init_frac=jnp.ones((), jnp.float32),
                           t=jnp.zeros((), jnp.int32))

    def gaps(d_lam, pi, wire):
        d_ref, pi_ref, _ = refs[wire]
        live = (cnts > 0)
        got = np.asarray(pi, np.float64)[live]
        want = np.concatenate(pi_ref).astype(np.float64)
        bf = lambda x: x.astype(jnp.bfloat16)  # noqa: E731
        return {"lam_step_gap": float(np.linalg.norm(d_lam - d_ref)
                                      / np.linalg.norm(d_ref)),
                "pi_gap": float(np.linalg.norm(got - want)
                                / np.linalg.norm(want)),
                "pi_bf16_differs": float(np.mean(
                    bf(got.astype(np.float32)) != bf(want.astype(
                        np.float32))))}

    nw = jnp.float32(float(data.counts.sum()))
    for backend in ("pallas", "gather"):
        c = dataclasses.replace(lda_config(cfg, mix, None),
                                estep_backend=backend)
        for wire in (True, False):
            with jax.default_matmul_precision("highest"):
                st, pi, _ = engines.incremental_update(
                    c, False, state(), jnp.asarray(ids), jnp.asarray(cnts),
                    jnp.zeros((b, data.max_unique, cfg["num_topics"])),
                    jnp.zeros((b,), bool), nw,
                    "bfloat16" if wire else "float32")
            d_lam = np.asarray(st.lam) - lam0
            key = f"{backend}.{'bf16' if wire else 'fp32'}_wire"
            out[key] = gaps(d_lam, np.asarray(pi), wire)
            print(json.dumps({key: out[key]}), flush=True)

    # the kernels' E[θ] on the reference's γ, topics padded to 128 lanes
    k = cfg["num_topics"]
    gamma = refs[True][2]
    gpad = np.full((gamma.shape[0], 128), cfg["alpha0"], np.float32)
    gpad[:, :k] = gamma

    def kern(g_ref, o_ref):
        o_ref[...] = lda_estep._exp_elog_theta(g_ref[...], k)

    et_k = np.asarray(pl.pallas_call(
        kern, out_shape=jax.ShapeDtypeStruct(gpad.shape, jnp.float32),
        interpret=lda_estep._default_interpret(None))(
            jnp.asarray(gpad)))[:, :k]
    et_j = np.asarray(lda_ref.exp_elog(jnp.asarray(gamma), axis=1))
    g64 = gamma.astype(np.float64)
    et_64 = np.exp(digamma64(g64) - digamma64(g64.sum(1, keepdims=True)))
    rel = lambda a, w: float(np.max(np.abs(a - w) / np.abs(w)))  # noqa
    out["exp_elog_theta"] = {"kernel_vs_f64": rel(et_k, et_64),
                             "jnp_vs_f64": rel(et_j, et_64),
                             "kernel_vs_jnp": rel(et_k, et_j)}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
