"""Compile each cell's step for a described v5e, on a machine with no chip.

    JAX_PLATFORMS=cpu python bench/tools/compile_rehearsal.py

Lowers and compiles, at the cells' real sizes, the programs their windows
drive (the padded IVI step and the CSR serving batch) and the reference's
step, for the TPU topology ``v5e:2x2``;
prints each program's memory analysis. What the TPU compiler would refuse
fails here; nothing runs.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench.configs import lda_ref
    from bench.run import load_cell
    from bench.traffic.train import lda_config
    from repro.core import engines
    from repro.core.types import GlobalState
    from repro.kernels import lda_estep

    jax.config.update("jax_enable_compilation_cache", False)
    lda_estep._default_interpret = lambda interpret: False \
        if interpret is None else interpret
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype, sharding=one):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def state(v, k, sharding=one):
        f = lambda s: sds(s, jnp.float32, sharding)  # noqa: E731
        return GlobalState(lam=f((v, k)), m_vk=f((v, k)),
                           init_mass=f((v, k)), init_frac=f(()),
                           t=sds((), jnp.int32, sharding))

    def report(name, compiled):
        m = compiled.memory_analysis()
        print(f"{name}: compiled; argument {m.argument_size_in_bytes} B, "
              f"output {m.output_size_in_bytes} B, temp "
              f"{m.temp_size_in_bytes} B", flush=True)

    # arxiv-ivi-train: the padded step at B=256, L=159
    _, _, cfg, mix = load_cell("arxiv-ivi-train")
    c = lda_config(cfg, mix, None)
    v, k, b, l = cfg["vocab_size"], cfg["num_topics"], 256, cfg["max_unique"]
    report("arxiv-ivi-train incremental_update", engines.incremental_update
           .lower(c, False, state(v, k), sds((b, l), jnp.int32),
                  sds((b, l), jnp.float32), sds((b, l, k), jnp.float32),
                  sds((b,), jnp.bool_), sds((), jnp.float32),
                  "bfloat16").compile())
    t = b * l
    report("arxiv reference ivi_step", lda_ref.ivi_step.lower(
        {kk: sds(x.shape, x.dtype) for kk, x in
         jax.eval_shape(lambda: lda_ref.init_state(cfg, 0)).items()},
        sds((cfg["num_train_docs"] * l + 1, k), jnp.float32),
        sds((b,), jnp.bool_), sds((t,), jnp.int32), sds((t,), jnp.float32),
        sds((t,), jnp.int32), sds((t,), jnp.int32), sds((), jnp.int32),
        sds((), jnp.float32), cfg_items=lda_ref.cfg_items(cfg),
        batch_rows=b, tile_rows=128, denominator="real",
        wire_bf16=True).compile())

    # arxiv-serve-burst: the CSR serving batch
    from repro.lda import infer
    _, _, cfg, mix = load_cell("arxiv-serve-burst")
    c = lda_config(cfg, mix, None)
    bs = mix["batch_size"]
    t = mix["token_budget"] or min(bs * 64, 8192)
    report("arxiv-serve-burst _posterior_batch_csr",
           infer._posterior_batch_csr.lower(
               c, sds((cfg["vocab_size"], cfg["num_topics"]), jnp.float32),
               sds((t,), jnp.int32), sds((t,), jnp.float32),
               sds((t,), jnp.int32), num_docs=bs).compile())

    return 0


if __name__ == "__main__":
    sys.exit(main())
