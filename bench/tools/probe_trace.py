"""Record a small profiler trace of the program's CSR kernels on the chip.

    python bench/tools/probe_trace.py OUT_DIR

Runs the flat E-step (three Pallas kernels) and the training step's global
update a few times under the JAX profiler, inside a ``bench/window``
annotation, and writes to OUT_DIR the trace file and a JSON listing of
its planes, lines and sample events. The trace is the recorded input of
``bench/tests/test_trace.py``.
"""
from __future__ import annotations

import os
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench.trace import WINDOW, dump_structure, xplane_file
    from repro.core.math import exp_dirichlet_expectation
    from repro.core.types import LDAConfig
    from repro.kernels import ops

    out = Path(argv[0])
    out.mkdir(parents=True, exist_ok=True)
    v, k, t, b = 4096, 100, 1024, 16
    cfg = LDAConfig(num_topics=k, vocab_size=v, estep_backend="csr")
    rng = np.random.default_rng(0)
    lam = jnp.asarray(rng.gamma(100.0, 0.01, (v, k)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, v, t), jnp.int32)
    cnts = jnp.asarray(rng.integers(1, 4, t), jnp.float32)
    segs = jnp.asarray(np.sort(rng.integers(0, b, t)), jnp.int32)

    @jax.jit
    def step(lam):
        eb = exp_dirichlet_expectation(lam, axis=0)
        res = ops.estep_pallas_csr(cfg, eb, ids, cnts, segs, num_docs=b)
        return lam + 1e-3 * res.sstats

    jax.block_until_ready(step(lam))
    tmp = tempfile.mkdtemp(prefix="probe_trace_")
    try:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation(WINDOW):
            x = lam
            for _ in range(3):
                x = step(x)
            jax.block_until_ready(x)
        jax.profiler.stop_trace()
        path = xplane_file(tmp)
        shutil.copy(path, out / "probe.xplane.pb")
        dump_structure(path, str(out / "structure.json"), per_line=8)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(f"probe trace: {os.path.getsize(out / 'probe.xplane.pb')} bytes "
          f"in {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
