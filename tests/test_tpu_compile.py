"""The production E-step kernels compile for a TPU v5e at Arxiv widths.

Interpret mode (every other kernel test) never meets the Mosaic lowering,
so a block shape that breaks the TPU's (8, 128) tiling passes on the CPU
and fails on the chip. These tests compile the four production kernels for
a *described* v5e — the TPU compiler is installed, no chip is needed —
with ``interpret=False``, at the widths of the Arxiv deployment (paper
Table 1: V=141,927; K=100, padded to 128 where the wrappers pad it;
B=256 documents, L=128 slots, T=8192 flat tokens).

The topology is described inside a fixture, never at import time: only one
process at a time may load the TPU library, and test workers import every
test file. Keep every such test in this one file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import lda_estep

V = 141_927                      # Arxiv vocabulary
V_PAD = 142_336                  # padded to the fixed point's 512-wide V-tile
K = 100                          # topics, as memo_delta takes them
K_PAD = 128                      # topics, lane-padded by the wrappers
B, L, T = 256, 128, 8192         # docs per batch, slots per doc, flat tokens


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """ShapeDtypeStruct factory placed on one described v5e chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described device is written to the persistent cache
    but can never be read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _assert_mosaic(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("stream", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_fixed_point_compiles(spec, stream):
    def fn(c, eb, g0):
        return lda_estep.estep_fixed_point(c, eb, g0, 0.5, 1e-4, 100, K, B,
                                           interpret=False)
    _assert_mosaic(fn, spec((B, V_PAD), stream), spec((V_PAD, K_PAD), stream),
                   spec((B, K_PAD)))


@pytest.mark.parametrize("stream,block_t", [(jnp.float32, T),
                                            (jnp.bfloat16, T),
                                            (jnp.float32, 512)],
                         ids=["f32-resident", "bf16-resident", "f32-tiled"])
def test_fixed_point_csr_compiles(spec, stream, block_t):
    def fn(cnts, segs, ebt, g0):
        return lda_estep.estep_fixed_point_csr(
            cnts, segs, ebt, g0, 0.5, 1e-4, 100, K, B, block_t=block_t,
            interpret=False)
    _assert_mosaic(fn, spec((T,)), spec((T,), jnp.int32),
                   spec((T, K_PAD), stream), spec((B, K_PAD)))


@pytest.mark.parametrize("quantize", [False, True], ids=["f32", "bf16-wire"])
def test_memo_delta_compiles(spec, quantize):
    def fn(ids, cnts, ebt, et, old):
        return lda_estep.memo_delta(ids, cnts, ebt, et, V, old,
                                    quantize=quantize, interpret=False)
    _assert_mosaic(fn, spec((B, L), jnp.int32), spec((B, L)),
                   spec((B, L, K)), spec((B, K)), spec((B, L, K)))


@pytest.mark.parametrize("quantize", [False, True], ids=["f32", "bf16-wire"])
def test_memo_delta_csr_compiles(spec, quantize):
    def fn(ids, cnts, segs, ebt, et, old):
        return lda_estep.memo_delta_csr(ids, cnts, segs, ebt, et, V, old,
                                        quantize=quantize, interpret=False)
    _assert_mosaic(fn, spec((T,), jnp.int32), spec((T,)),
                   spec((T,), jnp.int32), spec((T, K)), spec((B, K)),
                   spec((T, K)))
