"""Sharding rules + launch machinery (no real multi-device needed:
AbstractMesh provides shape/axis metadata for the spec rules; no launcher
lowers these rules over a real device mesh)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS
from repro.launch import hlo_analysis as H
from repro.launch.mesh import make_abstract_mesh
from repro.models import transformer as T
from repro.sharding import cache_specs, fsdp_axes, param_specs

MESH = make_abstract_mesh((16, 16), ("data", "model"))
MESH3 = make_abstract_mesh((2, 16, 16), ("pod", "data", "model"))


def _check_divisibility(shapes, specs, mesh):
    flat_s = jax.tree.leaves(shapes,
                             is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    flat_p, _ = jax.tree.flatten(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_s) == len(flat_p)
    for s, spec in zip(flat_s, flat_p):
        assert len(spec) <= len(s.shape), (s.shape, spec)
        for dim, axes in zip(s.shape, spec):
            if axes is None:
                continue
            axes = (axes,) if isinstance(axes, str) else axes
            size = 1
            for a in axes:
                size *= mesh.shape[a]
            assert dim % size == 0, (s.shape, spec)


@pytest.mark.parametrize("arch", sorted(ARCHS))
@pytest.mark.parametrize("mesh", [MESH, MESH3], ids=["single", "multi"])
def test_param_specs_divisible(arch, mesh):
    cfg = ARCHS[arch]
    shapes = jax.eval_shape(lambda: T.init_params(cfg, jax.random.key(0)))
    specs = param_specs(mesh, shapes)
    _check_divisibility(shapes, specs, mesh)


@pytest.mark.parametrize("arch", ["gemma2-27b", "musicgen-medium",
                                  "zamba2-1.2b", "xlstm-1.3b"])
def test_cache_specs_divisible(arch):
    cfg = ARCHS[arch]
    shapes = jax.eval_shape(lambda: T.init_caches(cfg, 128, 4096))
    specs = cache_specs(MESH, cfg, shapes)
    _check_divisibility(shapes, specs, MESH)


def test_param_specs_shard_big_dims():
    """The FFN hidden of yi-9b must actually be model-sharded."""
    cfg = ARCHS["yi-9b"]
    shapes = jax.eval_shape(lambda: T.init_params(cfg, jax.random.key(0)))
    specs = param_specs(MESH, shapes)
    mlp_spec = specs["stages"][0][0]["mlp"]["w_up"]
    assert "model" in tuple(mlp_spec)


def test_fsdp_axes():
    assert fsdp_axes(MESH) == ("data",)
    assert fsdp_axes(MESH3) == ("pod", "data")


# ---------------------------------------------------------------------------
# HLO analysis machinery
# ---------------------------------------------------------------------------

def test_hlo_trip_count_multiplication():
    """A scan of length 10 must multiply body dot flops by 10."""
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    comp = jax.jit(f).lower(
        jax.ShapeDtypeStruct((32, 64), jnp.float32),
        jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
    res = H.analyze(comp.as_text())
    want = 10 * 2 * 32 * 64 * 64
    assert abs(res["dot_flops"] - want) / want < 0.05, res["dot_flops"]


def test_hlo_nested_scan_multiplies():
    def f(x, w):
        def outer(c, _):
            def inner(ci, _):
                return ci @ w, None
            ci, _ = jax.lax.scan(inner, c, None, length=4)
            return ci, None
        y, _ = jax.lax.scan(outer, x, None, length=3)
        return y

    comp = jax.jit(f).lower(
        jax.ShapeDtypeStruct((8, 16), jnp.float32),
        jax.ShapeDtypeStruct((16, 16), jnp.float32)).compile()
    res = H.analyze(comp.as_text())
    want = 12 * 2 * 8 * 16 * 16
    assert abs(res["dot_flops"] - want) / want < 0.05, res["dot_flops"]


def test_hlo_shape_bytes():
    assert H._shape_bytes("f32[8,128]{1,0}") == 8 * 128 * 4
    assert H._shape_bytes("bf16[2,3]") == 12
    assert H._shape_bytes("(f32[4], s32[2])") == 24


def test_input_specs_cover_all_shapes():
    import os
    # avoid initializing the 512-device runtime here: only spec shapes
    from repro.configs import get_shape
    from repro.configs.base import INPUT_SHAPES
    for name in INPUT_SHAPES:
        s = get_shape(name)
        assert s.kind in ("train", "prefill", "decode")
