"""The production Pallas kernels across tilings, in interpret mode on the
CPU: several B-tiles and V-tiles, a single V tile, K = 100, the π kernel's
VMEM guard and L tiling, and the padded memo correction against the CSR
one on the same batch."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from kernel_oracles import fixed_point_per_tile, memo_delta_ref

from repro.kernels import lda_estep

SHAPES = [
    # (B, V, K, block_b, block_v)
    (8, 64, 16, 8, 32),
    (16, 256, 32, 8, 64),
    (128, 512, 128, 128, 512),
    (32, 768, 100, 16, 128),
    (64, 1024, 128, 32, 256),
    (8, 512, 64, 8, 512),      # single V tile
    (128, 128, 128, 64, 64),
]
ALPHA0 = 0.5
SWEEPS = 3


def _check_fixed_point(c, eb, g0, block_b, block_v):
    """``estep_fixed_point`` with ``tol = 0`` runs ``SWEEPS`` sweeps in
    every B-tile; γ and E[θ] are the per-tile jnp loop's."""
    b, k = g0.shape
    args = (c, eb, g0, ALPHA0, 0.0, SWEEPS, k, b)
    gamma, et, iters = lda_estep.estep_fixed_point(
        *args, block_b=block_b, block_v=block_v)
    want_g, want_et, want_sweeps = fixed_point_per_tile(*args, block_b,
                                                        block_v)
    assert want_sweeps == [SWEEPS] * (b // block_b)
    assert np.asarray(iters).ravel().tolist() == want_sweeps
    np.testing.assert_allclose(gamma, want_g, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(et, want_et, rtol=2e-5, atol=1e-7)


def _check_memo_delta(ids, cnts, ebt, et, v, old_pi, **tiles):
    """``memo_delta``'s π and both masses against π scattered in jnp."""
    pi, snew, sold = lda_estep.memo_delta(ids, cnts, ebt, et, v,
                                          old_pi=old_pi, **tiles)
    pref, sref, soldref = memo_delta_ref(ids, cnts, ebt, et, v, old_pi)
    np.testing.assert_allclose(pi, pref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(snew, sref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(sold, soldref, rtol=1e-4, atol=1e-4)


def _counts_problem(rng, b, v, k):
    c = jnp.asarray(rng.poisson(0.3, (b, v)).astype(np.float32))
    eb = jnp.asarray(rng.gamma(1.0, 1.0, (v, k)).astype(np.float32))
    g0 = jnp.asarray(ALPHA0 + rng.gamma(1.0, 1.0, (b, k)).astype(np.float32))
    return c, eb, g0


def _token_problem(rng, b, l, v, k):
    ids = jnp.asarray(rng.integers(0, v, (b, l)).astype(np.int32))
    cnts = jnp.asarray(rng.poisson(1.0, (b, l)).astype(np.float32))
    ebt = jnp.asarray(rng.gamma(1.0, 1.0, (b, l, k)).astype(np.float32))
    et = jnp.asarray(rng.gamma(1.0, 1.0, (b, k)).astype(np.float32))
    old_pi = jnp.asarray(rng.random((b, l, k)).astype(np.float32))
    return ids, cnts, ebt, et, old_pi


@pytest.mark.parametrize("b,v,k,bb,bv", SHAPES)
def test_fixed_point_tiling(b, v, k, bb, bv):
    _check_fixed_point(*_counts_problem(np.random.default_rng(b + v + k),
                                        b, v, k), bb, bv)


@pytest.mark.parametrize("b,v,k,bb,bv", SHAPES)
def test_memo_delta_tiling(b, v, k, bb, bv):
    """``SHAPES``' block_b and block_v as the π kernel's B-tile and the
    scatter's V chunk (``delta_block_b``, ``delta_block_v``)."""
    ids, cnts, ebt, et, old_pi = _token_problem(
        np.random.default_rng(b + v + k), b, 40, v, k)
    _check_memo_delta(ids, cnts, ebt, et, v, old_pi, block_b=bb,
                      block_v=bv)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000),
       b=st.sampled_from([4, 8, 16]),
       v=st.sampled_from([96, 160, 320]),
       k=st.sampled_from([8, 24, 100]))
def test_production_kernels_random_shapes(seed, b, v, k):
    rng = np.random.default_rng(seed)
    _check_fixed_point(*_counts_problem(rng, b, v, k), b, v // 2)
    ids, cnts, ebt, et, old_pi = _token_problem(rng, b, 12, v, k)
    _check_memo_delta(ids, cnts, ebt, et, v, old_pi, block_b=b,
                      block_v=v // 2)


@pytest.mark.parametrize("b,l,block_b,block_l,l_tiles", [
    (64, 32, 16, 512, 1),   # four B-tiles of π
    (32, 512, 32, 384, 2),  # the VMEM guard halves the B-tile; two L-tiles,
                            # the second padded
])
def test_memo_delta_multi_tile(b, l, block_b, block_l, l_tiles):
    v, k = 700, 128
    bb, bl = lda_estep.pi_tile_shape(b, l, k, block_b=block_b,
                                     block_l=block_l)
    assert b // bb >= 2 and -(-l // bl) == l_tiles
    ids, cnts, ebt, et, old_pi = _token_problem(np.random.default_rng(b),
                                                b, l, v, k)
    _check_memo_delta(ids, cnts, ebt, et, v, old_pi, block_b=block_b,
                      block_l=block_l)


def test_memo_delta_matches_csr_layout():
    """The padded (B, L) correction and the flat CSR one agree on the same
    batch: π token for token, and both masses."""
    b, l, v, k = 16, 48, 500, 64
    ids, cnts, ebt, et, old_pi = _token_problem(np.random.default_rng(7),
                                                b, l, v, k)
    pi, snew, sold = lda_estep.memo_delta(ids, cnts, ebt, et, v,
                                          old_pi=old_pi)
    segs = jnp.repeat(jnp.arange(b, dtype=jnp.int32), l)
    pi_f, snew_f, sold_f = lda_estep.memo_delta_csr(
        ids.reshape(-1), cnts.reshape(-1), segs, ebt.reshape(-1, k), et, v,
        old_pi=old_pi.reshape(-1, k))
    np.testing.assert_allclose(pi_f, pi.reshape(-1, k), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(snew_f, snew, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(sold_f, sold, rtol=1e-4, atol=1e-4)


def test_pi_tile_shape_vmem_guard():
    """The π kernel's VMEM guard halves the B-tile until two (block_b,
    block_l, K) cubes fit, and always returns a divisor of B."""
    f = lda_estep.pi_tile_shape
    assert f(128, 64, 128) == (32, 64)       # fits at the default
    assert f(128, 128, 128) == (32, 128)
    assert f(128, 512, 128) == (16, 512)     # halved once: 8 MiB exactly
    assert f(128, 512, 256) == (8, 512)      # halved twice
    assert f(12, 40, 16) == (12, 40)         # small batch: capped at B
    assert f(32, 1024, 128) == (16, 512)     # long L: tiled, then halved
    assert f(14, 512, 512) == (1, 512)       # 7 does not fit; 3 ∤ 14
    for b, l in [(96, 512), (32, 1024), (12, 512)]:
        bb, bl = f(b, l, 128)
        assert b % bb == 0, (b, l, bb)
        assert 2 * bb * bl * 128 * 4 <= lda_estep._PI_VMEM_BUDGET
