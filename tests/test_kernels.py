"""Pallas kernel validation: shape/dtype sweeps against the jnp oracles
(interpret mode on CPU), plus the full estep_pallas vs estep_dense path."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.experimental.pallas import tpu as pltpu

from repro.core import LDAConfig
from repro.core.estep import densify, estep_dense, estep_gather
from repro.core.math import exp_dirichlet_expectation
from repro.data import PAPER_CORPORA, make_corpus
from repro.kernels import lda_estep, ref
from repro.kernels.ops import (estep_pallas, memo_correction_pallas,
                               pad_inputs)


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


@pytest.mark.parametrize("b,v,k,bb,bv", SHAPES)
def test_sweep_kernel_matches_ref(b, v, k, bb, bv, rng):
    c = jnp.asarray(rng.poisson(0.3, (b, v)).astype(np.float32))
    et = jnp.asarray(rng.gamma(1.0, 1.0, (b, k)).astype(np.float32))
    eb = jnp.asarray(rng.gamma(1.0, 1.0, (v, k)).astype(np.float32))
    got = lda_estep.estep_sweep(c, et, eb, 0.5, block_b=bb, block_v=bv)
    want = ref.estep_sweep_ref(c, et, eb, 0.5)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("b,v,k,bb,bv", SHAPES)
def test_sstats_kernel_matches_ref(b, v, k, bb, bv, rng):
    c = jnp.asarray(rng.poisson(0.3, (b, v)).astype(np.float32))
    et = jnp.asarray(rng.gamma(1.0, 1.0, (b, k)).astype(np.float32))
    eb = jnp.asarray(rng.gamma(1.0, 1.0, (v, k)).astype(np.float32))
    got = lda_estep.sstats(c, et, eb, block_b=bb, block_v=bv)
    want = ref.sstats_ref(c, et, eb)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000),
       b=st.sampled_from([4, 8, 16]),
       v=st.sampled_from([96, 160, 320]),
       k=st.sampled_from([8, 24, 100]))
def test_kernel_property_random_shapes(seed, b, v, k):
    rng = np.random.default_rng(seed)
    c = jnp.asarray(rng.poisson(0.5, (b, v)).astype(np.float32))
    et = jnp.asarray(rng.gamma(0.7, 2.0, (b, k)).astype(np.float32))
    eb = jnp.asarray(rng.gamma(0.7, 2.0, (v, k)).astype(np.float32))
    bb = b
    bv = v // 2 if v % 2 == 0 else v
    got = lda_estep.estep_sweep(c, et, eb, 0.5, block_b=bb, block_v=bv)
    want = ref.estep_sweep_ref(c, et, eb, 0.5)
    np.testing.assert_allclose(got, want, rtol=5e-5, atol=5e-5)
    gs = lda_estep.sstats(c, et, eb, block_b=bb, block_v=bv)
    ws = ref.sstats_ref(c, et, eb)
    np.testing.assert_allclose(gs, ws, rtol=5e-5, atol=5e-5)


def test_estep_pallas_full_path():
    spec = PAPER_CORPORA["tiny"]
    corpus = make_corpus(spec, split="train", seed=0)
    cfg = LDAConfig(num_topics=8, vocab_size=spec.vocab_size,
                    estep_max_iters=60)
    lam = jax.random.gamma(jax.random.key(0), 100.0,
                           (spec.vocab_size, 8)) * 0.01
    eb = exp_dirichlet_expectation(lam, axis=0)
    ids, cnts = corpus.token_ids[:16], corpus.counts[:16]
    r1 = estep_dense(cfg, eb, ids, cnts)
    r2 = estep_pallas(cfg, eb, ids, cnts, block_b=16, block_v=125)
    np.testing.assert_allclose(r1.gamma, r2.gamma, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(r1.sstats, r2.sstats, rtol=1e-2, atol=1e-3)
    np.testing.assert_allclose(r1.pi, r2.pi, rtol=1e-3, atol=1e-4)


def _fixed_point_per_tile(c, eb, g0, alpha0, tol, max_iters, k_real,
                          b_real, block_b, block_v):
    """The fused fixed point's update, one B-tile at a time in jnp: the
    kernel's own E[θ]/ψ, the V-tiles summed in ascending order, the same
    mean |Δγ| over the tile's real rows. Returns (γ, E[θ], sweeps)."""
    c, eb = c.astype(jnp.float32), eb.astype(jnp.float32)
    real = jnp.arange(g0.shape[1])[None, :] < k_real
    gammas, sweeps = [], []
    for i in range(c.shape[0] // block_b):
        rows = slice(i * block_b, (i + 1) * block_b)
        rows_real = min(max(b_real - i * block_b, 1), block_b)
        g, t = g0[rows], 0
        while t < max_iters:
            et = lda_estep._exp_elog_theta(g, k_real)
            acc = jnp.zeros_like(g)
            for j in range(0, c.shape[1], block_v):
                ebj = eb[j:j + block_v]
                p = jax.lax.dot_general(
                    et, ebj, (((1,), (1,)), ((), ())),
                    precision=jax.lax.Precision.HIGHEST) + 1e-30
                acc = acc + jax.lax.dot(c[rows, j:j + block_v] / p, ebj,
                                        precision=jax.lax.Precision.HIGHEST)
            g_new = jnp.where(real, alpha0 + et * acc, alpha0)
            delta = float(jnp.abs(g_new - g).sum()) / (rows_real * k_real)
            g, t = g_new, t + 1
            if delta <= tol:
                break
        gammas.append(g)
        sweeps.append(t)
    gamma = jnp.concatenate(gammas)
    return gamma, lda_estep._exp_elog_theta(gamma, k_real), sweeps


def _tiles_batch(b=20, v=300, k=8, l=24, seed=0):
    """Three 8-document tiles that stop at different sweeps: tile 0 starts
    at its fixed point, tile 1 cold, tile 2 cold with 20× longer documents
    (it needs more than 40 sweeps)."""
    rng = np.random.default_rng(seed)
    ids = jnp.asarray(np.stack([rng.choice(v, l, replace=False)
                                for _ in range(b)]).astype(np.int32))
    cnts = rng.integers(1, 4, (b, l)).astype(np.float32)
    cnts[16:] *= 20
    cnts = jnp.asarray(cnts)
    lam = jax.random.gamma(jax.random.key(seed), 0.2, (v, k)) * 5 + 0.01
    eb = exp_dirichlet_expectation(lam, axis=0)
    cfg = LDAConfig(num_topics=k, vocab_size=v, estep_tol=1e-3,
                    estep_max_iters=40)
    settled = estep_gather(dataclasses.replace(cfg, estep_tol=0.0,
                                               estep_max_iters=200),
                           eb, ids[:8], cnts[:8]).gamma
    g0 = jnp.full((b, k), cfg.alpha0 + 1.0).at[:8].set(settled)
    return cfg, eb, ids, cnts, g0


@pytest.mark.parametrize("block_v,stream,workers", [
    (128, jnp.float32, None),      # V 384 in 3 tiles: streamed, two slots
    (384, jnp.float32, None),      # one V-resident tile, copied once
    (128, jnp.bfloat16, None),     # bf16 C and Eφ, fp32 accumulation
    (128, jnp.float32, 2),         # memo correction vmapped over workers
], ids=["streamed", "resident", "streamed-bf16", "vmap-workers"])
def test_fixed_point_stops_per_tile(block_v, stream, workers):
    """Each B-tile runs its own sweeps and stops when it converges: γ, E[θ]
    and the sweep counts are those of the per-tile jnp loop (to fp32
    rounding), γ is ``estep_gather``'s on the tile's documents, and a tile
    that hits ``max_iters`` stops there."""
    cfg, eb, ids, cnts, g0 = _tiles_batch()
    b, k = g0.shape
    block_b = 8
    cpad, ebpad, _ = pad_inputs(densify(ids, cnts, cfg.vocab_size), eb,
                                block_b, block_v)
    cpad, ebpad = cpad.astype(stream), ebpad.astype(stream)
    gpad = jnp.pad(g0, ((0, cpad.shape[0] - b), (0, ebpad.shape[1] - k)),
                   constant_values=cfg.alpha0)
    args = (cpad, ebpad, gpad, cfg.alpha0, cfg.estep_tol,
            cfg.estep_max_iters, k, b)
    gamma, et, iters = lda_estep.estep_fixed_point(
        *args, block_b=block_b, block_v=block_v)
    # the TPU interpreter runs each copy only when it is waited on: a
    # V-tile read before its wait would read what the slot held before
    late = lda_estep.estep_fixed_point(
        *args, block_b=block_b, block_v=block_v,
        interpret=pltpu.InterpretParams(dma_execution_mode="on_wait"))
    for got, want in zip(late, (gamma, et, iters)):
        np.testing.assert_array_equal(got, want)
    want_g, want_et, want_sweeps = _fixed_point_per_tile(
        *args, block_b, block_v)
    assert np.asarray(iters).ravel().tolist() == want_sweeps
    assert want_sweeps[0] < want_sweeps[1] < cfg.estep_max_iters
    assert want_sweeps[2] == cfg.estep_max_iters
    np.testing.assert_allclose(gamma, want_g, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(et, want_et, rtol=1e-5, atol=1e-7)
    if stream == jnp.float32:
        for i in range(3):
            rows = slice(i * block_b, min((i + 1) * block_b, b))
            ref_g = estep_gather(cfg, eb, ids[rows], cnts[rows],
                                 g0[rows]).gamma
            np.testing.assert_allclose(gamma[rows, :k], ref_g, rtol=2e-3,
                                       atol=2e-3)
    if workers:
        # D-IVI's workers share Eφ: vmap stacks their rows into one call
        ids_w = jnp.stack([ids, ids[::-1]])
        cnts_w = jnp.stack([cnts, cnts[::-1]])
        old_pi = jnp.zeros(ids_w.shape + (k,), jnp.float32)
        visited = jnp.zeros((workers, b), bool)

        def correction(i, c, o, vis):
            return memo_correction_pallas(cfg, eb, i, c, o, vis,
                                          block_b=block_b, block_v=block_v)
        batched = jax.vmap(correction)(ids_w, cnts_w, old_pi, visited)
        for w in range(workers):
            one = correction(ids_w[w], cnts_w[w], old_pi[w], visited[w])
            for got, want in zip(jax.tree_util.tree_leaves(batched),
                                 jax.tree_util.tree_leaves(one)):
                np.testing.assert_allclose(got[w], want, rtol=1e-6,
                                           atol=1e-6)


def _memo_delta_refs(ids, cnts, ebt, et, v):
    p = (et[:, None, :] * ebt).sum(-1) + 1e-30
    pi = jnp.where(cnts[:, :, None] > 0,
                   et[:, None, :] * ebt / p[:, :, None], 0.0)
    flat = ids.reshape(-1)
    k = et.shape[1]
    snew = jnp.zeros((v, k)).at[flat].add(
        (cnts[:, :, None] * pi).reshape(-1, k))
    return pi, snew


@pytest.mark.parametrize("b,l,block_b", [
    (64, 32, 16),    # nb = 4: the multi-partial reduction path
    (32, 512, 32),   # VMEM guard halves block_b (32 → 4 at L=512, K=128)
])
def test_memo_delta_onehot_multi_tile_partials(b, l, block_b, rng):
    """The retired (nb, V, K) partial scheme (the benchmark baseline) must
    still match the jnp scatter with nb ≥ 2 B-tiles and when the VMEM
    guard shrinks the tile — shapes at which the old cross-tile output
    accumulation (TPU-undefined) was actually exercised."""
    v, k = 700, 128
    ids = jnp.asarray(rng.integers(0, v, (b, l)).astype(np.int32))
    cnts = jnp.asarray(rng.poisson(1.0, (b, l)).astype(np.float32))
    ebt = jnp.asarray(rng.gamma(1.0, 1.0, (b, l, k)).astype(np.float32))
    et = jnp.asarray(rng.gamma(1.0, 1.0, (b, k)).astype(np.float32))
    opi = jnp.asarray(rng.random((b, l, k)).astype(np.float32))
    assert b // lda_estep.delta_effective_block_b(
        b, l, k, block_b=block_b) >= 2          # the shapes must fan out
    pi, snew, sold = lda_estep.memo_delta_onehot(ids, cnts, ebt, et, v,
                                                 old_pi=opi, block_b=block_b)
    pref, sref = _memo_delta_refs(ids, cnts, ebt, et, v)
    soldref = jnp.zeros((v, k)).at[ids.reshape(-1)].add(
        (cnts[:, :, None] * opi).reshape(-1, k))
    np.testing.assert_allclose(pi, pref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(snew, sref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(sold, soldref, rtol=1e-4, atol=1e-4)


def _scatter_case_ids(kind, b, l, v, rng):
    """Token ids and counts laid out to hit one edge of the sorted scatter
    (chunks of 128 words in the cases that set block_v=128)."""
    cnts = rng.poisson(1.0, (b, l)).astype(np.float32)
    if kind == "random":
        ids = rng.integers(0, v, (b, l))
    elif kind == "empty-chunks":     # chunks 0, 5, 11 of 16; the rest empty
        ids = rng.choice([3, 5 * 128 + 7, 5 * 128 + 100, 11 * 128 + 64],
                         (b, l))
    elif kind == "straddle":         # sorted tiles cross the 128 boundary
        ids = rng.integers(100, 160, (b, l))
    elif kind == "all-zero":
        ids = rng.integers(0, v, (b, l))
        cnts = np.zeros((b, l), np.float32)
    elif kind == "one-word":         # one chunk takes every tile
        ids = np.full((b, l), 300)
        cnts = cnts + 1.0
    elif kind == "last-chunk":       # the last chunk, 640..767, is partial
        ids = rng.integers(640, v, (b, l))
    else:
        raise ValueError(kind)
    return jnp.asarray(ids.astype(np.int32)), jnp.asarray(cnts)


@pytest.mark.parametrize("b,l,v,kwargs,kind", [
    # L grid axis: 2 L-tiles × 2 B-tiles (the old path capped L at ~4k —
    # this exercises the tiling machinery, test_estep_backend covers 8192)
    pytest.param(8, 700, 300, dict(block_l=512, block_b=4), "random",
                 id="8-700-300-kwargs0"),
    # V-chunk grid axis: 6 chunks over a non-lane-multiple vocab, and a
    # row count that pads up to the T tile
    pytest.param(12, 37, 700, dict(block_v=128, block_t=64), "random",
                 id="12-37-700-kwargs1"),
    # single-chunk V-resident degenerate case: a visit list of one chunk
    pytest.param(16, 24, 200, dict(), "random", id="16-24-200-kwargs2"),
    # the sorted visit list: empty chunks between visited ones
    pytest.param(8, 40, 2000, dict(block_v=128, block_t=64), "empty-chunks",
                 id="empty-chunks"),
    # a sorted row tile holds the end of one chunk and the start of the next
    pytest.param(8, 40, 512, dict(block_v=128, block_t=64), "straddle",
                 id="straddle"),
    # every row inert: no live visit, every chunk zeroed once
    pytest.param(4, 50, 700, dict(block_v=128, block_t=64), "all-zero",
                 id="all-zero"),
    # all ids equal: one chunk takes every tile, the others are empty
    pytest.param(6, 30, 700, dict(block_v=128, block_t=64), "one-word",
                 id="one-word"),
    # ids only in the last, partial chunk
    pytest.param(8, 33, 700, dict(block_v=128, block_t=64), "last-chunk",
                 id="last-chunk"),
    # V-resident with a padded last row tile: one chunk, trailing inert tile
    pytest.param(4, 50, 1000, dict(), "random", id="resident"),
])
def test_memo_delta_segment_grid(b, l, v, kwargs, kind, rng):
    """The segment-sum scatter must match the jnp scatter across the
    (B, L) tiling of the token-π kernel and the V chunks of the
    accumulator — the sorted visit list over one chunk and over many —
    including padded L remainders and padded row tiles, which must stay
    inert (count 0), with and without old π, on the fp32 and the bf16
    wire."""
    k = 128
    ids, cnts = _scatter_case_ids(kind, b, l, v, rng)
    ebt = jnp.asarray(rng.gamma(1.0, 1.0, (b, l, k)).astype(np.float32))
    et = jnp.asarray(rng.gamma(1.0, 1.0, (b, k)).astype(np.float32))
    opi = jnp.asarray(rng.random((b, l, k)).astype(np.float32))
    pref, sref = _memo_delta_refs(ids, cnts, ebt, et, v)
    soldref = jnp.zeros((v, k)).at[ids.reshape(-1)].add(
        (cnts[:, :, None] * opi).reshape(-1, k))
    for quantize in (False, True):
        pi, snew, sold = lda_estep.memo_delta(ids, cnts, ebt, et, v,
                                              old_pi=opi, quantize=quantize,
                                              **kwargs)
        pi1, snew1 = lda_estep.memo_delta(ids, cnts, ebt, et, v,
                                          quantize=quantize, **kwargs)
        if quantize:
            # π rounds through bf16 before the scatter: the masses must
            # hold exactly the rounded π the kernel returns
            np.testing.assert_allclose(pi, pref, rtol=2 ** -8, atol=1e-6)
            sexp = jnp.zeros((v, k)).at[ids.reshape(-1)].add(
                (cnts[:, :, None] * pi).reshape(-1, k))
        else:
            np.testing.assert_allclose(pi, pref, rtol=1e-5, atol=1e-6)
            sexp = sref
        np.testing.assert_array_equal(np.asarray(pi1), np.asarray(pi))
        np.testing.assert_allclose(snew, sexp, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(snew1, sexp, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(sold, soldref, rtol=1e-4, atol=1e-4)


def test_memo_delta_matches_onehot_baseline(rng):
    """Segment-sum and the retired one-hot baseline agree bit-for-bit in
    what they compute (π) and to fp32 summation tolerance in the masses —
    the 'measured, not asserted' bridge BENCH_estep quantifies."""
    b, l, v, k = 16, 48, 500, 64
    ids = jnp.asarray(rng.integers(0, v, (b, l)).astype(np.int32))
    cnts = jnp.asarray(rng.poisson(1.0, (b, l)).astype(np.float32))
    ebt = jnp.asarray(rng.gamma(1.0, 1.0, (b, l, k)).astype(np.float32))
    et = jnp.asarray(rng.gamma(1.0, 1.0, (b, k)).astype(np.float32))
    seg = lda_estep.memo_delta(ids, cnts, ebt, et, v, quantize=True)
    one = lda_estep.memo_delta_onehot(ids, cnts, ebt, et, v, quantize=True)
    np.testing.assert_array_equal(np.asarray(seg[0]), np.asarray(one[0]))
    np.testing.assert_allclose(seg[1], one[1], rtol=1e-4, atol=1e-4)


def test_segment_scatter_blocks_policy():
    """The V-chunk policy stays lane-aligned, under budget, and V-resident
    for small vocabs."""
    f = lda_estep.segment_scatter_blocks
    vc, tb = f(128, 141_952, True)
    assert vc % 128 == 0 and vc >= 2048            # big vocabs: few chunks
    assert (vc * tb + 2 * (vc * 128 + tb * 128)) * 4 <= 8 * 1024 * 1024
    assert f(128, 700, True)[0] == 768             # V-resident, lane-aligned
    assert f(128, 4096, False)[0] == 4096
    bb, bl = lda_estep.pi_tile_shape(32, 8192, 128)
    assert bl == 512 and 2 * bb * bl * 128 * 4 <= 8 * 1024 * 1024
    assert 32 % bb == 0


ARXIV_B, ARXIV_L, ARXIV_V, ARXIV_K = 256, 159, 141_927, 100


def test_memo_delta_scatter_grid_is_visit_list():
    """At Arxiv shapes (35 V-chunks × 318 row tiles) the scatter call runs
    the sorted visit list — at most row_tiles + chunks grid steps, not
    chunks × row_tiles — and keeps an s32 array as its first operand."""
    f32 = jnp.float32
    b, l, v, k = ARXIV_B, ARXIV_L, ARXIV_V, ARXIV_K
    sds = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(
        lambda ids, c, ebt, et, old: lda_estep.memo_delta(
            ids, c, ebt, et, v, old, quantize=True))(
        sds((b, l), jnp.int32), sds((b, l), f32), sds((b, l, k), f32),
        sds((b, k), f32), sds((b, l, k), f32))
    calls = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    scatter = [e for e in calls if e.invars[0].aval.dtype == jnp.int32]
    assert len(calls) == 2 and len(scatter) == 1
    grid = scatter[0].params["grid_mapping"].grid
    vc, tb = lda_estep.segment_scatter_blocks(k, v, True)
    chunks, tiles = -(-v // vc), b * l // tb
    assert (chunks, tiles) == (35, 318)
    assert len(grid) == 1 and grid[0] <= tiles + chunks
    assert grid[0] == lda_estep.scatter_grid_steps(b * l, k, v, True)[1]


def test_scatter_grid_steps():
    """The host count behind the train.scatter_* gauges and the kernel's
    grid: dense is chunks × row_tiles, the grid the visit list's
    row_tiles + chunks, one chunk included."""
    f = lda_estep.scatter_grid_steps
    rows = lda_estep.scatter_rows((ARXIV_B, ARXIV_L))
    assert rows == 40_704
    assert f(rows, ARXIV_K, ARXIV_V, True) == (35 * 318, 318 + 35)
    assert f(rows, ARXIV_K, 700, True) == (318, 318 + 1)   # one chunk
    assert f(rows, ARXIV_K, 700, True, block_v=128) == (6 * 318, 318 + 6)
    assert f(100, 64, 500, False) == (1, 1 + 1)            # rows < tile
    # the π kernel's padding: L past its tile rounds up; T to its tile
    assert lda_estep.scatter_rows((4, 700), block_l=512) == 4 * 1024
    assert lda_estep.scatter_rows((8192,), block_l=512) == 8192
    assert lda_estep.scatter_rows((300,), block_l=512) == 384


def test_memo_delta_sorted_under_vmap(rng):
    """D-IVI vmaps the memo correction over workers: the sorted scatter's
    prefetched visit lists differ per worker and must batch exactly."""
    w, b, l, v, k = 3, 8, 40, 700, 128
    ids = jnp.asarray(rng.integers(0, v, (w, b, l)).astype(np.int32))
    cnts = jnp.asarray(rng.poisson(1.0, (w, b, l)).astype(np.float32))
    ebt = jnp.asarray(rng.gamma(1.0, 1.0, (w, b, l, k)).astype(np.float32))
    et = jnp.asarray(rng.gamma(1.0, 1.0, (w, b, k)).astype(np.float32))
    opi = jnp.asarray(rng.random((w, b, l, k)).astype(np.float32))

    def f(*a):
        return lda_estep.memo_delta(*a[:4], v, a[4], block_v=128,
                                    block_t=64)
    out = jax.vmap(f)(ids, cnts, ebt, et, opi)
    for x in range(w):
        for got, want in zip(out, f(ids[x], cnts[x], ebt[x], et[x],
                                    opi[x])):
            np.testing.assert_array_equal(np.asarray(got[x]),
                                          np.asarray(want))


def test_delta_effective_block_b_guard():
    """The VMEM guard halves the B-tile for long token axes and always
    returns a divisor of B."""
    f = lda_estep.delta_effective_block_b
    assert f(128, 64, 128) == 32           # fits at the default
    assert f(128, 128, 128) == 16          # production L halves once
    assert f(128, 512, 128) == 4
    assert f(12, 40, 16) == 12             # small batch: capped at B
    for b, l in [(96, 512), (32, 1024), (12, 512)]:
        bb = f(b, l, 128)
        assert b % bb == 0, (b, l, bb)


def test_kernel_padding_exactness():
    """Padded vocab/topic/batch slots must not leak into real outputs."""
    rng = np.random.default_rng(1)
    spec = PAPER_CORPORA["tiny"]
    corpus = make_corpus(spec, split="train", seed=0)
    cfg = LDAConfig(num_topics=5, vocab_size=spec.vocab_size,
                    estep_max_iters=30)
    lam = jax.random.gamma(jax.random.key(2), 100.0,
                           (spec.vocab_size, 5)) * 0.01
    eb = exp_dirichlet_expectation(lam, axis=0)
    ids, cnts = corpus.token_ids[:7], corpus.counts[:7]   # odd batch
    r1 = estep_dense(cfg, eb, ids, cnts)
    # blocks force padding on every axis (B→8, V→256·k, K→128)
    r2 = estep_pallas(cfg, eb, ids, cnts, block_b=8, block_v=125)
    np.testing.assert_allclose(r1.gamma, r2.gamma, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(r1.sstats, r2.sstats, rtol=1e-2, atol=1e-3)
