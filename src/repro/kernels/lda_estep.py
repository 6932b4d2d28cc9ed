"""Pallas TPU kernels for the LDA E-step hotspot.

Production path (`ops.estep_pallas` / `ops.memo_correction_pallas`):

* ``estep_fixed_point`` — the ENTIRE γ fixed point in one ``pallas_call``:
  one grid step per B-tile, whose sweeps are a ``while`` loop inside the
  kernel with γ, Eθ and the sweep accumulator in VMEM. C and Eφ stay in
  HBM; each sweep copies them in by V-tile into two VMEM slots, the next
  tile's copy running while the current one is reduced (a V-resident tile
  is copied once). The loop ends when the tile's mean |Δγ| ≤ tol or at
  ``max_iters``, so a converged tile streams nothing more, and the sweeps
  each tile ran are emitted. Nothing γ-shaped ever round-trips to HBM
  between sweeps — the old path paid one pallas_call per sweep plus a jnp
  Eθ recomputation per sweep.
* ``memo_delta`` — token-aligned π AND the subtract-old/add-new scatter as
  a **segment-sum** over two kernels: the token-π kernel tiles the (B, L)
  axes (the L grid axis — VMEM no longer bounds the corpus L) and forms
  π = Eθ⊙Eφ_tok/φnorm per tile; the scatter kernel flattens the batch to
  token rows and accumulates cnt·π_new / cnt·π_old into (V, K) over a
  second-level **V-chunk** axis — each (block_v, K) accumulator is
  revisited only by grid-consecutive steps (the revisit pattern Pallas TPU
  defines) and hits HBM exactly once per chunk. The rows are sorted by
  word id and a prefetched visit list walks only the (chunk, row-tile)
  pairs that meet, so a row tile no longer meets every chunk. No dense (nb, V, K) one-hot partials exist anywhere, and the
  IVI correction still needs **no (B, L, K) jnp intermediates**: the only
  (B, L, K) array XLA sees is the Eφ token gather feeding the kernel.
  The retired one-hot-partial formulation is kept as
  ``memo_delta_onehot`` (benchmark baseline).

Legacy per-sweep path
---------------------
* ``estep_sweep``  — γ' = α₀ + Eθ ⊙ (R·Eφ),  R = C ⊘ (Eθ·Eφᵀ + ε)
* ``sstats``       — S  = Eφ ⊙ (Rᵀ·Eθ)

Tiling (DESIGN.md §7 and docs/estep.md): B-tile × V-tile × K — K is padded
to a multiple of 128 by the wrapper (`ops.py`), V-tiles default to 512 and
B-tiles to 128, so the fused fixed point's VMEM working set is

    2 × (C (128·512) + Eφ (512·128)) + γ/Eθ/acc (3·128·128) ≈ 1.2 MB « 48 MiB

and every matmul hits the MXU with ≥128 on both the lane and the
contraction dimension. ``stream_dtype=bfloat16`` streams C and Eφ in bf16
(fp32 accumulation), halving the dominant HBM terms of the fixed point.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_EPS = 1e-30  # fp32-safe (1e-100 underflows to 0)
# full-fp32 MXU contractions: Mosaic's default f32 matmul rounds its
# operands to bf16, and the γ fixed point then never settles under the
# mean-|Δγ| tolerance (measured on a v5e: 100 of 100 sweeps, γ off by 0.7)
_F32 = jax.lax.Precision.HIGHEST
# fp32 contractions keep bf16 splits of their operands in VMEM, which takes
# the Arxiv-width tiles past Mosaic's default 16 MiB scoped limit (up to
# ~22 MiB, tests/test_tpu_compile.py); a v5e core has 128 MiB of VMEM
_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=48 * 1024 * 1024)


def _default_interpret(interpret):
    """Interpret mode on the CPU, Mosaic on a TPU; any other backend raises.

    The kernels lower only through Mosaic, so a GPU (or any non-TPU
    accelerator) would otherwise run the Pallas interpreter silently.
    """
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"the LDA Pallas kernels run on a TPU (Mosaic) or in "
                       f"interpret mode on the CPU, not on {backend!r}")


# ---------------------------------------------------------------------------
# in-kernel Dirichlet expectation
# ---------------------------------------------------------------------------

def _digamma(x):
    """ψ(x) for x > 0, kernel-safe (no lax.digamma lowering dependence).

    Recurrence ψ(x) = ψ(x+1) − 1/x applied 8 times pushes the argument
    above 8, where the asymptotic series ln x − 1/2x − Σ B₂ₙ/(2n·x²ⁿ) is
    accurate to ~1e-7 relative — far inside the E-step tolerance.
    """
    shift = jnp.zeros_like(x)
    for _ in range(8):
        shift += 1.0 / x
        x = x + 1.0
    inv = 1.0 / x
    inv2 = inv * inv
    series = jnp.log(x) - 0.5 * inv - inv2 * (
        1.0 / 12.0 - inv2 * (1.0 / 120.0 - inv2 / 252.0))
    return series - shift


def _exp_elog_theta(g, k_real: int):
    """exp(E[ln θ]) over the first ``k_real`` topics; padded topics → 0.

    Padded γ columns carry exactly α₀ and a zero Eφ column (see
    ``ops.pad_inputs``); masking them out of the normaliser keeps the real
    topics' expectation identical to the unpadded computation.
    """
    mask = jax.lax.broadcasted_iota(jnp.int32, g.shape, 1) < k_real
    gm = jnp.where(mask, g, 0.0)
    s = gm.sum(-1, keepdims=True)
    et = jnp.exp(_digamma(jnp.maximum(g, 1e-10)) - _digamma(s))
    return jnp.where(mask, et, 0.0)


# ---------------------------------------------------------------------------
# fused fixed-point kernel
# ---------------------------------------------------------------------------

def _fixed_point_kernel(alpha0: float, tol: float, k_real: int,
                        b_real: int, block_b: int, block_v: int,
                        tile_rows: int, num_t: int, num_v: int,
                        c_hbm, eb_hbm, g0_ref,
                        gamma_ref, et_ref, iters_ref,
                        c_buf, eb_buf, acc_s, sems):
    """One B-tile's whole fixed point; γ and E[θ] live in the output blocks.

    C and Eφ stay in HBM. Each sweep walks the V-tiles in order and copies
    tile j+1 into the other half of a two-slot VMEM buffer while tile j is
    reduced, so the sweeps stop streaming as soon as the tile converges.
    A V-resident tile (``num_v == 1``) is copied once, before the first
    sweep. ``tile_rows`` is the rows of one problem: vmapped problems
    stacked along B (``estep_fixed_point``) each have their own ``b_real``.
    """
    i = pl.program_id(0)

    def copies(j, slot):
        return (pltpu.make_async_copy(
                    c_hbm.at[pl.ds(i * block_b, block_b),
                             pl.ds(j * block_v, block_v)],
                    c_buf.at[slot], sems.at[0, slot]),
                pltpu.make_async_copy(
                    eb_hbm.at[pl.ds(j * block_v, block_v)],
                    eb_buf.at[slot], sems.at[1, slot]))

    def fetch(j, slot):
        for cp in copies(j, slot):
            cp.start()

    def wait(j, slot):
        for cp in copies(j, slot):
            cp.wait()

    resident = num_v == 1
    if resident:
        fetch(0, 0)
        wait(0, 0)
    gamma_ref[...] = g0_ref[...]
    # mean |Δγ| over the tile's REAL rows/topics only — padding holds
    # γ = α₀ exactly (zero diff) but must not dilute the convergence
    # threshold, or the kernel stops earlier than the jnp backends
    first_row = (i % (tile_rows // block_b)) * block_b
    rows_real = jnp.clip(b_real - first_row, 1, block_b)

    def accumulate(j, carry):
        slot = 0 if resident else j % 2
        if not resident:
            @pl.when(j + 1 < num_v)
            def _prefetch():
                fetch(j + 1, 1 - slot)

            wait(j, slot)
        et = et_ref[...]                               # (bB, K)
        eb = eb_buf[slot].astype(jnp.float32)          # (bV, K)
        p = jax.lax.dot_general(et, eb, (((1,), (1,)), ((), ())),
                                precision=_F32,
                                preferred_element_type=jnp.float32) + _EPS
        r = c_buf[slot].astype(jnp.float32) / p        # (bB, bV)
        acc_s[...] += jax.lax.dot(r, eb,
                                  precision=_F32,
                                  preferred_element_type=jnp.float32)
        return carry

    def sweep(carry):
        t, _ = carry
        if not resident:
            fetch(0, 0)
        et_ref[...] = _exp_elog_theta(gamma_ref[...], k_real)
        acc_s[...] = jnp.zeros_like(acc_s)
        jax.lax.fori_loop(0, num_v, accumulate, 0)
        g_old = gamma_ref[...]
        mask = jax.lax.broadcasted_iota(jnp.int32, g_old.shape, 1) < k_real
        g_new = jnp.where(mask, alpha0 + et_ref[...] * acc_s[...], alpha0)
        delta = jnp.abs(g_new - g_old).sum() / (rows_real * k_real)
        gamma_ref[...] = g_new
        return t + 1, jnp.where(delta <= tol, 1, 0).astype(jnp.int32)

    def live(carry):
        t, converged = carry
        return (t < num_t) & (converged == 0)

    t, _ = jax.lax.while_loop(live, sweep, (jnp.int32(0), jnp.int32(0)))
    et_ref[...] = _exp_elog_theta(gamma_ref[...], k_real)
    iters_ref[...] = jnp.full(iters_ref.shape, t, jnp.int32)


def estep_fixed_point(c: jax.Array, eb: jax.Array, gamma0: jax.Array,
                      alpha0: float, tol: float, max_iters: int,
                      k_real: int, b_real: int | None = None, *,
                      block_b: int = 128, block_v: int = 512,
                      interpret: bool | None = None):
    """The whole γ fixed point as ONE pallas_call.

    Shapes: c (B, V), eb (V, K), gamma0 (B, K) → (γ (B, K), Eθ (B, K),
    per-B-tile sweep counts (nb, 1) int32). All dims pre-padded to the
    block grid; ``k_real``/``b_real`` mask the padded topic columns and
    batch rows out of the convergence mean. C/Eφ may be bf16 (fp32
    accumulation).

    Under ``vmap`` over problems that share Eφ (D-IVI's workers), the
    problems' rows are stacked into one call, each B-tile inside one
    problem: Mosaic takes no batched operand left in HBM.
    """
    b, v = c.shape
    k = gamma0.shape[1]
    b_real = b if b_real is None else b_real
    block_b, block_v = min(block_b, b), min(block_v, v)
    assert b % block_b == 0 and v % block_v == 0, (b, v, block_b, block_v)
    interpret = _default_interpret(interpret)
    nv = v // block_v
    nbuf = 1 if nv == 1 else 2
    kernel = functools.partial(_fixed_point_kernel, alpha0, tol, k_real,
                               b_real, block_b, block_v, b,
                               max(int(max_iters), 1), nv)

    @jax.custom_batching.custom_vmap
    def solve(c, eb, gamma0):
        nb = c.shape[0] // block_b
        return pl.pallas_call(
            kernel,
            grid=(nb,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((block_b, k), lambda i: (i, 0)),
            ],
            out_specs=[
                pl.BlockSpec((block_b, k), lambda i: (i, 0)),
                pl.BlockSpec((block_b, k), lambda i: (i, 0)),
                # one (1, 1) block per B-tile: with the tile axis squeezed
                # the block equals the array's last two dims, which Mosaic's
                # (8, 128) tiling rule accepts (a (1, 1) block of an (nb, 1)
                # array is not)
                pl.BlockSpec((None, 1, 1), lambda i: (i, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((nb * block_b, k), jnp.float32),
                jax.ShapeDtypeStruct((nb * block_b, k), jnp.float32),
                jax.ShapeDtypeStruct((nb, 1, 1), jnp.int32),
            ],
            scratch_shapes=[
                pltpu.VMEM((nbuf, block_b, block_v), c.dtype),
                pltpu.VMEM((nbuf, block_v, k), eb.dtype),
                pltpu.VMEM((block_b, k), jnp.float32),
                pltpu.SemaphoreType.DMA((2, nbuf)),
            ],
            compiler_params=_PARAMS,
            interpret=interpret,
        )(c, eb, gamma0)

    @solve.def_vmap
    def _stack_problems(n, batched, c, eb, gamma0):
        if batched[1]:
            raise NotImplementedError("vmap of the padded fixed point over "
                                      "Eφ: the problems must share it")
        c = c if batched[0] else jnp.broadcast_to(c, (n, b, v))
        gamma0 = gamma0 if batched[2] else jnp.broadcast_to(gamma0,
                                                            (n, b, k))
        gamma, et, iters = solve(c.reshape(n * b, v), eb,
                                 gamma0.reshape(n * b, k))
        return ((gamma.reshape(n, b, k), et.reshape(n, b, k),
                 iters.reshape(n, b // block_b, 1, 1)), (True,) * 3)

    gamma, et, iters = solve(c, eb, gamma0)
    return gamma, et, iters.reshape(b // block_b, 1)


# ---------------------------------------------------------------------------
# memo correction, production path: token-π kernel + segment-sum scatter
# ---------------------------------------------------------------------------

def _token_pi_kernel(quantize: bool, cnts_ref, ebtok_ref, et_ref, pi_ref):
    """π = Eθ⊙Eφ_tok/φnorm for one (B-tile, L-tile); each block written once.

    The L grid axis is what lifts the old ``L ≤ ~4k`` VMEM cap: the working
    set is two (block_b, block_l, K) cubes regardless of the corpus L.
    """
    et = et_ref[...]                                   # (bB, K)
    ebt = ebtok_ref[...]                               # (bB, bL, K)
    cnts = cnts_ref[...]                               # (bB, bL)
    p = (et[:, None, :] * ebt).sum(-1) + _EPS          # (bB, bL)
    pi = et[:, None, :] * ebt / p[:, :, None]
    pi = jnp.where(cnts[:, :, None] > 0, pi, 0.0)
    if quantize:
        # round through the memo store's wire dtype BEFORE the scatter,
        # so ⟨m_vk⟩ adds exactly what the store will later subtract
        pi = pi.astype(jnp.bfloat16).astype(jnp.float32)
    pi_ref[...] = pi


def _segment_scatter_kernel(has_old: bool, chunk_ref, tile_ref, nvis_ref,
                            *refs):
    """Segment-sum one tile of token rows into one V chunk.

    Rows are segmented arithmetically: a row contributes to the chunk its
    token id falls in (`iota == ids`, count-scaled), everything else
    multiplies to zero — padded rows carry count 0 and are inert. The rows
    arrive sorted by word id, and the 1-D grid walks the prefetched
    ``(chunk, row-tile)`` visits whose ids meet the chunk
    (``_scatter_visits``), chunks non-decreasing. The (block_v, K) output
    block of a chunk is therefore revisited only by grid-consecutive
    steps, the revisit pattern Pallas TPU defines for in-kernel
    accumulation, so the (V, K) masses build up in VMEM and hit HBM once
    per chunk, with **no** per-B-tile (nb, V, K) partials. A chunk's first
    visit zeroes its block; visits past the live count (``nvis``) repeat
    the last block and run nothing.
    """
    i = pl.program_id(0)
    j = chunk_ref[i]
    first = (i == 0) | (j != chunk_ref[jnp.maximum(i - 1, 0)])
    live = i < nvis_ref[0]
    if has_old:
        ids_ref, cnts_ref, wnew_ref, wold_ref, snew_ref, sold_ref = refs
    else:
        ids_ref, cnts_ref, wnew_ref, snew_ref = refs
        wold_ref = sold_ref = None

    @pl.when(first)
    def _init():
        snew_ref[...] = jnp.zeros_like(snew_ref)
        if has_old:
            sold_ref[...] = jnp.zeros_like(sold_ref)

    @pl.when(live)
    def _accumulate():
        bv = snew_ref.shape[0]
        tb = ids_ref.shape[-1]
        rows = j * bv + jax.lax.broadcasted_iota(jnp.int32, (bv, tb), 0)
        # count-scaled segment selector: (bV, T) — doubles as the MXU
        # scatter operand, so cnt·π never materialises as a row array
        weights = jnp.where(rows == ids_ref[...], cnts_ref[...], 0.0)
        snew_ref[...] += jax.lax.dot(weights, wnew_ref[...],
                                     precision=_F32,
                                     preferred_element_type=jnp.float32)
        if has_old:
            sold_ref[...] += jax.lax.dot(weights, wold_ref[...],
                                         precision=_F32,
                                         preferred_element_type=jnp.float32)


# VMEM budgets: the token-π step holds two (block_b, block_l, K) fp32 cubes
# (Eφ tokens in, π out); the scatter step holds the (block_v, T) selector
# plus one or two (block_v, K) accumulators and (T, K) row tiles. Each is a
# sixth of the 48 MiB scoped limit `_PARAMS` sets, which leaves room for the
# pipeline's double-buffered blocks and the bf16 operand splits of the fp32
# (HIGHEST) contractions.
_PI_VMEM_BUDGET = 8 * 1024 * 1024
_SEG_VMEM_BUDGET = 8 * 1024 * 1024


def _pi_l_tile(l: int, block_l: int) -> int:
    """The token-π kernel's L tile: the whole L, or ``block_l`` past it."""
    return l if l <= block_l else block_l


def _csr_pi_tile(t: int, block_t_pi: int) -> int:
    """The flat token-π kernel's token tile: lane-aligned, ≤ ``block_t_pi``."""
    return min(block_t_pi, _round_up(t, 128))


def pi_tile_shape(b: int, l: int, k: int, *, block_b: int = 32,
                  block_l: int = 512) -> Tuple[int, int]:
    """(block_b, block_l) for the token-π kernel under the VMEM budget.

    L longer than ``block_l`` is tiled by the L grid axis (the corpus L no
    longer bounds VMEM); the B tile is then halved until the two
    (block_b, block_l, K) cubes fit the step budget.
    """
    bl = _pi_l_tile(l, block_l)
    bb = min(block_b, b)
    while bb > 1 and 2 * bb * bl * k * 4 > _PI_VMEM_BUDGET:
        nxt = bb // 2
        bb = nxt if b % nxt == 0 else 1    # keep the grid exact
    return bb, bl


def segment_scatter_blocks(k: int, vocab_size: int, has_old: bool, *,
                           block_v: int | None = None,
                           block_t: int = 128) -> Tuple[int, int]:
    """(block_v, block_t) for the segment-sum scatter under its budget.

    ``block_v`` is the second-level V-chunk: the largest multiple of 128
    whose selector + accumulators fit ``_SEG_VMEM_BUDGET`` (capped at the
    lane-aligned vocab, so small vocabs run V-resident in one chunk). The
    rows are sorted by word id, so each row tile meets only the chunks its
    ids fall in (``scatter_grid_steps``).
    """
    nacc = 2 if has_old else 1

    def _step_bytes(vc):
        return (vc * block_t + nacc * (vc * k + block_t * k)) * 4

    if block_v is None:
        block_v = 8192
        while block_v > 128 and _step_bytes(block_v) > _SEG_VMEM_BUDGET:
            block_v //= 2
    block_v = min(block_v, _round_up(vocab_size, 128))
    return block_v, block_t


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def scatter_rows(token_shape: Tuple[int, ...], *, block_l: int = 512) -> int:
    """Token rows the segment scatter flattens for a ``memo_delta`` call on
    (B, L) token ids (L padded to the π kernel's L tile) or a
    ``memo_delta_csr`` call on (T,) flat tokens (T padded to its token
    tile); ``block_l`` is ``block_l`` or ``block_t_pi`` respectively."""
    if len(token_shape) == 2:
        b, l = token_shape
        return b * _round_up(l, _pi_l_tile(l, block_l))
    (t,) = token_shape
    return _round_up(t, _csr_pi_tile(t, block_l))


def scatter_grid_steps(rows: int, k: int, vocab_size: int, has_old: bool, *,
                       block_v: int | None = None,
                       block_t: int = 128) -> Tuple[int, int]:
    """(dense steps, grid steps) of the segment scatter over ``rows`` rows.

    The dense count is ``chunks × row_tiles``: every row tile against every
    V chunk, the grid the unsorted rows would need. The grid run is the
    sorted visit list's static length ``row_tiles + chunks`` — a chunk's
    rows are contiguous once sorted, so only the tiles at chunk boundaries
    are visited twice, and an empty chunk once. ``_segment_scatter`` takes
    its grid from here. Pure host arithmetic.
    """
    vc, tb = segment_scatter_blocks(k, vocab_size, has_old,
                                    block_v=block_v, block_t=block_t)
    nt = -(-rows // min(tb, rows))
    nv = -(-vocab_size // vc)
    return nv * nt, nt + nv


def _scatter_visits(keys: jax.Array, nv: int, vc: int, tb: int, steps: int):
    """The (chunk, row-tile) visit list over word-sorted row keys.

    ``keys`` (R,) are the sorted word ids, with the sentinel ``nv·vc`` on
    inert rows so they sort last and meet no chunk. Chunk c's rows are the
    contiguous run ``[start_c, end_c)``; it is visited at the row tiles
    that run spans, or once (at the tile where it would start) when it is
    empty, so that its output block is zeroed and written. Chunks and
    tiles are both non-decreasing along the list. Returns (chunk (G,),
    tile (G,), live count (1,)) int32 with ``G = steps``, the grid length
    ``scatter_grid_steps`` gives (tiles + chunks); the entries past the
    live count repeat the last visit.
    """
    nt = keys.shape[0] // tb
    edges = jnp.arange(nv + 1, dtype=jnp.int32) * vc
    bounds = jnp.searchsorted(keys, edges, side="left").astype(jnp.int32)
    start, end = bounds[:-1], bounds[1:]
    first = jnp.minimum(start // tb, nt - 1)
    last = jnp.where(end > start, (end - 1) // tb, first)
    count = last - first + 1                          # ≥ 1 visit a chunk
    stop = jnp.cumsum(count)                          # inclusive ends
    step = jnp.arange(steps, dtype=jnp.int32)
    chunk = jnp.minimum(jnp.searchsorted(stop, step, side="right"),
                        nv - 1).astype(jnp.int32)
    tile = first[chunk] + step - (stop - count)[chunk]
    live = step < stop[-1]
    tile = jnp.where(live, tile, last[nv - 1])
    return chunk, tile.astype(jnp.int32), stop[-1:].astype(jnp.int32)


def _segment_scatter(ids: jax.Array, cnts: jax.Array, rows_w,
                     vocab_size: int, block_v: int | None, block_t: int,
                     interpret: bool):
    """Σ cnt·w over flat token rows into (V, K) masses, one per ``rows_w``.

    ids/cnts (R,), each of ``rows_w`` (R, K) (π_new, then π_old if any).
    The rows are sorted by word id (inert rows last) and the kernel walks
    the visit list of ``_scatter_visits``: a grid of ``row_tiles +
    chunks`` steps in place of ``chunks × row_tiles``.
    """
    has_old = len(rows_w) == 2
    k = rows_w[0].shape[1]
    r = ids.shape[0]
    vc, tb = segment_scatter_blocks(k, vocab_size, has_old,
                                    block_v=block_v, block_t=block_t)
    _, steps = scatter_grid_steps(r, k, vocab_size, has_old,
                                  block_v=block_v, block_t=block_t)
    tb = min(tb, r)
    rows_p = _round_up(r, tb)
    if rows_p != r:
        ids, cnts = (jnp.pad(x, (0, rows_p - r)) for x in (ids, cnts))
        rows_w = [jnp.pad(w, ((0, rows_p - r), (0, 0))) for w in rows_w]
    nt = rows_p // tb
    vp = _round_up(vocab_size, vc)
    # inert rows take the sentinel vp: they sort last and meet no chunk
    keys = jnp.where(cnts > 0, ids, vp).astype(jnp.int32)
    keys, order = jax.lax.sort(
        (keys, jnp.arange(rows_p, dtype=jnp.int32)), num_keys=1,
        is_stable=True)
    cnts = cnts[order]
    rows_w = [w[order] for w in rows_w]
    chunk, tile, nvis = _scatter_visits(keys, vp // vc, vc, tb, steps)
    # ids/counts ride as (nt, 1, tb): the squeezed row-tile axis makes each
    # (1, tb) block span the last two dims, as Mosaic's tiling rule needs
    row_spec = pl.BlockSpec((None, 1, tb), lambda i, c, t, n: (t[i], 0, 0))
    w_spec = pl.BlockSpec((tb, k), lambda i, c, t, n: (t[i], 0))
    acc_spec = pl.BlockSpec((vc, k), lambda i, c, t, n: (c[i], 0))
    n_out = len(rows_w)
    outs = pl.pallas_call(
        functools.partial(_segment_scatter_kernel, has_old),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(steps,),
            in_specs=[row_spec, row_spec] + [w_spec] * n_out,
            out_specs=[acc_spec] * n_out),
        out_shape=[jax.ShapeDtypeStruct((vp, k), jnp.float32)] * n_out,
        compiler_params=_PARAMS, interpret=interpret,
    )(chunk, tile, nvis, keys.reshape(nt, 1, tb), cnts.reshape(nt, 1, tb),
      *rows_w)
    return [o[:vocab_size] for o in outs]


def memo_delta(token_ids: jax.Array, counts: jax.Array, eb_tok: jax.Array,
               etheta: jax.Array, vocab_size: int,
               old_pi: jax.Array | None = None, *,
               quantize: bool = False, block_b: int = 32,
               block_l: int = 512, block_v: int | None = None,
               block_t: int = 128, interpret: bool | None = None):
    """Token-aligned π plus segment-summed new/old masses — two kernels.

    Shapes: token_ids/counts (B, L), eb_tok (B, L, K) = Eφ[token_ids],
    etheta (B, K). Returns (π (B, L, K), S_new (V, K)[, S_old (V, K)]):
    S_new = Σ cnt·π_new and S_old = Σ cnt·π_old accumulated at the token
    ids, so the IVI correction is ``S_new − S_old`` and the batch
    sufficient statistics are ``S_new``.

    Two ``pallas_call``s because the two outputs want opposite grid
    orders: π blocks pin the (B, L) axes as owners (each written once),
    while the (V, K) masses accumulate over ALL rows — which is only
    TPU-safe when each (block_v, K) block's revisits are grid-consecutive.
    The first kernel tiles (B, L) — the **L grid axis** that removes the
    old L ≤ ~4k VMEM cap — and emits π (quantized through the memo wire
    dtype when asked). The second flattens the rows and segment-sums them
    into (V, K) chunk by chunk: no dense (nb, V, K) one-hot partials
    exist anywhere. The rows (ids, counts, π_new, π_old) are first sorted
    by word id, zero-count rows last, and the scatter visits only the
    (chunk, row-tile) pairs whose ids meet — ``row_tiles + chunks`` grid
    steps in place of ``chunks × row_tiles`` (``scatter_grid_steps``). The
    returned π stays in document order; the masses differ from a scatter
    in document order only by the fp32 summation order within a word's
    rows. The retired partial formulation is kept as
    ``memo_delta_onehot`` (benchmark baseline).

    B must divide by the effective B-tile (pad upstream with zero-count
    rows); V and L are padded here (zero-count padding is inert).
    """
    b, l = token_ids.shape
    k = etheta.shape[1]
    has_old = old_pi is not None
    interpret = _default_interpret(interpret)

    # -- kernel 1: token-aligned π over the (B-tiles, L-tiles) grid -----
    bb, bl = pi_tile_shape(b, l, k, block_b=block_b, block_l=block_l)
    assert b % bb == 0, (b, bb)
    lp = _round_up(l, bl)

    def _pad_l(x):
        if lp == l:
            return x
        pad = ((0, 0), (0, lp - l)) + ((0, 0),) * (x.ndim - 2)
        return jnp.pad(x, pad)

    ids_p, cnts_p, ebt_p = _pad_l(token_ids), _pad_l(counts), _pad_l(eb_tok)
    nb, nl = b // bb, lp // bl
    pi_pad = pl.pallas_call(
        functools.partial(_token_pi_kernel, quantize),
        grid=(nb, nl),
        in_specs=[
            pl.BlockSpec((bb, bl), lambda i, li: (i, li)),
            pl.BlockSpec((bb, bl, k), lambda i, li: (i, li, 0)),
            pl.BlockSpec((bb, k), lambda i, li: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bb, bl, k), lambda i, li: (i, li, 0)),
        out_shape=jax.ShapeDtypeStruct((b, lp, k), jnp.float32),
        compiler_params=_PARAMS,
        interpret=interpret,
    )(cnts_p, ebt_p, etheta)

    # -- kernel 2: segment-sum scatter over the V chunks -----------------
    rows = b * lp
    rows_w = [pi_pad.reshape(rows, k)]
    if has_old:
        rows_w.append(_pad_l(old_pi).reshape(rows, k))
    masses = _segment_scatter(ids_p.reshape(rows), cnts_p.reshape(rows),
                              rows_w, vocab_size, block_v, block_t,
                              interpret)
    pi = pi_pad if lp == l else pi_pad[:, :l]
    return (pi, *masses)


# ---------------------------------------------------------------------------
# CSR ragged E-step: the γ fixed point over a FLAT token stream
# ---------------------------------------------------------------------------
#
# The padded fixed point streams a dense (B, V) count matrix; the CSR
# kernels stream only the live tokens. A batch is the flat triplet
# (counts (T,), segment ids (T,), Eφ token rows (T, K)) — doc boundaries
# are carried arithmetically by the segment ids, exactly the PR-4 scatter
# trick run in reverse: a (B, block_t) selector `iota == segs` is both the
# per-token Eθ gather (selᵀ·Eθ on the MXU) and the segment-reduced γ
# accumulator (sel·weights · Eφ_tok). One compiled kernel therefore serves
# every document-length distribution: no (B, W) padding, no width ladder.

def _csr_fixed_point_kernel(alpha0: float, tol: float, k_real: int,
                            b_real: int, num_t: int, num_j: int,
                            cnts_ref, segs_ref, ebtok_ref, g0_ref,
                            gamma_ref, et_ref, iters_ref,
                            gamma_s, et_s, acc_s, flags):
    t = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((t == 0) & (j == 0))
    def _start():
        gamma_s[...] = g0_ref[...]
        flags[0] = 0                                   # converged flag
        flags[1] = 0                                   # sweeps run

    live = flags[0] == 0

    @pl.when(live & (j == 0))
    def _sweep_start():
        et_s[...] = _exp_elog_theta(gamma_s[...], k_real)
        acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(live)
    def _accumulate():
        et = et_s[...]                                 # (Bp, K)
        ebt = ebtok_ref[...].astype(jnp.float32)       # (bT, K)
        segs = segs_ref[...]                           # (1, bT)
        cnts = cnts_ref[...].astype(jnp.float32)       # (1, bT)
        bp = et.shape[0]
        bt = ebt.shape[0]
        rows = jax.lax.broadcasted_iota(jnp.int32, (bp, bt), 0)
        sel = rows == segs                             # owner-doc selector
        # φnorm per token = the selected row of Eθ·Eφ_tokᵀ — computed for
        # every (doc, token) pair on the MXU and masked down, which keeps
        # the kernel gather-free (the trade for zero padding)
        p = jax.lax.dot_general(et, ebt, (((1,), (1,)), ((), ())),
                                precision=_F32,
                                preferred_element_type=jnp.float32)
        pnorm = jnp.where(sel, p, 0.0).sum(0, keepdims=True) + _EPS
        w = jnp.where(sel, cnts / pnorm, 0.0)          # (Bp, bT)
        acc_s[...] += jax.lax.dot(w, ebt,
                                  precision=_F32,
                                  preferred_element_type=jnp.float32)

    @pl.when(live & (j == num_j - 1))
    def _sweep_end():
        g_old = gamma_s[...]
        mask = jax.lax.broadcasted_iota(jnp.int32, g_old.shape, 1) < k_real
        g_new = jnp.where(mask, alpha0 + et_s[...] * acc_s[...], alpha0)
        # token-free rows (doc padding) hold γ = α₀ exactly; mask them out
        # of the convergence mean like the fused kernel masks padded rows
        delta = jnp.abs(g_new - g_old).sum() / (b_real * k_real)
        gamma_s[...] = g_new
        flags[1] += 1
        flags[0] = jnp.where(delta <= tol, 1, 0).astype(jnp.int32)

    @pl.when((t == num_t - 1) & (j == num_j - 1))
    def _finish():
        g = gamma_s[...]
        gamma_ref[...] = g
        et_ref[...] = _exp_elog_theta(g, k_real)
        iters_ref[...] = jnp.full(iters_ref.shape, flags[1], jnp.int32)


def estep_fixed_point_csr(cnts: jax.Array, segs: jax.Array,
                          eb_tok: jax.Array, gamma0: jax.Array,
                          alpha0: float, tol: float, max_iters: int,
                          k_real: int, b_real: int | None = None, *,
                          block_t: int = 512,
                          interpret: bool | None = None):
    """The whole CSR γ fixed point as ONE pallas_call.

    Shapes: cnts/segs (T,) flat token stream, eb_tok (T, K) = Eφ gathered
    at the flat token ids, gamma0 (B, K) → (γ (B, K), Eθ (B, K), sweep
    count (1, 1) int32). γ/Eθ and the sweep accumulator stay resident in
    VMEM for the whole batch (no B tiling — a CSR batch's doc count is
    bounded by ``batch_size``); the token axis is the inner grid axis, so
    eb_tok streams HBM→VMEM once per sweep, or exactly once when the
    wrapper promotes ``block_t`` to the whole (budget-sized) stream.
    K is pre-padded to a lane multiple by the wrapper; T is padded here
    (zero-count tail tokens are inert in every reduction); padding tokens
    must carry segment 0 and count 0. eb_tok may be bf16 (fp32 accum).
    """
    b, k = gamma0.shape
    t = cnts.shape[0]
    b_real = b if b_real is None else b_real
    interpret = _default_interpret(interpret)
    block_t = min(block_t, _round_up(t, 128))
    tp = _round_up(t, block_t)
    if tp != t:
        cnts = jnp.pad(cnts, (0, tp - t))
        segs = jnp.pad(segs, (0, tp - t))
        eb_tok = jnp.pad(eb_tok, ((0, tp - t), (0, 0)))
    nj = tp // block_t
    cnts2 = cnts.reshape(nj, 1, block_t)
    segs2 = segs.reshape(nj, 1, block_t)
    grid = (max(int(max_iters), 1), nj)
    gamma, et, iters = pl.pallas_call(
        functools.partial(_csr_fixed_point_kernel, alpha0, tol, k_real,
                          b_real, grid[0], nj),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, 1, block_t), lambda t, j: (j, 0, 0)),
            pl.BlockSpec((None, 1, block_t), lambda t, j: (j, 0, 0)),
            pl.BlockSpec((block_t, k), lambda t, j: (j, 0)),
            pl.BlockSpec((b, k), lambda t, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((b, k), lambda t, j: (0, 0)),
            pl.BlockSpec((b, k), lambda t, j: (0, 0)),
            pl.BlockSpec((1, 1), lambda t, j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, k), jnp.float32),
            jax.ShapeDtypeStruct((b, k), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((b, k), jnp.float32),
            pltpu.VMEM((b, k), jnp.float32),
            pltpu.VMEM((b, k), jnp.float32),
            pltpu.SMEM((2,), jnp.int32),
        ],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(cnts2, segs2, eb_tok, gamma0)
    return gamma, et, iters


def _csr_token_pi_kernel(quantize: bool, cnts_ref, segs_ref, ebtok_ref,
                         et_ref, pi_ref):
    """π = Eθ[seg]⊙Eφ_tok/φnorm for one flat token tile, gather-free.

    The per-token Eθ gather is the selector matmul selᵀ·Eθ folded into the
    count/φnorm weighting, so the whole tile is two MXU matmuls.
    """
    et = et_ref[...]                                   # (Bp, K)
    ebt = ebtok_ref[...].astype(jnp.float32)           # (bT, K)
    segs = segs_ref[...]                               # (1, bT)
    cnts = cnts_ref[...]                               # (1, bT)
    bp = et.shape[0]
    bt = ebt.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (bp, bt), 0)
    sel = rows == segs
    p = jax.lax.dot_general(et, ebt, (((1,), (1,)), ((), ())),
                            precision=_F32, preferred_element_type=jnp.float32)
    pnorm = jnp.where(sel, p, 0.0).sum(0, keepdims=True) + _EPS
    selw = jnp.where(sel & (cnts > 0), 1.0 / pnorm, 0.0)
    pi = jax.lax.dot_general(selw, et, (((0,), (0,)), ((), ())),
                             precision=_F32,
                             preferred_element_type=jnp.float32) * ebt
    if quantize:
        # round through the memo wire dtype BEFORE the scatter, so ⟨m_vk⟩
        # adds exactly what the store will later subtract
        pi = pi.astype(jnp.bfloat16).astype(jnp.float32)
    pi_ref[...] = pi


def memo_delta_csr(token_ids: jax.Array, counts: jax.Array,
                   segs: jax.Array, eb_tok: jax.Array, etheta: jax.Array,
                   vocab_size: int, old_pi: jax.Array | None = None, *,
                   quantize: bool = False, block_t_pi: int = 512,
                   block_v: int | None = None, block_t: int = 128,
                   interpret: bool | None = None):
    """Flat-token π plus segment-summed new/old masses — two kernels.

    The CSR twin of ``memo_delta``: token_ids/counts/segs are the flat
    (T,) stream, eb_tok (T, K) the Eφ token gather, old_pi the memoized π
    in the SAME flat layout. Returns (π (T, K), S_new (V, K)[, S_old]).
    The scatter is the same ``_segment_scatter_kernel``, sorted visit list
    included — it always operated on flattened token rows, so the CSR
    layout is its native input and the (B, L) reshape simply disappears.
    """
    t = token_ids.shape[0]
    k = etheta.shape[1]
    has_old = old_pi is not None
    interpret = _default_interpret(interpret)

    # -- kernel 1: token-aligned π over the flat token grid -------------
    bt = _csr_pi_tile(t, block_t_pi)
    tp = _round_up(t, bt)

    def _pad_t(x):
        if tp == t:
            return x
        pad = ((0, tp - t),) + ((0, 0),) * (x.ndim - 1)
        return jnp.pad(x, pad)

    ids_p, cnts_p = _pad_t(token_ids), _pad_t(counts)
    segs_p, ebt_p = _pad_t(segs), _pad_t(eb_tok)
    nj = tp // bt
    pi_pad = pl.pallas_call(
        functools.partial(_csr_token_pi_kernel, quantize),
        grid=(nj,),
        in_specs=[
            pl.BlockSpec((None, 1, bt), lambda j: (j, 0, 0)),
            pl.BlockSpec((None, 1, bt), lambda j: (j, 0, 0)),
            pl.BlockSpec((bt, k), lambda j: (j, 0)),
            pl.BlockSpec(etheta.shape, lambda j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bt, k), lambda j: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((tp, k), jnp.float32),
        compiler_params=_PARAMS,
        interpret=interpret,
    )(cnts_p.reshape(nj, 1, bt), segs_p.reshape(nj, 1, bt), ebt_p, etheta)

    # -- kernel 2: the SAME segment-sum scatter as the padded path ------
    rows_w = [pi_pad] + ([_pad_t(old_pi)] if has_old else [])
    masses = _segment_scatter(ids_p, cnts_p, rows_w, vocab_size, block_v,
                              block_t, interpret)
    pi = pi_pad if tp == t else pi_pad[:t]
    return (pi, *masses)


# ---------------------------------------------------------------------------
# legacy one-hot memo-correction kernel (benchmark baseline)
# ---------------------------------------------------------------------------

def _memo_delta_onehot_kernel(block_v: int, has_old: bool, quantize: bool,
                              *refs):
    if has_old:
        (ids_ref, cnts_ref, ebtok_ref, oldpi_ref, et_ref,
         pi_ref, snew_ref, sold_ref) = refs
    else:
        ids_ref, cnts_ref, ebtok_ref, et_ref, pi_ref, snew_ref = refs
        oldpi_ref = sold_ref = None
    j = pl.program_id(1)
    cnts = cnts_ref[...]                               # (bB, L)

    @pl.when(j == 0)
    def _pi():
        et = et_ref[...]                               # (bB, K)
        ebt = ebtok_ref[...]                           # (bB, L, K)
        p = (et[:, None, :] * ebt).sum(-1) + _EPS      # (bB, L)
        pi = et[:, None, :] * ebt / p[:, :, None]
        pi = jnp.where(cnts[:, :, None] > 0, pi, 0.0)
        if quantize:
            # round through the memo store's wire dtype BEFORE scattering,
            # so ⟨m_vk⟩ adds exactly what the store will later subtract
            pi = pi.astype(jnp.bfloat16).astype(jnp.float32)
        pi_ref[...] = pi

    bb, ll, kk = pi_ref.shape
    ids_flat = ids_ref[...].reshape(1, bb * ll)
    rows = j * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (block_v, bb * ll), 0)
    onehot = (rows == ids_flat).astype(jnp.float32)    # (bV, bB·L)

    # Each (nb, V-tile) partial block is visited exactly once, so a plain
    # write is safe on TPU — accumulating (V, K) blocks across B-tiles is
    # not, because the B axis is the OUTER grid axis here (π pins it) and
    # Pallas only defines revisited output blocks for consecutive revisits.
    w_new = (cnts[:, :, None] * pi_ref[...]).reshape(bb * ll, kk)
    snew_ref[...] = jax.lax.dot(onehot, w_new,
                                preferred_element_type=jnp.float32)[None]

    if has_old:
        w_old = (cnts[:, :, None] * oldpi_ref[...]).reshape(bb * ll, kk)
        sold_ref[...] = jax.lax.dot(onehot, w_old,
                                    preferred_element_type=jnp.float32)[None]


# VMEM budget for one one-hot memo_delta grid step (≈4 (block_b, L, K) fp32
# cubes plus the (block_v, block_b·L) one-hot), kept at a sixth of the
# 48 MiB scoped limit to leave room for double buffering. The wrapper
# halves block_b until the step fits; the L axis is NOT tiled here, which
# is the L ≤ ~4k cap the segment-sum path removes.
_DELTA_VMEM_BUDGET = 8 * 1024 * 1024


def delta_effective_block_b(b: int, l: int, k: int, *, block_b: int = 32,
                            block_v: int = 128, has_old: bool = True) -> int:
    """The B-tile ``memo_delta_onehot`` actually runs after the VMEM guard.

    Larger B-tiles mean fewer (nb, V, K) partial blocks to spill and
    reduce, so the default starts at 32 and is halved until the per-step
    working set fits ``_DELTA_VMEM_BUDGET`` (e.g. L=128, K=128 lands on
    16; L=512 on 4). Exposed so the BENCH_estep HBM model can count the
    same grid the kernel uses.
    """
    block_b = min(block_b, b)
    ncubes = 4 if has_old else 3

    def _step_bytes(bb):
        return (ncubes * bb * l * k + block_v * bb * l) * 4

    while block_b > 1 and _step_bytes(block_b) > _DELTA_VMEM_BUDGET:
        nxt = block_b // 2
        block_b = nxt if b % nxt == 0 else 1   # keep the grid exact
    return block_b


def memo_delta_onehot(token_ids: jax.Array, counts: jax.Array,
                      eb_tok: jax.Array, etheta: jax.Array, vocab_size: int,
                      old_pi: jax.Array | None = None, *,
                      quantize: bool = False, block_b: int = 32,
                      block_v: int = 128, interpret: bool | None = None):
    """RETIRED production path, kept as the benchmark baseline.

    Same contract as ``memo_delta``, via the dense one-hot formulation: one
    kernel forms π and scatters cnt·π_new / cnt·π_old with a one-hot MXU
    matmul into per-B-tile (nb, V, K) partials (each output block written
    exactly once — the TPU-safe revisit discipline), reduced over nb in
    jnp here. Those partials are the cost the segment-sum path removes:
    ~2·nb·V·K fp32 of transient HBM per batch (~2.5 GB at Arxiv V=142k),
    and with the L axis untiled the VMEM guard caps L at ~4k (K=128).

    B must divide by ``block_b`` (pad upstream; ``block_b`` is halved
    automatically until the VMEM step budget holds, see
    ``_DELTA_VMEM_BUDGET``); V is padded here (ids are always < V so the
    padded rows are zero and stripped).
    """
    b, l = token_ids.shape
    k = etheta.shape[1]
    has_old = old_pi is not None
    block_b = delta_effective_block_b(b, l, k, block_b=block_b,
                                      block_v=block_v, has_old=has_old)
    assert b % block_b == 0, (b, block_b)
    interpret = _default_interpret(interpret)
    vp = ((vocab_size + block_v - 1) // block_v) * block_v
    nb, nv = b // block_b, vp // block_v

    row_spec = pl.BlockSpec((block_b, l), lambda i, j: (i, 0))
    cube_spec = pl.BlockSpec((block_b, l, k), lambda i, j: (i, 0, 0))
    part_spec = pl.BlockSpec((1, block_v, k), lambda i, j: (i, j, 0))
    in_specs = [row_spec, row_spec, cube_spec]
    inputs = [token_ids, counts, eb_tok]
    if has_old:
        in_specs.append(cube_spec)
        inputs.append(old_pi)
    in_specs.append(pl.BlockSpec((block_b, k), lambda i, j: (i, 0)))
    inputs.append(etheta)
    out_specs = [cube_spec, part_spec]
    out_shape = [jax.ShapeDtypeStruct((b, l, k), jnp.float32),
                 jax.ShapeDtypeStruct((nb, vp, k), jnp.float32)]
    if has_old:
        out_specs.append(part_spec)
        out_shape.append(jax.ShapeDtypeStruct((nb, vp, k), jnp.float32))

    outs = pl.pallas_call(
        functools.partial(_memo_delta_onehot_kernel, block_v, has_old,
                          quantize),
        grid=(nb, nv),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*inputs)
    pi, snew = outs[0], outs[1].sum(0)[:vocab_size]
    if has_old:
        return pi, snew, outs[2].sum(0)[:vocab_size]
    return pi, snew


# ---------------------------------------------------------------------------
# legacy γ-sweep kernel (one pallas_call per sweep)
# ---------------------------------------------------------------------------

def _sweep_kernel(alpha0: float, num_v_tiles: int,
                  c_ref, et_ref, eb_ref, out_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    et = et_ref[...]                                       # (bB, K)
    eb = eb_ref[...]                                       # (bV, K)
    p = jax.lax.dot_general(et, eb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) + _EPS
    r = c_ref[...] / p                                     # (bB, bV)
    out_ref[...] += jax.lax.dot(r, eb,
                                preferred_element_type=jnp.float32)

    @pl.when(j == num_v_tiles - 1)
    def _fin():
        out_ref[...] = alpha0 + et * out_ref[...]


def estep_sweep(c: jax.Array, etheta: jax.Array, eb: jax.Array,
                alpha0: float, *, block_b: int = 128, block_v: int = 512,
                interpret: bool | None = None) -> jax.Array:
    """One fixed-point sweep γ' = α₀ + Eθ ⊙ ((C ⊘ Eθ·Eφᵀ)·Eφ).

    Shapes: c (B, V), etheta (B, K), eb (V, K) → (B, K).
    B, V, K must already be padded to the block grid (see ops.py).
    """
    b, v = c.shape
    k = etheta.shape[1]
    block_b, block_v = min(block_b, b), min(block_v, v)
    assert b % block_b == 0 and v % block_v == 0, (b, v, block_b, block_v)
    interpret = _default_interpret(interpret)
    grid = (b // block_b, v // block_v)
    return pl.pallas_call(
        functools.partial(_sweep_kernel, alpha0, grid[1]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_v), lambda i, j: (i, j)),
            pl.BlockSpec((block_b, k), lambda i, j: (i, 0)),
            pl.BlockSpec((block_v, k), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, k), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, k), jnp.float32),
        interpret=interpret,
    )(c, etheta, eb)


# ---------------------------------------------------------------------------
# sufficient-statistics kernel
# ---------------------------------------------------------------------------

def _sstats_kernel(num_b_tiles: int, c_ref, et_ref, eb_ref, out_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    et = et_ref[...]                                       # (bB, K)
    eb = eb_ref[...]                                       # (bV, K)
    p = jax.lax.dot_general(et, eb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) + _EPS
    r = c_ref[...] / p                                     # (bB, bV)
    out_ref[...] += jax.lax.dot_general(
        r, et, (((0,), (0,)), ((), ())),                   # Rᵀ·Eθ → (bV, K)
        preferred_element_type=jnp.float32)

    @pl.when(j == num_b_tiles - 1)
    def _fin():
        out_ref[...] *= eb


def sstats(c: jax.Array, etheta: jax.Array, eb: jax.Array, *,
           block_b: int = 128, block_v: int = 512,
           interpret: bool | None = None) -> jax.Array:
    """Expected topic-word counts S = Eφ ⊙ (Rᵀ·Eθ) → (V, K)."""
    b, v = c.shape
    k = etheta.shape[1]
    block_b, block_v = min(block_b, b), min(block_v, v)
    assert b % block_b == 0 and v % block_v == 0, (b, v, block_b, block_v)
    interpret = _default_interpret(interpret)
    grid = (v // block_v, b // block_b)                    # B-axis innermost
    return pl.pallas_call(
        functools.partial(_sstats_kernel, grid[1]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_v), lambda i, j: (j, i)),
            pl.BlockSpec((block_b, k), lambda i, j: (j, 0)),
            pl.BlockSpec((block_v, k), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_v, k), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((v, k), jnp.float32),
        interpret=interpret,
    )(c, etheta, eb)
