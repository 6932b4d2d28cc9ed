"""Policy-aware structural HBM cost model for the E-step kernels.

A block is re-fetched only when its index-map output changes between
consecutive grid steps, and jnp intermediates cost one write + one read;
every :class:`KernelPolicy` knob the search can move is priced:

* ``block_b`` — the fused fixed point re-streams Eφ once per B-tile per
  sweep in the non-resident regime, so fewer B-tiles mean fewer Eφ bytes;
* ``block_v`` — only matters through whole-V residency promotion, which
  is applied here via ``ops.effective_fixed_point_blocks`` (the
  satellite fix: the model prices the tile that actually runs);
* ``delta_block_b`` / ``pi_block_l`` — row/L padding of the (B, L, K)
  π cubes the memo pair streams;
* ``delta_block_v`` — the scatter's V-chunk count: token rows are
  re-streamed once per chunk;
* ``scatter_block_t`` — enters through the chunk-size VMEM policy
  (``segment_scatter_blocks``) and row-tile padding;
* ``wire_dtype`` — a bf16 memo wire halves the π/old_pi stream bytes of
  the scatter;
* ``block_t`` — CSR token-cube residency (``csr_effective_block_t``).

This is the *fallback* objective (tagged ``proxy_regime=True``) when no
real accelerator is present to time; on a TPU the search times the real
``pallas_call`` executions instead. Modeled seconds divide bytes by the
``repro.obs.roofline`` HW table's HBM bandwidth.
"""
from __future__ import annotations

from typing import Optional

from repro.core.types import DEFAULT_KERNEL_POLICY, KernelPolicy


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _wire_bytes(policy: KernelPolicy) -> int:
    return 2 if policy.wire_dtype == "bfloat16" else 4


def modeled_fused_hbm_bytes(b: int, v: int, k: int, l: int, iters: int, *,
                            policy: Optional[KernelPolicy] = None,
                            stream_bytes: int = 4) -> int:
    """HBM bytes of one padded E-step + memo correction under ``policy``."""
    from repro.kernels import lda_estep, ops

    pol = policy or DEFAULT_KERNEL_POLICY
    block_b, block_v, _ = ops.effective_fixed_point_blocks(
        b, v, k, block_b=pol.block_b, block_v=pol.block_v,
        stream_bytes=stream_bytes)
    nb = -(-b // block_b)
    nv = -(-_round_up(v, 128) // block_v)
    bk = b * k * 4
    if nv == 1:
        c_elems, eb_elems = b * v, v * k              # fetched once
    else:
        c_elems = iters * b * v                       # re-streamed per sweep
        eb_elems = iters * nb * v * k
    fixed_point = (c_elems + eb_elems) * stream_bytes + 3 * bk

    bp = _round_up(b, pol.delta_block_b)              # padded B (ops wrapper)
    _, bl = lda_estep.pi_tile_shape(bp, l, k, block_b=pol.delta_block_b,
                                    block_l=pol.pi_block_l)
    lp = _round_up(l, bl)                             # padded token axis
    cube = bp * lp * k * 4
    wire = _wire_bytes(pol)
    pi_rows = bp * lp * (k * wire)                    # π / old_pi wire rows
    vc, _ = lda_estep.segment_scatter_blocks(
        k, v, True, block_v=pol.delta_block_v, block_t=pol.scatter_block_t)
    nvc = -(-v // vc)
    delta = (2 * bp * lp * 4 + 2 * cube + bk          # token-π kernel
             + nvc * (2 * pi_rows + 2 * bp * lp * 4)  # per-chunk re-streams
             + 2 * v * k * 4)                         # S_new/S_old out
    return fixed_point + delta


def modeled_csr_hbm_bytes(t: int, b: int, v: int, k: int, iters: int, *,
                          policy: Optional[KernelPolicy] = None,
                          stream_bytes: int = 4) -> int:
    """HBM bytes of one CSR flat-token E-step + memo correction."""
    from repro.kernels import lda_estep, ops

    pol = policy or DEFAULT_KERNEL_POLICY
    kp = _round_up(k, 128)
    bp = _round_up(b, 8)
    bt = ops.csr_effective_block_t(t, k, stream_bytes, pol.block_t)
    tp = _round_up(t, bt)
    resident = tp == bt                               # one (T, Kp) tile
    bk = bp * k * 4
    gather = v * k * 4 + tp * 4 + tp * kp * stream_bytes
    tok_fetch = tp * (4 + 4) + tp * kp * stream_bytes
    fixed_point = (1 if resident else iters) * tok_fetch + 3 * bp * kp * 4
    wire = _wire_bytes(pol)
    vc, _ = lda_estep.segment_scatter_blocks(
        k, v, True, block_v=pol.delta_block_v, block_t=pol.scatter_block_t)
    nvc = -(-v // vc)
    delta = (tp * (4 + 4) + tp * k * stream_bytes + bk + tp * k * 4
             + nvc * (tp * (4 + 4) + 2 * tp * k * wire)  # per-chunk re-streams
             + 2 * v * k * 4)                            # S_new/S_old out
    return gather + fixed_point + delta


def modeled_cost_seconds(task: str, *, policy: Optional[KernelPolicy],
                         b_or_t: int, v: int, k: int, w: Optional[int],
                         iters: int, stream_bytes: int = 4,
                         num_docs: Optional[int] = None) -> float:
    """Modeled wall seconds of one E-step: HBM bytes / roofline HBM BW.

    ``task`` is ``"padded"`` (``b_or_t`` = batch, ``w`` = token width) or
    ``"csr"`` (``b_or_t`` = token budget T, ``num_docs`` = doc rows).
    """
    from repro.obs.roofline import HW

    if task == "padded":
        if w is None:
            raise ValueError("padded task needs a token width w")
        bytes_ = modeled_fused_hbm_bytes(b_or_t, v, k, w, iters,
                                         policy=policy,
                                         stream_bytes=stream_bytes)
    elif task == "csr":
        bytes_ = modeled_csr_hbm_bytes(b_or_t, num_docs or 64, v, k, iters,
                                       policy=policy,
                                       stream_bytes=stream_bytes)
    else:
        raise ValueError(f"unknown tune task {task!r}")
    return bytes_ / HW["hbm_bw"]
