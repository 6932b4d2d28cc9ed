"""Operations and HBM bytes that LDA's kernels and training step need.

Counted from shapes and sweep counts, never measured. Each function gives
(flops, bytes) for one call; ``roofline_s`` turns them into the least time a
chip could take. Counts are of *needed* work, so that a kernel that streams
blocks after its fixed point has converged, or recomputes what it could
keep, reads below 100%:

* a sweep of the padded fixed point (``kernels/lda_estep.py``
  ``estep_fixed_point``) is two (rows × V) × (V × K) products on the dense
  counts C: p = E[θ]·E[φ]ᵀ, then (C/p)·E[φ], 2·rows·V·K flops each, plus the
  rows·V divisions; it reads C's rows and all of E[φ] once, since neither
  fits on-chip memory at a real vocabulary (the byte model of
  ``benchmarks/kernel_bench.py`` ``modeled_estep_hbm_bytes``, with the
  sweeps each B-tile really ran in place of a shared count);
* the token-π kernel forms π = E[θ]⊙E[φ_w]/φnorm per live token (3K flops),
  reading E[φ_w] and writing π; the scatter adds cnt·π and cnt·π_old into
  (V, K) (2K each), reading both π rows and writing the two (V, K) masses;
* the global update (``core/engines.py`` ``_incremental_core``) reads λ and
  writes E[φ] = exp(ψ(λ) − ψ(Σ_v λ)) (about 25 flops an entry for ψ and
  exp), adds the correction into ⟨m⟩ and rewrites λ: eight (V, K) fp32
  passes (λ, E[φ], S_new, S_old, ⟨m⟩ read and written, λ written).
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

F32 = 4                    # bytes of an fp32 value
PSI_EXP_FLOPS = 25         # ψ by recurrence + asymptotic series, and exp
GLOBAL_PASSES = 8          # (V, K) fp32 passes of the global update


def padded_fixed_point(rows: int, v: int, k: int, sweeps: Sequence[int],
                       tile_rows: int) -> Tuple[float, float]:
    """The padded fixed point over ``rows`` documents in tiles of
    ``tile_rows``; ``sweeps[i]`` is what tile i ran."""
    flops = byts = 0.0
    for i, s in enumerate(sweeps):
        r = min(tile_rows, rows - i * tile_rows)
        if r <= 0:
            continue
        flops += s * (4.0 * r * v * k + r * v)
        byts += s * (r * v + v * k) * F32
    return flops, byts


def memo_delta(tokens: int, v: int, k: int) -> Tuple[float, float]:
    """Token π plus the new/old scatter into (V, K)."""
    flops = 3.0 * tokens * k + 4.0 * tokens * k
    byts = (2 * tokens * k + 2 * tokens * k + 2 * v * k) * F32
    return flops, byts


def global_update(v: int, k: int) -> Tuple[float, float]:
    return (PSI_EXP_FLOPS + 4.0) * v * k, GLOBAL_PASSES * v * k * F32


def roofline_s(flops: float, byts: float, peaks: Dict) -> Tuple[float, str]:
    """The least time for the work, and which bound binds."""
    tf = flops / peaks["bf16_flops_per_s"]
    tb = byts / peaks["hbm_bytes_per_s"]
    return (tf, "flops") if tf >= tb else (tb, "bytes")


def estep_kernels(step: Dict, shape: Dict) -> Tuple[float, float]:
    """(flops, bytes) of one step's padded E-step kernels (fixed point +
    π + scatter)."""
    v, k = shape["V"], shape["K"]
    f1, b1 = padded_fixed_point(step["docs"], v, k, step["sweeps"],
                                shape["block_b"])
    f2, b2 = memo_delta(step["live_slots"], v, k)
    return f1 + f2, b1 + b2


def train_step(step: Dict, shape: Dict) -> Tuple[float, float]:
    """(flops, bytes) one chip needs for one training step: the E-step over
    the live tokens, needed as the flat formulation needs it, plus the
    global update."""
    v, k = shape["V"], shape["K"]
    tokens = step["live_slots"]
    sweeps = max(step["sweeps"])
    # fixed point, then π and the new/old scatter
    flops = sweeps * 4.0 * tokens * k + 7.0 * tokens * k
    # E[φ] token rows and π_old read, π written; the two (V, K) masses
    byts = 3 * tokens * k * F32 + 2 * v * k * F32
    f, b = global_update(v, k)
    return flops + f, byts + b
