"""Pure-jnp oracle for the flash-attention kernel.

The LDA kernels' references live with the E-step backends:
``repro.core.estep.estep_gather`` for the padded layout and
``repro.core.estep.estep_csr_ref`` for the flat CSR layout.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def mha_ref(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = True,
            scale: float | None = None) -> jax.Array:
    """Oracle for the flash-attention kernel. q,k,v: (BH, S, hd)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqd,bkd->bqk", q, k).astype(jnp.float32) * scale
    if causal:
        n = q.shape[1]
        mask = jnp.tril(jnp.ones((n, n), bool))
        s = jnp.where(mask, s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", w.astype(v.dtype), v)
