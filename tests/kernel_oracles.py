"""Plain jnp references for the production Pallas kernels, shared by the
kernel test files."""
import jax
import jax.numpy as jnp

from repro.core.estep import scatter_sstats
from repro.kernels import lda_estep


def fixed_point_per_tile(c, eb, g0, alpha0, tol, max_iters, k_real,
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


def memo_delta_ref(ids, cnts, ebt, et, v, old_pi=None):
    """π = Eθ⊙Eφ_tok/φnorm on the live tokens and the masses Σ cnt·π (and
    Σ cnt·π_old) scattered to (V, K) by ``scatter_sstats``."""
    p = (et[:, None, :] * ebt).sum(-1) + 1e-30
    pi = jnp.where(cnts[:, :, None] > 0,
                   et[:, None, :] * ebt / p[:, :, None], 0.0)
    out = (pi, scatter_sstats(ids, cnts[:, :, None] * pi, v))
    if old_pi is not None:
        out += (scatter_sstats(ids, cnts[:, :, None] * old_pi, v),)
    return out
