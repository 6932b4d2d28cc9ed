"""Plain reference of LDA's incremental variational inference and serving.

Written from the paper (arXiv:1507.05016, Algorithm 1 and eqs. 4-5) and the
configuration files beside this module; it imports nothing of the program.
Everything is flat-token jnp in float32: a batch is a stream of (word id,
count, owning row) triples, and the per-document sums are products with the
one-hot (row, token) selector taken at ``Precision.HIGHEST`` (full fp32 on
a TPU, whose default f32 matmul takes one bf16 pass).

Semantics that the comparison depends on, each stated by the configuration
or by the deployment's documented behaviour:

* the E-step fixed point γ ← α₀ + E[θ]·Σ_w cnt·E[φ_w]/φnorm runs until the
  mean |Δγ| of a *tile* of rows falls to ``estep_tol`` or ``estep_max_iters``
  sweeps have run. ``tile_rows`` rows form a tile; a tile's mean divides by
  its real rows × K (``denominator="real"``) or by all its rows × K
  (``denominator="all"``, the flat layout counts the padded document slots
  of its fixed-capacity batch). A stopped tile keeps its γ;
* π = E[θ]⊙E[φ_w]/φnorm at the final γ, rounded through the memo's wire
  dtype before it is added to the accumulator (the bf16 memo);
* IVI (eq. 4): ⟨m⟩ += Σ cnt·(π_new − π_old), λ = β₀ + ⟨m⟩ + f·init_mass,
  where f is the share of the random initial mass still live: each
  document's share of it retires on the first visit, and f snaps to 0
  below 1e-6;
* λ₀ = Gamma(100, 0.01) draws from ``jax.random.key(seed)``, the
  onlineldavb initialisation that Algorithm 1 line 1 leaves open.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import digamma

EPS = 1e-30
HI = jax.lax.Precision.HIGHEST


def exp_elog(a: jax.Array, axis: int) -> jax.Array:
    """exp(E[ln x]) of Dirichlet parameters ``a`` along ``axis``."""
    return jnp.exp(digamma(a) - digamma(a.sum(axis=axis, keepdims=True)))


def init_state(cfg: Dict, seed: int) -> Dict[str, jax.Array]:
    lam = jax.random.gamma(jax.random.key(seed), 100.0,
                           (cfg["vocab_size"], cfg["num_topics"])) * 0.01
    return {"lam": lam, "m": jnp.zeros_like(lam),
            "init_mass": lam - cfg["beta0"],
            "frac": jnp.ones((), jnp.float32)}


def selector(segs, rows: int):
    """(rows, T) one-hot: entry (b, t) is 1 where token t belongs to row b."""
    return (jnp.arange(rows)[:, None] == segs[None, :]).astype(jnp.float32)


def row_sum(sel, x):
    """Σ over each row's tokens: (T, K) → (B, K)."""
    return jnp.dot(sel, x, precision=HI)


def at_tokens(sel, x):
    """Each token's row of ``x``: (B, K) → (T, K)."""
    return jnp.dot(sel.T, x, precision=HI)


def fixed_point(eb_tok, cnts, sel, gamma0, tile_of_row, denom, *, alpha0,
                tol, max_iters, num_tiles):
    """γ (B, K) and the sweeps each tile ran, for a flat token batch.

    eb_tok (T, K) = E[φ] at the tokens' word ids, cnts (T,), sel the (B, T)
    selector, gamma0 (B, K), tile_of_row (B,), denom (num_tiles,) the
    divisor of each tile's summed |Δγ|."""

    def sweep(gamma):
        et = exp_elog(gamma, axis=1)
        p = (at_tokens(sel, et) * eb_tok).sum(-1) + EPS
        return alpha0 + et * row_sum(sel, (cnts / p)[:, None] * eb_tok)

    def cond(carry):
        _, live, it, _ = carry
        return live.any() & (it < max_iters)

    def body(carry):
        gamma, live, it, sweeps = carry
        new = sweep(gamma)
        delta = jax.ops.segment_sum(jnp.abs(new - gamma).sum(1), tile_of_row,
                                    num_segments=num_tiles) / denom
        gamma = jnp.where(live[tile_of_row][:, None], new, gamma)
        sweeps = sweeps + live.astype(jnp.int32)
        return gamma, live & (delta > tol), it + 1, sweeps

    init = (gamma0, jnp.ones((num_tiles,), bool), jnp.zeros((), jnp.int32),
            jnp.zeros((num_tiles,), jnp.int32))
    gamma, _, _, sweeps = jax.lax.while_loop(cond, body, init)
    return gamma, sweeps


def token_pi(gamma, eb_tok, cnts, sel):
    et = at_tokens(sel, exp_elog(gamma, axis=1))
    p = (et * eb_tok).sum(-1, keepdims=True) + EPS
    return jnp.where(cnts[:, None] > 0, et * eb_tok / p, 0.0)


def tiles(n_real, batch_rows: int, tile_rows: int, denominator: str,
          num_topics: int):
    """Row → tile map and each tile's divisor (see module docstring)."""
    num_tiles = -(-batch_rows // tile_rows)
    tile_of_row = jnp.arange(batch_rows) // tile_rows
    if denominator == "real":
        rows = jnp.clip(n_real - jnp.arange(num_tiles) * tile_rows, 1,
                        tile_rows)
    else:
        rows = jnp.full((num_tiles,), batch_rows)
    return tile_of_row, (rows * num_topics).astype(jnp.float32), num_tiles


@partial(jax.jit, static_argnames=("cfg_items", "batch_rows", "tile_rows",
                                   "denominator", "wire_bf16"),
         donate_argnames=("memo",))
def ivi_step(state, memo, visited, ids, cnts, segs, slot, n_real,
             num_words_total, *, cfg_items, batch_rows, tile_rows,
             denominator, wire_bf16):
    """One IVI step on one batch.

    memo (N + 1, K) holds π of every document's token slots, row N a zero
    sentinel; ``slot`` (T,) maps each token of the batch to its memo row
    (padding tokens to N, count 0, row ``batch_rows - 1``); visited (B,)
    flags the batch rows whose document was seen before. Returns the new state, memo,
    the batch's π (T, K) and γ (B, K)."""
    cfg = dict(cfg_items)
    k = cfg["num_topics"]
    eb = exp_elog(state["lam"], axis=0)
    eb_tok = eb[ids]
    old_pi = memo[slot]
    seen = visited                                     # (B,) per batch row
    sel = selector(segs, batch_rows)
    gamma_memo = cfg["alpha0"] + row_sum(sel, cnts[:, None] * old_pi)
    live_row = jnp.arange(batch_rows) < n_real
    gamma0 = jnp.where(seen[:, None], gamma_memo, cfg["alpha0"] + 1.0)
    gamma0 = jnp.where(live_row[:, None], gamma0, cfg["alpha0"])
    tile_of_row, denom, num_tiles = tiles(n_real, batch_rows, tile_rows,
                                          denominator, k)
    gamma, sweeps = fixed_point(
        eb_tok, cnts, sel, gamma0, tile_of_row, denom, alpha0=cfg["alpha0"],
        tol=cfg["estep_tol"], max_iters=cfg["estep_max_iters"],
        num_tiles=num_tiles)
    pi = token_pi(gamma, eb_tok, cnts, sel)
    if wire_bf16:
        pi = pi.astype(jnp.bfloat16).astype(jnp.float32)
    v = cfg["vocab_size"]
    corr = (jnp.zeros((v, k)).at[ids].add(cnts[:, None] * pi)
            - jnp.zeros((v, k)).at[ids].add(cnts[:, None] * old_pi))
    doc_words = jnp.dot(sel, cnts, precision=HI)
    words_first = jnp.sum(jnp.where(seen, 0.0, doc_words))
    frac = jnp.maximum(state["frac"] - words_first / num_words_total, 0.0)
    frac = jnp.where(frac < 1e-6, 0.0, frac)
    m = state["m"] + corr
    lam = cfg["beta0"] + m + frac * state["init_mass"]
    memo = memo.at[slot].set(pi, mode="drop")
    new = {"lam": lam, "m": m, "init_mass": state["init_mass"],
           "frac": frac}
    return new, memo, pi, gamma, sweeps


@partial(jax.jit, static_argnames=("cfg_items", "batch_rows", "tile_rows",
                                   "denominator"))
def serve_gamma(eb, ids, cnts, segs, n_real, *, cfg_items, batch_rows,
                tile_rows, denominator):
    """γ (nb, B, K) of nb independent request batches under topics ``eb``
    (fresh γ₀ = α₀ + 1 on every row, no memo)."""
    cfg = dict(cfg_items)

    def one(ids_b, cnts_b, segs_b, n_b):
        tile_of_row, denom, num_tiles = tiles(
            n_b, batch_rows, tile_rows, denominator, cfg["num_topics"])
        gamma0 = jnp.full((batch_rows, cfg["num_topics"]),
                          cfg["alpha0"] + 1.0)
        gamma, _ = fixed_point(
            eb[ids_b], cnts_b, selector(segs_b, batch_rows), gamma0,
            tile_of_row, denom,
            alpha0=cfg["alpha0"], tol=cfg["estep_tol"],
            max_iters=cfg["estep_max_iters"], num_tiles=num_tiles)
        return gamma

    return jax.vmap(one)(ids, cnts, segs, n_real)


def cfg_items(cfg: Dict) -> Tuple:
    """The configuration's numeric settings as a hashable jit static."""
    keys = ("num_topics", "vocab_size", "alpha0", "beta0", "estep_max_iters",
            "estep_tol")
    return tuple((k, cfg[k]) for k in keys)


class FlatCorpus:
    """The live token slots of a padded corpus, flattened in row order:
    document d owns memo rows ``start[d] : start[d] + n[d]``."""

    def __init__(self, token_ids: np.ndarray, counts: np.ndarray):
        live = counts > 0
        self.n = live.sum(1)
        self.start = np.concatenate([[0], np.cumsum(self.n)[:-1]])
        self.ids = token_ids[live]
        self.cnts = counts[live]
        self.num_slots = int(self.n.sum())

    def batch(self, rows: np.ndarray, t_cap: int, batch_rows: int):
        """Flat (T,) ids, counts, owning batch row and memo slot for ``rows``
        (padding: count 0, row ``batch_rows - 1``, slot = sentinel)."""
        n = self.n[rows]
        tot = int(n.sum())
        if tot > t_cap or len(rows) > batch_rows:
            raise ValueError(f"batch of {len(rows)} docs / {tot} tokens "
                             f"exceeds the reference's {batch_rows} rows / "
                             f"{t_cap} tokens")
        seg = np.repeat(np.arange(len(rows)), n)
        slot = np.repeat(self.start[rows], n) + (
            np.arange(tot) - np.repeat(np.cumsum(n) - n, n))
        ids = np.zeros(t_cap, np.int32)
        cnts = np.zeros(t_cap, np.float32)
        segs = np.full(t_cap, batch_rows - 1, np.int32)
        slots = np.full(t_cap, self.num_slots, np.int32)
        ids[:tot], cnts[:tot] = self.ids[slot], self.cnts[slot]
        segs[:tot], slots[:tot] = seg, slot
        return ids, cnts, segs, slots, tot


class Reference:
    """Follows a training run step by step from the seed."""

    def __init__(self, cfg: Dict, token_ids: np.ndarray, counts: np.ndarray,
                 seed: int, *, batch_rows: int, tile_rows: int,
                 denominator: str, t_cap: int, wire_bf16: bool):
        self.cfg = cfg
        self.flat = FlatCorpus(token_ids, counts)
        self.num_words_total = jnp.float32(float(counts.sum()))
        self.state = init_state(cfg, seed)
        self.memo = jnp.zeros((self.flat.num_slots + 1, cfg["num_topics"]),
                              jnp.float32)
        self.visited = np.zeros(token_ids.shape[0], bool)
        self.kw = dict(cfg_items=cfg_items(cfg), batch_rows=batch_rows,
                       tile_rows=tile_rows, denominator=denominator,
                       wire_bf16=wire_bf16)
        self.t_cap = t_cap

    def step(self, rows: np.ndarray):
        """One step on documents ``rows`` (in batch order). Returns the
        batch's π per document (list of (n_d, K) arrays), γ (len(rows), K)
        and the sweeps per tile."""
        rows = np.asarray(rows, np.int64)
        b = self.kw["batch_rows"]
        ids, cnts, segs, slots, tot = self.flat.batch(rows, self.t_cap, b)
        seen = np.zeros(b, bool)
        seen[: len(rows)] = self.visited[rows]
        self.state, self.memo, pi, gamma, sweeps = ivi_step(
            self.state, self.memo, jnp.asarray(seen), jnp.asarray(ids),
            jnp.asarray(cnts), jnp.asarray(segs), jnp.asarray(slots),
            jnp.int32(len(rows)), self.num_words_total, **self.kw)
        self.visited[rows] = True
        pi = np.asarray(pi[:tot])
        per_doc = np.split(pi, np.cumsum(self.flat.n[rows])[:-1])
        return per_doc, np.asarray(gamma[: len(rows)]), np.asarray(sweeps)

    def step_from(self, lam: np.ndarray, old_pi: np.ndarray,
                  seen: np.ndarray, rows: np.ndarray):
        """One IVI step on documents ``rows`` from a given state, once the
        random init mass has retired: topics ``lam`` (V, K), the memo rows
        ``old_pi`` (len(rows), ≥ n_d, K) and the visited flags ``seen``
        of the batch's documents. Its own memo and λ are left as they are.
        Returns Δλ (V, K) = Σ cnt·(π − π_old) (eq. 4), the norm of the
        mass the step adds, ‖Σ cnt·π‖_F over (V, K), the new π per document
        and the sweeps per tile."""
        rows = np.asarray(rows, np.int64)
        b, k = self.kw["batch_rows"], self.cfg["num_topics"]
        ids, cnts, segs, _, tot = self.flat.batch(rows, self.t_cap, b)
        n = self.flat.n[rows]
        memo = np.zeros((self.t_cap + 1, k), np.float32)
        memo[:tot] = np.concatenate(
            [old_pi[d, : n[d]] for d in range(len(rows))])
        slots = np.full(self.t_cap, self.t_cap, np.int32)
        slots[:tot] = np.arange(tot)
        flags = np.zeros(b, bool)
        flags[: len(rows)] = seen
        # ⟨m⟩ = 0 and no init mass: the new λ is β₀ + Δλ
        zero = jnp.zeros_like(self.state["lam"])
        state = {"lam": jnp.asarray(lam), "m": zero, "init_mass": zero,
                 "frac": jnp.zeros((), jnp.float32)}
        new, _, pi, _, sweeps = ivi_step(
            state, jnp.asarray(memo), jnp.asarray(flags), jnp.asarray(ids),
            jnp.asarray(cnts), jnp.asarray(segs), jnp.asarray(slots),
            jnp.int32(len(rows)), self.num_words_total, **self.kw)
        d_lam = new["lam"] - self.cfg["beta0"]
        added = jnp.zeros_like(zero).at[jnp.asarray(ids)].add(
            jnp.asarray(cnts)[:, None] * pi)
        pi = np.asarray(pi[:tot])
        per_doc = np.split(pi, np.cumsum(n)[:-1])
        return (d_lam, float(jnp.linalg.norm(added)), per_doc,
                np.asarray(sweeps))
