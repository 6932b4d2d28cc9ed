"""Seeded LDA corpora with the paper's Table 1 statistics (benchmark copy).

The benchmark's own copy of ``repro.data.synthetic.make_corpus``: documents
are sampled from the LDA generative model (paper eq. 1) with ground-truth
topics φ ~ Dir(β), θ_d ~ Dir(α), z ~ Cat(θ_d), w ~ Cat(φ_z). Two changes:

* the length distribution is a parameter (``poisson`` or ``lognormal``);
* document lengths are the distribution's quantiles at (i + ½)/D, shuffled
  by the seed, so every seed draws the same multiset of lengths (and so the
  same padded width and the same work) in another order. Topics, θ and
  words are drawn from the seed.

Sampling is vectorised over all tokens (inverse-CDF lookups on offset
cumulative sums), so 1% of Arxiv takes about a second on one CPU core.
"""
from __future__ import annotations

import math
import statistics
from typing import Dict, NamedTuple

import numpy as np

MIN_LEN = 4       # the program's generator floors lengths at 4 tokens too
_SPLIT_STREAM = {"train": 1, "test": 2}


class Corpus(NamedTuple):
    token_ids: np.ndarray    # (D, L) int32, unique ids ascending, 0-padded
    counts: np.ndarray       # (D, L) float32, 0 on padding
    doc_tokens: np.ndarray   # (D,) float64 tokens per document, as stored

    @property
    def num_docs(self) -> int:
        return self.token_ids.shape[0]

    @property
    def max_unique(self) -> int:
        return self.token_ids.shape[1]


def length_quantiles(n_docs: int, mean_len: float, dist: str,
                     sigma: float = 0.0) -> np.ndarray:
    """The ``n_docs`` document lengths at quantiles (i + ½)/n, ascending."""
    q = (np.arange(n_docs) + 0.5) / n_docs
    if dist == "poisson":
        # the smallest n whose CDF reaches q (the ppf), from the pmf table
        n = np.arange(int(mean_len + 20 * math.sqrt(mean_len) + 20))
        log_pmf = (n * math.log(mean_len) - mean_len
                   - np.array([math.lgamma(x + 1.0) for x in n]))
        lengths = np.searchsorted(np.cumsum(np.exp(log_pmf)), q)
    elif dist == "lognormal":
        # mean of exp(N(mu, s²)) is exp(mu + s²/2) = mean_len
        mu = math.log(mean_len) - sigma * sigma / 2.0
        nd = statistics.NormalDist(mu, sigma)
        lengths = np.exp([nd.inv_cdf(float(x)) for x in q])
    else:
        raise ValueError(f"unknown length distribution {dist!r} "
                         "(have poisson | lognormal)")
    return np.maximum(np.rint(lengths), MIN_LEN).astype(np.int64)


def topics(cfg: Dict, seed: int) -> np.ndarray:
    """Ground-truth topics φ (K, V) float64 for this seed."""
    rng = np.random.default_rng([seed, 0])
    return rng.dirichlet([cfg["gen_beta"]] * cfg["vocab_size"],
                         cfg["gen_topics"])


def _inverse_cdf(cdf: np.ndarray, rows: np.ndarray,
                 u: np.ndarray) -> np.ndarray:
    """Per-draw inverse-CDF lookup in row ``rows[i]`` of ``cdf`` (R, C):
    offsetting row r by r keeps the flattened table sorted, so one
    ``searchsorted`` serves every row (``side='right'`` as
    ``rng.choice`` does)."""
    r, c = cdf.shape
    flat = (cdf + np.arange(r)[:, None]).ravel()
    idx = np.searchsorted(flat, rows + u, side="right") - rows * c
    return np.minimum(idx, c - 1)


def make_corpus(cfg: Dict, phi: np.ndarray, *, n_docs: int, seed: int,
                split: str = "train") -> Corpus:
    """``n_docs`` documents of ``split`` under topics ``phi``, stored at the
    configuration's fixed width ``max_unique``: a document with more unique
    tokens keeps its most frequent ones (the program's ``corpus_from_docs``
    rule), so the padded width, and the work that scales with it, is the
    same for every seed."""
    rng = np.random.default_rng([seed, _SPLIT_STREAM[split]])
    k, v = phi.shape
    lengths = rng.permutation(length_quantiles(
        n_docs, cfg["mean_doc_len"], cfg["length_dist"],
        cfg.get("length_sigma") or 0.0))
    theta = rng.dirichlet([cfg["gen_alpha"]] * k, n_docs)
    doc = np.repeat(np.arange(n_docs), lengths)
    cdf_t = np.cumsum(theta, axis=1)
    cdf_t /= cdf_t[:, -1:]
    z = _inverse_cdf(cdf_t, doc, rng.random(len(doc)))
    cdf_w = np.cumsum(phi, axis=1)
    cdf_w /= cdf_w[:, -1:]
    words = _inverse_cdf(cdf_w, z, rng.random(len(doc)))
    # bag of words: unique (doc, word) pairs, ids ascending within a doc
    key, cnt = np.unique(doc * v + words, return_counts=True)
    d_of, w_of = key // v, key % v
    start = np.searchsorted(d_of, np.arange(n_docs))
    width = int(cfg["max_unique"])
    pos = np.arange(len(key)) - start[d_of]
    # rank of each token within its document by descending count (stable,
    # so equal counts keep ascending ids); ranks past the width are clipped
    order = np.lexsort((pos, -cnt, d_of))
    rank = np.empty(len(key), np.int64)
    rank[order] = np.arange(len(key)) - start[d_of[order]]
    keep = rank < width
    d_of, w_of, cnt = d_of[keep], w_of[keep], cnt[keep]
    start = np.searchsorted(d_of, np.arange(n_docs))
    pos = np.arange(len(d_of)) - start[d_of]
    ids = np.zeros((n_docs, width), np.int32)
    counts = np.zeros((n_docs, width), np.float32)
    ids[d_of, pos] = w_of
    counts[d_of, pos] = cnt
    doc_tokens = np.bincount(d_of, weights=cnt, minlength=n_docs)
    return Corpus(ids, counts, doc_tokens)
