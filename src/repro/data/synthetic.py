"""Synthetic corpora sampled from the LDA generative model (paper eq. 1).

Real AP / Newsgroup / Wikipedia / Arxiv / Customer-Review / NYT dumps are not
available offline, so we sample corpora *from the model itself* with the
summary statistics of Table 1 (documents, vocabulary, mean length) scaled to
CPU budgets. Trends (convergence order, mini-batch effects, speed-ups) are
reproduced; absolute LPP values are corpus-specific and are not.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import numpy as np

from repro.core.types import Corpus
from repro.data.bow import corpus_from_docs


@dataclasses.dataclass(frozen=True)
class SyntheticSpec:
    name: str
    num_train: int
    num_test: int
    mean_len: int
    vocab_size: int
    num_topics: int = 100       # ground-truth topics used to generate
    alpha: float = 0.1          # generative doc-topic concentration
    beta: float = 0.01          # generative topic-word concentration (sparse)


# Table 1 of the paper, scaled ~where needed for CPU execution.
PAPER_CORPORA: Dict[str, SyntheticSpec] = {
    "ap": SyntheticSpec("ap", 1246, 1000, 198, 10473),
    "newsgroup": SyntheticSpec("newsgroup", 13888, 5000, 249, 27059),
    "wikipedia": SyntheticSpec("wikipedia", 39565, 10000, 260, 42419),
    "arxiv": SyntheticSpec("arxiv", 782385, 100000, 116, 141927),
    "customer_review": SyntheticSpec("customer_review", 452944, 100000, 151,
                                     120043),
    "nyt": SyntheticSpec("nyt", 290000, 10000, 232, 102660),
    # CPU-sized variants used by tests/benchmarks
    "tiny": SyntheticSpec("tiny", 96, 32, 40, 250, num_topics=8),
    "small": SyntheticSpec("small", 512, 128, 80, 1200, num_topics=20),
    "medium": SyntheticSpec("medium", 2048, 256, 120, 4000, num_topics=50),
}


def make_corpus(spec: SyntheticSpec, *, split: str = "train",
                seed: int = 0, scale: float = 1.0) -> Corpus:
    """Sample a corpus from the LDA generative model.

    ``scale`` < 1 shrinks document counts (not lengths/vocab) so the paper's
    large corpora can be exercised at CPU scale while keeping their shape.
    """
    assert split in ("train", "test")
    rng = np.random.default_rng(seed + (1_000_003 if split == "test" else 0))
    n_docs = max(int((spec.num_train if split == "train" else spec.num_test)
                     * scale), 8)
    # ground-truth topics, shared across splits via a fixed topic seed.
    # NB: zlib.crc32, not hash() — Python string hashing is salted per
    # process and would make corpora (and every LPP) non-reproducible.
    import zlib
    topic_rng = np.random.default_rng(zlib.crc32(spec.name.encode()))
    phi = topic_rng.dirichlet([spec.beta] * spec.vocab_size, spec.num_topics)
    # each topic's normalised CDF, built once: exactly the table
    # ``rng.choice(V, p=phi[k])`` rebuilds per call (O(V) each), so the
    # inverse-CDF draws below consume the rng identically and give the
    # same words
    cdf = np.cumsum(phi, axis=1)
    cdf /= cdf[:, -1:]
    docs = []
    lengths = np.maximum(rng.poisson(spec.mean_len, n_docs), 4)
    for n in lengths:
        theta = rng.dirichlet([spec.alpha] * spec.num_topics)
        z = rng.choice(spec.num_topics, size=n, p=theta)
        # sample words per unique topic in bulk (much faster than per-word)
        doc = np.empty(n, np.int64)
        for k, cnt in zip(*np.unique(z, return_counts=True)):
            doc[z == k] = cdf[k].searchsorted(rng.random(cnt), side="right")
        docs.append(doc)
    return corpus_from_docs(docs, spec.vocab_size)
