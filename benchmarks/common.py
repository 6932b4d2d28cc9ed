"""Benchmark helpers: the shared LDA setup of the paper scripts.

The paper benchmarks build their estimators through the ``repro.lda.LDA``
facade (`make_lda` below) — the same public surface users drive — so a
facade regression shows up in the benchmark numbers, not just in unit
tests.
"""
from __future__ import annotations

from typing import Tuple


def paper_setup(corpus_name: str, *, estep_iters: int = 60, seed: int = 0):
    """(spec, train, test, cfg) with the benchmarks' shared topic sizing."""
    from repro.core import LDAConfig
    from repro.data import PAPER_CORPORA, make_corpus

    spec = PAPER_CORPORA[corpus_name]
    train = make_corpus(spec, split="train", seed=seed)
    test = make_corpus(spec, split="test", seed=seed)
    cfg = LDAConfig(num_topics=min(100, spec.num_topics * 2),
                    vocab_size=spec.vocab_size, estep_max_iters=estep_iters)
    return spec, train, test, cfg


def make_lda(corpus_name: str, *, algo: str = "ivi", batch: int = 32,
             seed: int = 0, estep_iters: int = 60, distributed=None,
             with_test: bool = True) -> Tuple["object", "object", "object"]:
    """(LDA facade, train corpus, test corpus) for one benchmark run."""
    from repro.lda import LDA

    _, train, test, cfg = paper_setup(corpus_name, estep_iters=estep_iters,
                                      seed=seed)
    lda = LDA(cfg, algo=algo, distributed=distributed, batch_size=batch,
              seed=seed)
    lda.partial_fit(train, steps=0,
                    test_corpus=test if with_test else None)
    return lda, train, test
