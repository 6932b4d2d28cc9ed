"""Serving + distributed-bound correctness fixes (PR 4 satellites).

* empty documents (all-zero counts) through the serving buckets (now the
  unified ``repro.data.stream.bucket_rows``) / ``posterior`` /
  ``transform`` / the ``serve_lda`` launcher: routed to the smallest
  bucket, returned at the prior γ = α₀ / uniform θ̄ — never an all-zero
  row or a NaN from normalising one;
* ``TopicInferencer.cache_info`` reports batch counters and compiled
  widths as separate quantities;
* ``DIVITrainer.full_bound``: the all-gather-free per-shard reduction must
  match the single-host ``elbo_memoized_store`` oracle on the same state,
  and distributed ``evaluate()`` now reports ``elbo`` without a test
  corpus (through ``LDA.bound()`` too).
"""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bound import elbo_memoized_store
from repro.core.memo import DenseMemoStore
from repro.core.types import Corpus, LDAConfig
from repro.data.bow import corpus_from_docs
from repro.dist.protocol import DIVIConfig
from repro.data.stream import bucket_rows
from repro.lda import LDA
from repro.lda.infer import TopicInferencer
from repro.lda.trainer import DIVITrainer


def _inferencer(vocab=60, k=6, **kwargs):
    import jax
    cfg = LDAConfig(num_topics=k, vocab_size=vocab, estep_max_iters=30)
    lam = jax.random.gamma(jax.random.key(0), 100.0, (vocab, k)) * 0.01
    return cfg, TopicInferencer(cfg, lam, **kwargs)


# ---------------------------------------------------------------------------
# empty documents through the serving path
# ---------------------------------------------------------------------------

def test_serving_buckets_cover_every_document():
    """Every row — empty ones included — lands in exactly one bucket."""
    rng = np.random.default_rng(0)
    cnts = (rng.poisson(0.4, (50, 40)) * (rng.random((50, 40)) < 0.5))
    cnts = cnts.astype(np.float32)
    cnts[::7] = 0.0                            # sprinkle empty docs
    buckets = bucket_rows(cnts)
    covered = np.sort(np.concatenate([rows for rows, _ in buckets]))
    np.testing.assert_array_equal(covered, np.arange(50))
    # the empty docs ride the smallest bucket
    smallest_rows, smallest_w = buckets[0]
    assert smallest_w == 8
    assert set(np.nonzero(~(cnts > 0).any(1))[0]) <= set(smallest_rows)


def test_serving_buckets_all_empty_corpus():
    buckets = bucket_rows(np.zeros((5, 12), np.float32))
    assert len(buckets) == 1
    rows, w = buckets[0]
    np.testing.assert_array_equal(rows, np.arange(5))
    assert w == 8                               # smallest ladder rung


def test_posterior_empty_docs_return_prior(tiny_corpus):
    """Empty docs come back at γ = α₀ exactly; transform gives the uniform
    prior posterior — no all-zero γ row, no NaN θ̄."""
    cfg, inf = _inferencer(batch_size=8)
    rng = np.random.default_rng(1)
    ids = rng.integers(0, cfg.vocab_size, (11, 9)).astype(np.int32)
    cnts = (rng.poisson(1.0, (11, 9)) + 1).astype(np.float32)
    cnts[3] = 0.0                               # empty (OOV-only request)
    cnts[8] = 0.0
    corpus = Corpus(jnp.asarray(ids), jnp.asarray(cnts))
    gamma = inf.posterior(corpus)
    assert np.all(np.abs(gamma[[3, 8]] - cfg.alpha0) < 1e-6)
    assert not np.any(np.all(gamma == 0.0, axis=1))
    theta = inf.transform(corpus)
    assert np.all(np.isfinite(theta))
    np.testing.assert_allclose(theta[[3, 8]], 1.0 / cfg.num_topics,
                               rtol=1e-5)
    np.testing.assert_allclose(theta.sum(-1), 1.0, rtol=1e-5)


def test_transform_all_zero_corpus():
    """An entirely empty corpus transforms to the uniform prior posterior."""
    cfg, inf = _inferencer(batch_size=4)
    corpus = Corpus(jnp.zeros((6, 5), jnp.int32),
                    jnp.zeros((6, 5), jnp.float32))
    theta = inf.transform(corpus)
    assert np.all(np.isfinite(theta))
    np.testing.assert_allclose(theta, 1.0 / cfg.num_topics, rtol=1e-5)


def test_facade_transform_empty_docs(tiny_corpus):
    """The LDA facade path (what serve_lda drives) survives empty docs."""
    train, _, spec = tiny_corpus
    lda = LDA(num_topics=6, vocab_size=spec.vocab_size, algo="ivi",
              estep_max_iters=25, seed=0)
    lda.fit(train, epochs=1)
    ids = np.asarray(train.token_ids[:5])
    cnts = np.asarray(train.counts[:5]).copy()
    cnts[2] = 0.0
    theta = lda.transform(Corpus(jnp.asarray(ids), jnp.asarray(cnts)))
    assert np.all(np.isfinite(theta))
    np.testing.assert_allclose(theta[2], 1.0 / 6, rtol=1e-5)


# ---------------------------------------------------------------------------
# cache_info semantics
# ---------------------------------------------------------------------------

def test_cache_info_separates_batches_from_compilations():
    cfg, inf = _inferencer(batch_size=4)
    rng = np.random.default_rng(2)
    ids = rng.integers(0, cfg.vocab_size, (10, 20)).astype(np.int32)
    cnts = (rng.poisson(1.0, (10, 20)) + 1).astype(np.float32)
    cnts[:, 6:] = 0.0                           # all docs fit width 8
    corpus = Corpus(jnp.asarray(ids), jnp.asarray(cnts))
    inf.posterior(corpus)
    first = inf.cache_info()
    assert first["compiled_widths"] == [8]
    assert first["jit_entries"] == 1
    assert first["batches_per_width"] == {8: 3}    # 10 docs / batch 4
    inf.posterior(corpus)                          # same width, more batches
    second = inf.cache_info()
    assert second["compiled_widths"] == [8]        # no new compilation
    assert second["jit_entries"] == 1
    assert second["batches_per_width"] == {8: 6}   # counters, not jit entries


def test_serve_lda_latency_report(tmp_path, monkeypatch, capsys):
    """The launcher end-to-end on the tiny corpus: its jit-cache line and
    JSON record must use the corrected cache_info fields."""
    import sys
    from repro.launch import compile_cache, serve_lda
    # keep the test process off the checkout's persistent compile cache
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")
    out = tmp_path / "serve.jsonl"
    monkeypatch.setattr(sys, "argv", [
        "serve_lda", "--corpus", "tiny", "--requests", "3", "--batch", "8",
        "--topics", "6", "--estep-iters", "20", "--warm-epochs", "1",
        "--out", str(out)])
    serve_lda.main()
    text = capsys.readouterr().out
    assert "compiled widths" in text
    rec = json.loads(out.read_text().strip().splitlines()[-1])
    assert rec["ok"] and rec["jit_widths"]
    assert set(map(int, rec["batches_per_width"]))  == set(rec["jit_widths"])


# ---------------------------------------------------------------------------
# D-IVI memoized bound
# ---------------------------------------------------------------------------

@pytest.fixture
def divi_trainer():
    rng = np.random.default_rng(3)
    docs = [rng.integers(0, 120, size=rng.integers(5, 30))
            for _ in range(41)]                 # 41 % 4: ragged shard sizes
    corpus = corpus_from_docs(docs, 120)
    cfg = LDAConfig(num_topics=6, vocab_size=120, estep_max_iters=30)
    dcfg = DIVIConfig(num_workers=4, batch_size=5, staleness=2)
    return DIVITrainer(cfg, dcfg, corpus, seed=0), corpus


def test_divi_full_bound_matches_single_host_oracle(divi_trainer):
    """Per-shard stream read-through == elbo_memoized_store on the
    flattened state. The flat oracle permutes the corpus into shard order
    (shard w's documents are the corpus rows at ``positions(w)``) and
    stacks the live memo rows of each shard — the trailing phantom row of
    the ``max(shard sizes)``-padded memo is excluded. ALL 41 documents are
    covered: streaming shards drop no ``D % P`` tail."""
    tr, corpus = divi_trainer
    for _ in range(3):
        tr.run_pass()
    got = tr.full_bound()
    eng = tr.eng
    sh = eng.shard
    order = np.concatenate([eng.sharded.positions(w) for w in range(4)])
    assert len(order) == 41
    flat = Corpus(corpus.token_ids[order], corpus.counts[order])
    sizes = eng.sharded.shard_sizes
    store = DenseMemoStore(
        pi=jnp.concatenate([sh.pi[w][:sizes[w]] for w in range(4)]),
        visited=jnp.concatenate([sh.visited[w][:sizes[w]]
                                 for w in range(4)]))
    want = float(elbo_memoized_store(tr.cfg, flat, store, tr.eng.state.lam))
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert np.isfinite(got)


def test_divi_evaluate_reports_elbo_without_test_corpus(divi_trainer):
    tr, _ = divi_trainer
    tr.run_pass()
    out = tr.evaluate()
    assert "elbo" in out and np.isfinite(out["elbo"])
    assert tr.history.elbo == [out["elbo"]]
    # D-IVI folds corrections into a Robbins–Monro average under parameter
    # lag, so round-to-round monotonicity is NOT guaranteed (unlike exact
    # IVI) — only that the bound stays finite and the history accumulates
    tr.run_pass()
    out2 = tr.evaluate()
    assert np.isfinite(out2["elbo"])
    assert len(tr.history.elbo) == 2


def test_facade_bound_distributed():
    """LDA.bound() no longer raises for distributed runs."""
    rng = np.random.default_rng(4)
    docs = [rng.integers(0, 80, size=rng.integers(5, 20))
            for _ in range(24)]
    corpus = corpus_from_docs(docs, 80)
    lda = LDA(num_topics=5, vocab_size=80, algo="divi",
              distributed=DIVIConfig(num_workers=2, batch_size=4),
              estep_max_iters=25, seed=0)
    lda.fit(corpus, rounds=2)
    assert np.isfinite(lda.bound())
    assert "elbo" in lda.evaluate()
