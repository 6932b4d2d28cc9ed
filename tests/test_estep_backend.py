"""EStepBackend / MemoStore contracts (the E-step/memo refactor).

* backend equivalence: gather / dense / pallas (interpret mode) produce
  the same EStepResult and the same memo correction on random ragged
  batches;
* MemoStore oracle: the dense store keeps the full-pass identity
  ⟨m_vk⟩ == Σ_d s_d exactly, the bf16 chunked store keeps it within bf16
  tolerance, and the γ-only store reconstructs π faithfully right after a
  write;
* epoch coverage: the D % batch_size tail is visited (init_frac retires
  to exact zero — the eq. 4 exactness precondition);
* fused-kernel structure: one pallas_call per fixed point (none under a
  loop) and no (B, L, K) jnp arithmetic in the correction jaxpr.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import LDAConfig, LDAEngine
from repro.core.estep import (BowBatch, estep_gather, get_backend,
                              quantize_pi, scatter_sstats, warm_start_gamma)
from repro.core.math import exp_dirichlet_expectation
from repro.core.memo import make_memo_store, memo_footprint_bytes
from repro.core.types import Corpus
from repro.data.bow import bucket_corpus, bucket_padding_stats, corpus_from_docs
from repro.launch.hlo_analysis import dense_vocab_cubes, pallas_call_sites

BACKENDS = ("gather", "dense", "pallas", "csr")


def _ragged_batch(seed, b=12, vocab=200, k=7, mean_len=25):
    rng = np.random.default_rng(seed)
    docs = [rng.integers(0, vocab, size=max(2, int(rng.poisson(mean_len))))
            for _ in range(b)]
    corpus = corpus_from_docs(docs, vocab)
    cfg = LDAConfig(num_topics=k, vocab_size=vocab, estep_max_iters=50)
    lam = jax.random.gamma(jax.random.key(seed), 100.0, (vocab, k)) * 0.01
    eb = exp_dirichlet_expectation(lam, axis=0)
    return cfg, corpus, eb


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("seed", [0, 3])
def test_backend_equivalence_solve(backend, seed):
    """All backends return the same (γ, π, sstats) on ragged batches."""
    cfg, corpus, eb = _ragged_batch(seed)
    batch = BowBatch(corpus.token_ids, corpus.counts)
    want = get_backend("gather").solve(cfg, eb, batch)
    got = get_backend(backend).solve(cfg, eb, batch)
    np.testing.assert_allclose(got.gamma, want.gamma, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(got.pi, want.pi, rtol=2e-3, atol=1e-4)
    np.testing.assert_allclose(got.sstats, want.sstats, rtol=1e-2, atol=2e-3)


@pytest.mark.parametrize("backend", BACKENDS)
def test_backend_equivalence_correction(backend):
    """solve_correction agrees across backends (memo warm start + delta)."""
    cfg, corpus, eb = _ragged_batch(1)
    batch = BowBatch(corpus.token_ids, corpus.counts)
    rng = np.random.default_rng(1)
    base = get_backend("gather").solve(cfg, eb, batch)
    visited = jnp.asarray(rng.random(corpus.num_docs) < 0.5)
    old_pi = jnp.where(visited[:, None, None], base.pi, 0.0)
    want = get_backend("gather").solve_correction(cfg, eb, batch, old_pi,
                                                  visited)
    got = get_backend(backend).solve_correction(cfg, eb, batch, old_pi,
                                                visited)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(float(got[1]), float(want[1]), rtol=1e-6)
    np.testing.assert_allclose(got[2].pi, want[2].pi, rtol=2e-3, atol=1e-4)


def _mass_identity_gap(eng):
    """max |⟨m_vk⟩ − Σ_d scatter(cnt·π_store)| over the corpus."""
    pi, _ = eng.memo.gather(np.arange(eng.corpus.num_docs))
    rebuilt = scatter_sstats(eng.corpus.token_ids,
                             eng.corpus.counts[:, :, None] * pi,
                             eng.cfg.vocab_size)
    return float(jnp.abs(eng.state.m_vk - rebuilt).max())


@pytest.mark.parametrize("store,tol", [("dense", 5e-4), ("chunked", 2e-3)])
def test_memo_store_mass_identity(store, tol, tiny_corpus):
    """Full-pass ⟨m_vk⟩ == Σ_d s_d, for the dense AND the bf16 store.

    The bf16 store stays tight because π is rounded through the wire dtype
    *before* the add-new scatter (estep.quantize_pi): the accumulator adds
    exactly what the store holds, so low precision shrinks no invariant —
    only the memo footprint."""
    train, _, spec = tiny_corpus
    cfg = LDAConfig(num_topics=8, vocab_size=spec.vocab_size,
                    estep_max_iters=50)
    eng = LDAEngine(cfg, train, algo="ivi", batch_size=16, seed=0,
                    memo_store=store, chunk_docs=40)
    eng.run_epoch()
    for _ in range(4):
        eng.run_minibatch()
    assert float(eng.state.init_frac) == 0.0
    gap = _mass_identity_gap(eng)
    assert gap < tol, gap
    if store == "dense":
        # eq. 4 exactness: λ = β₀ + ⟨m_vk⟩ after the covering pass
        np.testing.assert_allclose(np.asarray(eng.state.lam),
                                   cfg.beta0 + np.asarray(eng.state.m_vk),
                                   rtol=1e-5, atol=1e-5)


def test_gamma_store_reconstructs_pi(tiny_corpus):
    """Right after a write the γ-only store reproduces the dense store's π
    (same λ-epoch), and S-IVI still trains through it."""
    train, test, spec = tiny_corpus
    cfg = LDAConfig(num_topics=8, vocab_size=spec.vocab_size,
                    estep_max_iters=50)
    dense = LDAEngine(cfg, train, algo="sivi", batch_size=16, seed=0)
    gamma = LDAEngine(cfg, train, algo="sivi", batch_size=16, seed=0,
                      memo_store="gamma", chunk_docs=train.num_docs)
    rows = np.arange(16)
    dense.run_minibatch(rows)
    gamma.run_minibatch(rows)
    pi_d, vis_d = dense.memo.gather(rows)
    pi_g, vis_g = gamma.memo.gather(rows)
    np.testing.assert_array_equal(np.asarray(vis_d), np.asarray(vis_g))
    np.testing.assert_allclose(np.asarray(pi_g), np.asarray(pi_d),
                               rtol=2e-2, atol=2e-2)   # bf16 snapshot
    assert gamma.memo.footprint_bytes() < dense.memo.footprint_bytes()


def test_gamma_store_rejected_for_exact_ivi(tiny_corpus):
    train, _, spec = tiny_corpus
    cfg = LDAConfig(num_topics=8, vocab_size=spec.vocab_size)
    with pytest.raises(ValueError, match="eq. 4"):
        LDAEngine(cfg, train, algo="ivi", batch_size=16, memo_store="gamma")


def test_epoch_tail_documents_are_visited():
    """D % batch_size tail docs must be visited: init_frac retires to an
    exact 0 after ONE epoch and λ = β₀ + ⟨m_vk⟩ holds (the old epoch order
    dropped the tail and the eq. 4 exactness never arrived)."""
    rng = np.random.default_rng(7)
    docs = [rng.integers(0, 150, size=rng.integers(5, 40))
            for _ in range(37)]                      # 37 % 8 = 5 tail docs
    corpus = corpus_from_docs(docs, 150)
    cfg = LDAConfig(num_topics=5, vocab_size=150, estep_max_iters=50)
    eng = LDAEngine(cfg, corpus, algo="ivi", batch_size=8, seed=0)
    eng.run_epoch()
    assert eng.docs_seen == 37
    assert bool(eng.memo.visited.all())
    assert float(eng.state.init_frac) == 0.0
    np.testing.assert_allclose(np.asarray(eng.state.lam),
                               cfg.beta0 + np.asarray(eng.state.m_vk),
                               rtol=1e-5, atol=1e-5)


def test_bucketed_epoch_covers_and_shrinks_padding(tiny_corpus):
    train, test, spec = tiny_corpus
    buckets = bucket_corpus(train)
    covered = np.sort(np.concatenate(buckets.doc_idx))
    np.testing.assert_array_equal(covered, np.arange(train.num_docs))
    stats = bucket_padding_stats(train, buckets)
    assert stats["slot_ratio"] <= 1.0
    cfg = LDAConfig(num_topics=8, vocab_size=spec.vocab_size,
                    estep_max_iters=40)
    eng = LDAEngine(cfg, train, algo="ivi", batch_size=16, seed=0,
                    bucket_by_length=True)
    eng.run_epoch()
    assert eng.docs_seen == train.num_docs
    assert bool(eng.memo.visited.all())
    assert float(eng.state.init_frac) == 0.0


def test_fused_pallas_launch_structure():
    """One pallas_call per fixed point plus the memo_delta pair: no kernel
    under a while/scan, no (B, L, K) jnp arithmetic, and ZERO dense
    vocab-sized rank-3 values (the (nb, V, K) one-hot partials the
    segment-sum scatter eliminates) in the fused correction jaxpr."""
    cfg, corpus, eb = _ragged_batch(2)
    batch = BowBatch(corpus.token_ids, corpus.counts)
    old_pi = jnp.zeros(corpus.token_ids.shape + (cfg.num_topics,))
    visited = jnp.zeros((corpus.num_docs,), bool)

    def fused_corr():
        return get_backend("pallas").solve_correction(cfg, eb, batch,
                                                      old_pi, visited)

    fused = pallas_call_sites(fused_corr)
    # fixed point + token-π + segment scatter
    assert fused["total"] == 3, fused
    assert fused["under_loop"] == 0, fused
    assert fused["blk_intermediates"] == 0, fused
    assert dense_vocab_cubes(fused_corr, cfg.vocab_size) == 0

    # per-B-tile (nb, V, K) one-hot partials DO trip the guard, or the zero
    # above proves nothing
    def onehot_partials():
        hot = jax.nn.one_hot(corpus.token_ids.reshape(4, -1),
                             cfg.vocab_size)
        w = old_pi.reshape(4, -1, cfg.num_topics)
        return jnp.einsum("nrv,nrk->nvk", hot, w).sum(0)
    assert dense_vocab_cubes(onehot_partials, cfg.vocab_size) > 0


def test_pallas_correction_long_token_axis():
    """L=8192 — far past the one-hot path's ~4k VMEM cap — must match the
    jnp backend at fp32 tolerance (the L grid axis acceptance bar)."""
    b, l, vocab, k = 4, 8192, 300, 8
    rng = np.random.default_rng(11)
    ids = jnp.asarray(rng.integers(0, vocab, (b, l)).astype(np.int32))
    cnts = jnp.asarray((rng.poisson(0.8, (b, l))).astype(np.float32))
    cfg = LDAConfig(num_topics=k, vocab_size=vocab, estep_max_iters=15,
                    estep_backend="pallas")
    lam = jax.random.gamma(jax.random.key(4), 100.0, (vocab, k)) * 0.01
    eb = exp_dirichlet_expectation(lam, axis=0)
    batch = BowBatch(ids, cnts)
    visited = jnp.asarray(rng.random(b) < 0.5)
    base = get_backend("gather").solve(cfg, eb, batch)
    old_pi = jnp.where(visited[:, None, None], base.pi, 0.0)
    want = get_backend("gather").solve_correction(cfg, eb, batch, old_pi,
                                                  visited)
    got = get_backend("pallas").solve_correction(cfg, eb, batch, old_pi,
                                                 visited)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(got[2].pi, want[2].pi, rtol=2e-3, atol=1e-4)
    # L >= V here: the dense-partial guard must not mistake the (B, L, K)
    # token cubes' long L axis for a vocab axis
    assert dense_vocab_cubes(
        lambda: get_backend("pallas").solve_correction(cfg, eb, batch,
                                                       old_pi, visited),
        cfg.vocab_size) == 0


def test_pallas_correction_non_resident_vocab():
    """A non-lane-multiple vocab large enough to need several V chunks
    (forced via a small block_v) must match the jnp backend — the
    non-V-resident acceptance shape, run in interpret mode."""
    from repro.kernels import ops as kops
    rng = np.random.default_rng(12)
    b, l, vocab, k = 8, 40, 4999, 12
    ids = jnp.asarray(rng.integers(0, vocab, (b, l)).astype(np.int32))
    cnts = jnp.asarray((rng.poisson(1.0, (b, l)) + 1).astype(np.float32))
    cfg = LDAConfig(num_topics=k, vocab_size=vocab, estep_max_iters=20,
                    estep_backend="pallas")
    lam = jax.random.gamma(jax.random.key(5), 100.0, (vocab, k)) * 0.01
    eb = exp_dirichlet_expectation(lam, axis=0)
    batch = BowBatch(ids, cnts)
    old_pi = jnp.zeros((b, l, k), jnp.float32)
    visited = jnp.zeros((b,), bool)
    want = get_backend("gather").solve_correction(cfg, eb, batch, old_pi,
                                                  visited)
    got = kops.memo_correction_pallas(cfg, eb, ids, cnts, old_pi, visited,
                                      delta_block_v=512)   # 10 V chunks
    np.testing.assert_allclose(got[0], want[0], rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(got[2].sstats, want[2].sstats,
                               rtol=1e-2, atol=2e-3)


def test_pallas_correction_bf16_wire_segment_parity():
    """Under a bf16 memo wire the segment-sum path must return the SAME
    rounded π as the jnp backend and masses consistent with scattering
    exactly those rounded values (the store invariant across the new
    scatter)."""
    cfg, corpus, eb = _ragged_batch(5)
    batch = BowBatch(corpus.token_ids, corpus.counts)
    rng = np.random.default_rng(5)
    base = get_backend("gather").solve(cfg, eb, batch)
    visited = jnp.asarray(rng.random(corpus.num_docs) < 0.5)
    old_pi = jnp.where(visited[:, None, None],
                       quantize_pi(base.pi, "bfloat16"), 0.0)
    want = get_backend("gather").solve_correction(cfg, eb, batch, old_pi,
                                                  visited,
                                                  pi_dtype="bfloat16")
    got = get_backend("pallas").solve_correction(cfg, eb, batch, old_pi,
                                                 visited,
                                                 pi_dtype="bfloat16")
    # the rounded π must be bf16-representable and agree across backends
    pi = np.asarray(got[2].pi)
    np.testing.assert_array_equal(
        pi, np.asarray(quantize_pi(jnp.asarray(pi), "bfloat16")))
    np.testing.assert_allclose(pi, np.asarray(want[2].pi),
                               rtol=2e-3, atol=2e-3)
    # and the masses are the scatter of exactly those rounded rows
    rebuilt = scatter_sstats(corpus.token_ids,
                             corpus.counts[:, :, None] * got[2].pi,
                             cfg.vocab_size)
    np.testing.assert_allclose(np.asarray(got[2].sstats),
                               np.asarray(rebuilt), rtol=1e-4, atol=1e-4)


def test_engine_end_to_end_pallas_backend(tiny_corpus):
    """The whole IVI engine (store + backend interfaces) on the fused
    kernels — the CI guard requested for estep_backend='pallas'."""
    train, test, spec = tiny_corpus
    base = LDAConfig(num_topics=8, vocab_size=spec.vocab_size,
                     estep_max_iters=40)
    res = {}
    for backend in ("dense", "pallas"):
        cfg = dataclasses.replace(base, estep_backend=backend)
        eng = LDAEngine(cfg, train, algo="ivi", batch_size=16, seed=0,
                        test_corpus=test)
        eng.run_epoch()
        res[backend] = (np.asarray(eng.state.lam), eng.evaluate()["lpp"])
    np.testing.assert_allclose(res["dense"][0], res["pallas"][0],
                               rtol=2e-2, atol=2e-2)
    assert abs(res["dense"][1] - res["pallas"][1]) < 0.1


def test_memo_footprint_math():
    """The dry-run memo math: Arxiv scale, chunked under the 40 GB bar."""
    d, l, k, v = 782_384, 128, 128, 141_952
    dense = memo_footprint_bytes("dense", d, l, k)
    chunked = memo_footprint_bytes("chunked", d, l, k)
    gamma = memo_footprint_bytes("gamma", d, l, k, vocab_size=v)
    assert dense / 1e9 > 40.0                   # the wall the issue names
    assert chunked / 1e9 < 40.0
    assert gamma < chunked < dense
    # footprint math must match what a real (small) store allocates
    cfg = LDAConfig(num_topics=4, vocab_size=60)
    store = make_memo_store("chunked", cfg, 100, 12, chunk_docs=32)
    assert store.footprint_bytes() == memo_footprint_bytes(
        "chunked", 100, 12, 4)
