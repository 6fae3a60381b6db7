"""Batched multi-query WMD engine (repro.core.index) correctness.

Covers the ISSUE-1 contract: bucketed batched solves bit-match the
per-query oracle, query padding and doc-length grouping are inert,
a CorpusIndex is reusable across calls, and the in-VMEM GM reconstruction
equals the materialized (K*M) gather on both solver paths.
"""
import numpy as np
import jax.numpy as jnp
import pytest
from _resident import solve_knl

import repro.core.index as index_mod
from repro.core import (WmdEngine, build_index, bucket_size, many_to_many,
                        one_to_many, reconstruct_gm, select_support)
from repro.core.sinkhorn import cdist
from repro.data.corpus import make_corpus
from repro.kernels.ref import (reconstruct_gm_ref, sinkhorn_fused_all_ref,
                               sinkhorn_fused_all_materialized_ref)


@pytest.fixture(scope="module")
def engine_corpus():
    # mixed v_r across several buckets (v_r spans ~2..30)
    return make_corpus(vocab_size=512, embed_dim=16, n_docs=96, n_queries=10,
                       words_per_doc=(3, 60), seed=11)


def _oracle(corpus, q, lam, n_iter):
    return np.asarray(one_to_many(q, corpus.docs, corpus.vecs, lam, n_iter,
                                  impl="sparse"))


def test_bucket_size_policy():
    assert bucket_size(1) == 8
    assert bucket_size(8) == 8
    assert bucket_size(9) == 16
    assert bucket_size(17) == 32
    assert bucket_size(33, min_bucket=8) == 64
    assert bucket_size(3, min_bucket=4) == 4


@pytest.mark.parametrize("impl", ["sparse", "resident"])
def test_batched_matches_per_query_oracle(engine_corpus, impl, monkeypatch):
    """Engine distances == per-query sparse oracle, across buckets, on
    the einsum and (its platform probe patched) the resident solve."""
    c = engine_corpus
    monkeypatch.setattr(index_mod, "_on_tpu", lambda x: impl == "resident")
    eng = WmdEngine(build_index(c.docs, c.vecs), lam=8.0, n_iter=15)
    got = np.asarray(eng.query_batch(list(c.queries)))
    assert got.shape == (len(c.queries), c.docs.n_docs)
    assert (eng.host_stats()["resident_solves"] > 0) == (impl == "resident")
    for i, q in enumerate(c.queries):
        ref = _oracle(c, q, 8.0, 15)
        np.testing.assert_allclose(got[i], ref, rtol=5e-4, atol=5e-4)


def test_many_to_many_batched_equals_looped(engine_corpus):
    c = engine_corpus
    qs = list(c.queries[:4])
    batched = many_to_many(qs, c.docs, c.vecs, lam=8.0, n_iter=12,
                           impl="sparse", batched=True)
    looped = many_to_many(qs, c.docs, c.vecs, lam=8.0, n_iter=12,
                          impl="sparse", batched=False)
    for b, l in zip(batched, looped):
        np.testing.assert_allclose(np.asarray(b), np.asarray(l),
                                   rtol=5e-4, atol=5e-4)


def test_bucket_padding_inert(engine_corpus):
    """Padding a query to a larger bucket never changes its distances."""
    c = engine_corpus
    q = c.queries[0]
    small = WmdEngine(build_index(c.docs, c.vecs), lam=8.0, n_iter=10,
                      min_bucket=8)
    huge = WmdEngine(build_index(c.docs, c.vecs), lam=8.0, n_iter=10,
                     min_bucket=128)   # forces ~4x more pad rows
    d_small = np.asarray(small.query(q))
    d_huge = np.asarray(huge.query(q))
    np.testing.assert_allclose(d_huge, d_small, rtol=1e-5, atol=1e-6)


def test_doc_grouping_inert(engine_corpus):
    """Doc-length grouping (1 vs many groups) never changes distances."""
    c = engine_corpus
    outs = []
    for dg in (1, 2, 5):
        eng = WmdEngine(build_index(c.docs, c.vecs, doc_groups=dg),
                        lam=8.0, n_iter=10)
        outs.append(np.asarray(eng.query_batch(list(c.queries[:3]))))
    np.testing.assert_allclose(outs[1], outs[0], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(outs[2], outs[0], rtol=1e-5, atol=1e-6)


def test_index_reuse_identical(engine_corpus):
    """One frozen index, many calls: results are bitwise-identical, and
    single-query calls agree with the batch path."""
    c = engine_corpus
    eng = WmdEngine(build_index(c.docs, c.vecs), lam=8.0, n_iter=10)
    qs = list(c.queries[:4])
    first = np.asarray(eng.query_batch(qs))
    second = np.asarray(eng.query_batch(qs))
    np.testing.assert_array_equal(first, second)
    single = np.asarray(eng.query(qs[2]))
    np.testing.assert_array_equal(single, first[2])


def test_empty_batch(engine_corpus):
    c = engine_corpus
    eng = WmdEngine(build_index(c.docs, c.vecs))
    assert np.asarray(eng.query_batch([])).shape == (0, c.docs.n_docs)


# ------------------------------------------------- GM reconstruction proofs
def test_reconstruct_gm_equals_materialized(engine_corpus, rng):
    """-G*log(G)/lam == the materialized (K*M) gather, including pad zeros."""
    c = engine_corpus
    lam = 6.0
    r, vecs_sel, _ = select_support(c.queries[0], c.vecs)
    m = cdist(vecs_sel, jnp.asarray(c.vecs))
    k = jnp.exp(-lam * m)
    g = jnp.take(k, c.docs.idx, axis=1)
    gm_mat = jnp.take(k * m, c.docs.idx, axis=1)
    for recon in (reconstruct_gm(g, lam), reconstruct_gm_ref(g, lam)):
        np.testing.assert_allclose(np.asarray(recon), np.asarray(gm_mat),
                                   rtol=2e-4, atol=1e-6)
    # zero entries (pads / exp underflow) reconstruct to exactly 0
    gz = g.at[0, 0, 0].set(0.0)
    assert float(reconstruct_gm(gz, lam)[0, 0, 0]) == 0.0


def test_kernel_path_gm_reconstruction(rng):
    """Resident kernel (interpret) with in-VMEM GM reconstruction matches
    the explicit materialized-GM oracle."""
    v_r, n, length, lam, n_iter = 12, 64, 16, 4.0, 12
    g = jnp.asarray(rng.uniform(0.02, 1.0, (v_r, n, length)) ** 2,
                    dtype=jnp.float32)
    val = jnp.where(jnp.asarray(rng.random((n, length))) > 0.4,
                    jnp.asarray(rng.random((n, length)), jnp.float32), 0.0)
    val = val.at[:, 0].set(1.0)
    r = jnp.asarray(rng.uniform(0.1, 1.0, v_r).astype(np.float32))
    gm_mat = reconstruct_gm_ref(g, lam)     # == -g*log(g)/lam, materialized
    out = solve_knl(g[None], val, r[None], lam, n_iter)[0]
    want = sinkhorn_fused_all_materialized_ref(g, gm_mat, val, r, n_iter)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=5e-5, atol=5e-5)
    # and the lam-only ref is the same thing
    np.testing.assert_allclose(
        np.asarray(sinkhorn_fused_all_ref(g, val, r, lam, n_iter)),
        np.asarray(want), rtol=1e-6, atol=1e-6)


def test_sparse_precompute_sheds_gm(engine_corpus):
    """SparsePrecompute holds exactly two nnz-sized arrays (G, G_over_r)."""
    from repro.core.sinkhorn_sparse import SparsePrecompute, precompute_sparse
    assert set(SparsePrecompute._fields) == {"G", "G_over_r", "val"}
    c = engine_corpus
    r, vecs_sel, _ = select_support(c.queries[0], c.vecs)
    pre = precompute_sparse(r, vecs_sel, jnp.asarray(c.vecs), c.docs, 5.0)
    nnz_shaped = [f for f in pre if f.ndim == 3]
    assert len(nnz_shaped) == 2


# -------------------------------------------------- batched kernel vs einsum
def test_batched_kernel_matches_per_query_kernel(rng):
    """sinkhorn_resident at Q = 3 == three calls at Q = 1."""
    q_n, v_r, n, length, lam, n_iter = 3, 10, 64, 16, 5.0, 10
    g = jnp.asarray(rng.uniform(0.02, 1.0, (q_n, v_r, n, length)),
                    dtype=jnp.float32)
    val = jnp.where(jnp.asarray(rng.random((n, length))) > 0.4, 0.5, 0.0)
    val = val.at[:, 0].set(1.0)
    r = jnp.asarray(rng.uniform(0.1, 1.0, (q_n, v_r)).astype(np.float32))
    batched = solve_knl(g, val, r, lam, n_iter)
    assert batched.shape == (q_n, n)
    for qi in range(q_n):
        single = solve_knl(g[qi:qi + 1], val, r[qi:qi + 1], lam, n_iter)[0]
        np.testing.assert_allclose(np.asarray(batched[qi]),
                                   np.asarray(single), rtol=5e-5, atol=5e-5)


def test_batched_kernel_pad_query_inert(rng):
    """Appending an all-pad query (G == 0, r == 1) leaves the others
    untouched — the engine's q-padding contract."""
    q_n, v_r, n, length = 2, 8, 32, 8
    g = jnp.asarray(rng.uniform(0.05, 1.0, (q_n, v_r, n, length)),
                    dtype=jnp.float32)
    val = jnp.where(jnp.asarray(rng.random((n, length))) > 0.3, 0.7, 0.0)
    val = val.at[:, 0].set(1.0)
    r = jnp.asarray(rng.uniform(0.1, 1.0, (q_n, v_r)).astype(np.float32))
    base = solve_knl(g, val, r, 4.0, 8)
    g2 = jnp.concatenate([g, jnp.zeros((1, v_r, n, length))])
    r2 = jnp.concatenate([r, jnp.ones((1, v_r))])
    padded = solve_knl(g2, val, r2, 4.0, 8)
    np.testing.assert_allclose(np.asarray(padded[:q_n]), np.asarray(base),
                               rtol=1e-6, atol=1e-6)
