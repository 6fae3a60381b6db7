"""ISSUE 4 solve-stage overhaul: convergence-adaptive early-exit Sinkhorn,
SolvePrecision (bf16 GEMMs / log-domain stabilization), and the
cluster-major corpus layout.

Covers the contracts the overhaul rides on: early-exit == fixed-iteration
top-k on the fig8 near-duplicate corpus, residual masking inertness (padded
docs/queries can neither stall nor early-release the loop), bf16 within
tolerance and distance-monotone on ranked output, log-domain == linear at
small lam and underflow-free at any lam, and cluster-major append + search
== rebuild.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    LamUnderflowError,
    SolvePrecision,
    WmdEngine,
    append_docs,
    auto_n_clusters,
    build_index,
    select_support,
)
from repro.core.distributed import sinkhorn_wmd_sparse_distributed
from repro.core.index import _gather_g, _solve_gathered
from repro.core.sinkhorn_sparse import sinkhorn_wmd_sparse
from repro.core.sparse import PaddedDocs
from repro.data.corpus import make_corpus
from repro.kernels import ops


@pytest.fixture(scope="module")
def dedup():
    from benchmarks.fig8_topk_prune import dedup_corpus

    return dedup_corpus(256, vocab=1024, embed_dim=32, seed=5)


@pytest.fixture(scope="module")
def dedup_index(dedup):
    return build_index(dedup.docs, dedup.vecs)


@pytest.fixture(scope="module")
def corpus():
    return make_corpus(
        vocab_size=512,
        embed_dim=16,
        n_docs=96,
        n_queries=6,
        words_per_doc=(3, 60),
        seed=11,
    )


def _topk_sets(dists, k):
    return [set(np.argsort(dists[qi])[:k]) for qi in range(dists.shape[0])]


# ----------------------------------------------------------- SolvePrecision
def test_solve_precision_parse():
    assert SolvePrecision.parse(None) == SolvePrecision("fp32", False)
    assert SolvePrecision.parse("bf16").gemm == "bf16"
    assert SolvePrecision.parse("log").log_domain
    both = SolvePrecision.parse("bf16+log")
    assert both.gemm == "bf16" and both.log_domain
    assert SolvePrecision.parse("log+bf16") == both
    assert both.name == "bf16+log"
    p = SolvePrecision.parse("fp32")
    assert SolvePrecision.parse(p) is p
    assert p.gemm_dtype is None
    assert both.gemm_dtype == jnp.bfloat16
    with pytest.raises(ValueError):
        SolvePrecision.parse("fp64")


# ------------------------------------------------------- adaptive early exit
def test_early_exit_matches_fixed_topk(dedup, dedup_index):
    """Early exit == fixed-iteration top-k on the fig8 corpus, and the
    realized iteration counts show the exit actually happened."""
    queries = list(dedup.queries)
    fixed = WmdEngine(dedup_index, lam=0.25, n_iter=15)
    adaptive = WmdEngine(
        dedup_index, lam=0.25, n_iter=15, tol=3e-2, check_every=2
    )
    d_f = np.asarray(fixed.query_batch(queries))
    adaptive.reset_iter_stats()
    d_a = np.asarray(adaptive.query_batch(queries))
    for a, b in zip(_topk_sets(d_f, 8), _topk_sets(d_a, 8)):
        assert a == b
    iters = adaptive.iter_stats()
    assert iters.size > 0 and (iters <= 15).all()
    assert (iters < 15).any(), iters  # the exit did fire somewhere


def test_adaptive_at_cap_equals_fixed(corpus):
    """tol=0 never exits early: the while loop runs to the cap and matches
    the fixed scan (realized counts land on 1 + k*check_every, so
    n_iter = 13 with check_every = 4 hits the cap exactly)."""
    index = build_index(corpus.docs, corpus.vecs)
    fixed = WmdEngine(index, lam=4.0, n_iter=13)
    capped = WmdEngine(index, lam=4.0, n_iter=13, tol=0.0, check_every=4)
    qs = list(corpus.queries[:3])
    np.testing.assert_allclose(
        np.asarray(capped.query_batch(qs)),
        np.asarray(fixed.query_batch(qs)),
        rtol=1e-6,
        atol=1e-7,
    )
    assert (capped.iter_stats() == 13).all()
    assert (fixed.iter_stats() == 13).all()  # fixed path reports the cap


def test_iter_stats_reset(corpus):
    index = build_index(corpus.docs, corpus.vecs)
    eng = WmdEngine(index, lam=4.0, n_iter=7)
    eng.query_batch(list(corpus.queries[:2]))
    assert eng.iter_stats().size > 0
    eng.reset_iter_stats()
    assert eng.iter_stats().size == 0


def test_residual_padding_inert(corpus):
    """Padded docs (all-zero rows) and filler queries can neither stall the
    adaptive loop nor release it early: same realized iterations and same
    distances on the real slice."""
    index = build_index(corpus.docs, corpus.vecs)
    eng = WmdEngine(index, lam=4.0, n_iter=40, tol=1e-3, check_every=5)
    qs = list(corpus.queries[:2])
    _, chunks = eng._plan(qs)
    chunk, width = chunks[0]
    sup, r, mask = eng._prep_chunk([qs[qi] for qi in chunk], width)
    kqk, mq = eng._kq(sup, mask)
    grp = index.groups[0]
    g = _gather_g(kqk, grp.docs.idx)
    args = (eng.lam, eng.n_iter, eng.tol, eng.check_every, "fp32", False)
    wmd, iters = _solve_gathered(g, mq, grp.docs.idx, grp.docs.val, r, mask, *args)
    qc = len(chunk)
    n_real = grp.cols.shape[0]

    # pad 8 inert docs (idx 0 / val 0) and 2 filler queries (g rows 0,
    # r == 1, mask == 0)
    idx_p = jnp.concatenate(
        [grp.docs.idx, jnp.zeros((8, grp.docs.idx.shape[1]), jnp.int32)]
    )
    val_p = jnp.concatenate([grp.docs.val, jnp.zeros((8, grp.docs.val.shape[1]))])
    g_p = _gather_g(kqk, idx_p)
    g_p = jnp.concatenate([g_p, jnp.zeros((2,) + g_p.shape[1:])], axis=0)
    mq_p = jnp.concatenate([mq, mq[:2]], axis=0)
    r_p = jnp.concatenate([r, jnp.ones((2, r.shape[1]))])
    mask_p = jnp.concatenate([mask, jnp.zeros((2, mask.shape[1]))])
    wmd_p, iters_p = _solve_gathered(g_p, mq_p, idx_p, val_p, r_p, mask_p, *args)
    assert int(iters_p) == int(iters), "padding changed the exit iteration"
    np.testing.assert_allclose(
        np.asarray(wmd_p)[:qc, :n_real],
        np.asarray(wmd)[:qc, :n_real],
        rtol=1e-6,
        atol=1e-7,
    )


# ------------------------------------------------------------ bf16 policy
def test_bf16_within_tolerance_and_monotone(dedup, dedup_index):
    queries = list(dedup.queries)
    fixed = WmdEngine(dedup_index, lam=0.25, n_iter=15)
    bf = WmdEngine(dedup_index, lam=0.25, n_iter=15, precision="bf16")
    d_f = np.asarray(fixed.query_batch(queries))
    d_b = np.asarray(bf.query_batch(queries))
    np.testing.assert_allclose(d_b, d_f, rtol=5e-2, atol=1e-3)
    # ranked output is distance-monotone, and every returned doc is within
    # the documented tolerance of truly top-k under the fp32 reference
    k = 8
    res = bf.search(queries, k, prune="rwmd")
    for qi in range(len(queries)):
        row = res.distances[qi]
        assert (np.diff(row[~np.isnan(row)]) >= 0).all()
        kth = np.sort(d_f[qi])[k - 1]
        assert d_f[qi, res.indices[qi]].max() <= kth * 1.05 + 1e-3


# ------------------------------------------------------- log-domain policy
def test_log_domain_equals_linear_small_lam(corpus):
    index = build_index(corpus.docs, corpus.vecs)
    lin = WmdEngine(index, lam=2.0, n_iter=12)
    log = WmdEngine(index, lam=2.0, n_iter=12, precision="log")
    qs = list(corpus.queries[:3])
    np.testing.assert_allclose(
        np.asarray(log.query_batch(qs)),
        np.asarray(lin.query_batch(qs)),
        rtol=5e-4,
        atol=5e-4,
    )
    # and at the solver level
    r, vecs_sel, _ = select_support(corpus.queries[0], corpus.vecs)
    vecs = jnp.asarray(corpus.vecs)
    a = np.asarray(sinkhorn_wmd_sparse(r, vecs_sel, vecs, corpus.docs, 2.0, 12))
    b = np.asarray(
        sinkhorn_wmd_sparse(
            r, vecs_sel, vecs, corpus.docs, 2.0, 12, precision="log"
        )
    )
    np.testing.assert_allclose(b, a, rtol=5e-4, atol=5e-4)


def test_log_domain_large_lam_no_underflow(corpus):
    """lam far beyond the fp32 exp cutoff: the legacy path raises, the
    log-domain policy completes with finite distances on engine AND
    solver paths."""
    index = build_index(corpus.docs, corpus.vecs)
    qs = list(corpus.queries[:2])
    with pytest.raises(LamUnderflowError):
        WmdEngine(index, lam=80.0, n_iter=5).query_batch(qs)
    d = np.asarray(
        WmdEngine(index, lam=80.0, n_iter=5, precision="log").query_batch(qs)
    )
    assert np.isfinite(d).all()
    r, vecs_sel, _ = select_support(corpus.queries[0], corpus.vecs)
    vecs = jnp.asarray(corpus.vecs)
    with pytest.raises(LamUnderflowError):
        sinkhorn_wmd_sparse(r, vecs_sel, vecs, corpus.docs, 80.0, 5)
    out, iters = sinkhorn_wmd_sparse(
        r,
        vecs_sel,
        vecs,
        corpus.docs,
        80.0,
        5,
        precision="log",
        return_iters=True,
    )
    assert np.isfinite(np.asarray(out)).all()
    assert int(iters) == 5


def test_log_domain_adaptive_engine_search(dedup, dedup_index):
    """The composed policy (log + adaptive) keeps the pruned-search
    contract: pruned top-k == its own exhaustive top-k."""
    eng = WmdEngine(
        dedup_index,
        lam=0.25,
        n_iter=15,
        tol=3e-2,
        check_every=2,
        precision="log",
    )
    queries = list(dedup.queries)
    ex = eng.search(queries, 8, prune=None)
    pr = eng.search(queries, 8, prune="ivf+wcd+rwmd")
    for qi in range(len(queries)):
        assert set(ex.indices[qi]) == set(pr.indices[qi])


# ------------------------------------------------------------- kernel path
def test_kernel_adaptive_matches_fixed(rng):
    q_n, v_r, n, length = 2, 8, 64, 8
    g = jnp.asarray(
        rng.uniform(0.05, 1.0, (q_n, v_r, n, length)), dtype=jnp.float32
    )
    val = jnp.where(jnp.asarray(rng.random((n, length))) > 0.3, 0.7, 0.0)
    val = val.at[:, 0].set(1.0)
    r = jnp.asarray(rng.uniform(0.1, 1.0, (q_n, v_r)).astype(np.float32))
    base = ops.sinkhorn_fused_all_batched(g, val, r, 4.0, 9, block_n=32)
    capped, iters = ops.sinkhorn_fused_all_batched(
        g,
        val,
        r,
        4.0,
        9,
        block_n=32,
        tol=0.0,
        check_every=4,
        with_iters=True,
    )
    assert iters.shape == (q_n, n // 32)
    assert (np.asarray(iters) == 9).all()  # 1 + 2*check_every == the cap
    np.testing.assert_allclose(
        np.asarray(capped), np.asarray(base), rtol=1e-6, atol=1e-6
    )


def test_kernel_pad_query_block_exits_first_check(rng):
    """An all-pad query's grid blocks are inert (w == 0 throughout), so
    they exit at the FIRST residual check — per-block early exit."""
    q_n, v_r, n, length = 1, 8, 32, 8
    g = jnp.asarray(
        rng.uniform(0.05, 1.0, (q_n, v_r, n, length)), dtype=jnp.float32
    )
    val = jnp.where(jnp.asarray(rng.random((n, length))) > 0.3, 0.7, 0.0)
    val = val.at[:, 0].set(1.0)
    r = jnp.asarray(rng.uniform(0.1, 1.0, (q_n, v_r)).astype(np.float32))
    g2 = jnp.concatenate([g, jnp.zeros((1, v_r, n, length))])
    r2 = jnp.concatenate([r, jnp.ones((1, v_r))])
    wmd, iters = ops.sinkhorn_fused_all_batched(
        g2,
        val,
        r2,
        4.0,
        20,
        block_n=32,
        tol=1e-4,
        check_every=3,
        with_iters=True,
    )
    iters = np.asarray(iters)
    # pad query: the FIRST check exits (1 seed iter + one check window)
    assert (iters[1] == 4).all(), iters
    base = ops.sinkhorn_fused_all_batched(g, val, r, 4.0, 20, block_n=32)
    np.testing.assert_allclose(
        np.asarray(wmd)[:1], np.asarray(base), rtol=1e-3, atol=1e-4
    )


def test_kernel_log_domain_matches_linear(rng):
    """Log-domain kernel (g = log K, pad rows -inf) == linear kernel."""
    v_r, n, length = 6, 32, 8
    m = jnp.asarray(rng.uniform(0.1, 3.0, (v_r, n, length)), jnp.float32)
    lam = 2.0
    g = jnp.exp(-lam * m)
    val = jnp.where(jnp.asarray(rng.random((n, length))) > 0.3, 0.5, 0.0)
    val = val.at[:, 0].set(1.0)
    r = jnp.asarray(rng.uniform(0.1, 1.0, v_r).astype(np.float32))
    base = ops.sinkhorn_fused_all(g, val, r, lam, 10, block_n=32)
    got = ops.sinkhorn_fused_all(
        -lam * m, val, r, lam, 10, block_n=32, log_domain=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(base), rtol=5e-4, atol=5e-4
    )


def test_engine_kernel_impl_adaptive():
    """Kernel engine path with the adaptive/precision knobs stays close to
    the sparse fixed reference (tiny corpus; interpret mode)."""
    small = make_corpus(
        vocab_size=256, embed_dim=16, n_docs=32, n_queries=2, seed=4
    )
    index = build_index(small.docs, small.vecs)
    ref = WmdEngine(index, lam=4.0, n_iter=13)
    ker = WmdEngine(
        index,
        lam=4.0,
        n_iter=13,  # 1 + 3*check_every: the capped while hits it exactly
        impl="kernel",
        block_n=32,
        tol=0.0,
        check_every=4,
    )
    d_ref = np.asarray(ref.query_batch(list(small.queries)))
    d_ker = np.asarray(ker.query_batch(list(small.queries)))
    np.testing.assert_allclose(d_ker, d_ref, rtol=5e-4, atol=5e-4)
    assert (ker.iter_stats() == 13).all()


# ------------------------------------------------------------- distributed
def test_distributed_adaptive_at_cap_matches_fixed(corpus):
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    r, vecs_sel, _ = select_support(corpus.queries[0], corpus.vecs)
    vecs = jnp.asarray(corpus.vecs)
    base = sinkhorn_wmd_sparse_distributed(
        r, vecs_sel, vecs, corpus.docs, 4.0, 13, mesh
    )
    capped = sinkhorn_wmd_sparse_distributed(
        r, vecs_sel, vecs, corpus.docs, 4.0, 13, mesh, tol=0.0, check_every=4
    )
    np.testing.assert_allclose(
        np.asarray(capped), np.asarray(base), rtol=1e-6, atol=1e-7
    )
    # a genuinely adaptive run stays finite and consistent with the fixed
    # solve at loose tolerance (the pmax residual all-reduce path)
    loose = sinkhorn_wmd_sparse_distributed(
        r, vecs_sel, vecs, corpus.docs, 4.0, 13, mesh, tol=5e-2, check_every=4
    )
    np.testing.assert_allclose(
        np.asarray(loose), np.asarray(base), rtol=0.2, atol=1e-3
    )


# ------------------------------------------------ cluster-major layout/auto
def test_cluster_major_storage_invariants(corpus):
    index = build_index(corpus.docs, corpus.vecs)
    n = index.n_docs
    cl = index.clusters
    assert (np.diff(cl.assign) >= 0).all()  # storage is cluster-major
    np.testing.assert_array_equal(cl.order, np.arange(n))  # slices == rows
    np.testing.assert_array_equal(np.sort(index.ext_ids), np.arange(n))
    np.testing.assert_array_equal(index.ext_ids[index.remap], np.arange(n))
    for grp in index.groups:
        cols = np.asarray(grp.cols)
        assert (np.diff(cols) >= 0).all()  # cluster-major within the group
    # public tiles() takes caller-order ids
    ids = np.asarray([5, 17, 3], np.int32)
    for grp, part in index.tiles(ids, 8):
        np.testing.assert_array_equal(grp.cols, ids[part])
        want_rows = index.remap[ids[part]]
        np.testing.assert_array_equal(
            np.asarray(grp.docs.idx)[: part.size],
            np.asarray(index.docs.idx)[want_rows][:, : grp.docs.idx.shape[1]],
        )


def test_cluster_major_append_search_matches_rebuild():
    full = make_corpus(
        vocab_size=512,
        embed_dim=16,
        n_docs=128,
        n_queries=5,
        words_per_doc=(3, 60),
        seed=23,
    )
    head = PaddedDocs(idx=full.docs.idx[:96], val=full.docs.val[:96])
    tail = PaddedDocs(idx=full.docs.idx[96:], val=full.docs.val[96:])
    appended = append_docs(build_index(head, full.vecs), tail)
    rebuilt = build_index(full.docs, full.vecs)
    # the grown group keeps the cluster-major invariant
    for grp in appended.groups:
        cols = np.asarray(grp.cols)
        assert (np.diff(appended.clusters.assign[cols]) >= 0).all()
    # appended ids extend the caller space
    np.testing.assert_array_equal(
        np.sort(appended.ext_ids), np.arange(128)
    )
    qs = list(full.queries)
    ea = WmdEngine(appended, lam=8.0, n_iter=10, tol=1e-3, check_every=5)
    er = WmdEngine(rebuilt, lam=8.0, n_iter=10, tol=1e-3, check_every=5)
    sa = ea.search(qs, 5, prune="ivf+wcd+rwmd")
    sr = er.search(qs, 5, prune="ivf+wcd+rwmd")
    for qi in range(len(qs)):
        assert set(sa.indices[qi]) == set(sr.indices[qi])


def test_auto_n_clusters(dedup):
    from repro.core.index import default_n_clusters

    index = build_index(dedup.docs, dedup.vecs, n_clusters="auto")
    n = index.n_docs
    # dedup-style corpora want far MORE clusters than sqrt(N): the radius
    # statistic must push past the default
    assert index.clusters.n_clusters > default_n_clusters(n)
    assert index.clusters.n_clusters <= n
    # direct call is deterministic in the seed
    cents = np.asarray(index.centroids)
    assert auto_n_clusters(cents, seed=0) == auto_n_clusters(cents, seed=0)
    with pytest.raises(ValueError):
        build_index(dedup.docs, dedup.vecs, n_clusters="autoo")
