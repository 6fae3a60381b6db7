"""ISSUE 5 metamorphic tests: per-query residual scoping + warm-started
survivor solves.

The tentpole reworks the adaptive loop's convergence machinery from one
chunk-global scalar into per-query scoping (each query's residual covers
only its own live candidate slots, converged queries freeze their
x-columns, the loop exits when every live query converged or the cap
hits) and warm-starts the cascade's survivor solve from the seed solve's
converged profile. These tests pin the metamorphic contracts:

- per-query exit == chunk-global exit top-k on the fig8 dedup corpus,
  with the scoped engine realizing strictly fewer iterations;
- a planted one-stubborn-query chunk exits the other queries early
  (realized per-query iters asserted), and query/doc padding is inert;
- warm-started survivor solves == cold solves bit-tolerant, with
  strictly fewer realized survivor iterations;
- the distributed per-query (Q,) ``lax.pmax`` path agrees with its
  unscoped self where it matters.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import WmdEngine, build_index
from repro.core.index import DocGroup, _gather_g, _solve_gathered


@pytest.fixture(scope="module")
def dedup():
    from benchmarks.fig8_topk_prune import dedup_corpus

    return dedup_corpus(256, vocab=1024, embed_dim=32, seed=5)


@pytest.fixture(scope="module")
def dedup_index(dedup):
    return build_index(dedup.docs, dedup.vecs)


def _topk_sets(res):
    return [set(row.tolist()) for row in res.indices]


# ------------------------------------------------- per-query == chunk top-k
def test_per_query_exit_matches_chunk_topk(dedup, dedup_index):
    """Scoping the exit test per query must not change WHAT is retrieved
    — only how many iterations each query pays. ``iter_stats`` charges a
    chunk-scoped dispatch's exit to every live query (that is its real
    cost), so the per-query mean must come out strictly below it once
    any query freezes before its slowest chunkmate."""
    qs = list(dedup.queries)
    chunk = WmdEngine(dedup_index, lam=1.0, n_iter=60, tol=1e-2,
                      check_every=2, scope="chunk")
    query = WmdEngine(dedup_index, lam=1.0, n_iter=60, tol=1e-2,
                      check_every=2, scope="query")
    r_c = chunk.search(qs, 10, prune="rwmd")
    r_q = query.search(qs, 10, prune="rwmd")
    assert _topk_sets(r_c) == _topk_sets(r_q)
    np.testing.assert_allclose(np.sort(r_q.distances, axis=1),
                               np.sort(r_c.distances, axis=1),
                               rtol=2e-2, atol=1e-3)
    it_c, it_q = chunk.iter_stats(), query.iter_stats()
    assert it_q.mean() < it_c.mean(), (it_c, it_q)
    assert it_q.max() <= it_c.max()


def test_per_query_matches_fixed_reference(dedup, dedup_index):
    """And against the fixed-iteration reference (the fig10 gate): same
    top-k, realized mean strictly below the cap."""
    qs = list(dedup.queries)
    fixed = WmdEngine(dedup_index, lam=1.0, n_iter=60)
    scoped = WmdEngine(dedup_index, lam=1.0, n_iter=60, tol=3e-3,
                       check_every=2)
    r_f = fixed.search(qs, 10, prune="rwmd")
    r_s = scoped.search(qs, 10, prune="rwmd")
    assert _topk_sets(r_f) == _topk_sets(r_s)
    iters = scoped.iter_stats()
    assert iters.mean() < 60 and iters.size > 0


# ----------------------------------------------- planted stubborn query
def _group_mask(engine, queries, doc_scopes, width):
    """Stage ``queries`` as ONE chunk against the whole corpus and build
    the (Q, N_pad) per-query candidate mask from ``doc_scopes`` (storage
    positions; None = all docs)."""
    index = engine.index
    n = index.n_docs
    sup, r, mask = engine._prep_chunk(queries, width)
    all_ids = np.arange(n, dtype=np.int32)
    # the whole corpus as one candidate tile: an index of one doc group
    ((grp, _),) = index._replace(groups=(DocGroup(
        docs=index.docs, cols=jnp.asarray(all_ids)),)).tiles(
            all_ids, 8 * (-(-n // 8)), storage=True)
    n_pad = grp.docs.idx.shape[0]
    qdoc = np.zeros((sup.shape[0], n_pad), bool)
    for qi, scope in enumerate(doc_scopes):
        if scope is None:
            qdoc[qi, :n] = True
        else:
            qdoc[qi, scope] = True
    return sup, r, mask, grp, jnp.asarray(qdoc)


def test_stubborn_query_does_not_stall_chunkmates(dedup, dedup_index):
    """Plant a chunk with one stubborn query (a dedup query — its
    structured near-dup kernel converges slowly at lam=1) among
    fast-converging iid queries, and pin the metamorphic relation between
    the two scopes: the chunk-global exit is determined by the SLOWEST
    query (``iters_chunk == max(iters_q)`` — each query's trajectory is
    independent, so the slowest one's check sequence is identical in both
    modes), while per-query scoping freezes the fast members at their own
    counts (``min(iters_q) < iters_chunk``) instead of burning the
    chunk's full width until the stubborn one converges."""
    eng = WmdEngine(dedup_index, lam=1.0, n_iter=60, tol=1e-2,
                    check_every=2)
    rng = np.random.default_rng(0)

    def rand_q():
        q = np.zeros(dedup.queries.shape[1], np.float32)
        q[rng.choice(q.size, 24, replace=False)] = rng.random(24) + 0.1
        return q

    queries = [rand_q(), rand_q(), rand_q(), dedup.queries[0]]
    width = max(8, -(-max(int((q > 0).sum()) for q in queries) // 8) * 8)
    sup, r, mask, grp, _ = _group_mask(eng, queries, [None] * 4, width)
    kqk, mq = eng._kq(sup, mask)
    g = _gather_g(kqk, grp.docs.idx)
    args = (eng.lam, eng.n_iter, eng.tol, eng.check_every, "fp32", False)
    wmd, iters = _solve_gathered(g, mq, grp.docs.idx, grp.docs.val, r,
                                 mask, *args, scope="query")
    iters = np.asarray(iters)[:4]
    wmd_c, iters_c = _solve_gathered(g, mq, grp.docs.idx, grp.docs.val, r,
                                     mask, *args, scope="chunk")
    assert int(iters_c) == iters.max(), (iters, iters_c)
    assert iters.min() < iters.max(), iters    # the fast members froze early
    # frozen-early rows still match the chunk run at the solve tolerance
    n = dedup_index.n_docs
    np.testing.assert_allclose(np.asarray(wmd)[:4, :n],
                               np.asarray(wmd_c)[:4, :n],
                               rtol=5e-2, atol=1e-3)

    # padding inertness: two filler queries + 8 inert docs change nothing
    idx_p = jnp.concatenate([grp.docs.idx,
                             jnp.zeros((8, grp.docs.idx.shape[1]),
                                       jnp.int32)])
    val_p = jnp.concatenate([grp.docs.val,
                             jnp.zeros((8, grp.docs.val.shape[1]))])
    g_p = _gather_g(kqk, idx_p)
    g_p = jnp.concatenate([g_p, jnp.zeros((2,) + g_p.shape[1:])], axis=0)
    mq_p = jnp.concatenate([mq, mq[:2]], axis=0)
    r_p = jnp.concatenate([r, jnp.ones((2, r.shape[1]))])
    mask_p = jnp.concatenate([mask, jnp.zeros((2, mask.shape[1]))])
    wmd_p, iters_p = _solve_gathered(g_p, mq_p, idx_p, val_p, r_p, mask_p,
                                     *args, scope="query")
    np.testing.assert_array_equal(np.asarray(iters_p)[:4], iters)
    np.testing.assert_allclose(np.asarray(wmd_p)[:4, :n],
                               np.asarray(wmd)[:4, :n],
                               rtol=1e-6, atol=1e-7)


# --------------------------------------------------- warm-started survivors
def test_warm_survivor_matches_cold_with_fewer_iters(dedup, dedup_index):
    """Warm-starting the survivor solve from the seed solve's converged
    per-query profile returns the same distances (both inits land within
    tol of the same fixed point) in strictly fewer realized iterations."""
    qs = list(dedup.queries)
    cold = WmdEngine(dedup_index, lam=1.0, n_iter=60, tol=1e-2,
                     check_every=2, warm_start=False)
    warm = WmdEngine(dedup_index, lam=1.0, n_iter=60, tol=1e-2,
                     check_every=2, warm_start=True)
    r_c = cold.search(qs, 10, prune="rwmd")
    r_w = warm.search(qs, 10, prune="rwmd")
    np.testing.assert_allclose(np.sort(r_w.distances, axis=1),
                               np.sort(r_c.distances, axis=1),
                               rtol=5e-2, atol=1e-3)
    sc, sw = cold.iter_stats_by_stage(), warm.iter_stats_by_stage()
    # identical seed stage (warm start only applies to survivors)...
    np.testing.assert_array_equal(sw["seed"], sc["seed"])
    # ...and a strictly cheaper survivor stage
    assert sw["survivor"].mean() < sc["survivor"].mean(), (sc, sw)


def test_warm_start_inert_without_tol(dedup, dedup_index):
    """With tol=None (fixed-length loop) warm_start must change nothing —
    bit-for-bit, the PR 4 contract."""
    qs = list(dedup.queries[:2])
    a = WmdEngine(dedup_index, lam=1.0, n_iter=15, warm_start=False)
    b = WmdEngine(dedup_index, lam=1.0, n_iter=15, warm_start=True)
    r_a = a.search(qs, 8, prune="rwmd")
    r_b = b.search(qs, 8, prune="rwmd")
    np.testing.assert_array_equal(r_a.indices, r_b.indices)
    np.testing.assert_array_equal(r_a.distances, r_b.distances)


# ------------------------------------------------------- distributed (Q,)
def test_distributed_batched_per_query_exit(dedup):
    """Batched distributed solve: the residual all-reduce is a per-query
    (Q,) ``lax.pmax`` — still one collective — and per-query realized
    counts come back. A dup query (scoped pairs stationary fast at small
    lam) and its batchmates exit without waiting for the cap."""
    from repro.core import select_support
    from repro.core.distributed import sinkhorn_wmd_sparse_distributed
    from repro.core.sinkhorn_sparse import sinkhorn_wmd_sparse

    mesh = jax.make_mesh((1, 1), ("data", "model"))
    vecs = jnp.asarray(dedup.vecs)
    rs, sels = [], []
    for qi in range(2):
        rq, sq, _ = select_support(dedup.queries[qi], dedup.vecs)
        rs.append(np.asarray(rq))
        sels.append(np.asarray(sq))
    b = max(r.shape[0] for r in rs)
    rpad = np.ones((2, b), np.float32)
    spad = np.zeros((2, b, sels[0].shape[1]), np.float32)
    qmask = np.zeros((2, b), np.float32)
    for qi in range(2):
        n = rs[qi].shape[0]
        rpad[qi, :n], spad[qi, :n], qmask[qi, :n] = rs[qi], sels[qi], 1.0
    for vshard in (False, True):
        out, iters = sinkhorn_wmd_sparse_distributed(
            jnp.asarray(rpad), jnp.asarray(spad), vecs, dedup.docs, 0.25,
            40, mesh, vshard_precompute=vshard, qmask=jnp.asarray(qmask),
            tol=1e-2, check_every=2, return_iters=True)
        assert out.shape == (2, 256)
        iters = np.asarray(iters)
        assert iters.shape == (2,) and (iters < 40).all(), iters
        # each row matches its own single-query solve at the same tol
        for qi in range(2):
            ref = sinkhorn_wmd_sparse(
                jnp.asarray(rs[qi]), jnp.asarray(sels[qi]), vecs,
                dedup.docs, 0.25, 40, tol=1e-2, check_every=2)
            np.testing.assert_allclose(np.asarray(out[qi]),
                                       np.asarray(ref),
                                       rtol=5e-2, atol=1e-3)


def test_sparse_solver_doc_mask_scoping(dedup):
    """``sinkhorn_wmd_sparse(doc_mask=...)``: scoping the single-query
    residual to the caller's candidate docs exits earlier, and the
    scoped docs' distances match the unscoped solve at tolerance."""
    from repro.core import select_support
    from repro.core.sinkhorn_sparse import sinkhorn_wmd_sparse

    vecs = jnp.asarray(dedup.vecs)
    r, vecs_sel, _ = select_support(dedup.queries[0], dedup.vecs)
    full, it_full = sinkhorn_wmd_sparse(
        r, vecs_sel, vecs, dedup.docs, 1.0, 60, tol=1e-2, check_every=2,
        return_iters=True)
    # scope to the single fastest-converging doc: a subset's residual max
    # can only be <= the full sweep's, so the exit is monotone in scope
    per_doc = []
    for j in range(8):
        dm1 = np.zeros(256, bool)
        dm1[j] = True
        _, itj = sinkhorn_wmd_sparse(
            r, vecs_sel, vecs, dedup.docs, 1.0, 60, tol=1e-2,
            check_every=2, doc_mask=dm1, return_iters=True)
        per_doc.append(int(itj))
        assert int(itj) <= int(it_full), (j, itj, it_full)
    assert min(per_doc) < int(it_full), (per_doc, it_full)
    near = int(np.argmin(per_doc))
    dm = np.zeros(256, bool)
    dm[near] = True
    scoped, it_scoped = sinkhorn_wmd_sparse(
        r, vecs_sel, vecs, dedup.docs, 1.0, 60, tol=1e-2, check_every=2,
        doc_mask=dm, return_iters=True)
    np.testing.assert_allclose(np.asarray(scoped)[near],
                               np.asarray(full)[near], rtol=2e-2,
                               atol=1e-3)
    # an empty scope has nothing to wait for: first check exits
    none, it_none = sinkhorn_wmd_sparse(
        r, vecs_sel, vecs, dedup.docs, 1.0, 60, tol=1e-2, check_every=2,
        doc_mask=np.zeros(256, bool), return_iters=True)
    assert int(it_none) == 3, it_none        # 1 seed + one check window

