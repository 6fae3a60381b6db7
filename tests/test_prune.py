"""Staged retrieval pipeline (ISSUE 2): bound admissibility, pruned-vs-
exhaustive top-k equality, candidate-subset solves, streaming appends, and
the lam-underflow guard.

The admissibility chain (Kusner et al. §4.3, corrected for what our solver
actually returns): WCD <= RWMD <= exact EMD (the LP oracle) <= the
truncated-Sinkhorn score ``<P, M>`` — the Sinkhorn plan is (column-)
feasible, so its transport cost can only exceed the LP optimum; the
entropic term is not part of the returned distance.
"""
import numpy as np
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import (LamUnderflowError, MaxPruner, RwmdPruner,
                        SearchResult, WcdPruner, WmdEngine, append_docs,
                        build_index, one_to_many, resolve_pruner,
                        select_support)
from repro.core.exact_ot import exact_emd
from repro.core.sinkhorn import cdist
from repro.core.sparse import PaddedDocs
from repro.data.corpus import make_corpus


@pytest.fixture(scope="module")
def corpus():
    # mixed v_r across buckets; embed/lam chosen so lam*dist stays < 87
    return make_corpus(vocab_size=512, embed_dim=16, n_docs=96, n_queries=8,
                       words_per_doc=(3, 60), seed=11)


@pytest.fixture(scope="module")
def engine(corpus):
    return WmdEngine(build_index(corpus.docs, corpus.vecs), lam=8.0,
                     n_iter=15)


def _bounds(engine, queries):
    """(wcd, rwmd) lower bounds via the engine's own staging, mapped from
    the index's cluster-major STORAGE doc order back to caller order (the
    order engine.query_batch scores are in)."""
    _, chunks = engine._plan(queries)
    n = engine.index.n_docs
    ext = engine.index.ext_ids
    wcd = np.zeros((len(queries), n))
    rwmd = np.zeros((len(queries), n))
    for chunk, width in chunks:
        sup, r, mask = engine._prep_chunk([queries[qi] for qi in chunk],
                                          width)
        w = np.asarray(WcdPruner().lower_bounds(engine.index, sup, r, mask))
        rw = np.asarray(RwmdPruner().lower_bounds(engine.index, sup, r,
                                                  mask))
        wcd[np.ix_(chunk, ext)] = w[:len(chunk)]
        rwmd[np.ix_(chunk, ext)] = rw[:len(chunk)]
    return wcd, rwmd


# ------------------------------------------------------------- admissibility
def test_bounds_below_engine_scores(corpus, engine):
    """WCD and doc-side RWMD lower-bound the engine's computed Sinkhorn
    score for every (query, doc) pair — the property exact top-k rests on."""
    queries = list(corpus.queries)
    scores = np.asarray(engine.query_batch(queries))
    wcd, rwmd = _bounds(engine, queries)
    assert (rwmd <= scores + 1e-4).all(), float((rwmd - scores).max())
    assert (wcd <= scores + 1e-4).all(), float((wcd - scores).max())


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_bound_chain_vs_exact_lp(seed):
    """{WCD, RWMD(sym)} <= exact LP ~<= converged Sinkhorn, per doc.

    The first inequalities are exact (WCD by Jensen, RWMD as a constraint
    relaxation of the LP). Two deliberate deviations from the naive chain:
    (a) WCD <= RWMD is NOT asserted — it is empirically typical (Kusner et
    al.) but not a theorem, and random corpora do produce counterexamples;
    both bounds are individually admissible, which is all MaxPruner needs.
    (b) LP <= Sinkhorn holds only up to the truncated iteration's
    query-marginal residual — the Sinkhorn plan satisfies the doc marginal
    exactly but the query marginal approximately, so its cost can undercut
    the LP optimum by O(residual * distance scale); hence the looser
    tolerance (and hence the engine prunes with the doc-side RWMD, which
    bounds the computed score itself — see
    test_bounds_below_engine_scores)."""
    corp = make_corpus(vocab_size=128, embed_dim=8, n_docs=6, n_queries=1,
                       words_per_doc=(4, 12), seed=seed)
    q = corp.queries[0]
    r, vecs_sel, _ = select_support(q, corp.vecs)
    r = np.asarray(r, np.float64)
    sink = np.asarray(one_to_many(q, corp.docs, corp.vecs, lam=12.0,
                                  n_iter=400, impl="sparse"), np.float64)
    idx = np.asarray(corp.docs.idx)
    val = np.asarray(corp.docs.val)
    vecs = np.asarray(corp.vecs)
    qc = r @ np.asarray(vecs_sel)
    for j in range(6):
        live = val[j] > 0
        c = val[j][live].astype(np.float64)
        c = c / c.sum()
        m = np.asarray(cdist(vecs_sel, jnp.asarray(vecs[idx[j][live]])),
                       np.float64)
        lp = exact_emd(r, c, m)
        wcd = float(np.linalg.norm(qc - c @ vecs[idx[j][live]]))
        rwmd = max(float(r @ m.min(axis=1)), float(c @ m.min(axis=0)))
        assert wcd <= lp + 1e-5, (wcd, lp)
        assert rwmd <= lp + 1e-5, (rwmd, lp)
        assert lp <= sink[j] * 1.05 + 0.05, (lp, sink[j])


# ------------------------------------------------------------------- search
@pytest.mark.parametrize("prune", ["wcd", "rwmd", "wcd+rwmd"])
@pytest.mark.parametrize("k", [1, 5])
def test_pruned_topk_equals_exhaustive(corpus, engine, prune, k):
    queries = list(corpus.queries)
    ex = engine.search(queries, k, prune=None)
    pr = engine.search(queries, k, prune=prune)
    for qi in range(len(queries)):
        assert set(ex.indices[qi]) == set(pr.indices[qi]), (prune, k, qi)
        np.testing.assert_allclose(np.sort(pr.distances[qi]),
                                   np.sort(ex.distances[qi]),
                                   rtol=1e-4, atol=1e-5)


def test_search_prune_none_is_query_batch_argsort(corpus, engine):
    """The prune=None path must reproduce exhaustive scoring bit-for-bit."""
    queries = list(corpus.queries[:4])
    d = np.asarray(engine.query_batch(queries))
    res = engine.search(queries, 7, prune=None)
    order = np.argsort(d, axis=1, kind="stable")[:, :7]
    np.testing.assert_array_equal(res.indices, order.astype(np.int32))
    np.testing.assert_array_equal(res.distances,
                                  np.take_along_axis(d, order, 1))
    assert (res.solved == corpus.docs.n_docs).all()


def test_search_solves_strict_subset_on_separable_corpus():
    """On a corpus with genuine near-duplicates the prune stage must
    exclude most docs (the fig8 contract), not just stay correct."""
    from benchmarks.fig8_topk_prune import dedup_corpus
    corpus = dedup_corpus(256, vocab=1024, embed_dim=32, seed=5)
    eng = WmdEngine(build_index(corpus.docs, corpus.vecs), lam=2.0,
                    n_iter=15)
    queries = list(corpus.queries)
    ex = eng.search(queries, 8, prune=None)
    pr = eng.search(queries, 8, prune="rwmd")
    for qi in range(len(queries)):
        assert set(ex.indices[qi]) == set(pr.indices[qi])
    assert (pr.solved < 128).all(), pr.solved     # < half the corpus


def test_search_empty_and_edge_queries(corpus, engine):
    n = corpus.docs.n_docs
    empty = np.zeros(corpus.vecs.shape[0], np.float32)
    res = engine.search([corpus.queries[0], empty], 3)
    assert (res.indices[1] == -1).all() and np.isnan(res.distances[1]).all()
    assert res.solved[1] == 0
    ex = engine.search([corpus.queries[0], empty], 3, prune=None)
    np.testing.assert_array_equal(res.indices, ex.indices)
    # k >= n degrades to a full (sorted) scoring
    big = engine.search([corpus.queries[0]], n + 10)
    assert big.indices.shape == (1, n)
    with pytest.raises(ValueError):
        engine.search([corpus.queries[0]], 0)
    empty_res = engine.search([], 3)
    assert isinstance(empty_res, SearchResult)
    assert empty_res.indices.shape == (0, 3)


def test_resolve_pruner_specs():
    assert isinstance(resolve_pruner("wcd"), WcdPruner)
    assert isinstance(resolve_pruner("rwmd"), RwmdPruner)
    comp = resolve_pruner("wcd+rwmd")
    assert isinstance(comp, MaxPruner) and comp.name == "wcd+rwmd"
    assert isinstance(resolve_pruner("wcd,rwmd"), MaxPruner)
    assert resolve_pruner(comp) is comp
    with pytest.raises(ValueError):
        resolve_pruner("nope")
    with pytest.raises(TypeError):
        resolve_pruner(42)


# ------------------------------------------------------------ subset solves
def test_subset_solve_matches_full_columns(corpus, engine):
    """Candidate-subset solve == the same columns of the exhaustive solve
    (per-doc independence is what makes staged pruning exact)."""
    queries = list(corpus.queries[:3])
    full = np.asarray(engine.query_batch(queries))
    doc_ids = np.asarray([5, 17, 3, 90, 41], np.int32)
    _, chunks = engine._plan(queries)
    tiles = engine.index.tiles(doc_ids, 8)
    # fixed-shape tiles of the doc groups: every tile holds 8 doc slots
    # (inert pad docs past its real ones) at its group's own width, and
    # together the tiles hold each id once
    np.testing.assert_array_equal(
        np.sort(np.concatenate([part for _, part in tiles])), np.arange(5))
    for grp, part in tiles:
        np.testing.assert_array_equal(grp.cols, doc_ids[part])
        assert grp.docs.idx.shape[0] == 8
        assert grp.docs.idx.shape[1] in {
            g.docs.idx.shape[1] for g in engine.index.groups}
        assert not np.asarray(grp.docs.val)[part.size:].any()
    for chunk, width in chunks:
        sup, r, mask = engine._prep_chunk([queries[qi] for qi in chunk],
                                          width)
        for grp, part in tiles:
            w = np.asarray(engine._solve_group(engine._kq(sup, mask), r,
                                               mask, grp))
            np.testing.assert_allclose(
                w[:len(chunk), :part.size],
                full[np.ix_(chunk, doc_ids[part])], rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------- streaming index
def test_append_docs_matches_rebuild(corpus):
    full = make_corpus(vocab_size=512, embed_dim=16, n_docs=128, n_queries=6,
                       words_per_doc=(3, 60), seed=23)
    head = PaddedDocs(idx=full.docs.idx[:96], val=full.docs.val[:96])
    tail = PaddedDocs(idx=full.docs.idx[96:], val=full.docs.val[96:])
    base = build_index(head, full.vecs)
    appended = append_docs(base, tail)
    rebuilt = build_index(full.docs, full.vecs)
    assert appended.n_docs == rebuilt.n_docs == 128
    # only the smallest group grew; the others' arrays are reused as-is
    grown = [ga.cols.shape[0] != gb.cols.shape[0]
             for ga, gb in zip(appended.groups, base.groups)]
    assert sum(grown) == 1
    for ga, gb in zip(appended.groups, base.groups):
        if ga.cols.shape[0] == gb.cols.shape[0]:
            assert ga.docs.idx is gb.docs.idx
    # centroids live in cluster-major STORAGE order, which differs between
    # the appended and rebuilt indexes — compare in caller doc order
    def by_caller(index):
        out = np.empty_like(np.asarray(index.centroids))
        out[index.ext_ids] = np.asarray(index.centroids)
        return out

    np.testing.assert_allclose(by_caller(appended), by_caller(rebuilt),
                               rtol=1e-5, atol=1e-6)
    queries = list(full.queries)
    ea = WmdEngine(appended, lam=8.0, n_iter=12)
    er = WmdEngine(rebuilt, lam=8.0, n_iter=12)
    np.testing.assert_allclose(np.asarray(ea.query_batch(queries)),
                               np.asarray(er.query_batch(queries)),
                               rtol=1e-5, atol=1e-6)
    sa = ea.search(queries, 5, prune="rwmd")
    sr = er.search(queries, 5, prune="rwmd")
    for qi in range(len(queries)):
        assert set(sa.indices[qi]) == set(sr.indices[qi])


def test_append_docs_validates_vocab(corpus):
    index = build_index(corpus.docs, corpus.vecs)
    bad = PaddedDocs(idx=jnp.asarray([[9999]], jnp.int32),
                     val=jnp.asarray([[1.0]], jnp.float32))
    with pytest.raises(ValueError):
        append_docs(index, bad)
    assert append_docs(index, PaddedDocs(
        idx=jnp.zeros((0, 4), jnp.int32),
        val=jnp.zeros((0, 4), jnp.float32))) is index


# ---------------------------------------------------------- underflow guard
def test_lam_underflow_raises(corpus):
    hot = WmdEngine(build_index(corpus.docs, corpus.vecs), lam=80.0,
                    n_iter=5)
    with pytest.raises(LamUnderflowError, match="underflowed"):
        hot.query_batch(list(corpus.queries[:2]))
    with pytest.raises(LamUnderflowError, match="lam"):
        one_to_many(corpus.queries[0], corpus.docs, corpus.vecs, lam=80.0,
                    n_iter=5, impl="sparse")
    # the log-domain impl is the documented escape hatch: finite, no raise
    d = one_to_many(corpus.queries[0], corpus.docs, corpus.vecs, lam=80.0,
                    n_iter=5, impl="dense_stabilized")
    assert np.isfinite(np.asarray(d)).all()
    # and check_underflow=False preserves the raw-NaN escape hatch
    d = one_to_many(corpus.queries[0], corpus.docs, corpus.vecs, lam=80.0,
                    n_iter=5, impl="sparse", check_underflow=False)
    assert np.isnan(np.asarray(d)).any()
