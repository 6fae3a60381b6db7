"""IVF cascade (ISSUE 3): exactness at nprobe=all, frozen-cluster appends,
recall monotonicity in nprobe, the RWMD bound on the probed candidates
only, and the underflow guards folded into the low-level solvers."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (CascadePruner, LamUnderflowError, WmdEngine,
                        append_docs, build_index, resolve_pruner,
                        select_support)
from repro.core.distributed import sinkhorn_wmd_sparse_distributed
from repro.core.index import _assign_clusters
from repro.core import prune as prune_mod
from repro.core.prune import RwmdPruner
from repro.core.sinkhorn_sparse import sinkhorn_wmd_sparse
from repro.core.sparse import PaddedDocs
from repro.data.corpus import make_corpus


@pytest.fixture(scope="module")
def corpus():
    return make_corpus(vocab_size=512, embed_dim=16, n_docs=96, n_queries=8,
                       words_per_doc=(3, 60), seed=11)


@pytest.fixture(scope="module")
def engine(corpus):
    return WmdEngine(build_index(corpus.docs, corpus.vecs), lam=8.0,
                     n_iter=15)


def _recall(result, exhaustive, k):
    return float(np.mean([
        len(set(result.indices[qi]) & set(exhaustive.indices[qi])) / k
        for qi in range(result.indices.shape[0])]))


# -------------------------------------------------------------- exactness
@pytest.mark.parametrize("prune", ["ivf+wcd+rwmd", "ivf+rwmd", "ivf+wcd"])
@pytest.mark.parametrize("k", [1, 5])
def test_cascade_nprobe_all_equals_exhaustive(corpus, engine, prune, k):
    """nprobe = n_clusters (the default) keeps the exact-top-k contract."""
    queries = list(corpus.queries)
    ex = engine.search(queries, k, prune=None)
    pr = engine.search(queries, k, prune=prune)
    for qi in range(len(queries)):
        assert set(ex.indices[qi]) == set(pr.indices[qi]), (prune, k, qi)
        np.testing.assert_allclose(np.sort(pr.distances[qi]),
                                   np.sort(ex.distances[qi]),
                                   rtol=1e-4, atol=1e-5)


def test_cascade_solves_strict_subset_on_separable_corpus():
    """On the fig8 near-duplicate corpus the cascade must also PRUNE (the
    sub-O(N) contract), not just stay correct."""
    from benchmarks.fig8_topk_prune import dedup_corpus
    corpus = dedup_corpus(256, vocab=1024, embed_dim=32, seed=5)
    eng = WmdEngine(build_index(corpus.docs, corpus.vecs), lam=2.0,
                    n_iter=15)
    queries = list(corpus.queries)
    ex = eng.search(queries, 8, prune=None)
    pr = eng.search(queries, 8, prune="ivf+wcd+rwmd")
    for qi in range(len(queries)):
        assert set(ex.indices[qi]) == set(pr.indices[qi])
    assert (pr.solved < 128).all(), pr.solved


# ------------------------------------------------------------ cluster state
def test_cluster_invariants(corpus):
    index = build_index(corpus.docs, corpus.vecs)
    cl = index.clusters
    n = index.n_docs
    # Lloyd fixed point of the final pass: assign == nearest center
    want = np.asarray(_assign_clusters(index.centroids, cl.centers))
    np.testing.assert_array_equal(cl.assign, want)
    # membership arrays are consistent
    assert np.array_equal(np.sort(cl.order), np.arange(n))
    for c in range(cl.n_clusters):
        members = cl.order[cl.starts[c]:cl.starts[c + 1]]
        assert (cl.assign[members] == c).all()
    # radii dominate every member's distance to its center
    own = np.asarray(cl.centers)[cl.assign]
    d = np.linalg.norm(np.asarray(index.centroids) - own, axis=1)
    assert (d <= cl.radii[cl.assign] + 1e-5).all()


def test_append_assigns_to_nearest_cluster_without_rebuild(corpus):
    full = make_corpus(vocab_size=512, embed_dim=16, n_docs=128,
                       n_queries=6, words_per_doc=(3, 60), seed=23)
    head = PaddedDocs(idx=full.docs.idx[:96], val=full.docs.val[:96])
    tail = PaddedDocs(idx=full.docs.idx[96:], val=full.docs.val[96:])
    base = build_index(head, full.vecs)
    appended = append_docs(base, tail)
    # clusters are FROZEN: centers reused by identity, radii only grow
    assert appended.clusters.centers is base.clusters.centers
    assert (appended.clusters.radii >= base.clusters.radii - 1e-7).all()
    # new docs sit in their nearest existing cluster
    new_assign = appended.clusters.assign[96:]
    want = np.asarray(_assign_clusters(appended.centroids[96:],
                                       base.clusters.centers))
    np.testing.assert_array_equal(new_assign, want)
    # membership stays consistent after the re-sort
    for c in range(appended.clusters.n_clusters):
        members = appended.clusters.order[
            appended.clusters.starts[c]:appended.clusters.starts[c + 1]]
        assert (appended.clusters.assign[members] == c).all()
    # and append == rebuild through the exact cascade (nprobe = all)
    rebuilt = build_index(full.docs, full.vecs)
    queries = list(full.queries)
    ea = WmdEngine(appended, lam=8.0, n_iter=12)
    er = WmdEngine(rebuilt, lam=8.0, n_iter=12)
    sa = ea.search(queries, 5, prune="ivf+wcd+rwmd")
    sr = er.search(queries, 5, prune="ivf+wcd+rwmd")
    for qi in range(len(queries)):
        assert set(sa.indices[qi]) == set(sr.indices[qi])


# ------------------------------------------------------------------ recall
def test_recall_monotone_in_nprobe():
    from benchmarks.fig8_topk_prune import dedup_corpus
    corpus = dedup_corpus(256, vocab=1024, embed_dim=32, seed=5)
    index = build_index(corpus.docs, corpus.vecs)
    eng = WmdEngine(index, lam=2.0, n_iter=15)
    queries = list(corpus.queries)
    k = 8
    ex = eng.search(queries, k, prune=None)
    c = index.clusters.n_clusters
    recalls = []
    for nprobe in [1, 2, 4, max(8, c // 2), c]:
        res = eng.search(queries, k, prune="ivf+wcd+rwmd",
                         nprobe=min(nprobe, c))
        recalls.append(_recall(res, ex, k))
    # probe sets are nested, so the probed universe (and with it recall)
    # can only grow; the full probe is exact
    assert all(b >= a - 1e-9 for a, b in zip(recalls, recalls[1:])), recalls
    assert recalls[-1] == 1.0, recalls


def test_small_nprobe_pads_result_rows(corpus):
    """A query whose probed cluster holds fewer than k docs pads its row
    with -1 / NaN instead of inventing candidates."""
    index = build_index(corpus.docs, corpus.vecs, n_clusters=48)
    eng = WmdEngine(index, lam=8.0, n_iter=8)
    k = 30
    res = eng.search(list(corpus.queries[:2]), k, prune="ivf+wcd+rwmd",
                     nprobe=1)
    for qi in range(2):
        got = res.indices[qi]
        n_real = int((got >= 0).sum())
        assert n_real <= int(res.solved[qi])
        assert np.isnan(res.distances[qi][n_real:]).all()
        assert (got[:n_real] >= 0).all()


# ------------------------------------------ RWMD bound on probed members
def _staged(engine, queries):
    _, chunks = engine._plan(queries)
    chunk, width = chunks[0]
    return (len(chunk),) + engine._prep_chunk(
        [queries[qi] for qi in chunk], width)


@pytest.mark.parametrize("tile", [4, 2048])
def test_rwmd_bounds_on_ids_match_every_doc(corpus, engine, monkeypatch,
                                            tile):
    """The RWMD bound of chosen docs, gathered in tiles of their groups
    (several tiles per group at ``tile`` 4), equals the every-doc pass
    at those docs and is +inf at every other doc."""
    monkeypatch.setattr(prune_mod, "BOUND_TILE", tile)
    qc, sup, r, mask = _staged(engine, list(corpus.queries[:4]))
    index = engine.index
    full = np.asarray(prune_mod.rwmd_bounds(index, sup, mask))[:qc]
    ids = np.asarray([3, 5, 17, 41, 42, 43, 60, 90, 95], np.int32)
    got = np.asarray(prune_mod.rwmd_bounds(index, sup, mask, ids))[:qc]
    np.testing.assert_array_equal(got[:, ids], full[:, ids])
    rest = np.setdiff1d(np.arange(index.n_docs), ids)
    assert np.isinf(got[:, rest]).all()


def test_partial_probe_bounds_only_probed_members(corpus, engine):
    """Under nprobe < n_clusters the cascade's RWMD stage reads only the
    probed clusters' members: every other doc bounds +inf, and each
    query's own candidates keep the every-doc bound."""
    qc, sup, r, mask = _staged(engine, list(corpus.queries[:4]))
    index = engine.index
    casc = CascadePruner(nprobe=2)
    _, pm, qcent = casc.probe(index, sup, r, mask)
    (lb,) = casc.bounds(index, sup, mask, qcent, pm, ("rwmd",))
    lb = np.asarray(lb)[:qc]
    full = np.asarray(RwmdPruner().lower_bounds(index, sup, r, mask))[:qc]
    own = np.asarray(pm)[:qc][:, index.clusters.assign]      # (qc, N)
    np.testing.assert_array_equal(lb[own], full[own])
    assert np.isinf(lb[~own]).all()
    members = casc.probed_members(index, pm, mask)
    assert set(members) == set(np.nonzero(own.any(axis=0))[0])
    assert members.size < index.n_docs


@pytest.mark.parametrize("use_kernel", [False, True])
def test_cascade_rwmd_stage_matches_full_pruner(corpus, engine, use_kernel):
    """The cascade's RWMD stage == the full-sweep RwmdPruner's bounds
    (one implementation, rwmd_bounds), whether its min-cdist runs in XLA
    or in the Pallas kernel."""
    qc, sup, r, mask = _staged(engine, list(corpus.queries[:4]))
    index = engine.index
    full = np.asarray(RwmdPruner().lower_bounds(index, sup, r, mask))
    casc = CascadePruner(use_kernel=use_kernel,
                         interpret=True if use_kernel else None)
    _, pm, qcent = casc.probe(index, sup, r, mask)
    (lb,) = casc.bounds(index, sup, mask, qcent, pm, ("rwmd",))
    np.testing.assert_allclose(np.asarray(lb)[:qc], full[:qc],
                               rtol=5e-5, atol=5e-5)


def test_resolve_cascade_specs():
    p = resolve_pruner("ivf+wcd+rwmd", nprobe=3)
    assert isinstance(p, CascadePruner)
    assert p.stages == ("wcd", "rwmd") and p.nprobe == 3
    assert resolve_pruner("ivf").stages == ("wcd", "rwmd")
    assert resolve_pruner("ivf+rwmd").stages == ("rwmd",)
    assert resolve_pruner(p) is p
    with pytest.raises(ValueError):
        resolve_pruner(p, nprobe=7)      # conflicting override
    with pytest.raises(ValueError):
        resolve_pruner("rwmd", nprobe=4)  # nprobe needs a cascade
    with pytest.raises(ValueError):
        CascadePruner(stages=("nope",))


# -------------------------------------------------------- underflow guards
def test_sinkhorn_sparse_underflow_raises(corpus):
    r, vecs_sel, _ = select_support(corpus.queries[0], corpus.vecs)
    vecs = jnp.asarray(corpus.vecs)
    with pytest.raises(LamUnderflowError, match="underflowed"):
        sinkhorn_wmd_sparse(r, vecs_sel, vecs, corpus.docs, 80.0, 5)
    out = sinkhorn_wmd_sparse(r, vecs_sel, vecs, corpus.docs, 80.0, 5,
                              check_underflow=False)
    assert np.isnan(np.asarray(out)).any()


@pytest.mark.parametrize("vshard", [False, True])
def test_distributed_underflow_raises(corpus, vshard):
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    r, vecs_sel, _ = select_support(corpus.queries[0], corpus.vecs)
    vecs = jnp.asarray(corpus.vecs)
    with pytest.raises(LamUnderflowError, match="underflowed"):
        sinkhorn_wmd_sparse_distributed(r, vecs_sel, vecs, corpus.docs,
                                        80.0, 5, mesh,
                                        vshard_precompute=vshard)
    out = sinkhorn_wmd_sparse_distributed(r, vecs_sel, vecs, corpus.docs,
                                          80.0, 5, mesh,
                                          vshard_precompute=vshard,
                                          check_underflow=False)
    assert np.isnan(np.asarray(out)).any()
