"""Exact kNN search through the WCD -> RWMD cascade, with candidate solves
in fixed-shape tiles of the index's doc groups.

On a seeded topical corpus (word vectors around topic centres, so the
bounds prune), whose nnz doc groups differ in width and whose survivors
span several tiles of a group: the top-k equals the dense reference's
over all docs, searches of fresh queries compile nothing once every
staged shape was warmed, and no gathered K tile is larger than
Q x L_g x B x T x 4 bytes.
"""
import numpy as np
import jax
import pytest

import repro.core.index as index_mod
from repro.core import WmdEngine, build_index, select_support
from repro.core.sinkhorn import sinkhorn_wmd_dense
from repro.core.sparse import PaddedDocs

V, W, N_DOCS, TOPICS, LAM, N_ITER, K, TILE = 3000, 16, 600, 8, 1.0, 15, 5, 16
FEW = 8


def _texts(rng, topic_of, n, lo, hi):
    """``n`` texts of lognormal unique-word counts in [lo, hi]: 80% of a
    text's words from its own topic, the rest from the whole vocabulary;
    Zipf-like counts as weights."""
    lengths = np.clip(np.round(rng.lognormal(np.log((lo + hi) / 3), 0.5, n)),
                      lo, hi).astype(int)
    words = [np.nonzero(topic_of == t)[0] for t in range(TOPICS)]
    ids = np.zeros((n, hi), np.int32)
    val = np.zeros((n, hi), np.float32)
    for i, m in enumerate(lengths):
        own = words[rng.integers(TOPICS)]
        n_own = min(own.size, int(round(0.8 * m)))
        w = np.unique(np.concatenate([rng.choice(own, n_own, replace=False),
                                      rng.integers(V, size=m - n_own)]))
        ids[i, :w.size] = w
        c = 1.0 / (1.0 + rng.permutation(w.size))
        val[i, :w.size] = c / c.sum()
    return ids, val


@pytest.fixture(scope="module")
def topical():
    rng = np.random.default_rng(20150706)
    topic_of = rng.integers(TOPICS, size=V)
    centres = rng.normal(0.0, 0.41, (TOPICS, W))
    vecs = (centres[topic_of] + rng.normal(0.0, 0.08, (V, W))).astype(
        np.float32)
    ids, val = _texts(rng, topic_of, N_DOCS, 4, 60)
    return vecs, PaddedDocs(idx=ids, val=val), topic_of


def _queries(topical, n, seed, lo=4, hi=40):
    _, _, topic_of = topical
    ids, val = _texts(np.random.default_rng(seed), topic_of, n, lo, hi)
    rows = np.zeros((n, V), np.float32)
    for i in range(n):
        rows[i, ids[i][val[i] > 0]] = val[i][val[i] > 0]
    return list(rows)


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    """Tiles of TILE docs, so that a group's candidates span several, and
    a tile across the groups for at most FEW candidates."""
    monkeypatch.setattr(index_mod, "TILE_DOCS", TILE)
    monkeypatch.setattr(index_mod, "FEW_DOCS", FEW)


@pytest.fixture(scope="module")
def index(topical):
    vecs, docs, _ = topical
    return build_index(docs, vecs)


def _dense_reference(topical, queries):
    """(Q, N) distances of the dense Sinkhorn over every doc."""
    vecs, docs, _ = topical
    c = np.zeros((V, N_DOCS), np.float32)
    for n in range(N_DOCS):
        live = docs.val[n] > 0
        c[docs.idx[n][live], n] = docs.val[n][live]
    out = []
    for q in queries:
        r, sel, _ = select_support(q, vecs)
        out.append(np.asarray(sinkhorn_wmd_dense(r, sel, vecs, c, LAM,
                                                 N_ITER)))
    return np.stack(out)


# Both sides take word distances in the GEMM form |a|^2 + |b|^2 - 2 a.b,
# whose fp32 cancellation on words of one topic (distances near 0.45 on
# |a|^2 near 2.7) moves a WMD by up to ~3e-4 between two reduction
# orders; docs within that of the kth are ties.
RTOL = 1e-3


def _assert_exact_topk(res, ref, k):
    for qi in range(ref.shape[0]):
        kth = np.sort(ref[qi])[k - 1]
        got = res.indices[qi]
        np.testing.assert_allclose(res.distances[qi], ref[qi, got],
                                   rtol=RTOL)
        # a returned doc is the reference's kth or better, up to ties
        assert (ref[qi, got] <= kth * (1 + RTOL)).all(), qi


@pytest.mark.parametrize("prune, mode", [
    ("ivf+wcd+rwmd", "exact"),
    ("wcd+rwmd", "exact"),
    ("ivf+wcd+rwmd", "refine"),
])
def test_search_matches_dense_reference(topical, index, prune, mode):
    engine = WmdEngine(index, lam=LAM, n_iter=N_ITER)
    queries = _queries(topical, 6, seed=1)
    res = engine.search(queries, K, prune=prune, mode=mode,
                        refine_factor=-(-N_DOCS // K))   # full coverage
    _assert_exact_topk(res, _dense_reference(topical, queries), K)
    # the bounds pruned, and a group's candidates spanned several tiles
    stats = engine.host_stats()
    if mode == "exact":
        assert (res.solved < N_DOCS).all(), res.solved
    assert stats["survivor_tiles"] > 2 * len(index.groups), stats
    assert len({g.docs.idx.shape[1] for g in index.groups}) > 1


def _compiles():
    count = [0]

    def on_event(name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            count[0] += 1
    jax.monitoring.register_event_duration_secs_listener(on_event)
    return count


def _warm_rows(width, n):
    rng = np.random.default_rng(width * 10 + n)
    rows = []
    for _ in range(n):
        row = np.zeros(V, np.float32)
        row[rng.choice(V, width, replace=False)] = 1.0 / width
        rows.append(row)
    return rows


def test_fresh_searches_compile_nothing_after_warm_up(topical, index):
    """Warm every staged width and live-query count once, at k = N (every
    doc a seed, so every doc group's tile solve compiles) and at the
    served k; searches of fresh queries then compile no program, whatever
    their candidate counts."""
    engine = WmdEngine(index, lam=LAM, n_iter=N_ITER)
    widths = sorted({chunk[1] for n in range(4, 41)
                     for chunk in engine._plan(_warm_rows(n, 1))[1]})
    for width in widths:
        for n in (1, 2, 4):
            for k in (N_DOCS, K):
                engine.search(_warm_rows(width, n), k, prune="ivf+wcd+rwmd")
    count = _compiles()
    for seed in (2, 3, 4):
        res = engine.search(_queries(topical, 4, seed), K,
                            prune="ivf+wcd+rwmd")
        assert (res.solved < N_DOCS).all()
    assert count[0] == 0


@pytest.mark.parametrize("tile", [TILE, N_DOCS])
def test_no_gather_exceeds_the_tile_bound(topical, index, monkeypatch, tile):
    """Every tile holds ``tile`` doc slots, or its group's doc count where
    that is smaller (at ``N_DOCS`` every group is smaller), or FEW slots
    at the widest group's width."""
    monkeypatch.setattr(index_mod, "TILE_DOCS", tile)
    sizes = {}
    for g in index.groups:
        sizes.setdefault(g.docs.idx.shape[1], set()).add(
            min(tile, g.cols.shape[0]))
    sizes[max(sizes)].add(FEW)
    shapes = []
    real = index_mod._gather_g

    def gather(kq, idx, **kw):
        g = real(kq, idx, **kw)
        shapes.append((kq.shape, idx.shape, g.nbytes))
        return g

    monkeypatch.setattr(index_mod, "_gather_g", gather)
    engine = WmdEngine(index, lam=LAM, n_iter=N_ITER)
    engine.search(_queries(topical, 4, seed=5), K, prune="ivf+wcd+rwmd")
    assert shapes
    for (q, _, b), (t, length), nbytes in shapes:
        assert t in sizes[length], (t, length, sizes)
        assert nbytes <= q * length * b * t * 4


def test_inflight_bound_keeps_results(topical, index, monkeypatch):
    """With room for one gathered tile in flight, every tile's result is
    read back before the next is dispatched; the answers do not change."""
    queries = _queries(topical, 4, seed=6)
    engine = WmdEngine(index, lam=LAM, n_iter=N_ITER)
    want = engine.search(queries, K, prune="ivf+wcd+rwmd")
    monkeypatch.setattr(index_mod, "INFLIGHT_BYTES", 1)
    got = engine.search(queries, K, prune="ivf+wcd+rwmd")
    np.testing.assert_array_equal(got.indices, want.indices)
    np.testing.assert_array_equal(got.distances, want.distances)
    np.testing.assert_array_equal(got.solved, want.solved)


def test_few_candidates_take_one_tile_across_groups(topical, index,
                                                     monkeypatch):
    """A candidate set of at most FEW_DOCS docs is one tile of that many
    slots at the widest group's width, whatever groups its docs are in;
    the answers equal the per-group tiles'."""
    queries = _queries(topical, 2, seed=7)
    want = WmdEngine(index, lam=LAM, n_iter=N_ITER).search(
        queries, K, prune="ivf+wcd+rwmd")
    few = 64
    monkeypatch.setattr(index_mod, "FEW_DOCS", few)
    widest = max(g.docs.idx.shape[1] for g in index.groups)
    shapes = []
    real = index_mod.WmdEngine._solve_group

    def solve_group(self, kq, r, mask, grp, **kw):
        shapes.append(grp.docs.idx.shape)
        return real(self, kq, r, mask, grp, **kw)

    monkeypatch.setattr(index_mod.WmdEngine, "_solve_group", solve_group)
    engine = WmdEngine(index, lam=LAM, n_iter=N_ITER)
    got = engine.search(queries, K, prune="ivf+wcd+rwmd")
    assert (few, widest) in shapes, shapes      # the seeds, <= 2 * K docs
    np.testing.assert_array_equal(got.indices, want.indices)
    np.testing.assert_allclose(got.distances, want.distances, rtol=1e-6)
    np.testing.assert_array_equal(got.solved, want.solved)
