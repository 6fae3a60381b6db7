"""The engine's VMEM-resident solve (``WmdEngine._solve_group`` on a TPU).

On a TPU a fixed-iteration solve with no warm start runs
``kernels.sddmm_spmm.sinkhorn_resident`` over the gather's
(Q, L, B, N_pad) tile instead of the einsum. Here the platform probe is
patched, so the kernel runs in Pallas interpret mode on the CPU, and its
answers are held to the einsum path's.
"""
import numpy as np
import jax.numpy as jnp
import pytest

import repro.core.index as index_mod
from repro.core import LamUnderflowError, WmdEngine, build_index
from repro.data.corpus import make_corpus
from repro.kernels import ops

LAM, N_ITER = 1.0, 10
# query lengths: chunks of 1, 2, 3 (padded to 4 with a filler) and 4
# queries, staged at widths 8, 16, 32 and 48, and one empty query
LENGTHS = (3, 12, 14, 20, 25, 30, 40, 45, 41, 33, 0)


@pytest.fixture(scope="module")
def corpus():
    return make_corpus(vocab_size=300, embed_dim=8, n_docs=60, n_queries=1,
                       words_per_doc=(2, 37), seed=3)


@pytest.fixture(scope="module")
def index(corpus):
    idx = build_index(corpus.docs, corpus.vecs)
    widths = [g.docs.idx.shape[1] for g in idx.groups]
    assert len(widths) == 4 and any(w % 8 for w in widths), widths
    return idx


@pytest.fixture(scope="module")
def queries(corpus):
    rng = np.random.default_rng(5)
    v = corpus.vecs.shape[0]
    rows = []
    for n in LENGTHS:
        row = np.zeros(v, np.float32)
        row[rng.choice(v, n, replace=False)] = rng.uniform(0.5, 2.0, n)
        rows.append(row)
    return rows


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(index_mod, "_on_tpu", lambda x: True)


@pytest.mark.parametrize("precision", ["fp32", "log", "bf16"])
def test_query_batch_matches_einsum(index, queries, precision, monkeypatch):
    einsum = WmdEngine(index, lam=LAM, n_iter=N_ITER, precision=precision)
    want = np.asarray(einsum.query_batch(queries))
    assert einsum.host_stats()["resident_solves"] == 0
    monkeypatch.setattr(index_mod, "_on_tpu", lambda x: True)
    eng = WmdEngine(index, lam=LAM, n_iter=N_ITER, precision=precision)
    got = np.asarray(eng.query_batch(queries))
    chunks = len(eng._plan(queries)[1])
    assert chunks == 4
    assert eng.host_stats()["resident_solves"] == chunks * len(index.groups)
    assert np.isnan(got[-1]).all() and np.isnan(want[-1]).all()
    np.testing.assert_allclose(got[:-1], want[:-1], rtol=1e-5)
    assert eng.iter_stats().tolist() == einsum.iter_stats().tolist()


@pytest.mark.parametrize("prune", ["rwmd", "ivf+wcd+rwmd"])
def test_search_topk_matches_einsum(index, queries, monkeypatch, prune):
    live = queries[:-1]
    einsum = WmdEngine(index, lam=LAM, n_iter=N_ITER)
    want = einsum.search(live, k=5, prune=prune)
    assert einsum.host_stats()["resident_solves"] == 0
    monkeypatch.setattr(index_mod, "_on_tpu", lambda x: True)
    eng = WmdEngine(index, lam=LAM, n_iter=N_ITER)
    got = eng.search(live, k=5, prune=prune)
    assert eng.host_stats()["resident_solves"] > 0
    np.testing.assert_array_equal(got.indices, want.indices)
    np.testing.assert_allclose(got.distances, want.distances, rtol=1e-5)
    np.testing.assert_array_equal(got.solved, want.solved)


@pytest.mark.parametrize("case, resident", [
    ("tpu", True),
    ("cpu", False),
    ("tpu+tol", False),
    ("tpu+profile", False),
    ("tpu+warm", False),
    ("tpu+kernel_impl", False),
])
def test_selection_rule(index, queries, monkeypatch, case, resident):
    """The resident solve runs only on a TPU, without ``tol``, a warm
    start or a profile asked for; every other solve keeps its path."""
    monkeypatch.setattr(index_mod, "_on_tpu", lambda x: case != "cpu")
    eng = WmdEngine(index, lam=LAM, n_iter=N_ITER,
                    tol=1e-3 if case == "tpu+tol" else None,
                    impl="kernel" if case == "tpu+kernel_impl" else "sparse")
    chunk = queries[1:3]
    sup, r, mask = eng._prep_chunk(chunk, 16)
    kq = eng._kq(sup, mask)
    grp = index.groups[0]
    kw = {}
    if case == "tpu+profile":
        kw["want_profile"] = True
    if case == "tpu+warm":
        kw["x0q"] = jnp.full(r.shape, 1.0 / 16)
    out = eng._solve_group(kq, r, mask, grp, n_live=2, **kw)
    wmd = out[0] if "want_profile" in kw else out
    assert eng.host_stats() == {"dispatches": 3, "host_syncs": 0,
                                "resident_solves": int(resident)}
    assert wmd.shape == (r.shape[0], grp.docs.idx.shape[0])
    eng.reset_host_stats()
    assert eng.host_stats()["resident_solves"] == 0


def test_underflow_raises_on_resident_path(corpus, index, on_tpu):
    """A lam at which K = exp(-lam*M) underflows to 0 for a doc word
    raises, as on the einsum path, instead of dropping the word."""
    eng = WmdEngine(index, lam=400.0, n_iter=N_ITER)
    row = np.zeros(corpus.vecs.shape[0], np.float32)
    row[[1, 2, 3]] = 1.0
    with pytest.raises(LamUnderflowError):
        eng.query_batch([row])
    assert eng.host_stats()["resident_solves"] > 0


@pytest.mark.parametrize("impl, tpu", [("sparse", False), ("sparse", True),
                                       ("kernel", False)])
def test_block_wide_underflow_raises(corpus, monkeypatch, impl, tpu):
    """A live query whose K underflows against every doc word of a block
    raises on every path, rather than scoring the block's docs 0 as an
    all-pad filler query would be: its words lie far from every doc
    word, so each G entry of the block is 0."""
    monkeypatch.setattr(index_mod, "_on_tpu", lambda x: tpu)
    vecs = np.asarray(corpus.vecs)
    far = np.full((3, vecs.shape[1]), 1e3, vecs.dtype)
    index = build_index(corpus.docs, np.concatenate([vecs, far]))
    eng = WmdEngine(index, lam=LAM, n_iter=N_ITER, impl=impl)
    row = np.zeros(index.vecs.shape[0], np.float32)
    row[-3:] = 1.0
    with pytest.raises(LamUnderflowError):
        eng.query_batch([row])
    assert eng.host_stats()["resident_solves"] == (
        len(index.groups) if tpu else 0)


@pytest.mark.parametrize("n_docs", [128, 200])
def test_resident_kernel_matches_fused_kernel(n_docs):
    """The two layouts of the shared body give one answer: the resident
    (L, B, N) tile against the kernel impl's (B, N, L) tile, inert pad
    docs and a zero pad query row included."""
    rng = np.random.default_rng(n_docs)
    q, b, length = 2, 8, 5
    g = rng.uniform(0.05, 1.0, (q, b, n_docs, length)).astype(np.float32)
    g[:, -1] = 0.0                            # a pad query row
    r = rng.uniform(0.5, 1.0, (q, b)).astype(np.float32)
    r[:, -1] = 1.0
    r[:, :-1] /= r[:, :-1].sum(axis=1, keepdims=True)
    val = rng.uniform(0.0, 1.0, (n_docs, length)).astype(np.float32)
    val[val < 0.3] = 0.0
    val[:, 0] = 0.5                           # every doc has a live word
    val /= val.sum(axis=1, keepdims=True)
    want = ops.sinkhorn_fused_all_batched(g, val, r, 2.0, 7, block_n=8)
    n_pad = -(-n_docs // 128) * 128
    tile = np.zeros((q, length, b, n_pad), np.float32)
    tile[..., :n_docs] = np.transpose(g, (0, 3, 1, 2))
    tile[..., n_docs:] = 0.5                  # pad docs: K columns, val 0
    mask = np.ones((q, b), np.float32)
    mask[:, -1] = 0.0
    got = ops.sinkhorn_resident(tile, val, r, mask, 2.0, 7)
    assert got.shape == (q, n_docs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)
