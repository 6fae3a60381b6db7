"""The engine's VMEM-resident solve (``WmdEngine._solve_group`` on a TPU).

On a TPU a fixed-iteration solve with no warm start runs
``kernels.sddmm_spmm.sinkhorn_resident`` over the gather's
(Q, L, B, N_pad) tile instead of the einsum; the K block hands that gather
its vocabulary-major (V, Q*B) kernel, whose rows it copies whole. Here the
platform probe is patched, so the kernel runs in Pallas interpret mode on
the CPU, and its answers are held to the einsum path's.
"""
import numpy as np
import jax.numpy as jnp
import pytest

import repro.core.index as index_mod
from repro.core import LamUnderflowError, WmdEngine, build_index
from repro.core.index import _gather_g
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
    stats = eng.host_stats()
    assert stats["resident_solves"] == chunks * len(index.groups)
    assert stats["row_gathers"] == stats["resident_solves"]
    assert np.isnan(got[-1]).all() and np.isnan(want[-1]).all()
    np.testing.assert_allclose(got[:-1], want[:-1], rtol=1e-5)
    assert eng.iter_stats().tolist() == einsum.iter_stats().tolist()


@pytest.mark.parametrize("k", [5, 19])
@pytest.mark.parametrize("prune", ["rwmd", "ivf+wcd+rwmd"])
def test_search_topk_matches_einsum(index, queries, monkeypatch, prune, k):
    live = queries[:-1]
    einsum = WmdEngine(index, lam=LAM, n_iter=N_ITER)
    want = einsum.search(live, k=k, prune=prune)
    assert einsum.host_stats()["resident_solves"] == 0
    monkeypatch.setattr(index_mod, "_on_tpu", lambda x: True)
    eng = WmdEngine(index, lam=LAM, n_iter=N_ITER)
    got = eng.search(live, k=k, prune=prune)
    stats = eng.host_stats()
    assert stats["resident_solves"] > 0
    assert stats["row_gathers"] == stats["resident_solves"]
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
    """The resident solve runs only on a TPU, without ``tol``; every
    other solve keeps its path. Warm starts and profiles exist only with
    ``tol`` set (``WmdEngine._warms_survivors``), so they keep theirs."""
    monkeypatch.setattr(index_mod, "_on_tpu", lambda x: case != "cpu")
    adaptive = case in ("tpu+tol", "tpu+profile", "tpu+warm")
    eng = WmdEngine(index, lam=LAM, n_iter=N_ITER,
                    tol=1e-3 if adaptive else None,
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
                                "resident_solves": int(resident),
                                "row_gathers": int(resident),
                                "search_queries": 0, "pairs_solved": 0,
                                "slots_solved": 0, "survivor_tiles": 0,
                                "prune_syncs": 0}
    assert wmd.shape == (r.shape[0], grp.docs.idx.shape[0])
    v, (q, b) = index.vecs.shape[0], r.shape
    assert kq[0].shape == ((v, q * b) if resident else (q, v, b))
    eng.reset_host_stats()
    assert eng.host_stats()["resident_solves"] == 0
    assert eng.host_stats()["row_gathers"] == 0


@pytest.mark.parametrize("kw", [{"want_profile": True}, {"x0q": "warm"}])
def test_resident_hand_over_refuses_warm_start_and_profile(index, queries,
                                                           on_tpu, kw):
    """The vocabulary-major hand-over feeds the fixed-iteration resident
    solve only: a warm start or a profile asked of it raises rather than
    being dropped."""
    eng = WmdEngine(index, lam=LAM, n_iter=N_ITER)
    sup, r, mask = eng._prep_chunk(queries[1:3], 16)
    if "x0q" in kw:
        kw = {"x0q": jnp.full(r.shape, 1.0 / 16)}
    with pytest.raises(ValueError, match="tol"):
        eng._solve_group(eng._kq(sup, mask), r, mask, index.groups[0], **kw)
    assert eng.host_stats()["resident_solves"] == 0


@pytest.mark.parametrize("log_domain", [False, True])
@pytest.mark.parametrize("b", [24, 48, 192])
@pytest.mark.parametrize("q", [1, 2, 4])
def test_row_gather_equals_element_gather(q, b, log_domain):
    """The whole-row gather from the vocabulary-major (V, Q*B) kernel is
    the element gather of its (Q, V, B) transpose, bit for bit: pad docs
    (the doc count is not a multiple of ``block_n``) are word-0 columns,
    and masked query words keep their -inf rows in the log domain."""
    rng = np.random.default_rng(q * 1000 + b)
    v, n_docs, length, block_n = 97, 45, 7, 16
    kt = rng.uniform(0.0, 1.0, (v, q * b)).astype(np.float32)
    mask = rng.uniform(size=q * b) < 0.8
    kt[:, ~mask] = -np.inf if log_domain else 0.0
    if log_domain:
        kt[:, mask] = np.log(kt[:, mask])
    idx = rng.integers(0, v, (n_docs, length)).astype(np.int32)
    got = _gather_g(jnp.asarray(kt), jnp.asarray(idx), layout="qlbn",
                    block_n=block_n, q=q)
    kq = jnp.transpose(jnp.asarray(kt).reshape(v, q, b), (1, 0, 2))
    n_pad = -(-n_docs // block_n) * block_n
    idx_pad = np.zeros((n_pad, length), np.int32)
    idx_pad[:n_docs] = idx
    want = jnp.swapaxes(jnp.take(kq, jnp.asarray(idx_pad).T, axis=1), 2, 3)
    assert got.shape == want.shape == (q, length, b, n_pad)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_kcache_on_resident_path_keeps_topk(index, queries, on_tpu):
    """With a K cache attached, the resident path takes the cached
    block reordered vocabulary-major and returns the same top-k as the
    one-shot K block, fallback and cached chunks alike."""
    live = queries[:-1]
    plain = WmdEngine(index, lam=LAM, n_iter=N_ITER)
    want = plain.search(live, k=5, prune="ivf+wcd+rwmd")
    eng = WmdEngine(index, lam=LAM, n_iter=N_ITER, kcache_min_hits=1)
    assert eng.enable_kcache(256)
    for _ in range(2):          # the first pass warms, the second hits
        got = eng.search(live, k=5, prune="ivf+wcd+rwmd")
        np.testing.assert_array_equal(got.indices, want.indices)
        np.testing.assert_allclose(got.distances, want.distances,
                                   rtol=1e-5)
    stats = eng.host_stats()
    assert stats["row_gathers"] == stats["resident_solves"] > 0
    cache = eng.kcache_stats()
    assert cache["hits"] > 0 and cache["fallbacks"] < cache["lookups"]


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
