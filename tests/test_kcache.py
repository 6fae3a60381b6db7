"""Cross-request K-column cache tests (ISSUE 10).

The cache's whole value proposition is "faster, bitwise identical" — so
the oracle here is the uncached engine, compared with ``np.array_equal``
(not allclose) across prune cascades, both precision domains, eviction
pressure, and streaming appends. The Zipfian tests pin the reuse model:
hit rate must rise with traffic skew, because skew is the reason the
cache exists.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from _hypothesis_compat import given, settings, st
from repro.core import PaddedDocs, WmdEngine, append_docs, build_index
from repro.core.kcache import KCache, _cdist_rows
from repro.data.corpus import make_corpus

LAM = 1.0
N_ITER = 10
VOCAB = 512


@pytest.fixture(scope="module")
def index(small_corpus):
    return build_index(small_corpus.docs, small_corpus.vecs)


def _engine(index, cached, precision="fp32", slots=256, min_hits=1, **kw):
    return WmdEngine(index, lam=LAM, n_iter=N_ITER, impl="sparse",
                     precision=precision,
                     kcache_slots=slots if cached else None,
                     kcache_min_hits=min_hits, **kw)


def _hist(ids, vocab=VOCAB, seed=0):
    """Query histogram with exactly ``ids`` as support."""
    rng = np.random.default_rng(seed)
    q = np.zeros(vocab, np.float32)
    q[np.asarray(ids)] = rng.random(len(ids)).astype(np.float32) + 0.1
    return q / q.sum()


def _assert_same(a, b, ctx=""):
    assert np.array_equal(np.asarray(a.indices), np.asarray(b.indices)), \
        f"top-k membership differs {ctx}"
    assert np.array_equal(np.asarray(a.distances),
                          np.asarray(b.distances)), \
        f"distances differ {ctx} (bit-exact contract broken)"


# -------------------------------------------------------------- unit level
def test_kcache_rejects_zero_slots(small_corpus, index):
    with pytest.raises(ValueError):
        KCache(index.vecs, index.vecs_sq, 0)


def test_kernel_impl_refuses_cache(index):
    """``impl="sparse"`` is the engine's only value: a config asking for
    the deleted ``"kernel"`` impl fails loudly, with or without a cache."""
    with pytest.raises(ValueError, match="impl"):
        WmdEngine(index, lam=LAM, impl="kernel", kcache_slots=8)
    with pytest.raises(ValueError, match="impl"):
        WmdEngine(index, lam=LAM, impl="kernel")
    eng = WmdEngine(index, lam=LAM, impl="sparse")
    assert eng.enable_kcache(8) is True
    assert eng.kcache_stats() is not None


def test_rows_match_direct_cdist_and_lru_evicts_oldest(rng):
    vecs = jnp.asarray(rng.standard_normal((24, 4)).astype(np.float32))
    vecs_sq = jnp.sum(vecs * vecs, axis=-1)
    cache = KCache(vecs, vecs_sq, slots=4)

    def ref(ids):
        return np.asarray(_cdist_rows(jnp.asarray(np.asarray(ids,
                                                             np.int32)),
                                      vecs, vecs_sq))

    ids = np.asarray([3, 7, 11])
    assert cache.lookup(ids) == 0
    got = np.asarray(cache.rows(ids))[:3]
    assert np.array_equal(got, ref(ids))
    assert cache.stats()["used"] == 3 and cache.inserts == 3

    # fill the last slot, then miss twice: the two least-recently used
    # words (3 and 7 were touched before 20) are the victims
    cache.rows(np.asarray([20]))
    assert cache.stats()["used"] == 4
    cache.rows(np.asarray([1, 2]))
    assert cache.evictions == 2
    assert set(cache._slot_of) == {11, 20, 1, 2}
    # evicted words recompute correctly on re-entry
    back = np.asarray(cache.rows(np.asarray([3])))[:1]
    assert np.array_equal(back, ref([3]))
    st_ = cache.stats()
    assert st_["hits"] == 0 and st_["misses"] == 3 and st_["lookups"] == 1


def test_warm_fills_free_slots_only(rng):
    vecs = jnp.asarray(rng.standard_normal((16, 4)).astype(np.float32))
    vecs_sq = jnp.sum(vecs * vecs, axis=-1)
    cache = KCache(vecs, vecs_sq, slots=4)
    cache.rows(np.asarray([0, 1, 2]))          # 3 resident, 1 free

    sup = np.asarray([[5, 6, 7]])              # fallback chunk, 3 cold
    mq = jnp.asarray(np.stack(
        [np.asarray(_cdist_rows(jnp.asarray(sup[0].astype(np.int32)),
                                vecs, vecs_sq)).T]))     # (1, V, 3)
    cache.warm(sup, mq)
    # warming never evicts: only the single free slot was filled
    assert cache.evictions == 0
    assert cache.stats()["used"] == 4
    assert 5 in cache._slot_of
    w = np.asarray(cache.rows(np.asarray([5])))[:1]
    assert np.array_equal(
        w, np.asarray(_cdist_rows(jnp.asarray(np.asarray([5], np.int32)),
                                  vecs, vecs_sq)))


def test_rebind_drops_entries_keeps_counters(rng):
    vecs = jnp.asarray(rng.standard_normal((16, 4)).astype(np.float32))
    vecs_sq = jnp.sum(vecs * vecs, axis=-1)
    cache = KCache(vecs, vecs_sq, slots=8)
    cache.lookup(np.asarray([1, 2]))
    cache.rows(np.asarray([1, 2]))
    fresh = cache.rebind(vecs * 2.0, vecs_sq * 4.0)
    assert fresh.stats()["used"] == 0
    assert fresh.misses == 2 and fresh.inserts == 2
    assert fresh.vecs is not cache.vecs


# --------------------------------------------------- engine-level oracle
@settings(max_examples=6, deadline=None)
@given(prune=st.sampled_from(["rwmd", "wcd+rwmd", "ivf+wcd+rwmd"]),
       precision=st.sampled_from(["fp32", "bf16", "log", "bf16+log"]),
       slots=st.sampled_from([32, 128, 512]),
       min_hits=st.integers(min_value=1, max_value=6))
def test_cache_on_equals_cache_off(prune, precision, slots, min_hits):
    """The exactness contract, property-swept: any prune cascade, either
    precision domain, any capacity (including ones small enough to force
    the oversize fallback), any dispatch-economy threshold — cache-on
    search results are BITWISE the cache-off results, cold and warm."""
    corpus = make_corpus(vocab_size=VOCAB, embed_dim=32, n_docs=64,
                         n_queries=3, seed=7)
    index = build_index(corpus.docs, corpus.vecs)
    off = _engine(index, cached=False, precision=precision)
    on = _engine(index, cached=True, precision=precision, slots=slots,
                 min_hits=min_hits)
    queries = list(corpus.queries)
    for pass_ in ("cold", "warm"):
        _assert_same(off.search(queries, 5, prune=prune),
                     on.search(queries, 5, prune=prune),
                     f"({pass_}, {prune}, {precision}, slots={slots}, "
                     f"min_hits={min_hits})")
    stats = on.kcache_stats()
    assert stats["lookups"] > 0


def test_eviction_pressure_keeps_exactness(index):
    """Capacity pressure: a stream whose working set exceeds the slot
    count forces LRU evictions mid-stream — and every answer along the
    way still matches the uncached engine bit for bit."""
    on = _engine(index, cached=True, slots=24, min_hits=1)
    off = _engine(index, cached=False)
    a = _hist(range(40, 52), seed=1)               # 12 words
    b = _hist(list(range(40, 44)) + list(range(200, 216)), seed=2)
    for step, q in enumerate([a, b, a, b]):
        _assert_same(off.search([q], 5, prune="rwmd"),
                     on.search([q], 5, prune="rwmd"), f"(step {step})")
    stats = on.kcache_stats()
    assert stats["evictions"] > 0, stats
    assert stats["hits"] > 0, stats


def test_oversize_chunk_falls_back_exactly(index):
    """A chunk with more unique words than slots can't be cached — it
    must take the one-shot GEMM (counted ``oversize``) and stay exact."""
    on = _engine(index, cached=True, slots=8, min_hits=1)
    off = _engine(index, cached=False)
    q = _hist(range(100, 120), seed=3)             # 20 words > 8 slots
    _assert_same(off.search([q], 5, prune="rwmd"),
                 on.search([q], 5, prune="rwmd"), "(oversize)")
    stats = on.kcache_stats()
    assert stats["oversize"] > 0 and stats["fallbacks"] > 0
    assert stats["used"] <= 8


def test_append_then_search_matches_rebuild_with_warm_cache():
    """``append_docs`` reuses the embedding table by object identity, so
    a WARM cache sails through the append untouched (no rebind, hits keep
    landing) and post-append answers match both the uncached engine on
    the same index (bitwise) and a from-scratch rebuild (numerically)."""
    full = make_corpus(vocab_size=VOCAB, embed_dim=32, n_docs=96,
                       n_queries=4, seed=11)
    head = PaddedDocs(idx=full.docs.idx[:64], val=full.docs.val[:64])
    tail = PaddedDocs(idx=full.docs.idx[64:], val=full.docs.val[64:])
    queries = list(full.queries)

    on = _engine(build_index(head, full.vecs), cached=True, min_hits=1)
    on.search(queries, 5, prune="rwmd")            # warm the cache
    cache_obj = on._kcache
    assert cache_obj.stats()["used"] > 0

    on.index = append_docs(on.index, tail)
    on.reset_kcache_stats()
    appended = on.search(queries, 5, prune="rwmd")
    assert on._kcache is cache_obj                 # no rebind on append
    assert on.kcache_stats()["hits"] > 0           # warm rows survived

    off = _engine(on.index, cached=False)
    _assert_same(off.search(queries, 5, prune="rwmd"), appended,
                 "(post-append)")
    rebuilt = _engine(build_index(full.docs, full.vecs),
                      cached=False).search(queries, 5, prune="rwmd")
    for qi in range(len(queries)):
        assert set(np.asarray(appended.indices[qi]).tolist()) == \
            set(np.asarray(rebuilt.indices[qi]).tolist())
        np.testing.assert_allclose(np.asarray(appended.distances[qi]),
                                   np.asarray(rebuilt.distances[qi]),
                                   rtol=1e-5, atol=1e-6)


def test_swapped_index_rebinds_cache(small_corpus, index):
    """A DIFFERENT embedding table object (rebuilt index, reloaded
    snapshot) invalidates every resident row: the engine swaps in a
    fresh cache on its next staged chunk, results stay correct."""
    on = _engine(index, cached=True, min_hits=1)
    queries = list(small_corpus.queries)
    on.search(queries, 5, prune="rwmd")
    old = on._kcache
    assert old.stats()["used"] > 0

    on.index = build_index(small_corpus.docs, small_corpus.vecs)
    res = on.search(queries, 5, prune="rwmd")
    assert on._kcache is not old                   # rebound
    off = _engine(on.index, cached=False)
    _assert_same(off.search(queries, 5, prune="rwmd"), res, "(rebound)")


def test_zipf_hit_rate_monotone_in_skew(index):
    """The reuse model itself: hit rate must RISE with traffic skew
    (seeded streams; s=0 is uniform — the cache's worst case)."""
    from benchmarks.fig15_kcache import zipf_queries
    rates = []
    for s in (0.0, 0.8, 1.6):
        eng = _engine(index, cached=True, slots=64, min_hits=1)
        stream = zipf_queries(24, VOCAB, words=10, s=s, seed=5)
        for i in range(0, len(stream), 4):
            eng.search(stream[i:i + 4], 5, prune="rwmd")
        rates.append(eng.kcache_stats()["hit_rate"])
    assert rates == sorted(rates), rates
    assert rates[-1] > rates[0], rates
