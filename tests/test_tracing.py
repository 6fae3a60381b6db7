"""Spans and host counters of ``WmdEngine.query_batch`` and
``WmdEngine.search``.

The engine opens ``jax.profiler.TraceAnnotation`` spans named ``wmd.*``
at its layer boundaries and counts its device layer calls, blocking
result reads and search work (``host_stats``). A CPU trace records the
spans on the ``/host:CPU`` plane, so the nesting, the counts and the
profiler's effect on the results are checked here without a chip.
"""
import asyncio
import glob
import os
from collections import Counter

import jax
import numpy as np
import pytest

from repro.core import WmdEngine, build_index
from repro.data.corpus import make_corpus


@pytest.fixture(scope="module")
def corpus():
    # query lengths fall in two v_r buckets, so a call stages two chunks
    return make_corpus(vocab_size=256, embed_dim=8, n_docs=40, n_queries=6,
                       words_per_doc=(3, 40), seed=3)


def _engine(corpus, **kw):
    return WmdEngine(build_index(corpus.docs, corpus.vecs), lam=2.0,
                     n_iter=5, **kw)


ZERO = {"dispatches": 0, "host_syncs": 0, "resident_solves": 0,
        "row_gathers": 0, "search_queries": 0, "pairs_solved": 0,
        "slots_solved": 0, "survivor_tiles": 0, "prune_syncs": 0}


def _plan_counts(engine, queries):
    _, chunks = engine._plan([np.asarray(q) for q in queries])
    return len(chunks), len(engine.index.groups)


def _program_spans(trace_dir):
    """{host line: [(name, start, end)]} of the ``wmd.*`` events."""
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    profile = jax.profiler.ProfileData.from_file(path)
    out = {}
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                     for e in line.events if e.name.startswith("wmd.")]
            if spans:
                out[(plane.name, line.name)] = spans
    return out


def test_spans_nest_inside_query_batch(corpus, tmp_path):
    engine = _engine(corpus)
    queries = list(corpus.queries)
    engine.query_batch(queries).block_until_ready()     # compile first
    with jax.profiler.trace(str(tmp_path)):
        engine.query_batch(queries).block_until_ready()
    lines = _program_spans(str(tmp_path))
    assert len(lines) == 1, lines.keys()
    (spans,) = lines.values()
    outer = [(s, e) for name, s, e in spans if name == "wmd.query_batch"]
    assert len(outer) == 1
    lo, hi = outer[0]
    for name, s, e in spans:
        assert lo <= s and e <= hi, (name, s, e, lo, hi)
    c, g = _plan_counts(engine, queries)
    assert c == 2
    assert Counter(name for name, _, _ in spans) == {
        "wmd.query_batch": 1, "wmd.plan": 1, "wmd.stage": c,
        "wmd.dispatch": c * (1 + g), "wmd.collect": c * g,
        "wmd.scatter": c * g, "wmd.return": 1}


@pytest.mark.parametrize("path", ["sparse", "resident"])
def test_host_stats_match_the_plan(corpus, path, monkeypatch):
    """The counters follow the plan on the einsum and (its platform probe
    patched) the resident solve, which also counts its solves."""
    import repro.core.index as index_mod
    monkeypatch.setattr(index_mod, "_on_tpu", lambda x: path == "resident")
    engine = _engine(corpus)
    queries = list(corpus.queries)
    assert engine.host_stats() == ZERO
    engine.query_batch(queries)
    engine.reset_host_stats()
    engine.query_batch(queries)
    engine.query_batch(queries[:1])
    c, g = _plan_counts(engine, queries)
    c1, _ = _plan_counts(engine, queries[:1])
    solves = (c + c1) * g if path == "resident" else 0
    assert engine.host_stats() == dict(
        ZERO, dispatches=(c + c1) * (1 + 2 * g), host_syncs=(c + c1) * g,
        resident_solves=solves, row_gathers=solves)
    engine.reset_host_stats()
    assert engine.host_stats() == ZERO


SEARCH_SPANS = ("wmd.probe", "wmd.seed", "wmd.threshold", "wmd.survivors",
                "wmd.rank")


def test_search_spans_nest_inside_search(corpus, tmp_path, monkeypatch):
    """The cascade's stages open their spans inside ``wmd.search``, once
    per call; the solves keep ``wmd.stage``/``wmd.dispatch``."""
    import repro.core.index as index_mod
    monkeypatch.setattr(index_mod, "TILE_DOCS", 8)
    engine = _engine(corpus)
    queries = list(corpus.queries)
    engine.search(queries, 3, prune="ivf+wcd+rwmd")     # compile first
    with jax.profiler.trace(str(tmp_path)):
        engine.search(queries, 3, prune="ivf+wcd+rwmd")
    (spans,) = _program_spans(str(tmp_path)).values()
    outer = [(s, e) for name, s, e in spans if name == "wmd.search"]
    assert len(outer) == 1
    lo, hi = outer[0]
    for name, s, e in spans:
        assert lo <= s and e <= hi, (name, s, e, lo, hi)
    count = Counter(name for name, _, _ in spans)
    assert {name: count[name] for name in SEARCH_SPANS} == dict.fromkeys(
        SEARCH_SPANS, 1)
    assert count["wmd.stage"] >= 1 and count["wmd.dispatch"] >= 1


@pytest.mark.parametrize("prune", ["ivf+wcd+rwmd", "wcd+rwmd"])
def test_search_counters_count_the_work(corpus, prune, monkeypatch):
    """``pairs_solved`` is the sum of ``SearchResult.solved`` over the
    live queries, ``survivor_tiles`` the tile solves dispatched and
    ``slots_solved`` their live queries times doc slots; ``prune_syncs``
    the prune stages' blocking reads (seed pick and survivor pass: two
    per cascade call, two per chunk of a full sweep); ``dispatches`` one
    K block per chunk, a gather and a solve per tile solve, and one row
    gather per tile."""
    import repro.core.index as index_mod
    tile = 8
    monkeypatch.setattr(index_mod, "TILE_DOCS", tile)
    monkeypatch.setattr(index_mod, "FEW_DOCS", tile)
    engine = _engine(corpus)
    queries = list(corpus.queries) + [np.zeros(corpus.vecs.shape[0])]
    solves, row_gathers = [], []
    real = index_mod.WmdEngine._solve_group
    real_rows = index_mod._tile_rows

    def solve_group(self, kq, r, mask, grp, n_live=None, **kw):
        solves.append((n_live, grp.docs.idx.shape[0]))
        return real(self, kq, r, mask, grp, n_live=n_live, **kw)

    def tile_rows(*args):
        row_gathers.append(args[2].shape)
        return real_rows(*args)

    monkeypatch.setattr(index_mod.WmdEngine, "_solve_group", solve_group)
    monkeypatch.setattr(index_mod, "_tile_rows", tile_rows)
    res = engine.search(queries, 3, prune=prune)
    stats = engine.host_stats()
    live = res.solved > 0
    assert stats["search_queries"] == len(queries)
    assert stats["pairs_solved"] == int(res.solved[live].sum())
    assert stats["survivor_tiles"] == len(solves)
    assert all(t == tile for _, t in solves)
    assert stats["slots_solved"] == sum(n * t for n, t in solves)
    _, chunks = engine._plan([np.asarray(q) for q in queries])
    assert stats["prune_syncs"] == (2 if prune.startswith("ivf")
                                    else 2 * len(chunks))
    assert stats["dispatches"] == (len(chunks) + 2 * len(solves)
                                   + len(row_gathers))
    assert engine.last_solved_ids.size == int(res.solved.max())


def test_results_identical_with_profiler_on_and_off(corpus, tmp_path):
    engine = _engine(corpus)
    queries = list(corpus.queries) + [np.zeros(corpus.vecs.shape[0])]
    off = np.asarray(engine.query_batch(queries))
    with jax.profiler.trace(str(tmp_path)):
        on = np.asarray(engine.query_batch(queries))
    assert np.isnan(off[-1]).all()
    assert np.array_equal(off, on, equal_nan=True)


def test_sharded_host_stats_sum_the_shards(corpus):
    from repro.core import ShardedWmdEngine, shard_corpus
    engine = ShardedWmdEngine(shard_corpus(corpus.docs, corpus.vecs, 1,
                                           n_clusters=4),
                              lam=2.0, n_iter=5)
    queries = list(corpus.queries)
    engine.search(queries, 3, prune=None)
    (shard,) = engine.engines
    c, g = _plan_counts(shard, queries)
    assert engine.host_stats() == shard.host_stats() == dict(
        ZERO, dispatches=c * (1 + 2 * g), host_syncs=c * g,
        search_queries=len(queries))
    engine.reset_host_stats()
    assert engine.host_stats() == ZERO


def test_serving_stats_report_host_counters(corpus):
    from repro.runtime.serving import ServeConfig, ServingRuntime
    engine = _engine(corpus)
    rt = ServingRuntime(engine, ServeConfig(max_batch=2, window_s=0.02,
                                            deadline_s=None, prune=None))

    async def go():
        await rt.start()
        out = await asyncio.gather(*[rt.submit(q, k=3)
                                     for q in corpus.queries[:2]])
        await rt.stop()
        return out

    assert all(r.ok for r in asyncio.run(go()))
    assert rt.stats()["host"] == engine.host_stats()
    assert engine.host_stats()["dispatches"] > 0
