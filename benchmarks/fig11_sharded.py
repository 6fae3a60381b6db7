"""fig11: sharded corpus serving — prune+solve scaling over a device mesh.

The ROADMAP's scale-out scenario (ISSUE 7): the corpus is partitioned
into cluster-aligned doc shards (whole IVF clusters per shard, greedy
bin-packed by doc count), each shard runs the ENTIRE cascade locally on
its own device, and the global top-k is ONE all_gather + local top_k.

Run on a forced multi-device CPU mesh:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python -m benchmarks.fig11_sharded

Contract gates (asserted BEFORE any timing):
- sharded top-k == single-device top-k at nprobe=None for every shard
  count (tie-tolerant set equality + sorted-distance match);
- the merge jaxpr contains EXACTLY one all_gather and no other
  collective (the structural single-collective guarantee).

Records: ``fig11.wall_s{S}`` end-to-end search wall (us) per shard count
(gated by compare.py via the ``fig11.wall`` prefix), plus informational
``fig11.speedup_s4`` (wall_s1 / wall_s4 ratio), ``fig11.merge_us_s4``
(merge-collective wall per search), and ``fig11.collective_frac_s4``
(merge as a fraction of total wall — the carried measurement note: the
residual pmax contributes ZERO on this path because per-shard cascades
are collective-free, so the merge IS the entire communication budget a
future multi-host design starts from).

Scaling: wall_s1/wall_s4 >= 1.6x is asserted only when the host has >= 4
cores and FIG11_SMOKE is off — shard parallelism is real thread/device
overlap, which a 1-core container or a noisy smoke run cannot show; the
trajectory records stay honest either way. TPU-pod notes live in
``repro/core/shard_index.py``'s module docstring.
"""
from __future__ import annotations

import os

import numpy as np

from .common import row, timeit

LAM = 4.0
TOL = 1e-3
SHARD_COUNTS = (1, 2, 4)


def _tie_tolerant_equal(ref, res, rtol=2e-4):
    """Top-k set equality up to ties: sorted distances match, and every
    returned id's distance matches the reference distance at its rank."""
    nq, k = ref.indices.shape
    for qi in range(nq):
        rd, sd = np.sort(ref.distances[qi]), np.sort(res.distances[qi])
        if not np.allclose(rd, sd, rtol=rtol, equal_nan=True):
            return False, f"query {qi}: distance mismatch {rd} vs {sd}"
        only_ref = set(ref.indices[qi]) - set(res.indices[qi])
        for doc in only_ref:    # tie slots: distance must still be matched
            pos = np.where(ref.indices[qi] == doc)[0][0]
            if not np.isclose(ref.distances[qi][pos], sd[pos], rtol=rtol):
                return False, f"query {qi}: doc {doc} not a tie"
    return True, ""


def main(out=print) -> None:
    smoke = os.environ.get("FIG11_SMOKE") == "1"
    n_docs = 512 if smoke else 4096
    vocab = 1024 if smoke else 4096
    n_queries = 4 if smoke else 8
    n_clusters = 32 if smoke else 64
    k = 10

    from repro.runtime.sharding import ensure_host_devices
    # raises when too few devices are visible (e.g. a combined
    # benchmarks.run invocation whose backend initialized single-device):
    # fig11 then needs its own process, and CI runs it as a dedicated step
    ensure_host_devices(max(SHARD_COUNTS))

    import jax
    from repro.core import (ShardedWmdEngine, WmdEngine, build_index,
                            count_collectives, shard_corpus)
    from repro.data.corpus import make_corpus

    corpus = make_corpus(vocab_size=vocab, embed_dim=32, n_docs=n_docs,
                         n_queries=n_queries, seed=7)
    queries = list(corpus.queries)
    kw = dict(lam=LAM, n_iter=15, tol=TOL)

    index = build_index(corpus.docs, corpus.vecs, n_clusters=n_clusters)
    ref_engine = WmdEngine(index, **kw)
    ref = ref_engine.search(queries, k, prune="ivf+wcd+rwmd")

    walls = {}
    merge_us = {}
    for s in SHARD_COUNTS:
        sindex = shard_corpus(corpus.docs, corpus.vecs, s,
                              n_clusters=n_clusters)
        engine = ShardedWmdEngine(sindex, **kw)
        # ---- contract gates, BEFORE timing -------------------------------
        res = engine.search(queries, k, prune="ivf+wcd+rwmd")
        ok, why = _tie_tolerant_equal(ref, res)
        assert ok, f"fig11 exactness gate ({s} shards): {why}"
        if s == 1:
            # shard-count-1 must be bit-compatible, not just tie-equal
            assert np.array_equal(ref.indices, res.indices), \
                "fig11: 1-shard indices differ from single-device"
        packed = np.zeros((s, n_queries, 2 * k), np.float32)
        jaxpr = jax.make_jaxpr(engine._merge_fn(k))(packed)
        colls = count_collectives(jaxpr)
        n_ag = sum(v for p, v in colls.items() if "all_gather" in p)
        assert n_ag == 1 and sum(colls.values()) == 1, \
            f"fig11 single-collective gate: merge jaxpr has {colls}"
        # ---- timing ------------------------------------------------------
        engine.reset_iter_stats()       # also zeroes merge_seconds
        wall = timeit(lambda e=engine: e.search(queries, k,
                                                prune="ivf+wcd+rwmd"),
                      warmup=1, iters=3 if smoke else 5)
        n_searches = (1 + (3 if smoke else 5))  # warmup + timed
        merge_us[s] = engine.merge_seconds / n_searches * 1e6
        walls[s] = wall * 1e6
        out(row(f"fig11.wall_s{s}", walls[s],
                f"search wall | {s} shards | docs/shard "
                f"{list(engine.docs_per_shard)}"))

    speedup = walls[1] / walls[max(SHARD_COUNTS)]
    out(row(f"fig11.speedup_s{max(SHARD_COUNTS)}", speedup,
            "wall_s1 / wall_s4 ratio (info, not a wall time)"))
    out(row(f"fig11.merge_us_s{max(SHARD_COUNTS)}",
            merge_us[max(SHARD_COUNTS)],
            "top-k merge collective wall per search"))
    frac = merge_us[max(SHARD_COUNTS)] / walls[max(SHARD_COUNTS)]
    out(row(f"fig11.collective_frac_s{max(SHARD_COUNTS)}", frac,
            "merge / total wall (residual pmax: structurally zero on "
            "this path)"))
    if not smoke and (os.cpu_count() or 1) >= 4:
        assert speedup >= 1.6, \
            f"fig11 scaling gate: {speedup:.2f}x < 1.6x at " \
            f"{max(SHARD_COUNTS)} shards"


if __name__ == "__main__":
    main()
