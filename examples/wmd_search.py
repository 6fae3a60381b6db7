"""End-to-end serving driver: staged top-k WMD document retrieval.

    PYTHONPATH=src python examples/wmd_search.py [--n-docs 2048] [--queries 8]

The paper's practical use case ("find whether a tweet is similar to any
other tweets of a given day"): a stream of query documents retrieved
against the WHOLE corpus through the staged pipeline — the corpus index is
frozen once, queries are bucketed by support size, and each batch runs
*prune -> solve -> rank*: an admissible lower bound (``--prune``) excludes
most documents, the fused Sinkhorn solve runs only on the surviving
candidates, and the exact top-k comes back with latency stats and the
solved-fraction per query. ``--prune none`` scores every document
(exhaustive oracle); ``--mode refine`` bounds the solve budget to
``refine-factor * topk`` bound-ranked candidates per query (distances
stay exact, membership is approximate — fig13 measures the recall);
``--looped`` falls back to the seed per-query loop.
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

import jax
import numpy as np

from repro.core import WmdEngine, build_index, one_to_many
from repro.data.corpus import make_corpus

LAM = 4.0   # distance scale here is ~sqrt(2*64) ~ 11; keep lam*dist << 87


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-docs", type=int, default=2048)
    ap.add_argument("--vocab", type=int, default=8192)
    ap.add_argument("--queries", type=int, default=8)
    ap.add_argument("--topk", type=int, default=5)
    ap.add_argument("--prune", default="rwmd",
                    choices=["none", "wcd", "rwmd", "wcd+rwmd", "ivf+wcd",
                             "ivf+rwmd", "ivf+wcd+rwmd",
                             "ivf+pivot+wcd+rwmd", "ivf+pivot+rwmd"],
                    help="prune-stage lower bound or IVF cascade; "
                         "'none' = exhaustive; 'pivot' rungs use the "
                         "index's precomputed pivot triangle bounds")
    ap.add_argument("--nprobe", type=int, default=0,
                    help="ivf cascades: clusters probed per query "
                         "(0 = all = exact top-k)")
    ap.add_argument("--mode", default="exact", choices=["exact", "refine"],
                    help="'refine': rank candidates by the cascade's "
                         "bound, Sinkhorn-solve only the top "
                         "refine-factor*topk per query (needs --prune)")
    ap.add_argument("--refine-factor", type=int, default=4,
                    help="--mode refine: solve budget multiple")
    ap.add_argument("--impl", default="sparse",
                    help="--looped: the one_to_many impl, any "
                         "repro.core.IMPLS entry")
    ap.add_argument("--n-clusters", default=None,
                    help="IVF cluster count at index build (int, or 'auto' "
                         "to sweep cluster-radius statistics; default "
                         "sqrt(n_docs))")
    ap.add_argument("--precision", default="fp32",
                    choices=["fp32", "bf16", "log", "bf16+log"],
                    help="solve precision policy ('log' is underflow-free "
                         "at any lam)")
    ap.add_argument("--tol", type=float, default=0.0,
                    help="> 0: convergence-adaptive solve (exit at this "
                         "relative doc-marginal residual; 15 iters becomes "
                         "a cap)")
    ap.add_argument("--check-every", type=int, default=4,
                    help="adaptive solve: iterations between residual "
                         "checks")
    ap.add_argument("--scope", default="query", choices=["chunk", "query"],
                    help="adaptive-exit granularity (with --tol): 'query' "
                         "freezes each query at its own convergence, "
                         "'chunk' keeps the global scalar exit")
    ap.add_argument("--warm-start", action="store_true",
                    help="warm-start survivor solves from the seed "
                         "solve's converged profile (with --tol)")
    ap.add_argument("--shards", type=int, default=0,
                    help="> 1: cluster-aligned doc shards over a device "
                         "mesh (host-platform CPU devices are forced when "
                         "no accelerators exist); per-shard cascades, one "
                         "top-k merge collective")
    ap.add_argument("--batches", type=int, default=4,
                    help="timed engine passes over the query set")
    ap.add_argument("--looped", action="store_true",
                    help="seed per-query loop instead of the staged engine")
    args = ap.parse_args()

    if args.shards > 1:
        # must precede the first jax array op / device query below
        from repro.runtime.sharding import ensure_host_devices
        ensure_host_devices(args.shards)

    corpus = make_corpus(vocab_size=args.vocab, embed_dim=64,
                         n_docs=args.n_docs, n_queries=args.queries, seed=7)
    queries = list(corpus.queries)
    print(f"corpus: {args.n_docs} docs, vocab {args.vocab}, "
          f"{len(jax.devices())} device(s)")

    if args.looped:
        for q in queries:                                 # compile pass
            jax.block_until_ready(one_to_many(q, corpus.docs, corpus.vecs,
                                              lam=LAM, n_iter=15,
                                              impl=args.impl))
        lat = []
        rows = []
        for q in queries:
            t0 = time.perf_counter()
            rows.append(np.asarray(one_to_many(q, corpus.docs, corpus.vecs,
                                               lam=LAM, n_iter=15,
                                               impl=args.impl)))
            lat.append(time.perf_counter() - t0)
        d = np.stack(rows)
        batch_ms = [sum(lat) * 1e3]
        for qi, q in enumerate(queries):
            top = np.argsort(d[qi])[:args.topk]
            print(f"query {qi} (v_r={int((q > 0).sum())}): "
                  f"top-{args.topk} = {top.tolist()} "
                  f"d={np.round(d[qi][top], 3).tolist()}")
    else:
        prune = None if args.prune == "none" else args.prune
        nprobe = args.nprobe if args.nprobe > 0 else None
        kw = dict(lam=LAM, n_iter=15,
                  tol=args.tol if args.tol > 0 else None,
                  check_every=args.check_every, precision=args.precision,
                  scope=args.scope, warm_start=args.warm_start)
        if args.shards > 1:
            from repro.core import ShardedWmdEngine, shard_corpus
            sindex = shard_corpus(corpus.docs, corpus.vecs, args.shards,
                                  n_clusters=args.n_clusters)
            engine = ShardedWmdEngine(sindex, **kw)
            print(f"sharded: {engine.n_shards} cluster-aligned shards, "
                  f"docs/shard {list(engine.docs_per_shard)}, "
                  f"clusters/shard {list(engine.cluster_counts)}")
        else:
            index = build_index(corpus.docs, corpus.vecs,
                                n_clusters=args.n_clusters)  # frozen once;
            # 'auto'/numeric strings parsed by build_index itself
            engine = WmdEngine(index, **kw)
        res = engine.search(queries, args.topk, prune=prune,
                            nprobe=nprobe, mode=args.mode,
                            refine_factor=args.refine_factor)  # compile
        batch_ms = []
        for _ in range(args.batches):
            t0 = time.perf_counter()
            res = engine.search(queries, args.topk, prune=prune,
                                nprobe=nprobe, mode=args.mode,
                                refine_factor=args.refine_factor)
            batch_ms.append((time.perf_counter() - t0) * 1e3)
        for qi, q in enumerate(queries):
            print(f"query {qi} (v_r={int((q > 0).sum())}): "
                  f"top-{args.topk} = {res.indices[qi].tolist()} "
                  f"d={np.round(res.distances[qi], 3).tolist()} "
                  f"solved={int(res.solved[qi])}/{args.n_docs}")

    batch_ms = np.asarray(batch_ms)
    per_query = batch_ms.mean() / args.queries
    print(f"\nbatch latency p50={np.percentile(batch_ms, 50):.1f}ms "
          f"({args.queries} queries)  per-query={per_query:.2f}ms  "
          f"throughput={args.n_docs / (per_query / 1e3):,.0f} docs/s/query")
    if not args.looped and args.tol > 0:
        iters = engine.iter_stats()
        if iters.size:
            stages = ", ".join(
                f"{st}={arr.mean():.1f}" for st, arr in
                engine.iter_stats_by_stage().items() if arr.size)
            print(f"adaptive solve: realized iters/query "
                  f"mean={iters.mean():.1f} max={int(iters.max())} "
                  f"(cap 15, tol={args.tol:g}, scope={args.scope}; "
                  f"per-stage means: {stages})")


if __name__ == "__main__":
    main()
