"""Serving launchers.

Two servers, matching the paper's two workload kinds:

LM decode server (assigned archs):
    PYTHONPATH=src python -m repro.launch.serve --arch granite_3_2b \
        --reduced --batch 4 --steps 32

WMD query server (the paper's own workload — query documents against the
whole corpus through the persistent batched engine; ``--batch-queries Q``
scores Q stream requests per fused solve; ``--top-k K`` switches to the
staged retrieval pipeline — prune with ``--prune`` bounds, Sinkhorn-solve
only the surviving candidates, rank):
    PYTHONPATH=src python -m repro.launch.serve --wmd --n-docs 2048 \
        --batch-queries 8
    PYTHONPATH=src python -m repro.launch.serve --wmd --n-docs 2048 \
        --top-k 10 --prune rwmd
    PYTHONPATH=src python -m repro.launch.serve --wmd --n-docs 8192 \
        --top-k 10 --prune ivf+wcd+rwmd --nprobe 8   # sub-O(Q*N) prune
    PYTHONPATH=src python -m repro.launch.serve --wmd --n-docs 8192 \
        --top-k 10 --prune ivf+pivot+wcd+rwmd --mode refine \
        --refine-factor 4      # rank-then-refine: bounded solve budget

Async serving runtime (``--serve``, ISSUE 6): the long-lived front-end —
deadline-or-full micro-batching, bounded-queue backpressure, tiered
degradation under load, per-dispatch retry/watchdog, optional seeded
fault injection. Drives an open-loop request stream at ``--rate`` qps and
prints one JSON line per request plus a summary record:
    PYTHONPATH=src python -m repro.launch.serve --wmd --serve \
        --n-docs 2048 --top-k 10 --requests 64 --rate 50
    PYTHONPATH=src python -m repro.launch.serve --wmd --serve \
        --requests 64 --rate 200 --inject-transient-rate 0.2 \
        --inject-poison-rate 0.05 --inject-seed 3     # chaos drill

Shard-level fault tolerance (ISSUE 9): with ``--shards N --serve`` the
fan-out is deadline-bounded (``--shard-timeout-ms``) and shard-site
faults can be injected (``--inject-shard-crash`` etc.); responses
covering fewer docs than the full corpus are tagged ``partial`` with
honest coverage. ``--snapshot-dir`` writes per-shard snapshots after
warmup so a dead shard can be restored bit-compatibly. SIGTERM/SIGINT
drain the admission queue (graceful shutdown) instead of dropping
in-flight work:
    PYTHONPATH=src python -m repro.launch.serve --wmd --serve --shards 2 \
        --n-docs 2048 --top-k 10 --requests 64 --shard-timeout-ms 2000 \
        --inject-shard-crash 1 --inject-shard-crash-after 8 \
        --snapshot-dir /tmp/wmd-snap
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ARCH_IDS, get_config
from repro.models import model as M
from repro.models import transformer as T


def serve_lm(args) -> None:
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    cache = T.init_cache(cfg, args.batch, max_len=args.steps + 8)
    step = jax.jit(M.make_serve_step(cfg))
    tok = jnp.ones((args.batch, 1), jnp.int32)
    times = []
    for i in range(args.steps):
        t0 = time.time()
        tok, logits, cache = step(params, cache, tok)
        tok.block_until_ready()
        times.append(time.time() - t0)
    times = np.asarray(times[2:]) * 1e3
    print(json.dumps({
        "arch": cfg.name, "batch": args.batch, "steps": args.steps,
        "ms_per_token_p50": round(float(np.percentile(times, 50)), 2),
        "ms_per_token_p99": round(float(np.percentile(times, 99)), 2),
        "tokens_per_s": round(args.batch / (times.mean() / 1e3), 1),
    }))


def _build_wmd_engine(args, corpus):
    """Engine construction shared by serve_wmd/serve_async: the
    single-device engine by default; with ``--shards N`` the corpus is
    partitioned cluster-aligned over an N-device mesh. ``main()`` forces
    host-platform devices right after argparse (before the first jax
    array op); the ``ensure_host_devices`` here re-validates the count
    for callers that enter below ``main()``."""
    kw = dict(lam=args.lam, n_iter=args.n_iter,
              tol=args.tol if args.tol > 0 else None,
              check_every=args.check_every, precision=args.precision,
              scope=args.scope, warm_start=args.warm_start)
    if getattr(args, "kcache_slots", -1) > 0:
        # explicit opt-in at engine build; -1 leaves it to the serving
        # runtime's default-on behaviour (ServeConfig.kcache_slots), 0
        # disables there too
        kw["kcache_slots"] = args.kcache_slots
    if args.shards > 1:
        from repro.core import ShardedWmdEngine, shard_corpus
        from repro.runtime.sharding import ensure_host_devices
        ensure_host_devices(args.shards)
        sindex = shard_corpus(corpus.docs, corpus.vecs, args.shards,
                              n_clusters=args.n_clusters)
        timeout = getattr(args, "shard_timeout_ms", 0.0)
        return ShardedWmdEngine(
            sindex,
            shard_timeout_s=timeout / 1e3 if timeout > 0 else None,
            snapshot_dir=getattr(args, "snapshot_dir", None), **kw)
    from repro.core import WmdEngine, build_index
    # corpus side frozen ONCE; every request after this touches only its
    # own (v_r, ...) slice of work ('auto'/numeric strings parsed by
    # build_index itself)
    index = build_index(corpus.docs, corpus.vecs,
                        n_clusters=args.n_clusters)
    return WmdEngine(index, **kw)


def serve_wmd(args) -> None:
    from repro.core.sinkhorn import LamUnderflowError
    from repro.data.corpus import make_corpus
    from repro.data.pipeline import wmd_request_stream
    corpus = make_corpus(vocab_size=args.vocab, embed_dim=args.embed_dim,
                         n_docs=args.n_docs, n_queries=8, seed=0)
    engine = _build_wmd_engine(args, corpus)
    reqs = wmd_request_stream(corpus)
    bq = max(1, args.batch_queries)
    prune = None if args.prune == "none" else args.prune
    nprobe = args.nprobe if args.nprobe > 0 else None

    def score(batch):
        if args.top_k > 0:
            res = engine.search(batch, args.top_k, prune=prune,
                                nprobe=nprobe, mode=args.mode,
                                refine_factor=args.refine_factor)
            jax.block_until_ready(res.distances)
            return res
        d = engine.query_batch(batch)
        jax.block_until_ready(d)
        return d

    times = []
    solved = []
    underflows = 0
    for i in range(args.steps):
        batch = [next(reqs) for _ in range(bq)]
        t0 = time.time()
        try:
            out = score(batch)
        except LamUnderflowError:
            # per-request isolation (ISSUE 6 satellite): lam underflow is
            # deterministic for the query that hit it — re-score one at a
            # time so its batchmates still get answers, and emit the
            # failing request's diagnostics as a structured JSON error
            # instead of killing the server
            out = None
            for qi, q in enumerate(batch):
                try:
                    sub = score([q])
                    out = sub if out is None else out
                except LamUnderflowError as e:
                    underflows += 1
                    print(json.dumps({
                        "step": i, "query": qi, "ok": False,
                        "error": {"code": "lam_underflow",
                                  "underflow_report": str(e)}}))
        if i == 0 and out is not None:
            if args.top_k > 0:
                print(f"query 0 -> top-3 docs "
                      f"{out.indices[0][:3].tolist()}")
            else:
                top = np.argsort(np.asarray(out[0]))[:3]
                print(f"query 0 -> top-3 docs {top.tolist()}")
        if args.top_k > 0 and out is not None:
            solved.append(float(out.solved.mean()))
        times.append(time.time() - t0)
    times = np.asarray(times[1:]) * 1e3
    p50 = float(np.percentile(times, 50))   # median: late batches may still
    rec = {                                 # compile fresh bucket shapes
        "workload": "wmd_topk" if args.top_k > 0 else "wmd_batched",
        "n_docs": args.n_docs, "vocab": args.vocab, "batch_queries": bq,
        "ms_per_batch_p50": round(p50, 2),
        "queries_per_s": round(bq / (p50 / 1e3), 1),
        "docs_per_s": round(bq * args.n_docs / (p50 / 1e3), 0),
        "precision": engine.precision.name,
        "iter_stats_dropped": engine.iter_stats_dropped,
    }
    if underflows:
        rec["underflow_errors"] = underflows
    iters = engine.iter_stats()
    if args.tol > 0 and iters.size:
        rec["tol"] = args.tol
        rec["scope"] = args.scope
        rec["solve_iters_mean"] = round(float(iters.mean()), 1)
        rec["solve_iters_max"] = int(iters.max())
        # per-stage realized counts (ISSUE 5): the warm-start win is the
        # "survivor" series relative to the cold "seed" solves
        by_stage = engine.iter_stats_by_stage()
        for st, arr in by_stage.items():
            if arr.size:
                rec[f"solve_iters_{st}_mean"] = round(float(arr.mean()), 1)
        if args.warm_start:
            rec["warm_start"] = True
    if args.top_k > 0:
        rec["top_k"] = args.top_k
        rec["prune"] = args.prune
        if args.mode != "exact":
            rec["mode"] = args.mode
            rec["refine_factor"] = args.refine_factor
        if solved:
            rec["solved_frac"] = round(float(np.mean(solved))
                                       / args.n_docs, 4)
        if args.prune.startswith("ivf"):
            counts = getattr(engine, "cluster_counts", None) \
                or (engine.index.clusters.n_clusters,)
            rec["n_clusters"] = (list(counts) if len(counts) > 1
                                 else counts[0])
            rec["nprobe"] = nprobe if nprobe else \
                ("all" if len(counts) > 1 else counts[0])
    if getattr(engine, "n_shards", 1) > 1:
        rec["shards"] = engine.n_shards
        rec["docs_per_shard"] = list(engine.docs_per_shard)
    print(json.dumps(rec))


def serve_async(args) -> None:
    """ISSUE 6 front-end: drive the long-lived :class:`ServingRuntime`
    open-loop and print per-request JSON lines + a summary record."""
    from repro.data.corpus import make_corpus
    from repro.data.pipeline import wmd_request_stream
    from repro.runtime.serving import (FaultInjector, ServeConfig,
                                       ServingRuntime, poisson_arrivals,
                                       run_open_loop)
    corpus = make_corpus(vocab_size=args.vocab, embed_dim=args.embed_dim,
                         n_docs=args.n_docs, n_queries=8, seed=0)
    engine = _build_wmd_engine(args, corpus)
    injector = None
    if args.inject_latency_rate or args.inject_transient_rate \
            or args.inject_poison_rate or args.inject_shard_latency_rate \
            or args.inject_shard_transient_rate \
            or args.inject_shard_crash >= 0:
        injector = FaultInjector(
            latency_rate=args.inject_latency_rate,
            latency_s=args.inject_latency_ms / 1e3,
            transient_rate=args.inject_transient_rate,
            poison_rate=args.inject_poison_rate,
            shard_latency_rate=args.inject_shard_latency_rate,
            shard_latency_s=args.inject_shard_latency_ms / 1e3,
            shard_transient_rate=args.inject_shard_transient_rate,
            crash_shard=args.inject_shard_crash,
            crash_after=args.inject_shard_crash_after,
            seed=args.inject_seed)
    cfg = ServeConfig(
        max_batch=max(1, args.batch_queries),
        window_s=args.window_ms / 1e3, max_queue=args.max_queue,
        deadline_s=args.deadline_ms / 1e3 if args.deadline_ms > 0 else None,
        prune="rwmd" if args.prune == "none" else args.prune,
        nprobe=args.nprobe if args.nprobe > 0 else None,
        refine_factor=args.refine_factor,
        kcache_slots=(args.kcache_slots if args.kcache_slots >= 0
                      else ServeConfig.kcache_slots))
    runtime = ServingRuntime(engine, cfg, injector=injector)
    # warm the compile caches OUTSIDE the measured stream: one dispatch per
    # tier (first-request latency would otherwise be compile time)
    reqs = wmd_request_stream(corpus)
    warm = [next(reqs) for _ in range(2)]
    for tier in runtime.tiers:
        if tier.solve:
            engine.search(warm, max(1, args.top_k), prune=cfg.prune,
                          nprobe=tier.nprobe, mode=tier.mode,
                          refine_factor=tier.refine_factor or 4)
        else:
            from repro.runtime.serving import rwmd_topk
            rwmd_topk(engine, warm, max(1, args.top_k))
    engine.reset_iter_stats()
    if args.snapshot_dir and hasattr(engine, "snapshot"):
        # take the recovery snapshot AFTER warmup so a mid-stream
        # restore_shard() rejoins with compile caches already primed
        engine.snapshot()
    n = max(1, args.requests)
    queries = [next(reqs) for _ in range(n)]
    arrivals = poisson_arrivals(n, rate_per_s=args.rate, seed=1)
    # handle_signals: SIGTERM/SIGINT drain the admission queue instead of
    # killing in-flight futures — late arrivals get `shutting_down`
    responses, stats = run_open_loop(runtime, queries, arrivals,
                                     k=max(1, args.top_k),
                                     handle_signals=True)
    for r in responses:
        print(json.dumps(r.to_json()))
    lat = np.asarray([r.queue_ms + r.service_ms for r in responses
                      if r.ok])
    span = float(arrivals[-1]) + max(
        (r.service_ms for r in responses), default=0.0) / 1e3
    print(json.dumps({
        "workload": "wmd_serve",
        "n_docs": args.n_docs, "requests": n, "rate_qps": args.rate,
        "latency_ms_p50": round(float(np.percentile(lat, 50)), 2)
        if lat.size else None,
        "latency_ms_p99": round(float(np.percentile(lat, 99)), 2)
        if lat.size else None,
        "throughput_qps": round(n / span, 1) if span > 0 else None,
        "stats": stats,
    }))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--wmd", action="store_true")
    ap.add_argument("--batch-queries", type=int, default=8)
    ap.add_argument("--top-k", type=int, default=0,
                    help="> 0: staged top-k retrieval (prune->solve->rank) "
                         "instead of exhaustive scoring")
    ap.add_argument("--prune", default="rwmd",
                    choices=["none", "wcd", "rwmd", "wcd+rwmd", "ivf+wcd",
                             "ivf+rwmd", "ivf+wcd+rwmd",
                             "ivf+pivot+wcd+rwmd", "ivf+pivot+rwmd"],
                    help="lower bound / cascade for the prune stage "
                         "(with --top-k); 'pivot' rungs read the index's "
                         "precomputed pivot-word triangle bounds")
    ap.add_argument("--nprobe", type=int, default=0,
                    help="ivf cascades: probe this many clusters per query "
                         "(0 = all = exact top-k; fewer trades recall for "
                         "prune speed)")
    ap.add_argument("--mode", default="exact",
                    choices=["exact", "refine"],
                    help="with --top-k: 'refine' ranks candidates by the "
                         "cascade's lower bound and Sinkhorn-solves only "
                         "the top refine-factor*k per query (distances "
                         "exact, membership approximate; recall measured "
                         "in fig13)")
    ap.add_argument("--refine-factor", type=int, default=4,
                    help="--mode refine: solve budget multiple (k' = "
                         "refine_factor*k; at a covering factor the "
                         "result equals the exact path)")
    ap.add_argument("--shards", type=int, default=0,
                    help="> 1: partition the corpus into this many "
                         "cluster-aligned doc shards over a device mesh "
                         "(forces host-platform CPU devices when no real "
                         "accelerators exist); per-shard cascades merge "
                         "through one top-k collective")
    ap.add_argument("--n-clusters", default=None,
                    help="IVF cluster count at index build (default: "
                         "sqrt(n_docs); 'auto' sweeps cluster-radius "
                         "statistics — dedup-style corpora want more)")
    ap.add_argument("--precision", default="fp32",
                    choices=["fp32", "bf16", "log", "bf16+log"],
                    help="solve-stage precision policy: bf16 GEMMs with "
                         "fp32 accumulation and/or the log-domain kernel "
                         "(underflow-free at any lam)")
    ap.add_argument("--tol", type=float, default=0.0,
                    help="> 0: convergence-adaptive solve — exit the "
                         "Sinkhorn loop at this relative doc-marginal "
                         "residual; --n-iter becomes a cap (realized counts "
                         "land on 1 + k*check-every)")
    ap.add_argument("--check-every", type=int, default=4,
                    help="adaptive solve: iterations between residual "
                         "checks")
    ap.add_argument("--scope", default="query",
                    choices=["chunk", "query"],
                    help="adaptive-exit granularity: 'query' scopes each "
                         "query's residual to its own candidate docs and "
                         "freezes it on convergence (one stubborn query "
                         "no longer stalls its chunkmates); 'chunk' keeps "
                         "the chunk-global scalar exit")
    ap.add_argument("--warm-start", action="store_true",
                    help="warm-start survivor solves from the seed "
                         "solve's converged per-query profile (with "
                         "--tol; sound when solves converge, see "
                         "WmdEngine docs)")
    ap.add_argument("--serve", action="store_true",
                    help="long-lived async serving runtime (ISSUE 6): "
                         "deadline-or-full micro-batching, backpressure, "
                         "tiered degradation, fault injection")
    ap.add_argument("--requests", type=int, default=32,
                    help="--serve: open-loop request count")
    ap.add_argument("--rate", type=float, default=50.0,
                    help="--serve: offered load (requests/s)")
    ap.add_argument("--window-ms", type=float, default=10.0,
                    help="--serve: coalescer deadline (a partial batch "
                         "dispatches once its oldest member waited this)")
    ap.add_argument("--max-queue", type=int, default=64,
                    help="--serve: admission bound (queued + in flight); "
                         "arrivals beyond it get structured rejections")
    ap.add_argument("--deadline-ms", type=float, default=500.0,
                    help="--serve: per-request deadline budget "
                         "(0 = none); blown budgets degrade, not drop")
    ap.add_argument("--kcache-slots", type=int, default=-1,
                    help="cross-request cdist-row cache capacity (ISSUE "
                         "10). -1 (default): engine built without a cache "
                         "but --serve enables its default "
                         "(ServeConfig.kcache_slots); 0: disabled "
                         "everywhere; > 0: enabled at engine build with "
                         "this many device-resident (V,) rows. Results "
                         "are bit-exact either way")
    ap.add_argument("--inject-latency-rate", type=float, default=0.0,
                    help="fault injection: per-attempt probability of "
                         "added dispatch latency")
    ap.add_argument("--inject-latency-ms", type=float, default=50.0)
    ap.add_argument("--inject-transient-rate", type=float, default=0.0,
                    help="fault injection: per-dispatch probability of a "
                         "transient first-attempt failure (retried)")
    ap.add_argument("--inject-poison-rate", type=float, default=0.0,
                    help="fault injection: per-request probability of a "
                         "poison request (isolated, structured error)")
    ap.add_argument("--inject-seed", type=int, default=0,
                    help="fault injection: deterministic replay seed")
    ap.add_argument("--shard-timeout-ms", type=float, default=30000.0,
                    help="sharded fan-out (--shards > 1): per-dispatch "
                         "deadline; shards that miss it are excluded from "
                         "the merge and the response is tagged partial "
                         "(0 = wait forever)")
    ap.add_argument("--snapshot-dir", default=None,
                    help="sharded engine: write per-shard snapshots here "
                         "after warmup; restore_shard() rebuilds a dead "
                         "shard from them (bit-compatible at nprobe=None)")
    ap.add_argument("--inject-shard-latency-rate", type=float, default=0.0,
                    help="fault injection: per-shard-attempt probability "
                         "of added latency inside the fan-out")
    ap.add_argument("--inject-shard-latency-ms", type=float, default=50.0)
    ap.add_argument("--inject-shard-transient-rate", type=float,
                    default=0.0,
                    help="fault injection: per-shard-attempt probability "
                         "of a transient failure (burns a shard retry)")
    ap.add_argument("--inject-shard-crash", type=int, default=-1,
                    help="fault injection: crash this shard id on every "
                         "attempt from --inject-shard-crash-after "
                         "onwards (-1 = off); responses go partial with "
                         "honest coverage until the shard is restored")
    ap.add_argument("--inject-shard-crash-after", type=int, default=0,
                    help="fan-out sequence number the crash window "
                         "opens at")
    ap.add_argument("--n-docs", type=int, default=1024)
    ap.add_argument("--vocab", type=int, default=8192)
    ap.add_argument("--embed-dim", type=int, default=64)
    # this synthetic corpus' distance scale is ~sqrt(2*embed_dim) ~ 11;
    # lam must keep lam*dist < ~87 or K underflows (the engine now raises)
    ap.add_argument("--lam", type=float, default=1.0)
    ap.add_argument("--n-iter", type=int, default=15)
    args = ap.parse_args()
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.shards > 1:
        # must run before make_corpus/engine build does the first jax
        # array op — forcing host devices after backend init is a no-op
        from repro.runtime.sharding import ensure_host_devices
        ensure_host_devices(args.shards)
    if args.serve:
        serve_async(args)
    elif args.wmd:
        serve_wmd(args)
    else:
        assert args.arch, "--arch required for LM serving"
        serve_lm(args)


if __name__ == "__main__":
    main()
