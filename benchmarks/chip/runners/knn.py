"""Closed loop, one client, into ``WmdEngine.search``: exact kNN.

Traffic parameters (``traffic/<name>.json``):

- ``queries_per_step``: queries handed to ``search`` at once; the next
  step starts when their top-k ids and distances are on the host;
- ``k``, ``prune``, ``nprobe``: the search's arguments (``nprobe`` null
  probes every cluster: the exact mode);
- ``pool_rate_qps``: the pool holds ``pool_rate_qps x seconds`` queries,
  drawn from the seed before the window, so that no query is sent twice
  unless the program answers more than that many per second;
- ``check``: how many of the window's queries, drawn from the seed, are
  compared with the plain reference.

The pool's lengths come in the same order on every seed, so every seed
sends the same length mix, step for step, on other words. The warm-up
runs ``search`` once for each query width the traffic can stage and each
live-query count a chunk can hold, on words the window never sends: at
``k`` = the corpus size (every doc is then a seed, so every doc group's
tile solve compiles) and at the served ``k``. Every program's shapes
then follow only these, so the window compiles nothing. The warm-up runs
in threads, so that their compiles overlap.
"""
from __future__ import annotations

import math
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np

import data
import harness

WARM_THREADS = 4

# Ties, relative: on a v5e the program's distances came within 1.64e-3 of
# the reference's over 19 seeds, so two docs whose reference distances
# differ by less than twice that may rank either way. A returned doc
# within TIE of the reference's kth distance is a tie, not a miss.
TIE = 4e-3


def _synthetic_rows(cfg: dict, seed: int, width: int, count: int):
    rng = data.rng_for(seed, data.WARMUP, width, count)
    v = int(cfg["vocab_size"])
    rows = []
    for _ in range(count):
        row = np.zeros(v, np.float32)
        row[rng.choice(v, width, replace=False)] = 1.0 / width
        rows.append(row)
    return rows


def _warm(ctx: harness.Ctx, engine, search_kw: dict, per_step: int):
    """Every staged width x live-query count x {N, k}, in threads, each
    on an engine of its own over the shared index."""
    cfg = ctx.cell.config
    spec = cfg["query_words"]
    lengths = range(int(spec["min"]), int(spec["max"]) + 1)
    widths = sorted({width for n in lengths for _, width in engine._plan(
        [np.ones(n, np.float32)])[1]})
    kw = dict(cfg["engine"], **ctx.engine_override)
    k_values = (int(cfg["n_docs"]), int(search_kw["k"]))
    tasks = [(w, n, k) for w in widths for n in range(1, per_step + 1)
             for k in k_values]

    def one(task):
        width, n, k = task
        type(engine)(engine.index, **kw).search(
            _synthetic_rows(cfg, ctx.seed, width, n), **dict(search_kw, k=k))

    t0 = time.perf_counter()
    with ThreadPoolExecutor(WARM_THREADS) as pool:
        for _ in pool.map(one, tasks):
            pass
    # and the engine the window drives, once (its own host-side state)
    engine.search(_synthetic_rows(cfg, ctx.seed, widths[-1], per_step),
                  **search_kw)
    print(f"warm-up: {len(widths)} widths {widths}, {len(tasks)} searches, "
          f"{time.perf_counter() - t0:.1f} s", file=ctx.log, flush=True)


def _closed_loop(engine, rows, per_step: int, seconds: float,
                 search_kw: dict, nnz: np.ndarray):
    """Run ``search`` steps for ``seconds``; ``rows(i)`` gives the
    ``i``-th query as a full-vocabulary row. Returns (t0, t_end, per-step
    query positions, per-step (ids, distances) on the host, per-step
    (solved candidates, their words in all))."""
    steps, outs, work = [], [], []
    pos = 0
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while time.perf_counter() - t0 < seconds:
            idx = list(range(pos, pos + per_step))
            pos += per_step
            with jax.profiler.TraceAnnotation("bench.prepare"):
                batch = [rows(i) for i in idx]
            with jax.profiler.TraceAnnotation("bench.search"):
                res = engine.search(batch, **search_kw)
            solved = engine.last_solved_ids
            outs.append((res.indices, res.distances))
            work.append((int(solved.size), float(nnz[solved].sum())))
            steps.append(idx)
        t_end = time.perf_counter()
    return t0, t_end, steps, outs, work


def run(ctx: harness.Ctx) -> dict:
    cfg, tr = ctx.cell.config, ctx.cell.traffic
    per_step = int(tr["queries_per_step"])
    v = int(cfg["vocab_size"])
    search_kw = {"k": int(tr["k"]), "prune": tr["prune"],
                 "nprobe": tr["nprobe"]}
    engine = harness.new_engine(ctx)
    if not hasattr(engine, "last_solved_ids"):
        raise RuntimeError("the program under test does not report which "
                           "docs its search solved "
                           "(WmdEngine.last_solved_ids)")
    size = per_step * math.ceil(float(tr["pool_rate_qps"]) * ctx.seconds
                                / per_step)
    pool = data.make_queries(cfg, ctx.corpus.vocab, ctx.seed, size,
                             fixed_order=True)
    _warm(ctx, engine, search_kw, per_step)
    docs = ctx.corpus.docs
    engine.reset_host_stats()
    with harness.profiled(ctx.trace_dir):
        c0 = ctx.counter.count
        t_window = time.perf_counter()
        t0, t_end, steps, outs, work = _closed_loop(
            engine, lambda i: pool.dense(i % size, v), per_step,
            ctx.seconds, search_kw, docs.nnz)
    done = per_step * len(steps)
    print(f"pool {size} queries, window sent {done}"
          f" ({max(0, done - size)} repeated)", file=ctx.log, flush=True)
    return {"kind": "scan", "t_window": t_window,
            "window_s": t_end - t0, "compiles": ctx.counter.count - c0,
            "steps": steps, "outs": outs, "pool": pool,
            "queries_done": done, "k": search_kw["k"],
            "host_stats": engine.host_stats(),
            "step_vr": [[int(pool.nnz[i % size]) for i in s]
                        for s in steps],
            "step_solved": [n for n, _ in work],
            "step_solved_nnz": [s for _, s in work],
            "n_iter": int(cfg["engine"]["n_iter"]),
            "n_docs": int(cfg["n_docs"])}


def compare_knn(got: dict, ref: dict, k: int, tie: float) -> dict:
    """``got``: {query: (ids (k,), distances (k,))} of the program;
    ``ref``: {query: (N,) reference distances to every doc}.

    - ``dist_rel_err``: the widest relative gap between a returned
      distance and the reference's for the same doc (inf for a missing or
      non-finite answer);
    - ``topk_miss``: returned docs whose reference distance exceeds the
      reference's own kth smallest by more than the relative ``tie``;
    - ``nonfinite``: missing ids and non-finite distances;
    - ``checked``: queries compared."""
    err, miss, bad = 0.0, 0, 0
    for q in sorted(got):
        ids, dist = got[q]
        want = np.asarray(ref[q], np.float64)
        real = ids >= 0
        bad += int((~real).sum() + (~np.isfinite(dist[real])).sum())
        if not real.all() or not np.all(np.isfinite(dist)):
            err = np.inf
        ids = ids[real]
        d_ref = want[ids]
        gap = np.abs(np.asarray(dist[real], np.float64) - d_ref)
        err = max(err, float(np.max(gap / np.maximum(np.abs(d_ref), 1e-30),
                                    initial=0.0)))
        kth = np.sort(want)[k - 1]
        miss += int((d_ref > kth * (1.0 + tie)).sum())
    return {"dist_rel_err": err, "topk_miss": miss, "nonfinite": bad,
            "checked": len(got)}


def check(ctx: harness.Ctx, run: dict) -> tuple:
    """Readings of the sampled queries against the reference over every
    doc, with ties within :data:`TIE`; every answered query with a
    missing id or a non-finite distance counts as failed."""
    import reference
    cfg = ctx.cell.config
    pool = run["pool"]
    done = run["queries_done"]
    rng = data.rng_for(ctx.seed, data.SAMPLE)
    n = min(int(ctx.cell.traffic["check"]), done)
    picks = sorted(rng.choice(done, n, replace=False).tolist())
    per_step = len(run["steps"][0]) if run["steps"] else 1
    got = {}
    for i in picks:
        ids, dist = run["outs"][i // per_step]
        got[i] = (ids[i % per_step], dist[i % per_step])
    docs = ctx.corpus.docs
    rows = [i % len(pool.nnz) for i in picks]
    ref = reference.wmd_rows(
        ctx.corpus.vecs, docs.ids, docs.val, docs.nnz,
        [(pool.ids[i, :pool.nnz[i]], pool.val[i, :pool.nnz[i]])
         for i in rows],
        cfg["engine"]["lam"], cfg["engine"]["n_iter"])
    readings = compare_knn(got, dict(zip(picks, ref)), run["k"], TIE)
    failed = sum(int(np.any(ids < 0) or not np.all(np.isfinite(dist)))
                 for ids_b, dist_b in run["outs"]
                 for ids, dist in zip(ids_b, dist_b))
    return readings, done, failed
