"""Closed loop, one client, into ``WmdEngine.query_batch``.

Traffic parameters (``traffic/<name>.json``):

- ``queries_per_step``: queries handed to ``query_batch`` at once; the
  next step starts when their distances to every doc are on the host;
- ``pool_rate_qps``: the pool holds ``pool_rate_qps x seconds`` queries,
  drawn from the seed before the window, so that no query is sent twice
  unless the program scores more than that many per second;
- ``check``: how many of the window's queries, drawn from the seed, are
  compared with the plain reference.

The pool's lengths come in the same order on every seed, so every seed
does the same work, step for step, on other words. The warm-up scores
one chunk of each width and live-query count the steps can stage, on
words the window never sends.
"""
from __future__ import annotations

import math
import time

import jax
import numpy as np

import checks
import data
import harness


def _warm_widths(spec: dict) -> list[int]:
    """Query widths the traffic can stage: multiples of 8 covering every
    query length it draws (the engine's staging grid)."""
    lo, hi = int(spec["min"]), int(spec["max"])
    return sorted({max(8, -(-n // 8) * 8) for n in range(lo, hi + 1)})


def _synthetic_rows(cfg: dict, seed: int, width: int, count: int):
    rng = data.rng_for(seed, data.WARMUP, width)
    v = int(cfg["vocab_size"])
    rows = []
    for _ in range(count):
        row = np.zeros(v, np.float32)
        row[rng.choice(v, width, replace=False)] = 1.0 / width
        rows.append(row)
    return rows


def _closed_loop(engine, rows, per_step: int, seconds: float):
    """Run ``query_batch`` steps for ``seconds``; ``rows(i)`` gives the
    ``i``-th query as a full-vocabulary row. Returns (t0, t_end, per-step
    query positions, per-step distances copied to the host)."""
    steps, outs = [], []
    pos = 0
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while time.perf_counter() - t0 < seconds:
            idx = list(range(pos, pos + per_step))
            pos += per_step
            with jax.profiler.TraceAnnotation("bench.prepare"):
                batch = [rows(i) for i in idx]
            with jax.profiler.TraceAnnotation("bench.query_batch"):
                out = engine.query_batch(batch)
            with jax.profiler.TraceAnnotation("bench.sync"):
                outs.append(np.asarray(out))
            steps.append(idx)
        t_end = time.perf_counter()
    return t0, t_end, steps, outs


def run(ctx: harness.Ctx) -> dict:
    cfg, tr = ctx.cell.config, ctx.cell.traffic
    per_step = int(tr["queries_per_step"])
    v = int(cfg["vocab_size"])
    engine = harness.new_engine(ctx)
    size = per_step * math.ceil(float(tr["pool_rate_qps"]) * ctx.seconds
                                / per_step)
    pool = data.make_queries(cfg, ctx.corpus.vocab, ctx.seed, size,
                             fixed_order=True)
    for width in _warm_widths(cfg["query_words"]):
        for n in range(1, per_step + 1):
            engine.query_batch(_synthetic_rows(cfg, ctx.seed, width, n)
                               ).block_until_ready()
    with harness.profiled(ctx.trace_dir):
        c0 = ctx.counter.count
        t_window = time.perf_counter()
        t0, t_end, steps, outs = _closed_loop(
            engine, lambda i: pool.dense(i % size, v), per_step,
            ctx.seconds)
    done = per_step * len(steps)
    print(f"pool {size} queries, window sent {done}"
          f" ({max(0, done - size)} repeated)", file=ctx.log, flush=True)
    docs = ctx.corpus.docs
    return {"kind": "scan", "t_window": t_window,
            "window_s": t_end - t0, "compiles": ctx.counter.count - c0,
            "steps": steps, "outs": outs, "pool": pool,
            "queries_done": done,
            "step_vr": [[int(pool.nnz[i % size]) for i in s]
                        for s in steps],
            "n_iter": int(cfg["engine"]["n_iter"]),
            "n_docs": int(cfg["n_docs"]),
            "docs_nnz_sum": float(docs.nnz.sum())}


def _texts_queries(texts: data.Texts, rows) -> list:
    return [(texts.ids[i, :texts.nnz[i]], texts.val[i, :texts.nnz[i]])
            for i in rows]


def check(ctx: harness.Ctx, run: dict) -> tuple:
    """Readings of the sampled queries against the reference; every
    scored query with a non-finite distance counts as failed."""
    import reference
    cfg = ctx.cell.config
    pool = run["pool"]
    done = run["queries_done"]
    rng = data.rng_for(ctx.seed, data.SAMPLE)
    n = min(int(ctx.cell.traffic["check"]), done)
    picks = sorted(rng.choice(done, n, replace=False).tolist())
    per_step = len(run["steps"][0]) if run["steps"] else 1
    got = {i: run["outs"][i // per_step][i % per_step] for i in picks}
    docs = ctx.corpus.docs
    ref = reference.wmd_rows(
        ctx.corpus.vecs, docs.ids, docs.val, docs.nnz,
        _texts_queries(pool, [i % len(pool.nnz) for i in picks]),
        cfg["engine"]["lam"], cfg["engine"]["n_iter"])
    readings = checks.compare_scan(got, dict(zip(picks, ref)))
    nonfinite = sum(int(not np.all(np.isfinite(o[j])))
                    for o in run["outs"] for j in range(len(o)))
    return readings, done, nonfinite
