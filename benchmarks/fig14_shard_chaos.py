"""Beyond-paper Fig 14: shard-level chaos drill — kill a shard mid-load,
serve honest partials, snapshot-restore back to exact (ISSUE 9).

The scale-out story (fig11) assumed every shard answers every fan-out.
This drill is the failure half of that contract, run as one open-loop
scenario on a forced 2-device CPU mesh:

1. *snapshot first*: the warmed 2-shard engine writes per-shard
   snapshots (``snapshot_shards``) and a never-failed exact baseline is
   recorded at ``nprobe=None``.
2. *crash window*: the seeded :class:`FaultInjector` kills shard 1 on
   every fan-out attempt starting a few dispatches into the request
   stream (``crash_shard``/``crash_after`` keyed on the engine's public
   ``fanouts`` counter, so the window is deterministic, not timed).
3. *partial serving, asserted*: EVERY submitted request resolves (result
   or structured error — zero process deaths); once shard retries burn
   and the circuit opens, responses are tagged ``partial`` with
   ``missing_shards == [1]``, coverage == shard 0's doc fraction, a
   recall caveat, and ``exact`` forced off.
4. *recovery, measured*: ``revive_shard()`` + ``engine.restore_shard(1)``
   rebuilds the dead shard from its snapshot; the drill asserts the
   restore-then-search result is BIT-COMPATIBLE with the never-failed
   baseline (same indices, same distances) and reports time-to-exact-
   recovery. A second injector-free stream then confirms no partials.

Records: ``fig14.p50`` (ok-response end-to-end latency during the crash
window, gated by compare.py) and ``fig14.recovery_s`` (revive -> first
exact full-coverage search, compile included — that IS the recovery a
pager sees; gated loosely as a wall time).

``FIG14_SMOKE=1`` shrinks the corpus/request counts; all asserts still
gate. Needs its own process (2 forced host devices) — CI runs it as a
dedicated step, and a combined ``benchmarks.run`` invocation without
``XLA_FLAGS`` prints a skip instead of failing.
"""
from __future__ import annotations

import argparse
import os
import tempfile
import time

import numpy as np

from .common import row

K = 10
PRUNE = "ivf+wcd+rwmd"
N_SHARDS = 2
CRASHED = 1          # the shard the drill kills
DEADLINE_S = 2.0


def _setup(smoke: bool):
    """Sharded engine with drill-friendly fault knobs: fast retries, a
    2-strike breaker, and a snapshot dir for the recovery phase."""
    from repro.core import ShardedWmdEngine, shard_corpus
    from repro.data.corpus import make_corpus
    n_docs = 256 if smoke else 2048
    corpus = make_corpus(vocab_size=1024 if smoke else 4096,
                         embed_dim=32, n_docs=n_docs,
                         n_queries=16, seed=0)
    sindex = shard_corpus(corpus.docs, corpus.vecs, N_SHARDS,
                          n_clusters=16 if smoke else 32)
    engine = ShardedWmdEngine(
        # shard_timeout_s is generous ON PURPOSE: first-touch compiles of
        # fresh batch shapes can take ~10s on a small CI box, and this
        # drill's partials must come from the injected crash, not from a
        # compile racing a tight deadline (the timeout path has its own
        # tests)
        sindex, lam=1.0, n_iter=15, tol=1e-3,
        shard_timeout_s=60.0, shard_retries=1, shard_backoff_s=0.002,
        fail_threshold=2, probe_every=3,
        snapshot_dir=tempfile.mkdtemp(prefix="fig14_snap_"))
    return corpus, engine


def _warm(engine, queries) -> float:
    """Compile every tier outside the measured stream; return the exact
    tier's closed-loop capacity estimate (queries/s)."""
    from repro.runtime.serving import rwmd_topk
    c = min(engine.cluster_counts)
    for bs in (8, 4, 2, 1):   # pow2 ladder: open-loop batches are 1..8
        batch = [queries[i % len(queries)] for i in range(bs)]
        engine.search(batch, K, prune=PRUNE)
        engine.search(batch, K, prune=PRUNE, nprobe=max(1, c // 4))
        rwmd_topk(engine, batch, K)
    batch = [queries[i % len(queries)] for i in range(8)]
    t0 = time.perf_counter()
    engine.search(batch, K, prune=PRUNE)
    dt = time.perf_counter() - t0
    engine.reset_iter_stats()
    return len(batch) / max(dt, 1e-6)


def _drive(engine, queries, n: int, rate: float, injector=None):
    from repro.runtime.serving import (ServeConfig, ServingRuntime,
                                       poisson_arrivals, run_open_loop)
    runtime = ServingRuntime(
        engine,
        ServeConfig(max_batch=8, window_s=0.01, max_queue=64,
                    deadline_s=DEADLINE_S, prune=PRUNE, backoff_s=0.002,
                    seed=9),
        injector=injector)
    reqs = [queries[i % len(queries)] for i in range(n)]
    arrivals = poisson_arrivals(n, rate_per_s=rate, seed=9)
    responses, stats = run_open_loop(runtime, reqs, arrivals, k=K)
    assert len(responses) == n, (
        f"runtime lost requests: {len(responses)}/{n} resolved")
    unresolved = [r for r in responses if not r.ok and r.error is None]
    assert not unresolved, f"unstructured failures: {unresolved}"
    return responses, stats


def run_chaos(out=print, smoke: bool | None = None) -> dict:
    """The CI shard-chaos drill; returns the final stats dict."""
    smoke = bool(os.environ.get("FIG14_SMOKE")) if smoke is None else smoke

    from repro.runtime.sharding import ensure_host_devices
    ensure_host_devices(N_SHARDS)     # raises when too few are visible

    from repro.runtime.serving import FaultInjector

    corpus, engine = _setup(smoke)
    queries = list(corpus.queries)
    cap = _warm(engine, queries)
    engine.snapshot()                     # recovery source, post-warmup
    baseline = engine.search(queries, K, prune=PRUNE)
    assert engine.last_coverage.full, "baseline must be full-coverage"

    frac0 = engine.docs_per_shard[1 - CRASHED] / engine.n_docs
    n = 24 if smoke else 64

    # ---- phase A: crash window opens a few dispatches into the stream
    injector = FaultInjector(seed=7, crash_shard=CRASHED,
                             crash_after=engine.fanouts + 2)
    responses, stats = _drive(engine, queries, n, rate=0.5 * cap,
                              injector=injector)
    partials = [r for r in responses if r.ok and r.partial]
    assert partials, (
        f"crash window never produced a partial response: "
        f"tiers={stats['tiers']} errors={stats['errors']}")
    for r in partials:
        assert r.missing_shards == [CRASHED], r.missing_shards
        assert abs(r.coverage - frac0) < 1e-3, (r.coverage, frac0)
        assert not r.exact, "partial response must never claim exactness"
        assert "PARTIAL" in (r.caveat or ""), r.caveat
    assert stats["partial"] == len(partials)
    health = stats["shard_health"]
    assert health["opened"][CRASHED] >= 1, (
        f"breaker never opened for shard {CRASHED}: {health}")
    lat = np.asarray([r.queue_ms + r.service_ms
                      for r in responses if r.ok])
    out(row("fig14.p50", float(np.percentile(lat, 50)) * 1e3,
            f"end-to-end ms*1e3 during crash window; {len(partials)}/{n} "
            f"partial (coverage {frac0:.2%}) "
            f"breaker opened={health['opened'][CRASHED]} "
            f"probes={health['probes'][CRASHED]}"))

    # ---- recovery: revive + snapshot-restore, then prove exactness
    t0 = time.monotonic()
    injector.revive_shard()
    engine.restore_shard(CRASHED)
    res = engine.search(queries, K, prune=PRUNE)
    recovery_s = time.monotonic() - t0
    assert engine.last_coverage.full, engine.last_coverage
    assert np.array_equal(baseline.indices, res.indices), \
        "restore-then-search indices diverge from never-failed baseline"
    assert np.array_equal(
        np.nan_to_num(np.asarray(baseline.distances), nan=-1.0),
        np.nan_to_num(np.asarray(res.distances), nan=-1.0)), \
        "restore-then-search distances diverge from baseline"
    out(row("fig14.recovery_s", recovery_s * 1e6,
            "revive -> restore_shard -> first exact full-coverage "
            "search (compile included; usec of wall)"))

    # ---- phase B: injector-free stream must be partial-free again
    responses, stats = _drive(engine, queries, n, rate=0.5 * cap)
    bad = [r for r in responses if not r.ok or r.partial]
    assert not bad, (
        f"post-recovery stream not clean: "
        f"{[(r.rid, r.ok, r.partial) for r in bad]}")
    return stats


def main(out=print) -> None:
    run_chaos(out=out)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--chaos", action="store_true",
                    help="run the shard-kill drill (CI serve-chaos job): "
                         "asserts every request resolves, partials carry "
                         "honest coverage, and snapshot restore returns "
                         "the engine to bit-exact full coverage")
    args = ap.parse_args()
    stats = run_chaos()
    if args.chaos and stats:
        print(f"shard-chaos OK: {stats['submitted']} submitted, "
              f"{stats['errors']} structured errors, 0 unhandled, "
              f"recovery exact")
