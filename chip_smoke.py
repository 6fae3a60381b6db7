#!/usr/bin/env python3
"""Chip smoke test: WMD serving at the paper's corpus size on a TPU.

    python3 chip_smoke.py               # one chip: serving
    python3 chip_smoke.py --four-chips  # four chips: sharded search only

One chip. The paper's corpus (``paper_corpus``: V=100,000 words of w=300,
N=5,000 documents of 19-43 words) goes through the normal serving path:
``build_index`` -> ``WmdEngine(impl="sparse")`` -> ``ServingRuntime``
(default config: 512-slot K-column cache, ``ivf+wcd+rwmd`` cascade, exact
tier at ``nprobe=None``, 0.5 s deadline) -> ``run_open_loop``. Every batch
the coalescer can form from the request stream is compiled before the
stream starts. Then the answers are checked:

- every response is ``ok`` on the ``exact`` tier, the runtime counted no
  error, retry or isolation, and nothing compiled while serving;
- each served top-k equals the exhaustive ``query_batch`` top-k up to tie
  order;
- the sparse engine's distances agree with the dense reference
  (``core/sinkhorn.py``, full-precision matmuls) on a few queries and docs,
  and with an uncached sparse engine, which builds its K block with one
  stacked GEMM instead of from cached per-word rows. The resident Pallas
  solve's Mosaic compile is checked without a chip by
  ``tests/test_tpu_compile.py``.

Four chips. ``shard_corpus(paper_corpus, 4)`` searched at ``nprobe=None``
must equal the one-device engine's exhaustive top-k up to tie order; each
shard's index and its outputs sit on their own chip; the merge compiles to
one all-gather.

lam is 1.0, the serving CLI's default: the paper config's lam=10 makes
K = exp(-lam*M) underflow on this Gaussian w=300 corpus, whose word
distances sit near sqrt(2*300) ~ 24.5 (lam*M > ~87 is 0 in fp32), and the
engine raises ``LamUnderflowError`` there.

Every phase prints one JSON line. The last line is
``{"ok": true, "device": {...}}`` and is printed only when every check
passed; any failure exits non-zero without it. The script refuses to run
without a TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent

LAM, N_ITER, K = 1.0, 15, 10
N_REQUESTS, RATE_QPS = 32, 6.0
# the longest the host may hold back a submission; it widens the set of
# batches the warm-up covers (see coalescible_batches)
HOST_LAG_S = 0.25
# relative tolerances, fp32 on both sides of each comparison
TOPK_RTOL = 1e-4      # served vs exhaustive: same solver, other doc subsets
# the same K block from the other K builder (cached rows vs one stacked
# GEMM): only the fp32 rounding of the solve differs. Both builders reduce the query-word
# norms through ``sq_dists``, so a word's distance to itself (the
# square root of cancellation noise) is the same bits from either
SAME_K_RTOL = 1e-5
# the dense reference builds its own cdist: |a|^2 + |b|^2 - 2 a.b leaves
# a word's distance to itself at sqrt(cancellation noise), which differs
# from the engine's and moves docs that share a query word (1.5e-5
# relative on a v5e at seed 1)
DENSE_RTOL = 1e-4
N_DENSE_QUERIES, N_DENSE_DOCS = 2, 16


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


class Checks:
    """Failed checks, collected so that every phase still reports its
    numbers; the run passes only when this stays empty."""

    def __init__(self):
        self.failures: list[str] = []

    def expect(self, cond: bool, what: str) -> bool:
        if not cond:
            self.failures.append(what)
        return bool(cond)

    def run(self, name: str, fn, *args):
        """Run one phase; an exception fails the run, not the script."""
        try:
            return fn(*args)
        except Exception as e:  # noqa: BLE001 — every phase reports
            traceback.print_exc()
            self.failures.append(f"{name}: {type(e).__name__}: {e}")
            return None


class CompileCounter:
    """Counts XLA backend compiles (and their seconds) as they happen. A
    load from the persistent compile cache counts too: JAX times it as
    the same event, so a warm cache cannot hide a shape the warm-up
    missed."""

    def __init__(self):
        from jax import monitoring
        self.count, self.seconds = 0, 0.0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += secs

    def snapshot(self):
        return self.count, self.seconds


def max_rel_diff(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if a.size == 0:
        return 0.0
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))


def topk_mismatch(ids, dists, ref_row, k: int, rtol: float) -> str | None:
    """``None`` when (ids, dists) is the top-k of ``ref_row`` (all doc
    distances) up to tie order: k distinct ids, each within the k-th
    smallest reference distance, each distance matching its reference."""
    import numpy as np
    ids = np.asarray(ids)
    if len(set(ids.tolist())) != k:
        return f"{len(set(ids.tolist()))} distinct ids, want {k}"
    kth = np.sort(ref_row)[k - 1]
    ref = ref_row[ids]
    if np.any(ref > kth * (1 + rtol)):
        return f"doc {ids[np.argmax(ref)]} is outside the top-{k}"
    diff = max_rel_diff(dists, ref)
    if diff > rtol:
        return f"distance rel. diff {diff:.3g} > {rtol:g}"
    return None


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes() -> int | None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ------------------------------------------------------------ one chip
def phase_setup(seed: int):
    from repro.core import WmdEngine, build_index
    from repro.data.corpus import paper_corpus
    t0 = time.perf_counter()
    corpus = paper_corpus(seed=seed)
    index = build_index(corpus.docs, corpus.vecs)
    engine = WmdEngine(index, impl="sparse", lam=LAM, n_iter=N_ITER)
    emit("setup", seconds=time.perf_counter() - t0, vocab=index.vocab_size,
         embed_dim=index.embed_dim, n_docs=index.n_docs,
         n_queries=int(corpus.queries.shape[0]),
         n_clusters=int(index.clusters.n_clusters))
    return corpus, index, engine


def coalescible_batches(stream, arrivals, queries, cfg, min_bucket):
    """Every batch the runtime's coalescer can form from this stream.

    A batch is the requests of one v_r bucket, in arrival order, that
    reach the queue within ``window_s`` of its first one, at most
    ``max_batch`` of them. Host lag (a late submission or a late turn of
    the coalescer) can only add later arrivals to a batch, so any batch
    is a bucket's share of a run of consecutive arrivals spanning at most
    ``window_s + HOST_LAG_S``. Returns tuples of stream query positions,
    each batch once."""
    from repro.core.index import bucket_size
    bucket = [bucket_size(int((queries[i] > 0).sum()), min_bucket)
              for i in stream]
    span = cfg.window_s + HOST_LAG_S
    out = {}
    for lo in range(len(stream)):
        hi = lo
        while hi < len(stream) and arrivals[hi] - arrivals[lo] <= span:
            run = range(lo, hi + 1)
            for b in set(bucket[j] for j in run):
                batch = tuple(int(stream[j]) for j in run
                              if bucket[j] == b)[:cfg.max_batch]
                out[batch] = None
            hi += 1
    return list(out)


def phase_serve(checks, corpus, engine, counter, seed: int):
    """Warm every batch the stream can coalesce into, then serve it
    open-loop through the default runtime; returns the request stream
    as corpus query positions and the responses."""
    import numpy as np
    from repro.runtime.serving import (ServingRuntime, poisson_arrivals,
                                       run_open_loop)
    runtime = ServingRuntime(engine)
    cfg = runtime.cfg
    queries = list(corpus.queries)
    exact = runtime.tiers[0]
    checks.expect(exact.name == "exact" and exact.nprobe is None,
                  f"top tier is {exact}")
    rng = np.random.default_rng(seed)
    stream = rng.integers(0, len(queries), N_REQUESTS)
    arrivals = poisson_arrivals(N_REQUESTS, rate_per_s=RATE_QPS, seed=seed)
    batches = coalescible_batches(stream, arrivals, queries, cfg,
                                  engine.min_bucket)

    c0, s0 = counter.snapshot()
    t0 = time.perf_counter()
    # first each query alone, which fills the K-column cache through its
    # cold path; then every batch against the filled cache, as it serves
    for qi in sorted(set(stream.tolist())):
        engine.search([queries[qi]], K, prune=cfg.prune, nprobe=None)
    for batch in batches:
        engine.search([queries[qi] for qi in batch], K, prune=cfg.prune,
                      nprobe=None)
    engine.reset_iter_stats()
    c1, s1 = counter.snapshot()
    emit("warmup", seconds=time.perf_counter() - t0, batches=len(batches),
         largest_batch=max(map(len, batches)), compiles=c1 - c0,
         compile_seconds=s1 - s0)

    t0 = time.perf_counter()
    responses, stats = run_open_loop(runtime, [queries[i] for i in stream],
                                     arrivals, k=K)
    c2, s2 = counter.snapshot()
    bad = [r.to_json() for r in responses if not r.ok or r.tier != "exact"]
    service_ms = [r.service_ms for r in responses if r.ok]
    emit("serve", seconds=time.perf_counter() - t0, requests=len(responses),
         ok=sum(r.ok for r in responses), tiers=stats["tiers"],
         deadline_s=cfg.deadline_s, errors=stats["errors"],
         retries=stats["retries"], isolations=stats["isolations"],
         deadline_missed=stats["deadline_missed"],
         watchdog_trips=stats["watchdog_trips"],
         dispatches=stats["dispatches"],
         largest_batch=max((r.batch_size or 0 for r in responses),
                           default=0),
         service_ms_max=max(service_ms, default=None),
         compiles=c2 - c1, compile_seconds=s2 - s1,
         kcache=stats.get("kcache"))
    checks.expect(len(responses) == N_REQUESTS,
                  f"{len(responses)} responses for {N_REQUESTS} requests")
    checks.expect(not bad, f"responses not ok on the exact tier: {bad[:2]}")
    for key in ("errors", "retries", "isolations"):
        checks.expect(stats[key] == 0, f"runtime counted {stats[key]} {key}")
    checks.expect(c2 == c1, f"{c2 - c1} compiles inside the serving window")
    return stream, responses


def phase_exhaustive(checks, corpus, engine, served):
    """Exhaustive sparse scores of every corpus query; the served top-k
    lists are checked against them."""
    import numpy as np
    t0 = time.perf_counter()
    full = np.asarray(engine.query_batch(list(corpus.queries)))
    problems = []
    stream, responses = served if served is not None else ((), ())
    for qi, resp in zip(stream, responses):
        why = topk_mismatch(resp.indices, resp.distances, full[qi], K,
                            TOPK_RTOL)
        if why:
            problems.append(f"request {resp.rid} (query {qi}): {why}")
    emit("exhaustive", seconds=time.perf_counter() - t0,
         finite=bool(np.all(np.isfinite(full))),
         answers_checked=len(responses), rtol=TOPK_RTOL,
         mismatches=len(problems))
    checks.expect(bool(np.all(np.isfinite(full))),
                  "exhaustive scores are not finite")
    checks.expect(served is not None, "nothing was served to check")
    checks.expect(not problems, "; ".join(problems[:3]))
    return full


def phase_dense_reference(checks, corpus, full):
    """Sparse engine distances against the dense reference solver."""
    import jax
    import numpy as np
    from repro.core import one_to_many
    from repro.core.sparse import PaddedDocs
    t0 = time.perf_counter()
    docs = np.argsort(full[0])[:N_DENSE_DOCS // 2]
    docs = np.concatenate([docs, np.linspace(0, full.shape[1] - 1,
                                             N_DENSE_DOCS // 2).astype(int)])
    sub = PaddedDocs(idx=np.asarray(corpus.docs.idx)[docs],
                     val=np.asarray(corpus.docs.val)[docs])
    diffs = []
    # the reference's own dense GEMMs in full fp32 too, not the TPU default
    with jax.default_matmul_precision("highest"):
        for qi in range(N_DENSE_QUERIES):
            ref = one_to_many(corpus.queries[qi], sub, corpus.vecs, lam=LAM,
                              n_iter=N_ITER, impl="dense")
            diffs.append(max_rel_diff(full[qi, docs], ref))
    emit("dense_reference", seconds=time.perf_counter() - t0,
         queries=N_DENSE_QUERIES, docs=len(docs), rtol=DENSE_RTOL,
         max_rel_diff=max(diffs))
    checks.expect(max(diffs) <= DENSE_RTOL,
                  f"sparse vs dense reference rel. diff {max(diffs):.3g}")


def shares_a_word(corpus):
    """(Q, N) bool: does doc n hold a word of query q's support?"""
    import numpy as np
    idx = np.asarray(corpus.docs.idx)
    live = np.asarray(corpus.docs.val) > 0
    return np.any((corpus.queries > 0)[:, idx] & live[None], axis=2)


def phase_stacked(checks, corpus, index, full):
    """Exhaustive scores from an uncached sparse engine, whose K block is
    one stacked GEMM per chunk, against the served engine's K block
    assembled from cached per-word rows. Docs that share a query word
    are reported apart: a self distance that differs between the two
    builders moves only them."""
    import numpy as np
    from repro.core import WmdEngine
    t0 = time.perf_counter()
    stacked = np.asarray(WmdEngine(index, impl="sparse", lam=LAM,
                                   n_iter=N_ITER).query_batch(
        list(corpus.queries)))
    shared = shares_a_word(corpus)
    other = max_rel_diff(full[~shared], stacked[~shared])
    sharing = max_rel_diff(full[shared], stacked[shared])
    emit("kcache_vs_stacked", seconds=time.perf_counter() - t0,
         pairs=int(full.size), sharing_a_word=int(shared.sum()),
         max_rel_diff_other=other, max_rel_diff_sharing=sharing,
         rtol=SAME_K_RTOL)
    checks.expect(other <= SAME_K_RTOL,
                  f"cached vs stacked K rel. diff {other:.3g}")
    checks.expect(sharing <= SAME_K_RTOL,
                  f"cached vs stacked K rel. diff {sharing:.3g} on docs "
                  f"sharing a query word")


def run_one_chip(checks: Checks, seed: int) -> None:
    counter = CompileCounter()
    corpus, index, engine = phase_setup(seed)
    served = checks.run("serve", phase_serve, checks, corpus, engine,
                        counter, seed)
    full = checks.run("exhaustive", phase_exhaustive, checks, corpus,
                      engine, served)
    if full is not None:
        checks.run("dense_reference", phase_dense_reference, checks,
                   corpus, full)
        checks.run("kcache_vs_stacked", phase_stacked, checks, corpus,
                   index, full)
    emit("memory", peak_bytes_in_use=peak_bytes())


# ---------------------------------------------------------- four chips
def run_four_chips(checks: Checks, seed: int) -> None:
    """Sharded search over four chips against the one-device engine."""
    import re

    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import (ShardedWmdEngine, WmdEngine, build_index,
                            shard_corpus)
    from repro.data.corpus import paper_corpus
    n_shards, prune = 4, "ivf+wcd+rwmd"

    t0 = time.perf_counter()
    corpus = paper_corpus(seed=seed)
    sindex = shard_corpus(corpus.docs, corpus.vecs, n_shards)
    sharded = ShardedWmdEngine(sindex, lam=LAM, n_iter=N_ITER)
    single = WmdEngine(build_index(corpus.docs, corpus.vecs), lam=LAM,
                       n_iter=N_ITER)
    emit("setup", seconds=time.perf_counter() - t0, shards=n_shards,
         docs_per_shard=list(sindex.docs_per_shard))

    # each shard's committed index, and a device output computed from it
    # with an uncommitted staged query chunk, must sit on the shard's chip
    chips, placed = [], []
    for ix, eng in zip(sindex.shards, sharded.engines):
        sup, _, mask = eng._prep_chunk([corpus.queries[0]], 48)
        kq, _ = eng._kq(sup, mask)
        where = ix.vecs.devices() | ix.docs.idx.devices() | kq.devices()
        chips.append(next(iter(ix.vecs.devices())))
        placed.append(len(where) == 1)
    emit("placement", shard_devices=[d.id for d in chips],
         one_chip_per_shard=placed)
    checks.expect(len(set(chips)) == n_shards, "shards share a chip")
    checks.expect(all(placed), "a shard's index or output left its chip")

    packed = jax.ShapeDtypeStruct(
        (n_shards, len(corpus.queries), 2 * K), np.float32,
        sharding=NamedSharding(sindex.mesh, P("shard")))
    text = sharded._merge_fn(K).lower(packed).compile().as_text()
    gathers = len(re.findall(r"= \S+ all-gather(?:-start)?\(", text))
    others = len(re.findall(
        r"= \S+ (?:all-reduce|all-to-all|reduce-scatter|"
        r"collective-permute)(?:-start)?\(", text))
    emit("merge", all_gathers=gathers, other_collectives=others)
    checks.expect(gathers == 1 and others == 0,
                  f"merge has {gathers} all-gathers, {others} other "
                  f"collectives")

    queries = list(corpus.queries)
    t0 = time.perf_counter()
    full = np.asarray(single.query_batch(queries))
    res = sharded.search(queries, K, prune=prune, nprobe=None)
    problems = []
    for qi in range(len(queries)):
        why = topk_mismatch(res.indices[qi], res.distances[qi], full[qi], K,
                            TOPK_RTOL)
        if why:
            problems.append(f"query {qi}: {why}")
    emit("sharded_search", seconds=time.perf_counter() - t0,
         answers_checked=len(queries), rtol=TOPK_RTOL,
         coverage=float(sharded.last_coverage.fraction),
         mismatches=len(problems))
    checks.expect(sharded.last_coverage.full, "sharded search was partial")
    checks.expect(not problems, "; ".join(problems[:3]))
    emit("memory", peak_bytes_in_use=[
        (d.memory_stats() or {}).get("peak_bytes_in_use") for d in chips])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sharded search and its "
                         "one-device comparison")
    ap.add_argument("--seed", type=int, default=0,
                    help="corpus and request-stream seed")
    args = ap.parse_args(argv)

    import jax
    info = device_info()
    if info["platform"] != "tpu":
        print(f"chip_smoke: no TPU (JAX found {info}); refusing to run",
              file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if info["count"] < want:
        print(f"chip_smoke: {info['count']} chip(s), need {want}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.runtime.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's sources are missing ({e})",
              file=sys.stderr)
        return 2
    emit("device", **info, compile_cache=enable_compile_cache(),
         jax=jax.__version__)

    checks = Checks()
    run = run_four_chips if args.four_chips else run_one_chip
    checks.run("four_chips" if args.four_chips else "one_chip", run, checks,
               args.seed)
    if checks.failures:
        for f in checks.failures:
            print(f"chip_smoke FAILED: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
