"""Sharded corpus serving: cluster-aligned doc shards over a device mesh.

The paper's headline scenario — one query against a day of tweets — is a
corpus-scale problem; one device's memory and FLOPs bound the single-host
:class:`~repro.core.index.WmdEngine`. This module partitions the corpus
into DOC SHARDS across a 1-D device mesh and runs the *entire* existing
cascade (probe -> radius-drop -> WCD -> RWMD -> seed/survivor Sinkhorn)
per shard, locally, on each shard's own device:

- **Cluster-aligned**: whole IVF clusters per shard. One k-means runs
  globally (:func:`shard_corpus`), then a greedy bin-pack over cluster
  sizes balances doc counts; each shard's :class:`CorpusIndex` is built
  via :func:`build_index`'s precomputed-clusters hook over its owned
  clusters (locally relabeled), so PR 4's cluster-major storage makes
  every shard slice contiguous and all downstream invariants hold
  unchanged.
- **One merge collective**: per-shard local top-k results are packed into
  a single ``(S, Q, 2k)`` tensor laid out over the mesh, and the global
  top-k is ONE ``lax.all_gather`` inside a ``shard_map`` followed by a
  local ``lax.top_k`` — never a per-doc or per-cluster exchange. The
  per-shard cascades themselves are collective-free (each shard's
  adaptive exit is local); the only other collective in the codebase's
  sharded story is the per-query ``(Q,)`` residual ``pmax`` on
  :func:`repro.core.distributed.sinkhorn_wmd_sparse_distributed`'s
  cross-shard *solve* path (the PR 5 pattern, unchanged).
- **Exactness contract**: at ``nprobe=None`` (= all clusters) the sharded
  top-k equals the single-device top-k up to tie order, because every
  shard scores all of its clusters exactly and the merge is a true global
  top-k. Smaller ``nprobe`` applies PER SHARD: each shard probes its
  ``nprobe`` nearest owned clusters, so recall semantics match today's
  measured-recall story cluster-for-cluster (a doc is reachable iff its
  cluster is among the ``nprobe`` nearest of its OWNING shard).

Device placement uses committed arrays: each shard's index leaves are
``jax.device_put`` to that shard's device, so the per-shard jitted
cascades execute on their own device (uncommitted staged query arrays
follow the committed index operands). On CPU, force a multi-device mesh
with ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (see
:func:`repro.runtime.sharding.ensure_host_devices`).

TPU-pod design notes: the same structure maps onto a pod slice — the
mesh axis becomes a physical ring, the packed ``(S, Q, 2k)`` merge rides
the ICI all-gather (``2k * 4`` bytes per query per shard, independent of
corpus size), and per-shard HBM residency is ``~N/S`` docs. The pieces
that change are placement (``jax.make_mesh`` over the slice instead of
host devices) and the host-side staging loop, which should move to
per-shard async dispatch; the collective inventory (one all-gather per
search) already fits a pod's latency budget.

Single-shard use runs in-process with no mesh setup (runnable — the CI
``docs`` job executes this as a doctest)::

    >>> from repro.core import ShardedWmdEngine, shard_corpus
    >>> from repro.data.corpus import make_corpus
    >>> c = make_corpus(vocab_size=64, embed_dim=8, n_docs=12,
    ...                 n_queries=2, words_per_doc=(3, 8), seed=0)
    >>> sindex = shard_corpus(c.docs, c.vecs, 1, n_clusters=3)
    >>> engine = ShardedWmdEngine(sindex, lam=2.0, n_iter=10)
    >>> engine.search(list(c.queries), 3).indices.shape
    (2, 3)
"""
from __future__ import annotations

import functools
import os
import time
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import TimeoutError as _FutTimeout
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.runtime.fault_tolerance import PoisonStep, ShardHealth

from .index import (CorpusIndex, SearchResult, WmdEngine, _assign_clusters,
                    _compact_slots, _doc_centroids, _kmeans, append_docs,
                    auto_n_clusters, build_index, default_n_clusters,
                    load_index, save_index, snapshot_checksum)
from .sinkhorn import LamUnderflowError
from .sparse import PaddedDocs

# global doc ids ride through the merge collective as float32 payload
# lanes; above 2^24 the round-trip stops being exact
_MAX_DOCS_F32 = 1 << 24


def bin_pack_clusters(sizes: np.ndarray, n_shards: int) -> np.ndarray:
    """Greedy bin-pack: assign whole clusters to shards, balancing doc
    count. Clusters are placed largest-first onto the currently-lightest
    shard (LPT scheduling — within 4/3 of the optimal makespan, and in
    practice near-balanced for IVF size distributions). Returns
    ``shard_of_cluster`` (C,) int32. Deterministic: ties in both the size
    sort and the argmin break toward lower ids."""
    sizes = np.asarray(sizes, np.int64)
    order = np.argsort(-sizes, kind="stable")
    loads = np.zeros(n_shards, np.int64)
    shard_of = np.empty(sizes.shape[0], np.int32)
    for c in order:
        s = int(np.argmin(loads))
        shard_of[c] = s
        loads[s] += sizes[c]
    return shard_of


def _index_to_device(index: CorpusIndex, device) -> CorpusIndex:
    """Commit every device-array leaf of a :class:`CorpusIndex` to one
    device. Host mirrors (``docs_host``, cluster membership arrays) stay
    host-side; committed leaves pin the per-shard jitted cascades to the
    shard's device, and uncommitted staged query arrays follow them."""
    put = functools.partial(jax.device_put, device=device)
    groups = tuple(g._replace(docs=PaddedDocs(idx=put(g.docs.idx),
                                              val=put(g.docs.val)),
                              cols=put(g.cols)) for g in index.groups)
    clusters = index.clusters
    if clusters is not None:
        clusters = clusters._replace(centers=put(clusters.centers),
                                     assign_dev=put(clusters.assign_dev))
    return index._replace(
        docs=PaddedDocs(idx=put(index.docs.idx), val=put(index.docs.val)),
        groups=groups, vecs=put(index.vecs), vecs_sq=put(index.vecs_sq),
        centroids=put(index.centroids), clusters=clusters,
        pivots=None if index.pivots is None else put(index.pivots),
        doc_pivot_d=(None if index.doc_pivot_d is None
                     else put(index.doc_pivot_d)))


class ShardedCorpusIndex(NamedTuple):
    """Corpus partitioned into cluster-aligned doc shards over a mesh.

    Ids: each shard's :class:`CorpusIndex` speaks its own local id space
    (``ext_ids`` inside a shard translate shard storage -> shard-local
    caller order, exactly as single-device); ``global_ids[s]`` then lifts
    shard-local caller ids to the GLOBAL caller-order doc ids the sharded
    engine reports. ``owner`` is the inverse direction: global doc id ->
    owning shard.
    """

    shards: tuple            # tuple[CorpusIndex] — one per mesh device
    global_ids: tuple        # tuple[np (n_s,)]: shard-local -> global id
    owner: np.ndarray        # (N,) host: global doc id -> shard
    centers: jax.Array       # (C, w) GLOBAL frozen k-means centers
    shard_of_cluster: np.ndarray  # (C,) host: global cluster -> shard
    mesh: Mesh               # 1-D mesh, axis "shard"
    devices: tuple           # the mesh's devices, shard-major

    @property
    def n_shards(self) -> int:
        return len(self.shards)

    @property
    def n_docs(self) -> int:
        return int(self.owner.shape[0])

    @property
    def docs_per_shard(self) -> tuple:
        return tuple(ix.n_docs for ix in self.shards)

    @property
    def cluster_counts(self) -> tuple:
        return tuple(ix.clusters.n_clusters for ix in self.shards)


def _resolve_devices(n_shards: int, devices=None):
    devs = tuple(devices) if devices is not None else tuple(jax.devices())
    if len(devs) < n_shards:
        raise RuntimeError(
            f"{n_shards} shards need {n_shards} devices but only "
            f"{len(devs)} are visible. On CPU, set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n_shards} "
            f"before the first jax call (or use "
            f"repro.runtime.sharding.ensure_host_devices).")
    return devs[:n_shards]


def shard_corpus(docs: PaddedDocs, vecs, n_shards: int, dtype=jnp.float32,
                 doc_groups: int = 4, n_clusters=None, ivf_iters: int = 10,
                 ivf_seed: int = 0, devices=None, n_pivots: int = 8,
                 pivot_seed: int = 0) -> ShardedCorpusIndex:
    """Partition a corpus into cluster-aligned doc shards.

    One global mini-batch-Lloyd k-means over the per-doc centroids (the
    same quantizer :func:`build_index` would freeze), then
    :func:`bin_pack_clusters` balances whole clusters across ``n_shards``
    by doc count, and each shard's :class:`CorpusIndex` is assembled over
    its owned docs with the global centers subset as a precomputed frozen
    quantizer. The vocabulary embedding table is replicated per shard
    (every shard's cascade needs all word vectors); doc-proportional state
    is ``~N/S`` per shard.

    ``n_clusters`` resolves exactly as in :func:`build_index` (int /
    ``None`` = sqrt(N) / ``"auto"`` / numeric string) and is then clamped
    up to ``n_shards`` so every shard can own at least one cluster.
    ``n_pivots``/``pivot_seed`` flow into each shard's
    :func:`build_index`: pivot selection is over the REPLICATED
    vocabulary embeddings, so every shard freezes the identical pivot set
    and only the per-doc distance tables are shard-local.

    Failure modes: raises :class:`ValueError` when the corpus exceeds the
    merge's 2^24 float32 id-lane limit, when ``n_docs < n_shards``, or
    when a shard would own zero docs; raises :class:`RuntimeError` when
    fewer than ``n_shards`` devices are visible (on CPU, set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` first).
    """
    n_shards = int(n_shards)
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    devs = _resolve_devices(n_shards, devices)
    mesh = Mesh(np.asarray(devs), axis_names=("shard",))

    dtype = jnp.dtype(dtype)
    vecs_np = np.asarray(vecs, dtype)
    idx_np, val_np = _compact_slots(docs, dtype)
    n_docs = idx_np.shape[0]
    if n_docs >= _MAX_DOCS_F32:
        raise ValueError(
            f"sharded merge packs doc ids into float32 lanes; corpus size "
            f"{n_docs} >= 2^24 breaks the exact round-trip")
    if n_docs < n_shards:
        raise ValueError(f"cannot spread {n_docs} docs over {n_shards} "
                         f"shards")
    centroids_np = _doc_centroids(idx_np, val_np, vecs_np)
    if isinstance(n_clusters, str):
        if n_clusters == "auto":
            n_clusters = auto_n_clusters(centroids_np, seed=ivf_seed)
        elif n_clusters.isdigit():
            n_clusters = int(n_clusters)
        else:
            raise ValueError(f"n_clusters must be an int, None, or "
                             f"'auto', got {n_clusters!r}")
    elif n_clusters is None:
        n_clusters = default_n_clusters(n_docs)
    n_clusters = max(n_shards, min(int(n_clusters), n_docs))

    centers, assign = _kmeans(jnp.asarray(centroids_np), n_clusters,
                              n_iters=ivf_iters, seed=ivf_seed)
    centers_np = np.asarray(centers)
    sizes = np.bincount(assign, minlength=n_clusters)
    shard_of_cluster = bin_pack_clusters(sizes, n_shards)

    shards, global_ids = [], []
    owner = np.empty(n_docs, np.int32)
    for s in range(n_shards):
        owned = np.nonzero(shard_of_cluster == s)[0]
        doc_sel = np.nonzero(np.isin(assign, owned))[0].astype(np.int32)
        if doc_sel.size == 0:
            raise ValueError(
                f"shard {s} of {n_shards} would own no docs "
                f"({n_clusters} clusters, sizes {sizes.tolist()}); use "
                f"fewer shards or more clusters")
        owner[doc_sel] = s
        relabel = np.full(n_clusters, -1, np.int32)
        relabel[owned] = np.arange(owned.size, dtype=np.int32)
        ix = build_index(
            PaddedDocs(idx=idx_np[doc_sel], val=val_np[doc_sel]),
            vecs_np, dtype, doc_groups=doc_groups,
            clusters=(centers_np[owned], relabel[assign[doc_sel]]),
            n_pivots=n_pivots, pivot_seed=pivot_seed)
        shards.append(_index_to_device(ix, devs[s]))
        global_ids.append(doc_sel)
    return ShardedCorpusIndex(
        shards=tuple(shards), global_ids=tuple(global_ids), owner=owner,
        centers=jax.device_put(centers, devs[0]),
        shard_of_cluster=shard_of_cluster, mesh=mesh, devices=devs)


def append_docs_sharded(sindex: ShardedCorpusIndex, new_docs: PaddedDocs,
                        dtype=jnp.float32) -> ShardedCorpusIndex:
    """Streaming sharded append: route each new doc to the shard owning
    its nearest FROZEN global center, then run the single-device
    :func:`append_docs` per grown shard. Because every shard's local
    quantizer is a subset of the global centers and the routed shard
    contains the global argmin center, the per-shard nearest-center
    assignment agrees with the global one — append-then-search matches
    rebuild-then-search exactly at ``nprobe=None`` (property-tested)."""
    n_new = new_docs.idx.shape[0]
    if n_new == 0:
        return sindex
    new_idx, new_val = _compact_slots(new_docs, dtype)
    n_old = sindex.n_docs
    if n_old + n_new >= _MAX_DOCS_F32:
        raise ValueError("appended corpus would exceed the 2^24-doc "
                         "float32 id-lane limit of the sharded merge")
    cent_new = _doc_centroids(new_idx, new_val,
                              np.asarray(sindex.shards[0].vecs))
    assign_new = np.asarray(_assign_clusters(jnp.asarray(cent_new),
                                             sindex.centers))
    owner_new = sindex.shard_of_cluster[assign_new]

    shards, global_ids = list(sindex.shards), list(sindex.global_ids)
    tail = np.arange(n_old, n_old + n_new, dtype=np.int32)
    for s in range(sindex.n_shards):
        mine = np.nonzero(owner_new == s)[0]
        if mine.size == 0:
            continue
        grown = append_docs(
            shards[s],
            PaddedDocs(idx=new_idx[mine], val=new_val[mine]), dtype)
        shards[s] = _index_to_device(grown, sindex.devices[s])
        global_ids[s] = np.concatenate([global_ids[s], tail[mine]])
    return sindex._replace(
        shards=tuple(shards), global_ids=tuple(global_ids),
        owner=np.concatenate([sindex.owner,
                              owner_new.astype(np.int32)]))


class ShardSearchError(Exception):
    """Structured shard fan-out failure, naming the shard(s) involved.

    Raised when a shard's dispatch exhausts its retry budget (per-shard
    structured error, the fan-out analogue of the underflow diagnostics
    that already name the owning shard), or by the fan-out itself when
    EVERY shard failed and there is nothing to merge. Deliberately NOT a
    ``RuntimeError``: the serving ``DispatchGuard`` classifies
    RuntimeError as transient-and-retryable, and a fan-out that already
    consumed its own per-shard retries must not be retried again
    upstream (the ``DispatchFailed`` convention)."""

    def __init__(self, message: str, shard_reasons: dict | None = None):
        super().__init__(message)
        self.shard_reasons = dict(shard_reasons or {})


class ShardCoverage(NamedTuple):
    """How much of the corpus a sharded result actually covers.

    ``fraction == 1.0`` (empty ``missing_shards``) means every shard
    contributed and the usual exactness contract holds; anything less is
    a PARTIAL result — still a true top-k over the responding shards'
    docs, but recall against the full corpus is bounded above by
    ``fraction`` and the serving layer must not claim exactness."""

    fraction: float          # covered docs / corpus docs
    covered_docs: int
    missing_shards: tuple    # shard ids that did not contribute
    reasons: dict            # {shard id: "timeout" | "open_circuit" | error}

    @property
    def full(self) -> bool:
        return not self.missing_shards


# ----------------------------------------------------------------- snapshots
_SHARD_META_FILE = "meta.npz"


def _shard_file(shard_id: int) -> str:
    return f"shard_{shard_id:04d}.npz"


def snapshot_shards(sindex: ShardedCorpusIndex, snapshot_dir) -> list:
    """Persist a sharded index: one :func:`repro.core.index.save_index`
    file per shard plus a checksummed ``meta.npz`` holding the mesh-level
    state (owner map, global centers, cluster->shard map, per-shard
    global ids). Recovery granularity is ONE shard:
    :func:`restore_shard` reloads a single dead shard's file and rejoins
    it to the live mesh without touching the survivors. Returns the
    written paths."""
    os.makedirs(snapshot_dir, exist_ok=True)
    paths = []
    for si, ix in enumerate(sindex.shards):
        p = os.path.join(snapshot_dir, _shard_file(si))
        save_index(ix, p)
        paths.append(p)
    meta = {
        "owner": np.asarray(sindex.owner),
        "centers": np.asarray(sindex.centers),
        "shard_of_cluster": np.asarray(sindex.shard_of_cluster),
        "n_shards": np.asarray(sindex.n_shards, np.int64),
    }
    for si, gids in enumerate(sindex.global_ids):
        meta[f"global_ids_{si}"] = np.asarray(gids)
    meta["checksum"] = np.asarray(snapshot_checksum(meta), np.uint32)
    mp = os.path.join(snapshot_dir, _SHARD_META_FILE)
    with open(mp, "wb") as f:
        np.savez(f, **meta)
    paths.append(mp)
    return paths


def restore_shard(sindex: ShardedCorpusIndex, shard_id: int,
                  snapshot_dir) -> ShardedCorpusIndex:
    """Dead-shard recovery: reload shard ``shard_id`` from its
    :func:`snapshot_shards` file, commit it to the shard's mesh device,
    and return the sharded index with that shard replaced.

    Validates before trusting: the meta checksum must verify, the
    snapshot's shard count must match the live mesh, and the snapshot's
    global-id set for this shard must equal the live one — a snapshot
    taken before an :func:`append_docs_sharded` is STALE for the grown
    shard and restoring it would silently drop documents, so that is a
    ``ValueError``, not a best-effort merge. Restore-then-search is
    bit-compatible with never-failed search (``load_index`` reconstructs
    the identical index; property-tested at ``nprobe=None``)."""
    si = int(shard_id)
    with np.load(os.path.join(snapshot_dir, _SHARD_META_FILE)) as z:
        meta = {k: z[k] for k in z.files}
    stored = int(meta.pop("checksum"))
    actual = snapshot_checksum(meta)
    if actual != stored:
        raise ValueError(
            f"sharded snapshot meta in {snapshot_dir!r} failed its "
            f"integrity check (stored crc32 {stored:#010x}, recomputed "
            f"{actual:#010x})")
    snap_shards = int(meta["n_shards"])
    if snap_shards != sindex.n_shards:
        raise ValueError(f"snapshot has {snap_shards} shards; live mesh "
                         f"has {sindex.n_shards}")
    if not 0 <= si < sindex.n_shards:
        raise ValueError(f"shard id {si} out of range "
                         f"[0, {sindex.n_shards})")
    gids = meta[f"global_ids_{si}"]
    if not np.array_equal(gids, sindex.global_ids[si]):
        raise ValueError(
            f"snapshot for shard {si} is STALE: it covers {gids.size} "
            f"docs but the live shard owns {sindex.global_ids[si].size} "
            f"(the corpus grew since the snapshot; re-snapshot after "
            f"append_docs_sharded)")
    ix = load_index(os.path.join(snapshot_dir, _shard_file(si)))
    ix = _index_to_device(ix, sindex.devices[si])
    shards = sindex.shards[:si] + (ix,) + sindex.shards[si + 1:]
    return sindex._replace(shards=shards)


# --------------------------------------------------------------- collectives
# NOTE: shard_map's `pbroadcast` is deliberately absent — it is the
# replication-rule annotation (identity at lowering), not communication
_COLLECTIVE_STEMS = ("all_gather", "psum", "pmax", "pmin", "ppermute",
                     "all_to_all", "reduce_scatter", "pgather")


def count_collectives(jaxpr) -> dict:
    """Count communication primitives in a (closed) jaxpr, recursing into
    sub-jaxprs (while/cond/pjit/shard_map bodies). The sharded engine's
    structural contract — exactly ONE all_gather in the merge, zero
    collectives in the per-shard cascade — is asserted with this in
    ``tests/test_shard_index.py``."""
    counts: dict[str, int] = {}

    def walk_param(v):
        if isinstance(v, (list, tuple)):
            for x in v:
                walk_param(x)
        elif hasattr(v, "jaxpr"):       # ClosedJaxpr
            walk(v.jaxpr)
        elif hasattr(v, "eqns"):        # raw Jaxpr
            walk(v)

    def walk(jx):
        for eqn in jx.eqns:
            name = eqn.primitive.name
            if any(stem in name for stem in _COLLECTIVE_STEMS):
                counts[name] = counts.get(name, 0) + 1
            for v in eqn.params.values():
                walk_param(v)

    walk(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr)
    return counts


def _build_merge(mesh: Mesh, n_shards: int, k: int):
    """The ONE cross-shard collective: global top-k merge.

    Input: ``(S, Q, 2k)`` float32 laid out over the mesh's shard axis —
    per shard, ``k`` ascending local-best distances then ``k`` global doc
    ids as float lanes (invalid slots: +inf distance / -1 id). Inside the
    shard_map: one tiled ``all_gather`` reunites all shards' candidates
    (the only communication), then each device computes the identical
    global ``lax.top_k`` over its ``S*k`` candidates per query — the
    output is replicated. Flattening is SHARD-MAJOR with shard 0 first,
    so ``top_k``'s lowest-index tie-break makes the 1-shard mesh
    bit-compatible with the single-device ranking.
    """

    def merge(packed):                       # local block: (1, Q, 2k)
        packed = lax.all_gather(packed, "shard", axis=0, tiled=True)
        scores, ids = packed[:, :, :k], packed[:, :, k:]
        qn = scores.shape[1]
        s_flat = jnp.transpose(scores, (1, 0, 2)).reshape(qn, n_shards * k)
        i_flat = jnp.transpose(ids, (1, 0, 2)).reshape(qn, n_shards * k)
        neg, pos = lax.top_k(-s_flat, k)
        return -neg, jnp.take_along_axis(i_flat, pos, axis=1)

    return jax.jit(shard_map(merge, mesh=mesh, in_specs=(P("shard"),),
                             out_specs=(P(), P()), check_vma=False))


class ShardedWmdEngine:
    """Drop-in sharded counterpart of :class:`~repro.core.index.WmdEngine`.

    Holds one single-device :class:`WmdEngine` per shard (identical
    hyperparameters) and a compiled single-collective top-k merge over
    the mesh. ``search`` dispatches the full per-shard cascades
    concurrently (one host thread per shard — jit dispatch releases the
    GIL during device execution, so shards overlap on a real multi-device
    mesh), lifts shard-local ids to global ids, and merges with ONE
    ``all_gather`` + local ``top_k``. Exposes the same duck-typed surface
    ``runtime/serving.py`` consumes (``search``, ``min_bucket``,
    ``iter_stats*``, ``dtype``/``precision``)
    plus sharding extras (``n_shards``, ``docs_per_shard``,
    ``cluster_counts``, ``iter_stats_by_shard``).

    Fault tolerance (ISSUE 9): the fan-out is deadline-bounded and
    health-gated. Each shard dispatch runs under a per-shard retry loop
    (``shard_retries`` transient retries with exponential backoff); the
    collection waits at most ``shard_timeout_s`` wall-clock for the whole
    fan-out; a shard that times out or errors is EXCLUDED from the merge
    — the packed ``(S, Q, 2k)`` tensor's +inf/-1 defaults make a missing
    shard's lane inert, so the collective itself is unchanged — and the
    result is tagged via ``last_coverage`` (a :class:`ShardCoverage`)
    with the covered doc fraction and the missing shard ids. A
    :class:`~repro.runtime.fault_tolerance.ShardHealth` breaker skips a
    consecutively-failing shard and probes it on a deterministic cadence;
    ``snapshot()``/``restore_shard()`` persist and recover shards via
    :func:`snapshot_shards`/:func:`restore_shard` (restore-then-search is
    bit-compatible with never-failed search). ``last_coverage`` is a
    plain attribute handoff: safe under the serving runtime, which
    serializes engine dispatches on one worker thread.

    Deterministic per-request failures (``LamUnderflowError``) are NOT
    shard faults: they re-raise unchanged (naming the owning shard) so
    the serving layer can isolate the poisoned request. ``query_batch``
    is the unguarded debugging path and keeps the bare fan-out.

    Accepts every :class:`WmdEngine` keyword and forwards it per shard.
    """

    def __init__(self, sindex: ShardedCorpusIndex, *,
                 shard_timeout_s: float | None = 30.0,
                 shard_retries: int = 1, shard_backoff_s: float = 0.01,
                 fail_threshold: int = 3, probe_every: int = 4,
                 snapshot_dir: str | None = None,
                 shard_fault_hook=None, **engine_kwargs):
        self.sindex = sindex
        # kept for shard recovery: a restored shard's WmdEngine must be
        # rebuilt with the exact hyperparameters of its dead predecessor
        self._engine_kwargs = dict(engine_kwargs)
        self.engines = tuple(WmdEngine(ix, **engine_kwargs)
                             for ix in sindex.shards)
        e0 = self.engines[0]
        self.lam, self.n_iter = e0.lam, e0.n_iter
        self.min_bucket, self.dtype = e0.min_bucket, e0.dtype
        self.precision, self.tol = e0.precision, e0.tol
        self._pool = ThreadPoolExecutor(max_workers=sindex.n_shards,
                                        thread_name_prefix="wmd-shard")
        self._merge_cache: dict = {}
        # collective-overhead accounting for the fig11 trajectory note:
        # wall seconds spent in the merge step (pack + collective + sync)
        self.merge_seconds = 0.0
        self.shard_timeout_s = shard_timeout_s
        self.shard_retries = max(0, int(shard_retries))
        self.shard_backoff_s = float(shard_backoff_s)
        self.health = ShardHealth(sindex.n_shards,
                                  fail_threshold=fail_threshold,
                                  probe_every=probe_every)
        self.snapshot_dir = snapshot_dir
        # fault-injection entry point (shard, fan-out seq, attempt) ->
        # None, run inside the per-shard retry region; the serving
        # runtime wires FaultInjector.before_shard_attempt here
        self.shard_fault_hook = shard_fault_hook
        self.fanouts = 0       # fan-out sequence counter (public: chaos
        #                        drills key crash windows off it)
        self.last_coverage = ShardCoverage(1.0, sindex.n_docs, (), {})

    # ------------------------------------------------------------- surface
    @property
    def n_shards(self) -> int:
        return self.sindex.n_shards

    @property
    def n_docs(self) -> int:
        return self.sindex.n_docs

    @property
    def docs_per_shard(self) -> tuple:
        return self.sindex.docs_per_shard

    @property
    def cluster_counts(self) -> tuple:
        return self.sindex.cluster_counts

    @property
    def iter_stats_dropped(self) -> int:
        return sum(e.iter_stats_dropped for e in self.engines)

    def reset_iter_stats(self) -> None:
        for e in self.engines:
            e.reset_iter_stats()
        self.merge_seconds = 0.0

    def iter_stats(self, stage: str | None = None) -> np.ndarray:
        """Aggregated realized-iteration log across shards (per-shard
        split: :meth:`iter_stats_by_shard`)."""
        parts = [e.iter_stats(stage=stage) for e in self.engines]
        return (np.concatenate(parts) if parts
                else np.zeros((0,), np.int64))

    def iter_stats_by_stage(self) -> dict:
        stages: list[str] = []
        for e in self.engines:
            for st in e.iter_stats_by_stage():
                if st not in stages:
                    stages.append(st)
        return {st: self.iter_stats(stage=st) for st in stages}

    def iter_stats_by_shard(self) -> dict:
        """{shard id: {stage: realized iteration counts}} — the sharded
        ``iter_stats()`` aggregate, split by owning shard."""
        return {s: e.iter_stats_by_stage()
                for s, e in enumerate(self.engines)}

    # ------------------------------------------- cross-request cache (ISSUE 10)
    def enable_kcache(self, slots: int) -> bool:
        """Attach a PER-SHARD cdist-row cache to every shard engine
        (each shard's rows live against its own device-resident ``vecs``
        copy). Recorded in ``_engine_kwargs`` so a restored shard
        (:meth:`restore_shard`) rebuilds with a fresh cache of the same
        capacity. Returns ``True``."""
        for e in self.engines:
            e.enable_kcache(slots)
        self._engine_kwargs["kcache_slots"] = int(slots)
        return True

    def kcache_stats(self) -> dict | None:
        """Shard-summed cache counters (``None`` when no shard carries a
        cache); per-shard split under ``"per_shard"``."""
        per = [e.kcache_stats() for e in self.engines]
        if all(p is None for p in per):
            return None
        agg: dict = {"slots": 0, "used": 0, "hits": 0, "misses": 0,
                     "evictions": 0, "inserts": 0, "lookups": 0,
                     "fallbacks": 0, "oversize": 0}
        for p in per:
            for k in agg:
                agg[k] += p[k] if p else 0
        total = agg["hits"] + agg["misses"]
        agg["hit_rate"] = round(agg["hits"] / total, 4) if total else 0.0
        agg["per_shard"] = per
        return agg

    def reset_kcache_stats(self) -> None:
        for e in self.engines:
            e.reset_kcache_stats()

    def host_stats(self) -> dict:
        """Shard-summed :meth:`WmdEngine.host_stats` counters."""
        per = [e.host_stats() for e in self.engines]
        return {k: sum(p[k] for p in per) for k in per[0]}

    def reset_host_stats(self) -> None:
        for e in self.engines:
            e.reset_host_stats()

    # --------------------------------------------------------------- merge
    def _merge_fn(self, k: int):
        fn = self._merge_cache.get(k)
        if fn is None:
            fn = self._merge_cache[k] = _build_merge(
                self.sindex.mesh, self.n_shards, k)
        return fn

    def _merge_topk(self, per_shard: dict, nq: int, k: int):
        """Pack per-shard ``{shard id: (indices, distances)}`` host
        results into the (S, Q, 2k) mesh tensor and run the
        single-collective merge. A shard ABSENT from the dict (timed
        out, errored, open-circuited) leaves its lane at the +inf/-1
        defaults — inert under ``top_k`` — so a partial merge uses the
        identical collective as a full one (the dead shard's DEVICE is
        alive; only its dispatch failed). Returns host (Q, k) indices
        (int32, -1 pad) and distances (NaN pad), ascending."""
        t0 = time.perf_counter()
        s_count = self.n_shards
        packed = np.full((s_count, nq, 2 * k), np.inf, np.float32)
        packed[:, :, k:] = -1.0
        for si, (ids, dists) in per_shard.items():
            ks = ids.shape[1]
            gids = np.where(
                ids >= 0,
                self.sindex.global_ids[si][np.maximum(ids, 0)], -1)
            d = np.asarray(dists, np.float32)
            d = np.where((ids >= 0) & np.isfinite(d), d, np.inf)
            packed[si, :, :ks] = d
            packed[si, :, k:k + ks] = gids.astype(np.float32)
        sharding = NamedSharding(self.sindex.mesh, P("shard"))
        dist, ids = self._merge_fn(k)(jax.device_put(packed, sharding))
        dist = np.asarray(jax.device_get(dist))
        ids = np.asarray(jax.device_get(ids)).astype(np.int32)
        dist = np.where(ids >= 0, dist, np.nan).astype(self.dtype)
        self.merge_seconds += time.perf_counter() - t0
        return ids, dist

    # -------------------------------------------------------------- search
    def _shard_search(self, si: int, queries, k, prune, nprobe, mode,
                      refine_factor):
        try:
            return self.engines[si].search(queries, k, prune=prune,
                                           nprobe=nprobe, mode=mode,
                                           refine_factor=refine_factor)
        except LamUnderflowError as e:
            raise LamUnderflowError(
                f"owning shard {si} of {self.n_shards} "
                f"({self.docs_per_shard[si]} docs; any doc counts below "
                f"are shard-local, reported ids are external): {e}"
            ) from e

    def _guarded_shard(self, si: int, seq: int, fn):
        """One shard's dispatch under its DispatchGuard-style retry loop
        (runs on the shard's pool thread). Transient failures — the same
        class set :class:`~repro.runtime.fault_tolerance.StepGuard`
        retries — back off exponentially up to ``shard_retries`` times;
        deterministic per-request failures (``LamUnderflowError``,
        ``PoisonStep``) re-raise immediately; exhaustion raises a
        structured :class:`ShardSearchError` NAMING THE SHARD instead of
        letting the raw exception propagate unstructured out of the
        future. Returns ``(service_seconds, result)``."""
        last = None
        for attempt in range(self.shard_retries + 1):
            t0 = time.perf_counter()
            try:
                if self.shard_fault_hook is not None:
                    self.shard_fault_hook(si, seq, attempt)
                return time.perf_counter() - t0, fn(si)
            except (PoisonStep, FloatingPointError):
                raise          # deterministic per-request: never a retry
            except (jax.errors.JaxRuntimeError, RuntimeError, OSError) as e:
                last = e
                if attempt < self.shard_retries:
                    time.sleep(self.shard_backoff_s * (2 ** attempt))
        raise ShardSearchError(
            f"shard {si} of {self.n_shards} failed after "
            f"{self.shard_retries + 1} attempts "
            f"({type(last).__name__}: {last})",
            {si: f"{type(last).__name__}: {last}"}) from last

    def _fan_out(self, fn, label: str):
        """Deadline-bounded, health-gated fan-out of ``fn(si)`` across
        shards. Returns ``({shard id: result}, ShardCoverage)`` and
        updates ``last_coverage``/``health``.

        Admission: open-circuited shards are skipped (probed on the
        breaker's deterministic cadence); if EVERY circuit is open, all
        shards are force-probed — the engine never refuses to serve on
        breaker state alone. Collection: one shared wall-clock deadline
        of ``shard_timeout_s`` over the whole fan-out; a shard that
        misses it is recorded as ``"timeout"`` and excluded (its worker
        thread finishes in the background — a cooperative bound, like
        the DispatchGuard watchdog: Python cannot preempt a running XLA
        dispatch). A ``LamUnderflowError`` from any shard re-raises
        after the others drain (deterministic per-request poison, not a
        shard fault). Raises :class:`ShardSearchError` only when NO
        shard responded."""
        seq = self.fanouts
        self.fanouts += 1
        reasons: dict = {}
        live = []
        for si in range(self.n_shards):
            if self.health.admit(si):
                live.append(si)
            else:
                reasons[si] = "open_circuit"
        if not live:                     # all circuits open: force-probe
            live = sorted(reasons)
            reasons = {}
        futures = {si: self._pool.submit(self._guarded_shard, si, seq, fn)
                   for si in live}
        deadline = (None if self.shard_timeout_s is None
                    else time.monotonic() + self.shard_timeout_s)
        results: dict = {}
        underflow = None
        for si, f in futures.items():
            try:
                remaining = (None if deadline is None
                             else max(0.0, deadline - time.monotonic()))
                dt, out = f.result(timeout=remaining)
                results[si] = out
                self.health.record_success(si, dt)
            except _FutTimeout:
                reasons[si] = "timeout"
                self.health.record_failure(si)
            except LamUnderflowError as e:
                underflow = e
            except Exception as e:  # noqa: BLE001 — fan-out boundary
                reasons[si] = (str(e) if isinstance(e, ShardSearchError)
                               else f"{type(e).__name__}: {e}")
                self.health.record_failure(si)
        if underflow is not None:
            raise underflow
        if not results:
            detail = "; ".join(f"shard {s}: {r}"
                               for s, r in sorted(reasons.items()))
            raise ShardSearchError(
                f"{label}: all {self.n_shards} shards failed ({detail})",
                reasons)
        covered = sum(self.docs_per_shard[si] for si in results)
        cov = ShardCoverage(
            fraction=covered / max(self.n_docs, 1),
            covered_docs=covered,
            missing_shards=tuple(si for si in range(self.n_shards)
                                 if si not in results),
            reasons=reasons)
        self.last_coverage = cov
        return results, cov

    def search(self, queries: Sequence, k: int, prune: object = "rwmd",
               nprobe: int | None = None, mode: str = "exact",
               refine_factor: int = 4) -> SearchResult:
        """Sharded staged top-k: per-shard cascade -> single-collective
        global merge. Same contract as :meth:`WmdEngine.search`, with the
        per-shard ``nprobe`` semantics documented in the module header;
        ``solved`` sums exact per-query solves across shards.

        ``mode="refine"`` runs rank-then-refine PER SHARD (each shard
        ranks its own candidates and solves its own top
        ``refine_factor * k``); the merge is unchanged — still one
        all_gather over exact distances, so every returned distance is
        exact and the global result at a covering ``refine_factor``
        equals ``mode="exact"`` at the same ``nprobe`` (each shard's
        contribution already does).

        Under shard failure the result is PARTIAL: a true top-k over the
        responding shards only, reported via ``last_coverage`` (see
        :meth:`_fan_out`); callers that need the exactness contract must
        check ``last_coverage.full``."""
        queries = [np.asarray(q) for q in queries]
        nq = len(queries)
        if k <= 0:
            raise ValueError(f"k must be positive, got {k}")
        k = min(int(k), self.n_docs)
        if nq == 0:
            self.last_coverage = ShardCoverage(1.0, self.n_docs, (), {})
            return SearchResult(np.full((0, k), -1, np.int32),
                                np.full((0, k), np.nan, self.dtype),
                                np.zeros(0, np.int64))
        results, _ = self._fan_out(
            lambda si: self._shard_search(si, queries, k, prune, nprobe,
                                          mode, refine_factor),
            label="search")
        ids, dist = self._merge_topk(
            {si: (res.indices, res.distances)
             for si, res in results.items()}, nq, k)
        solved = np.sum([res.solved for res in results.values()], axis=0)
        return SearchResult(ids, dist, solved.astype(np.int64))

    def query_batch(self, queries: Sequence) -> np.ndarray:
        """Exhaustive (Q, N) distance matrix in GLOBAL caller doc order,
        assembled from concurrent per-shard exhaustive solves."""
        queries = [np.asarray(q) for q in queries]
        nq = len(queries)
        out = np.full((nq, self.n_docs), np.nan, self.dtype)
        if nq == 0:
            return out
        futures = [self._pool.submit(self.engines[si].query_batch, queries)
                   for si in range(self.n_shards)]
        for si, f in enumerate(futures):
            out[:, self.sindex.global_ids[si]] = np.asarray(f.result())
        return out

    def rwmd_topk(self, queries: Sequence, k: int):
        """Bound-only ranking for the serving runtime's degraded tier:
        per-shard :func:`repro.runtime.serving.rwmd_topk` over each local
        engine, merged through the same single collective as
        :meth:`search`. Returns ``(indices, distances)`` exactly like the
        single-device free function (which delegates here when handed a
        sharded engine). Routed through the same deadline-bounded
        health-gated fan-out as :meth:`search`, so the last-resort tier
        degrades to a partial result (``last_coverage``) under shard
        failure instead of stalling on a hung shard."""
        from repro.runtime.serving import rwmd_topk as _local_rwmd
        queries = [np.asarray(q) for q in queries]
        nq = len(queries)
        k = min(int(k), self.n_docs)
        if nq == 0 or k <= 0:
            self.last_coverage = ShardCoverage(1.0, self.n_docs, (), {})
            return (np.full((nq, max(k, 0)), -1, np.int32),
                    np.full((nq, max(k, 0)), np.nan, self.dtype))
        results, _ = self._fan_out(
            lambda si: _local_rwmd(self.engines[si], queries, k),
            label="rwmd_topk")
        return self._merge_topk(dict(results), nq, k)

    # ----------------------------------------------------------- snapshots
    def snapshot(self, snapshot_dir=None) -> list:
        """Persist every shard's index (see :func:`snapshot_shards`) and
        remember the directory for :meth:`restore_shard`. Returns the
        written paths."""
        d = snapshot_dir if snapshot_dir is not None else self.snapshot_dir
        if d is None:
            raise ValueError("no snapshot directory: pass snapshot_dir "
                             "here or at engine construction")
        self.snapshot_dir = d
        return snapshot_shards(self.sindex, d)

    def restore_shard(self, shard_id: int, snapshot_dir=None) -> None:
        """Dead-shard recovery: reload one shard from its snapshot
        (:func:`restore_shard`), rebuild its :class:`WmdEngine` with the
        same hyperparameters, and reset its circuit breaker — the
        restored shard rejoins the mesh with a clean record and is
        admitted on the next fan-out. Post-restore search is
        bit-compatible with a never-failed engine."""
        d = snapshot_dir if snapshot_dir is not None else self.snapshot_dir
        if d is None:
            raise ValueError("no snapshot directory: pass snapshot_dir "
                             "here or at engine construction")
        si = int(shard_id)
        self.sindex = restore_shard(self.sindex, si, d)
        rebuilt = WmdEngine(self.sindex.shards[si], **self._engine_kwargs)
        self.engines = (self.engines[:si] + (rebuilt,)
                        + self.engines[si + 1:])
        self.health.reset(si)
