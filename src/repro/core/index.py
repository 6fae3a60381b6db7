"""Batched multi-query WMD engine: persistent corpus index + bucketed solves.

The paper's motivating scenario ("finding whether a given tweet is similar to
any other tweets happened in a day") is *many* queries against one shared
corpus, but a per-query loop over :func:`repro.core.wmd.one_to_many` re-ships
the vocabulary embeddings to the device, re-reduces their norms, and re-jits
for every distinct query support size ``v_r`` — the naive-baseline shape the
paper gets its 700x over. This module keeps the corpus side *resident* and
batches the query side:

``CorpusIndex``
    Freezes everything query-independent exactly once: the ELL document
    collection (``docs.idx/val``), the vocabulary embeddings, and the
    per-word squared norms that form the corpus half of the ``cdist`` GEMM.
    Documents are also nnz-sorted and split into width-trimmed
    :class:`DocGroup` slices (ELL row grouping), so the per-query solve
    never touches padding slots shorter docs don't have — a one-time cost
    at build that every subsequent query amortizes. Every query after the
    first touches none of this again.

``WmdEngine``
    Shape-buckets incoming queries to a small set of power-of-two ``v_r``
    sizes (padded query rows carry ``r = 1, G = 0`` — the established
    padding contract of :mod:`repro.kernels.sddmm_spmm`, proven inert by the
    kernel tests), stacks each bucket into one ``(Q, v_r, ...)`` problem and
    runs the solver ONCE per bucket: the per-query ``(v_r, V)`` cdist
    becomes a single ``(Q*v_r, V)`` GEMM, the Sinkhorn loop runs as one
    batched einsum or one Pallas launch with a query grid dimension
    (:func:`repro.kernels.sddmm_spmm.sinkhorn_fused_all_batched`), and jit
    caching collapses to one executable per bucket shape instead of one per
    distinct ``v_r``. GM is reconstructed from G everywhere (never
    materialized), so the per-bucket footprint is two nnz-sized arrays.

``WmdEngine.search`` (the staged retrieval pipeline, ISSUE 2)
    The paper's motivating workload is top-k retrieval, and exhaustive
    scoring does asymptotically too much work for it: ``search(queries, k)``
    runs *prune -> solve -> rank*. A cheap admissible lower bound from
    :mod:`repro.core.prune` (WCD / doc-side RWMD) scores every (query, doc)
    pair first; the Sinkhorn solve then runs only on (a) the k best-bounded
    seed docs and (b) the docs whose bound cannot be excluded by the kth
    seed distance — solved in fixed-shape tiles of the index's own doc
    groups (:meth:`CorpusIndex.tiles`). With an admissible bound the
    returned top-k equals the
    exhaustive one exactly; ``prune=None`` reproduces exhaustive
    ``query_batch`` + argsort bit-for-bit.

``WmdEngine`` solve policy (ISSUE 4)
    The solve stage is convergence-adaptive and precision-polymorphic:
    ``tol`` switches the fixed-length Sinkhorn scan to a
    ``lax.while_loop`` that exits once every live doc's marginal residual
    drops below it (``n_iter`` becomes a cap; realized counts are reported
    via :meth:`WmdEngine.iter_stats`), and ``precision`` selects bf16
    GEMMs and/or the log-domain kernel
    (:class:`~repro.core.sinkhorn_sparse.SolvePrecision`) — the log path
    makes :class:`LamUnderflowError` structurally impossible, so the
    paper's ``lam=9`` runs on corpora whose distance scale underflows
    fp32 ``exp(-lam*M)``.

Cluster-major layout (ISSUE 4)
    ``build_index`` stores the corpus sorted by IVF cluster id: cluster
    ``c``'s documents occupy the contiguous STORAGE rows
    ``starts[c]:starts[c+1]``, and the members of each nnz doc group
    are kept in that order too. Storage ids are internal;
    ``ext_ids``/``remap`` translate to/from the caller's original doc
    order at the output boundary only, so
    ``query_batch`` rows and ``search`` indices are unchanged.
    ``append_docs`` keeps the invariant within the grown group.

Typical use (runnable — the CI ``docs`` job executes it as a doctest)::

    >>> from repro.core import WmdEngine, build_index
    >>> from repro.data.corpus import make_corpus
    >>> c = make_corpus(vocab_size=64, embed_dim=8, n_docs=12,
    ...                 n_queries=2, words_per_doc=(3, 8), seed=0)
    >>> index = build_index(c.docs, c.vecs, n_clusters=3)  # frozen once
    >>> engine = WmdEngine(index, lam=2.0, n_iter=10)
    >>> res = engine.search(list(c.queries), k=3,
    ...                     prune="ivf+pivot+wcd+rwmd")    # exact top-3
    >>> res.indices.shape, res.distances.shape
    ((2, 3), (2, 3))
    >>> ref = engine.search(list(c.queries), k=3,
    ...                     prune="ivf+pivot+wcd+rwmd", mode="refine",
    ...                     refine_factor=4)  # bounded solve budget
    >>> bool((ref.solved <= 4 * 3).all())
    True

At larger ``lam`` (the paper's ``lam=9``) pass ``precision="log"`` —
fp32 ``exp(-lam*M)`` underflows first and the engine raises
:class:`LamUnderflowError` with a diagnosis rather than returning NaN.
``append_docs(index, more_docs)`` grows the corpus without a rebuild.
"""
from __future__ import annotations

import collections
import functools
import zlib
from typing import NamedTuple, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from .sinkhorn import LamUnderflowError, sq_dists, underflow_report
from .sinkhorn_sparse import (SolvePrecision, adaptive_loop,
                              adaptive_loop_scoped, marginal_residual,
                              marginal_residual_per_query)
from .sparse import PaddedDocs

ENGINE_IMPLS = ("sparse", "kernel")


class DocGroup(NamedTuple):
    """One length-homogeneous slice of the corpus, ELL-trimmed to its own
    max word count (classic ELL row-grouping: the solver never multiplies
    padding slots a shorter doc group doesn't have)."""

    docs: PaddedDocs    # idx/val (N_g, L_g), L_g = group max words
    cols: jax.Array     # (N_g,) original doc positions (for reassembly)


class IvfClusters(NamedTuple):
    """Frozen IVF coarse quantizer over the per-doc WCD centroids.

    k-means runs ONCE at :func:`build_index` (mini-batch Lloyd, device-side);
    :func:`append_docs` assigns new docs to the nearest existing center
    without touching the clustering — centers are reused by identity, only
    the host-side membership arrays (and the grown clusters' radii) change.
    The cluster structure powers the :class:`~repro.core.prune.CascadePruner`
    cascade twice: the (Q, n_clusters) probe GEMM replaces the (Q, N) sweep
    for candidate generation, and ``radii`` gives a *cluster-level* lower
    bound ``||qcent - center_c|| - radius_c <= wcd(q, n)`` for every member
    n (triangle inequality; Werner & Laber-style), so whole clusters are
    excluded against the pruning threshold without touching their docs.
    """

    centers: jax.Array   # (C, w) cluster centers, device-resident
    assign: np.ndarray   # (N,) host: cluster id per doc
    order: np.ndarray    # (N,) host: doc ids sorted by cluster id
    starts: np.ndarray   # (C + 1,) host: cluster c owns order[starts[c]:
    #                      starts[c + 1]] — contiguous shortlist slices
    radii: np.ndarray    # (C,) host: max ||center_c - centroid_n|| over
    #                      members (cluster-level bound; grows on append)
    assign_dev: jax.Array  # (N,) device mirror of ``assign`` (the dense
    #                        prune pass looks up doc -> probed cluster)

    @property
    def n_clusters(self) -> int:
        return self.centers.shape[0]

    @property
    def sizes(self) -> np.ndarray:
        return np.diff(self.starts)


@jax.jit
def _assign_clusters(points: jax.Array, centers: jax.Array) -> jax.Array:
    """Nearest-center assignment for one mini-batch: (B, w) -> (B,)."""
    return jnp.argmin(sq_dists(points, centers), axis=1)


@jax.jit
def _kmeans_accum(points: jax.Array, centers: jax.Array):
    """One mini-batch's contribution to the Lloyd update: per-center
    coordinate sums + member counts (one-hot GEMM, stays on device)."""
    onehot = jax.nn.one_hot(_assign_clusters(points, centers),
                            centers.shape[0], dtype=points.dtype)
    return onehot.T @ points, jnp.sum(onehot, axis=0)


@functools.partial(jax.jit, static_argnames=("c",))
def _farthest_point_init(points: jax.Array, c: int, start) -> jax.Array:
    """Maxmin (farthest-point) seeding: each new center is the point
    farthest from all chosen so far. Deterministic, device-side, O(C*N*w)
    once at build — spreads centers across the corpus' actual modes (a
    random draw lands several centers in one dense mode and none in small
    ones, which inflates cluster radii and blunts the triangle bound)."""
    mind = jnp.sum((points - points[start]) ** 2, axis=1)
    centers = jnp.zeros((c, points.shape[1]), points.dtype)
    centers = centers.at[0].set(points[start])

    def body(i, carry):
        centers, mind = carry
        cen = points[jnp.argmax(mind)]
        centers = centers.at[i].set(cen)
        return centers, jnp.minimum(mind, jnp.sum((points - cen) ** 2,
                                                  axis=1))

    centers, _ = lax.fori_loop(1, c, body, (centers, mind))
    return centers


def _kmeans(centroids: jax.Array, n_clusters: int, n_iters: int = 10,
            batch: int = 4096, seed: int = 0, init_sample: int = 65536):
    """Mini-batch Lloyd k-means over the doc centroids, device-side.

    Farthest-point init (on an ``init_sample``-capped subset at corpus
    scale), then each Lloyd iteration streams the (N, w) centroid matrix
    through :func:`_kmeans_accum` in ``batch``-sized slices (the (B, C)
    one-hot and the assignment cdist never exceed a mini-batch) and applies
    one exact update; empty clusters keep their previous center.
    Deterministic in ``seed``. Returns (centers (C, w), assign host (N,)).
    """
    n = centroids.shape[0]
    rng = np.random.default_rng(seed)
    pool = centroids
    if n > init_sample:
        keep = np.sort(rng.choice(n, size=init_sample, replace=False))
        pool = jnp.take(centroids, jnp.asarray(keep, jnp.int32), axis=0)
    centers = _farthest_point_init(pool, n_clusters,
                                   int(rng.integers(pool.shape[0])))
    for _ in range(n_iters):
        sums = jnp.zeros_like(centers)
        counts = jnp.zeros((n_clusters,), centers.dtype)
        for lo in range(0, n, batch):
            s, c = _kmeans_accum(centroids[lo:lo + batch], centers)
            sums, counts = sums + s, counts + c
        centers = jnp.where(counts[:, None] > 0,
                            sums / jnp.maximum(counts, 1.0)[:, None],
                            centers)
    assign = np.concatenate([
        np.asarray(_assign_clusters(centroids[lo:lo + batch], centers))
        for lo in range(0, n, batch)]).astype(np.int32)
    return centers, assign


@jax.jit
def _pivot_dists(points: jax.Array, pivots: jax.Array) -> jax.Array:
    """(M, w) points x (P, w) pivots -> (M, P) Euclidean distances — the
    precomputed corpus half (and the per-chunk query half) of the pivot
    triangle prestage ``|d(q, p) - d(n, p)| <= ||qcent - centroid_n||``."""
    return jnp.sqrt(jnp.maximum(sq_dists(points, pivots), 0.0))


def _select_pivots(vecs: jax.Array, n_pivots: int, seed: int = 0,
                   sample: int = 65536) -> jax.Array:
    """Pivot words for the triangle prestage: farthest-point selection over
    the vocabulary embeddings (``sample``-capped at vocabulary scale), so
    the reference set spans the embedding space's extremes — that is what
    makes ``max_p |d(q,p) - d(n,p)|`` a tight reverse-triangle bound.
    Returns (P, w) rows of ``vecs`` (actual word vectors, not centroids).
    """
    v = vecs.shape[0]
    n_pivots = max(1, min(int(n_pivots), v))
    rng = np.random.default_rng(seed)
    pool = vecs
    if v > sample:
        keep = np.sort(rng.choice(v, size=sample, replace=False))
        pool = jnp.take(vecs, jnp.asarray(keep, jnp.int32), axis=0)
    return _farthest_point_init(pool, n_pivots,
                                int(rng.integers(pool.shape[0])))


def _membership(assign: np.ndarray, n_clusters: int):
    """(order, starts) from an assignment: cluster c's docs are the
    contiguous slice order[starts[c]:starts[c + 1]]."""
    order = np.argsort(assign, kind="stable").astype(np.int32)
    starts = np.searchsorted(assign[order],
                             np.arange(n_clusters + 1)).astype(np.int64)
    return order, starts


def _member_dists(centroids, centers, assign: np.ndarray,
                  chunk: int = 4096) -> np.ndarray:
    """(N,) host distances from each doc centroid to its assigned center."""
    n = assign.shape[0]
    out = np.empty(n, np.float64)
    assign_dev = jnp.asarray(assign.astype(np.int32))
    for lo in range(0, n, chunk):
        own = jnp.take(centers, assign_dev[lo:lo + chunk], axis=0)
        d = jnp.linalg.norm(centroids[lo:lo + chunk] - own, axis=1)
        out[lo:lo + chunk] = np.asarray(d, np.float64)
    return out


def _cluster_radii(centroids, centers, assign: np.ndarray,
                   n_clusters: int) -> np.ndarray:
    """(C,) max member distance per cluster (0 for empty clusters)."""
    radii = np.zeros(n_clusters, np.float64)
    if assign.size:
        np.maximum.at(radii, assign, _member_dists(centroids, centers,
                                                   assign))
    return radii


def default_n_clusters(n_docs: int) -> int:
    """sqrt(N) coarse-quantizer heuristic (classic IVF sizing)."""
    return max(1, min(n_docs, int(round(float(np.sqrt(max(n_docs, 1)))))))


def auto_n_clusters(centroids: np.ndarray, seed: int = 0,
                    sample: int = 2048, sweep_iters: int = 4,
                    drop: float = 0.7) -> int:
    """Data-tuned cluster count from cluster-radius statistics.

    The sqrt(N) default is wrong for dedup-style corpora (fig9's wants
    ~N/16): once the cluster count reaches the near-duplicate group
    count, the mass-weighted mean cluster radius COLLAPSES (each cluster
    becomes one tight group; measured per-doubling ratio ~0.5 on the fig8
    corpus), which is exactly what makes the triangle-bound prune bite.
    A diffuse corpus has no such elbow — its radius declines gently
    (~0.85-0.95 per doubling) and extra clusters buy nothing.

    So: sweep cluster counts by doubling over a ``sample``-capped subset
    of the doc centroids (cheap mini-batch Lloyd each), and return the
    LARGEST candidate whose doubling shrank the weighted mean radius by
    more than ``1 - drop`` (the structure-driven collapse), scaled back
    to the full corpus size; with no collapse below ``m // 8``, fall
    back to the sqrt default. Spelled ``n_clusters="auto"`` in
    :func:`build_index`, serve, and ``examples/wmd_search.py``.
    """
    n = centroids.shape[0]
    if n <= 4:
        return max(1, n)
    rng = np.random.default_rng(seed)
    pts = centroids
    if n > sample:
        pick = np.sort(rng.choice(n, size=sample, replace=False))
        pts = centroids[pick]
    m = pts.shape[0]
    pts_dev = jnp.asarray(pts)
    best = None
    prev = None
    c = 2
    while c <= max(4, m // 8):
        centers, assign = _kmeans(pts_dev, c, n_iters=sweep_iters,
                                  seed=seed)
        radii = _cluster_radii(pts_dev, centers, assign, c)
        sizes = np.bincount(assign, minlength=c)
        wmean = float((sizes * radii).sum() / max(m, 1))
        if prev is not None and wmean < drop * prev:
            best = c
        prev = wmean
        c *= 2
    if best is None:
        # no collapse: the sqrt default, computed on the FULL corpus (a
        # sample-level sqrt scaled by n/m would be ~n/sqrt(sample))
        return default_n_clusters(n)
    # a collapse point is a density statement about the sample — scale it
    return max(1, min(n, int(round(best * n / m))))


class CorpusIndex(NamedTuple):
    """Query-independent corpus state, frozen once and reused forever.

    Documents live in CLUSTER-MAJOR storage order (sorted by IVF cluster
    id at build): all per-doc arrays — ``docs``, ``docs_host``,
    ``centroids``, group ``cols``, ``clusters.assign`` — are indexed by
    STORAGE id, and cluster ``c``'s members are the contiguous storage
    rows ``clusters.starts[c]:starts[c+1]`` at build time. ``ext_ids``
    maps storage -> the caller's original doc id (``remap`` is the
    inverse); the engine translates at its output boundary, so results
    are always in the caller's order."""

    docs: PaddedDocs     # full ELL corpus: idx (N, L) int32, val (N, L)
    groups: tuple        # tuple[DocGroup, ...] — nnz-sorted, width-trimmed
    vecs: jax.Array      # (V, w) vocabulary embeddings, device-resident
    vecs_sq: jax.Array   # (V,) per-word |b|^2 — corpus half of the cdist GEMM
    centroids: jax.Array  # (N, w) per-doc mass centroids (WCD prune stage)
    docs_host: PaddedDocs  # np mirror of ``docs`` — candidate staging reads
    #                        row slices host-side without a full D2H copy
    clusters: IvfClusters = None  # IVF coarse quantizer over the centroids
    #                               (the CascadePruner's shortlist stage)
    ext_ids: np.ndarray = None   # (N,) host: storage id -> original doc id
    remap: np.ndarray = None     # (N,) host: original doc id -> storage id
    pivots: jax.Array = None     # (P, w) pivot word embeddings (the
    #                              cascade's pivot triangle prestage)
    doc_pivot_d: jax.Array = None  # (N, P) device: ||centroid_n - pivot_p||
    #                                frozen at build; grows on append

    @property
    def n_docs(self) -> int:
        return self.docs.idx.shape[0]

    @property
    def vocab_size(self) -> int:
        return self.vecs.shape[0]

    @property
    def embed_dim(self) -> int:
        return self.vecs.shape[1]

    def save(self, path) -> None:
        """Persist this index to one integrity-checksummed ``.npz`` file
        (see :func:`save_index`). ``CorpusIndex.load(path)`` round-trips
        it bit-compatibly — the shard-recovery snapshot primitive."""
        save_index(self, path)

    @staticmethod
    def load(path) -> "CorpusIndex":
        """Rebuild an index from a :meth:`save` snapshot (see
        :func:`load_index`); raises ``ValueError`` if the checksum or
        format version does not match."""
        return load_index(path)

    def to_external(self, storage_ids: np.ndarray) -> np.ndarray:
        """Storage ids -> the caller's original doc ids."""
        storage_ids = np.asarray(storage_ids, np.int32)
        if self.ext_ids is None:
            return storage_ids
        return self.ext_ids[storage_ids]

    def tiles(self, doc_ids, tile: int, storage: bool = False,
              lookup=None, few: int = 0) -> list:
        """Candidate docs in fixed-shape tiles of the index's own doc
        groups, for the solve stage: ``doc_ids`` are split by the nnz
        :class:`DocGroup` each lives in, and each group's share is cut
        into tiles of ``tile`` docs (of the group's own doc count, where
        that is smaller). A tile's ELL rows are gathered on the
        device from its group's arrays by position (one small id upload,
        no host slice), at the group's own width L_g; slots past the
        tile's real docs are inert pad docs (word 0, weight 0), as a
        group's own padding is. So a solve's shapes follow the group and
        ``tile``, whatever the candidate count, and its gathered K tile is
        never larger than Q x L_g x B x ``tile``.

        Returns ``[(DocGroup, part)]``: the tile's docs (``cols`` holds
        its real ids as passed, a host array) and ``part``, the positions
        in ``doc_ids`` its first ``len(part)`` columns answer.
        ``doc_ids`` are the caller's ids, or storage ids with
        ``storage=True`` (the engine's internal path). ``lookup`` is
        :func:`group_lookup` of this index's groups, when the caller
        keeps one. At most ``few`` docs are one tile of ``few`` slots
        across the groups, at the widest group's width (``few <= tile``
        keeps it within the widest group's tile bound)."""
        doc_ids = np.asarray(doc_ids, np.int32).reshape(-1)
        rows = doc_ids
        if not storage and self.remap is not None:
            rows = self.remap[doc_ids]
        few = min(few, self.n_docs)
        if 0 < doc_ids.size <= few:
            pos = np.zeros(few, np.int32)
            pos[:doc_ids.size] = rows
            width = max(g.docs.idx.shape[1] for g in self.groups)
            idx, val = _tile_rows(self.docs.idx, self.docs.val,
                                  jnp.asarray(pos), doc_ids.size,
                                  width=width)
            return [(DocGroup(PaddedDocs(idx=idx, val=val), cols=doc_ids),
                     np.arange(doc_ids.size))]
        grp_of, pos_of = lookup if lookup is not None else group_lookup(
            self.groups)
        in_grp = grp_of[rows]
        out = []
        for gi, grp in enumerate(self.groups):
            sel = np.nonzero(in_grp == gi)[0]
            # a tile never holds more slots than its group has docs
            size = min(tile, int(grp.cols.shape[0]))
            for lo in range(0, sel.size, size):
                part = sel[lo:lo + size]
                pos = np.zeros(size, np.int32)
                pos[:part.size] = pos_of[rows[part]]
                idx, val = _tile_rows(grp.docs.idx, grp.docs.val,
                                      jnp.asarray(pos), part.size)
                out.append((DocGroup(PaddedDocs(idx=idx, val=val),
                                     cols=doc_ids[part]), part))
        return out


def group_lookup(groups) -> tuple:
    """Host ``(group, position)`` of every storage id in ``groups``."""
    n = sum(int(g.cols.shape[0]) for g in groups)
    grp_of = np.zeros(n, np.int32)
    pos_of = np.zeros(n, np.int32)
    for gi, g in enumerate(groups):
        cols = np.asarray(g.cols)
        grp_of[cols] = gi
        pos_of[cols] = np.arange(cols.size, dtype=np.int32)
    return grp_of, pos_of


@functools.partial(jax.jit, static_argnames=("width",))
def _tile_rows(idx, val, pos, n_real, width=None):
    """One candidate tile's ELL rows, gathered from its group's (N_g, L_g)
    arrays (or the corpus' first ``width`` slots) at ``pos``; slots from
    ``n_real`` on are inert pad docs."""
    if width is not None:
        idx, val = idx[:, :width], val[:, :width]
    live = (jnp.arange(pos.shape[0]) < n_real)[:, None]
    return (jnp.where(live, jnp.take(idx, pos, axis=0), 0),
            jnp.where(live, jnp.take(val, pos, axis=0), 0))


def _compact_slots(docs: PaddedDocs, dtype):
    """Host copies with live slots compacted to the front (front-filled is
    the builders' contract, but cheap to enforce for arbitrary inputs)."""
    idx_np = np.asarray(docs.idx, np.int32)
    val_np = np.asarray(docs.val, dtype)
    slot_order = np.argsort(~(val_np > 0), axis=1, kind="stable")
    return (np.take_along_axis(idx_np, slot_order, 1),
            np.take_along_axis(val_np, slot_order, 1))


def _doc_centroids(idx_np, val_np, vecs_np, chunk: int = 2048):
    """Per-doc mass centroids sum_l val[n,l] * vecs[idx[n,l]] — the frozen
    corpus half of the WCD prune stage. Chunked so the (n, L, w) gather
    intermediate stays small at corpus scale."""
    n = idx_np.shape[0]
    out = np.empty((n, vecs_np.shape[1]), vecs_np.dtype)
    for lo in range(0, max(n, 1), chunk):
        hi = min(lo + chunk, n)
        out[lo:hi] = np.einsum("nl,nlw->nw", val_np[lo:hi],
                               vecs_np[idx_np[lo:hi]])
    return out


def build_index(docs: PaddedDocs, vecs, dtype=jnp.float32,
                doc_groups: int = 4, n_clusters=None,
                ivf_iters: int = 10, ivf_seed: int = 0,
                clusters=None, n_pivots: int = 8,
                pivot_seed: int = 0) -> CorpusIndex:
    """Freeze the corpus side: device-resident docs + embeddings + norms +
    per-doc centroids (the WCD prune stage's corpus half) + the IVF coarse
    quantizer over those centroids (the cascade's shortlist stage).

    Storage is CLUSTER-MAJOR (ISSUE 4): after clustering, documents are
    permuted so cluster ids are non-decreasing, so a cluster's members
    are neighbouring rows of each doc group. ``ext_ids``/``remap``
    record the permutation; every engine result stays in the caller's
    doc order.

    ``n_clusters`` accepts an int, ``None`` (sqrt(N) default), ``"auto"``
    (the radius sweep), or a numeric string (CLI passthrough).

    Documents are additionally sorted by nnz and split into ``doc_groups``
    equal-count groups, each trimmed to its own max word count (members
    kept in cluster-major order within the group) — the per-query solve
    work drops by the corpus' ELL padding fraction, paid once here instead
    of on every query. ``n_clusters`` defaults to the sqrt(N) IVF
    heuristic; ``"auto"`` sweeps :func:`auto_n_clusters`'s radius
    statistic instead (dedup-style corpora want far more than sqrt(N)).
    Clustering runs mini-batch Lloyd on device and is frozen afterwards
    (:func:`append_docs` only assigns).

    ``clusters=(centers, assign)`` skips the k-means entirely and freezes
    the given quantizer instead: ``centers`` is a (C, w) array, ``assign``
    a host (N,) cluster id per doc. This is the sharded-index hook
    (:func:`repro.core.shard_index.shard_corpus` runs ONE global k-means,
    then builds each shard's :class:`CorpusIndex` over its owned clusters
    with locally relabeled ids) — membership, radii, and the cluster-major
    permutation are still derived here, so every downstream invariant
    holds unchanged.

    ``n_pivots`` pivot words (farthest-point over the vocabulary
    embeddings, deterministic in ``pivot_seed``) are frozen with their
    per-doc centroid distances ``doc_pivot_d`` — the corpus half of the
    :class:`~repro.core.prune.CascadePruner`'s ``"pivot"`` triangle
    prestage (Werner & Laber style, arXiv:1912.00509): at query time
    ``max_p |d(q, p) - d(n, p)|`` lower-bounds the WCD at O(P) per pair
    instead of O(w). ``n_pivots=0`` skips the precompute (the ``"pivot"``
    stage then raises if requested).

    Exactness contract: the index itself is lossless — every document is
    stored exactly (permuted only), and ``WmdEngine`` results over it are
    independent of ``doc_groups``, ``n_clusters``, ``n_pivots``, and the
    storage permutation. Clustering and pivots only steer *pruning*; they
    change which docs get bounded/solved, never a returned distance.
    """
    vecs = jnp.asarray(vecs, dtype)
    vecs_np = np.asarray(vecs)
    idx_np, val_np = _compact_slots(docs, dtype)
    n_docs = idx_np.shape[0]
    centroids_np = _doc_centroids(idx_np, val_np, vecs_np)
    if clusters is not None:
        pre_centers, pre_assign = clusters
        centers = jnp.asarray(pre_centers, dtype)
        assign = np.asarray(pre_assign, np.int32)
        n_clusters = int(centers.shape[0])
        if assign.shape[0] != n_docs:
            raise ValueError(f"precomputed assign has {assign.shape[0]} "
                             f"entries for {n_docs} docs")
        if assign.size and (assign.min() < 0
                            or assign.max() >= n_clusters):
            raise ValueError("precomputed assign references cluster ids "
                             f"outside [0, {n_clusters})")
        return _assemble_index(idx_np, val_np, centroids_np, vecs,
                               centers, assign, n_clusters, doc_groups,
                               dtype, n_pivots, pivot_seed)
    if isinstance(n_clusters, str):
        if n_clusters == "auto":
            n_clusters = auto_n_clusters(centroids_np, seed=ivf_seed)
        elif n_clusters.isdigit():
            n_clusters = int(n_clusters)    # CLI passthrough
        else:
            raise ValueError(f"n_clusters must be an int, None, or 'auto', "
                             f"got {n_clusters!r}")
    elif n_clusters is None:
        n_clusters = default_n_clusters(n_docs)
    n_clusters = max(1, min(int(n_clusters), max(n_docs, 1)))
    if n_docs:
        centers, assign = _kmeans(jnp.asarray(centroids_np), n_clusters,
                                  n_iters=ivf_iters, seed=ivf_seed)
    else:
        centers = jnp.zeros((n_clusters, vecs.shape[1]), dtype)
        assign = np.zeros((0,), np.int32)
    return _assemble_index(idx_np, val_np, centroids_np, vecs, centers,
                           assign, n_clusters, doc_groups, dtype,
                           n_pivots, pivot_seed)


def _assemble_index(idx_np, val_np, centroids_np, vecs, centers, assign,
                    n_clusters: int, doc_groups: int, dtype,
                    n_pivots: int = 8, pivot_seed: int = 0) -> CorpusIndex:
    """Shared :func:`build_index` tail: cluster-major permutation, nnz
    grouping, membership/radii, device upload. Split out so the sharded
    builder can reuse it with a precomputed (frozen) quantizer."""
    # cluster-major storage: permute every per-doc array so assign is
    # non-decreasing; ext_ids/remap translate at the output boundary
    perm = np.argsort(assign, kind="stable").astype(np.int32)
    idx_np, val_np = idx_np[perm], val_np[perm]
    centroids_np, assign = centroids_np[perm], assign[perm]
    ext_ids = perm
    remap = np.empty_like(perm)
    remap[perm] = np.arange(perm.size, dtype=np.int32)

    groups = _nnz_groups(idx_np, val_np, doc_groups)
    centroids = jnp.asarray(centroids_np)
    c_order, c_starts = _membership(assign, n_clusters)
    radii = _cluster_radii(centroids, centers, assign, n_clusters)
    pivots = doc_pivot_d = None
    if n_pivots and int(n_pivots) > 0:
        pivots = _select_pivots(vecs, int(n_pivots), seed=pivot_seed)
        doc_pivot_d = _pivot_dists(centroids, pivots)
    return CorpusIndex(docs=PaddedDocs(idx=jnp.asarray(idx_np),
                                       val=jnp.asarray(val_np)),
                       groups=groups, vecs=vecs,
                       vecs_sq=jnp.sum(vecs * vecs, axis=1),
                       centroids=centroids,
                       docs_host=PaddedDocs(idx=idx_np, val=val_np),
                       clusters=IvfClusters(centers=centers, assign=assign,
                                            order=c_order, starts=c_starts,
                                            radii=radii,
                                            assign_dev=jnp.asarray(assign)),
                       ext_ids=ext_ids, remap=remap,
                       pivots=pivots, doc_pivot_d=doc_pivot_d)


def _nnz_groups(idx_np, val_np, doc_groups: int) -> tuple:
    """nnz-sorted, width-trimmed :class:`DocGroup` split of an ELL corpus.

    Shared by :func:`_assemble_index` and :func:`load_index`: the split is
    a pure function of (idx, val, doc_groups), so a snapshot only needs to
    persist the full ELL arrays plus the GROUP COUNT to reconstruct the
    groups bit-identically (``g = ceil(n/k)`` is an involution on its
    image: rebuilding with ``doc_groups = len(groups)`` reproduces the
    build-time group size exactly)."""
    nnz = (val_np > 0).sum(1)
    order = np.argsort(nnz, kind="stable")
    n = max(1, len(order))
    gsz = -(-n // max(1, doc_groups))
    groups = []
    for lo in range(0, len(order), gsz):
        # ascending storage ids within the group == cluster-major
        sel = np.sort(order[lo:lo + gsz])
        lg = max(1, int(nnz[sel].max(initial=0)))
        groups.append(DocGroup(
            docs=PaddedDocs(idx=jnp.asarray(idx_np[sel][:, :lg]),
                            val=jnp.asarray(val_np[sel][:, :lg])),
            cols=jnp.asarray(sel.astype(np.int32))))
    return tuple(groups)


INDEX_SNAPSHOT_VERSION = 1


def snapshot_checksum(arrays: dict) -> int:
    """CRC32 over every array's name, dtype, shape, and bytes (key-sorted)
    — the integrity tag :func:`load_index` verifies before trusting a
    snapshot. Not cryptographic; it catches truncated/garbled files, not
    adversarial tampering."""
    crc = 0
    for name in sorted(arrays):
        a = np.ascontiguousarray(arrays[name])
        hdr = f"{name}:{a.dtype.str}:{a.shape}".encode()
        crc = zlib.crc32(a.tobytes(), zlib.crc32(hdr, crc))
    return crc


def save_index(index: CorpusIndex, path) -> None:
    """Persist a frozen :class:`CorpusIndex` to one ``.npz`` file.

    Saves only the HOST-canonical arrays (ELL docs, embeddings, cluster
    membership/radii, ext_ids/remap, pivots) plus the group count;
    everything else — device uploads, ``vecs_sq``, the nnz group split —
    is a deterministic pure function of those and is recomputed on
    :func:`load_index`, which is what makes restore-then-search
    bit-compatible with build-then-search. The payload is tagged with
    :func:`snapshot_checksum`; ``load_index`` refuses a mismatch."""
    idx_np = np.asarray(index.docs_host.idx)
    val_np = np.asarray(index.docs_host.val)
    arrays = {
        "idx": idx_np,
        "val": val_np,
        "vecs": np.asarray(index.vecs),
        "centroids": np.asarray(index.centroids),
        "n_groups": np.asarray(len(index.groups), np.int64),
        "version": np.asarray(INDEX_SNAPSHOT_VERSION, np.int64),
    }
    if index.clusters is not None:
        arrays["c_centers"] = np.asarray(index.clusters.centers)
        arrays["c_assign"] = np.asarray(index.clusters.assign)
        arrays["c_order"] = np.asarray(index.clusters.order)
        arrays["c_starts"] = np.asarray(index.clusters.starts)
        arrays["c_radii"] = np.asarray(index.clusters.radii)
    if index.ext_ids is not None:
        arrays["ext_ids"] = np.asarray(index.ext_ids)
        arrays["remap"] = np.asarray(index.remap)
    if index.pivots is not None:
        arrays["pivots"] = np.asarray(index.pivots)
        arrays["doc_pivot_d"] = np.asarray(index.doc_pivot_d)
    # checksum covers everything ABOVE (computed before its own insertion)
    arrays["checksum"] = np.asarray(snapshot_checksum(arrays), np.uint32)
    with open(path, "wb") as f:
        np.savez(f, **arrays)


def load_index(path) -> CorpusIndex:
    """Rebuild a :class:`CorpusIndex` from a :func:`save_index` snapshot.

    Verifies the integrity checksum first (raises ``ValueError`` on
    mismatch — a half-written snapshot must not silently serve wrong
    results), then re-uploads the host arrays and re-derives the pure
    functions of them (``vecs_sq``, nnz groups, device mirrors). The
    result is bit-compatible with the index that was saved: identical
    host arrays in, identical derivations out."""
    with np.load(path) as z:
        data = {k: z[k] for k in z.files}
    stored = int(data.pop("checksum"))
    actual = snapshot_checksum(data)
    if actual != stored:
        raise ValueError(
            f"index snapshot {path!r} failed its integrity check "
            f"(stored crc32 {stored:#010x}, recomputed {actual:#010x}) — "
            "refusing to serve from a corrupt/truncated snapshot")
    version = int(data["version"])
    if version != INDEX_SNAPSHOT_VERSION:
        raise ValueError(f"index snapshot {path!r} has version {version}; "
                         f"this build reads {INDEX_SNAPSHOT_VERSION}")
    idx_np = data["idx"]
    val_np = data["val"]
    vecs = jnp.asarray(data["vecs"])
    clusters = None
    if "c_centers" in data:
        clusters = IvfClusters(
            centers=jnp.asarray(data["c_centers"]),
            assign=data["c_assign"], order=data["c_order"],
            starts=data["c_starts"], radii=data["c_radii"],
            assign_dev=jnp.asarray(data["c_assign"]))
    pivots = doc_pivot_d = None
    if "pivots" in data:
        pivots = jnp.asarray(data["pivots"])
        doc_pivot_d = jnp.asarray(data["doc_pivot_d"])
    return CorpusIndex(
        docs=PaddedDocs(idx=jnp.asarray(idx_np), val=jnp.asarray(val_np)),
        groups=_nnz_groups(idx_np, val_np, int(data["n_groups"])),
        vecs=vecs, vecs_sq=jnp.sum(vecs * vecs, axis=1),
        centroids=jnp.asarray(data["centroids"]),
        docs_host=PaddedDocs(idx=idx_np, val=val_np),
        clusters=clusters,
        ext_ids=data.get("ext_ids"), remap=data.get("remap"),
        pivots=pivots, doc_pivot_d=doc_pivot_d)


def _pad_width(a, width: int):
    """Right-pad axis 1 with zeros; np in -> np out, jax in -> jax out."""
    if a.shape[1] >= width:
        return a
    pads = ((0, 0), (0, width - a.shape[1]))
    return (jnp.pad(a, pads) if isinstance(a, jax.Array)
            else np.pad(a, pads))


def append_docs(index: CorpusIndex, new_docs: PaddedDocs,
                dtype=jnp.float32) -> CorpusIndex:
    """Streaming index update: add documents WITHOUT a full rebuild.

    The new docs join the group with the fewest members (widened only if
    they are longer than its current ELL trim); every other group's arrays
    are reused as-is — no re-sort, no re-gather, no centroid recompute for
    existing docs. New docs get ids ``[n_docs, n_docs + n_new)``.
    ``search``/``query_batch`` after an append match a from-scratch
    ``build_index`` exactly: per-doc solves are independent and grouping /
    ELL padding are inert (proven by the engine tests).

    IVF clusters are FROZEN: the new docs are assigned to their nearest
    existing center (no re-clustering — ``centers`` is reused by identity)
    and only the host-side membership arrays are rebuilt. Exact search
    (``nprobe = n_clusters``) is unaffected; smaller-``nprobe`` recall
    degrades only as far as the frozen centers drift from the grown
    corpus — rebuild when that matters.

    Cluster-major invariant: appended docs take the NEXT storage ids (the
    global storage is no longer one contiguous run per cluster — member
    slices go through ``clusters.order`` and stay *near*-contiguous), but
    the grown group's rows are re-sorted by cluster id so its arrays keep
    the build-time layout; a rebuild restores full contiguity.
    """
    n_new = new_docs.idx.shape[0]
    if n_new == 0:
        return index
    new_idx, new_val = _compact_slots(new_docs, dtype)
    if int(new_idx.max(initial=0)) >= index.vocab_size:
        raise ValueError("new docs reference word ids outside the index "
                         f"vocabulary ({index.vocab_size})")
    nnz = (new_val > 0).sum(1)
    lg_new = max(1, int(nnz.max(initial=0)))
    new_idx, new_val = new_idx[:, :lg_new], new_val[:, :lg_new]
    n_old = index.n_docs

    # full ELL corpus: widen whichever side is narrower, then concat — the
    # device side on-device and the host mirror on-host, so only the NEW
    # docs ever cross the device boundary
    width = max(index.docs.idx.shape[1], lg_new)
    docs = PaddedDocs(
        idx=jnp.concatenate([_pad_width(index.docs.idx, width),
                             jnp.asarray(_pad_width(new_idx, width))]),
        val=jnp.concatenate([_pad_width(index.docs.val, width),
                             jnp.asarray(_pad_width(new_val, width))]))
    docs_host = PaddedDocs(
        idx=np.concatenate([_pad_width(index.docs_host.idx, width),
                            _pad_width(new_idx, width)]),
        val=np.concatenate([_pad_width(index.docs_host.val, width),
                            _pad_width(new_val, width)]))

    cent_new = _doc_centroids(new_idx, new_val, np.asarray(index.vecs))
    clusters = index.clusters
    assign = None
    if clusters is not None:
        cent_new_dev = jnp.asarray(cent_new)
        assign_new = np.asarray(
            _assign_clusters(cent_new_dev,
                             clusters.centers)).astype(np.int32)
        assign = np.concatenate([clusters.assign, assign_new])
        c_order, c_starts = _membership(assign, clusters.n_clusters)
        # frozen centers: only the grown clusters' radii can expand
        radii = clusters.radii.copy()
        np.maximum.at(radii, assign_new,
                      _member_dists(cent_new_dev, clusters.centers,
                                    assign_new))
        clusters = clusters._replace(assign=assign, order=c_order,
                                     starts=c_starts, radii=radii,
                                     assign_dev=jnp.asarray(assign))

    # grow only the smallest group; all others are reused untouched
    gi = int(np.argmin([g.cols.shape[0] for g in index.groups]))
    grp = index.groups[gi]
    gw = max(grp.docs.idx.shape[1], lg_new)
    g_idx = jnp.concatenate([_pad_width(grp.docs.idx, gw),
                             jnp.asarray(_pad_width(new_idx, gw))])
    g_val = jnp.concatenate([_pad_width(grp.docs.val, gw),
                             jnp.asarray(_pad_width(new_val, gw))])
    g_cols = np.concatenate([np.asarray(grp.cols),
                             np.arange(n_old, n_old + n_new, dtype=np.int32)])
    if assign is not None:
        # keep the grown group cluster-major (ISSUE 4 invariant): one
        # O(group) device gather per append, amortized over every
        # subsequent query
        gorder = np.argsort(assign[g_cols], kind="stable").astype(np.int32)
        if not np.array_equal(gorder, np.arange(gorder.size)):
            gd = jnp.asarray(gorder)
            g_idx = jnp.take(g_idx, gd, axis=0)
            g_val = jnp.take(g_val, gd, axis=0)
            g_cols = g_cols[gorder]
    grown = DocGroup(docs=PaddedDocs(idx=g_idx, val=g_val),
                     cols=jnp.asarray(g_cols))
    groups = tuple(grown if i == gi else g
                   for i, g in enumerate(index.groups))

    tail_ids = np.arange(n_old, n_old + n_new, dtype=np.int32)
    ext_ids = (np.concatenate([index.ext_ids, tail_ids])
               if index.ext_ids is not None else None)
    remap = (np.concatenate([index.remap, tail_ids])
             if index.remap is not None else None)
    doc_pivot_d = index.doc_pivot_d
    if index.pivots is not None:
        # frozen pivots (like the cluster centers): only the new rows of
        # the distance table are computed
        doc_pivot_d = jnp.concatenate(
            [index.doc_pivot_d,
             _pivot_dists(jnp.asarray(cent_new), index.pivots)])
    return index._replace(
        docs=docs, groups=groups, docs_host=docs_host,
        centroids=jnp.concatenate([index.centroids,
                                   jnp.asarray(cent_new)]),
        clusters=clusters, ext_ids=ext_ids, remap=remap,
        doc_pivot_d=doc_pivot_d)


def bucket_size(v_r: int, min_bucket: int = 8) -> int:
    """Smallest power-of-two bucket (>= min_bucket) holding v_r query rows."""
    b = max(1, int(min_bucket))
    while b < v_r:
        b *= 2
    return b


def _safe_inv(x):
    return jnp.where(x > 0, 1.0 / jnp.where(x > 0, x, 1.0), 0.0)


def _stabilize_log_g(g):
    """Column-stabilize a gathered LOG-kernel tile (Q, N, L, B): subtract
    each (q, n, l) column's max over the query-word axis and exponentiate.
    Masked/padded rows carry -inf and exponentiate to exactly 0; a column
    with no live row (an all-pad filler query) gets shift 0 and stays
    all-zero. Returns (G', shift) with every live column's max entry == 1,
    so an all-zero K column — the LamUnderflowError mode — cannot occur."""
    shift = jnp.max(g, axis=-1)                         # (Q, N, L)
    shift = jnp.where(jnp.isfinite(shift), shift, 0.0)
    gp = jnp.where(jnp.isfinite(g), jnp.exp(g - shift[..., None]), 0.0)
    return gp, shift


def _solve_batched_einsum(g, mq, idx, val, r, mask, lam, n_iter, tol=None,
                          check_every: int = 4, gemm: str = "fp32",
                          log_domain: bool = False, scope: str = "chunk",
                          qdoc_mask=None, x0q=None,
                          with_profile: bool = False, prof_mask=None):
    """Batched ELL Sinkhorn + distance line in the CPU/XLA-friendly layout.

    g (Q, N, L, B): query rows on the MINOR axis, so both contractions are
    contiguous per-(doc, query) tiles — measured ~4x faster per live row
    than the (Q, B, N, L) order whose k-reduction strides by N*L. Only ONE
    G tensor is kept: diag(1/r) is folded into the x-update (r is constant
    per row) instead of materializing G_over_r, halving resident bytes.
    val (N, L); r, mask (Q, B); padded rows (G == 0, r == 1) are inert.

    ``tol`` switches the fixed-length scan to a ``lax.while_loop`` that
    checks the doc-marginal residual ``max|val/t - w_prev|`` every
    ``check_every`` iterations — measured RELATIVE to each doc's own
    marginal scale, and masked to live queries x live slots so padded
    docs/queries can neither stall the loop nor release it early.
    ``n_iter`` becomes a cap (realized counts land on
    ``1 + k*check_every``; the residual window is seeded with one real
    iteration so even the first check can exit). ``gemm="bf16"`` runs both contractions with bf16
    inputs and fp32 accumulation; ``log_domain=True`` takes ``g`` as
    UNexponentiated ``log K`` (masked rows -inf) and stabilizes it per
    column before the loop.

    Per-query residual scoping (ISSUE 5): ``scope="query"`` replaces the
    chunk-global scalar exit with the per-query machinery of
    :func:`~repro.core.sinkhorn_sparse.adaptive_loop_scoped` — each
    query's residual is a masked segment-max over its OWN doc slots
    (``qdoc_mask`` (Q, N) narrows that scope to the query's candidate
    docs, so far pairs the ranking never needs stop holding its exit
    open), queries FREEZE their x-columns once converged (their update
    rows are zeroed — semantically dropped; the dense einsum still
    executes at chunk width until the loop exits, so the wall-clock win
    is the EARLIER per-query exit, not fewer FLOPs per iteration), and
    the loop exits once every live query converged or the cap hits.
    ``iters`` is then a (Q,) vector of per-query realized counts instead
    of a scalar. ``x0q`` (Q, B) warm-starts every doc column from a
    per-query profile (the engine passes the seed solve's converged
    column mean for survivor solves); ``with_profile=True`` additionally
    returns that (Q, B) profile — the doc-mean of the final x over
    ``prof_mask`` docs (each query's own candidates; falls back to
    ``qdoc_mask``, then all live docs).

    Distance-line epilogue (ISSUE 4): instead of reconstructing
    ``GM = -G*log(G)/lam`` (a transcendental over the whole nnz tensor —
    measured ~6 iterations' worth on CPU, and wrong for the stabilized
    log-domain G anyway), the TRUE transport costs are gathered from the
    chunk's (Q, V, B) cdist output ``mq`` — one gather + multiply, exact
    in both domains, and the reason the log path needs NO shift
    correction here. The vocab-level M is held for the chunk (same size
    as ``kq``); the nnz-level (Q, N, L, B) product exists only inside
    this jit. The Pallas kernel path keeps the in-VMEM ``reconstruct_gm``
    (on TPU recompute beats the extra HBM gather).

    Returns (wmd (Q, N), realized iterations (int32 scalar)).
    """
    q, n, length, b = g.shape
    live = val > 0                                      # (N, L)
    if log_domain:
        g, _ = _stabilize_log_g(g)
    gd = jnp.bfloat16 if gemm == "bf16" else None
    gb = g if gd is None else g.astype(gd)

    def _sddmm(u):
        if gd is None:
            return jnp.einsum("qnlb,qnb->qnl", gb, u)
        return jnp.einsum("qnlb,qnb->qnl", gb, u.astype(gd),
                          preferred_element_type=jnp.float32)

    def _spmm(w):
        if gd is None:
            return jnp.einsum("qnlb,qnl->qnb", gb, w)
        return jnp.einsum("qnlb,qnl->qnb", gb, w.astype(gd),
                          preferred_element_type=jnp.float32)

    rinv = _safe_inv(r)[:, None, :]                     # (Q, 1, B)
    denom = jnp.sum(mask, axis=1, keepdims=True)
    if x0q is None:
        x0 = jnp.where(mask > 0, 1.0 / jnp.maximum(denom, 1.0), 0.0)
    else:
        # warm start: the caller's per-query profile, zeroed on pad slots
        # (a frozen profile can only carry mass on the query's live words)
        x0 = jnp.where(mask > 0, x0q, 0.0)
    x = jnp.broadcast_to(x0[:, None, :], (q, n, b)).astype(jnp.float32)

    def _select_w(t):
        # linear path: raw val/t so a K-column underflow surfaces as NaN
        # for the engine's LamUnderflowError guard. log path: t == 0 can
        # only mean a fully-underflowed query-word ROW at extreme lam —
        # guard it so the word drops out instead of poisoning the doc.
        if not log_domain:
            return jnp.where(live[None], val[None] / t, 0.0)
        ok = live[None] & (t > 0)
        return jnp.where(ok, val[None] / jnp.where(ok, t, 1.0), 0.0)

    # pad rows keep x == 0 exactly (their G is 0), so a single x > 0 guard
    # on u suffices — the untaken 1/0 branch yields inf which the select
    # discards; live-entry arithmetic matches the per-query oracle's.
    def step(carry, _):
        x, _ = carry
        u = jnp.where(x > 0, 1.0 / x, 0.0)
        t = _sddmm(u)                                   # SDDMM
        w = _select_w(t)
        x = _spmm(w) * rinv                             # SpMM (fused)
        return (x, w), None

    if tol is None:
        # x-only carry: bit-identical to the pre-adaptive dispatch (the
        # step's w is only needed by the residual check)
        x, _ = lax.scan(lambda x, _: (step((x, None), None)[0][0], None),
                        x, None, length=n_iter)
        iters = jnp.asarray(n_iter, jnp.int32)
    elif scope == "chunk":
        # residual mask: live queries (any support) x live doc slots —
        # filler queries' w is inf/NaN and padded docs' is 0; both are
        # excluded so they can neither hold the loop open nor close it
        resmask = ((jnp.sum(mask, axis=1) > 0)[:, None, None]
                   & live[None])                        # (Q, N, L)
        x, iters = adaptive_loop(
            lambda x: step((x, None), None)[0],
            lambda w, wp: marginal_residual(w, wp, resmask),
            x, n_iter, tol, check_every)
    else:
        # per-query scope (ISSUE 5): each query's residual covers only
        # its own live slots — narrowed to its candidate docs when the
        # caller provides qdoc_mask — and converged queries freeze
        live_q = jnp.sum(mask, axis=1) > 0              # (Q,)
        resmask = live_q[:, None, None] & live[None]    # (Q, N, L)
        if qdoc_mask is not None:
            resmask = resmask & qdoc_mask[:, :, None]

        def step_active(x, active):
            # frozen queries' rows drop out of the update: their u rows
            # are zeroed, so SDDMM/SpMM emit zeros the freeze discards
            u = jnp.where(x > 0, 1.0 / x, 0.0) * active[:, None, None]
            t = _sddmm(u)
            w = _select_w(t)
            return _spmm(w) * rinv, w

        x, iters = adaptive_loop_scoped(
            step_active,
            lambda w, wp: marginal_residual_per_query(w, wp, resmask),
            x, n_iter, tol, check_every, live_q)

    u = jnp.where(x > 0, 1.0 / x, 0.0)
    t = _sddmm(u)
    w = _select_w(t)
    mg = jnp.take(mq, idx, axis=1)                      # (Q, N, L, B)
    gm = jnp.where(g > 0, g * mg, 0.0)
    # wmd[q,n] = sum_b u sum_l GM w — with the TRUE gathered M, exact for
    # the stabilized log-domain G too (G' M w' == G M w identically)
    wmd = jnp.einsum("qnb,qnlb,qnl->qn", u, gm, w)
    if not with_profile:
        return wmd, iters
    # per-query doc-mean of the converged x: the warm-start profile
    # survivor solves reuse (survivors share the query's gathered columns,
    # so the converged per-word scaling transfers). Averaged over each
    # query's OWN candidate docs (prof_mask) — the chunk union includes
    # other queries' seeds, whose far-pair columns would pollute the
    # profile with a wildly different scale
    doc_live = jnp.sum(val, axis=1) > 0                       # (N,)
    sel = prof_mask if prof_mask is not None else qdoc_mask
    pmask = (doc_live[None] if sel is None
             else sel & doc_live[None])                       # (Q, N)
    pmask = pmask.astype(x.dtype)
    cnt = jnp.maximum(jnp.sum(pmask, axis=1), 1.0)            # (Q,)
    xprof = jnp.einsum("qnb,qn->qb", x, pmask) / cnt[:, None]
    return wmd, iters, xprof


@functools.partial(jax.jit, static_argnames=("lam", "gemm", "log_domain",
                                             "with_m", "vocab_major"))
def _compute_kq(sup: jax.Array, mask: jax.Array, vecs: jax.Array,
                vecs_sq: jax.Array, lam: float, gemm: str = "fp32",
                log_domain: bool = False, with_m: bool = True,
                vocab_major: bool = False):
    """Stacked cdist GEMM -> K for one query chunk: (Q, B) ids -> (Q, V, B).

    One (V, Q*B) GEMM replaces Q separate (v_r, V) cdists. Its output
    holds each vocabulary word's Q*B kernel values in one contiguous row.
    ``vocab_major=True`` returns that (V, Q*B) kernel ``kt`` alone, the
    resident solve's hand-over: its gather copies one whole row per doc
    word (:func:`_gather_g`). Otherwise it is reordered to (Q, V, B) on
    this SMALL matrix, never on the Q*N*L*B gather output. Padded rows
    (mask == 0) come out as all-zero K columns (G == 0).

    Returns (kq (Q, V, B), mq (Q, V, B)): the kernel AND the raw cdist —
    the solve's distance-line epilogue gathers its transport costs from
    ``mq`` instead of reconstructing them via ``log(G)`` (see
    :func:`_solve_batched_einsum`). ``mq`` is unmasked (the epilogue's
    ``g > 0`` guard excludes pad rows). ``with_m=False`` returns ``kq``
    alone — the Pallas paths reconstruct GM in VMEM and must not pay an
    unused (Q, V, B) buffer per staged chunk.

    ``gemm="bf16"`` casts only the GEMM operands (fp32 accumulation via
    ``preferred_element_type``); ``log_domain=True`` returns
    UNexponentiated ``log K = -lam*M`` with masked rows at -inf — the
    solve stabilizes per gathered column (:func:`_stabilize_log_g`), so
    no K column can underflow at any lam.
    """
    q, b = sup.shape
    a = jnp.take(vecs, sup.reshape(-1), axis=0)         # (Q*B, w)
    d2 = sq_dists(vecs, a, vecs_sq, gemm_dtype=(
        jnp.bfloat16 if gemm == "bf16" else None))      # (V, Q*B)
    m = jnp.sqrt(jnp.maximum(d2, 0.0))
    if log_domain:
        kt = jnp.where(mask.reshape(1, -1) > 0, -lam * m, -jnp.inf)
    else:
        kt = jnp.exp(-lam * m) * mask.reshape(1, -1)
    if vocab_major:
        return kt
    kq = jnp.transpose(kt.reshape(-1, q, b), (1, 0, 2))       # (Q, V, B)
    if not with_m:
        return kq
    return kq, jnp.transpose(m.reshape(-1, q, b), (1, 0, 2))


@functools.partial(jax.jit, static_argnames=("layout", "block_n", "q"))
def _gather_g(kq: jax.Array, idx: jax.Array, layout: str = "qnlb",
              block_n: int = 1, q: int = 1):
    """Gather doc-word columns of K: (Q, V, B) x (N, L) -> G.

    Kept as its own jit (with :func:`_compute_kq` separate too): XLA CPU
    otherwise fuses the exp/sqrt producer INTO the gather and recomputes it
    per gathered element (~2.4x slower end to end); on TPU the boundary is
    where the engine hands off to the Mosaic kernel anyway.

    ``layout="qlbn"`` is the resident solve's tile (Q, L, B, N_pad) from
    the vocabulary-major (V, Q*B) kernel ``kq`` of ``q`` queries: the docs
    padded to whole ``block_n`` blocks of word-0 columns (inert: the solve
    gives them val 0) and on the minor axis, with the query words
    second-minor. Each doc word's Q*B values are one contiguous row,
    gathered whole in (L, N_pad) order, so the reorder to the tile is a
    batch of 2-D (N_pad, Q*B) -> (Q*B, N_pad) transposes. Doc word ids
    lie in [0, V), so ``mode="clip"`` changes no value; it spares the
    masking pass over the tile that ``jnp.take``'s default fill mode adds
    (on a v5e, 10-30% of the gather's time).
    """
    if layout == "qbnl":
        # TPU tile layout: (v_r, block_n, L) per query, sublane = query rows
        return jnp.take(jnp.transpose(kq, (0, 2, 1)), idx, axis=2)
    if layout == "qlbn":
        idx = jnp.pad(idx, ((0, -idx.shape[0] % block_n), (0, 0)))
        n_pad, length = idx.shape
        rows = jnp.take(kq, idx.T.reshape(-1), axis=0,
                        mode="clip")                        # (L*N_pad, Q*B)
        rows = jnp.swapaxes(rows.reshape(length, n_pad, -1), 1, 2)
        return jnp.swapaxes(rows.reshape(length, q, -1, n_pad), 0, 1)
    return jnp.take(kq, idx, axis=1)                         # (Q, N, L, B)


def _on_tpu(x: jax.Array) -> bool:
    """Whether the device array ``x`` lives on a TPU."""
    return all(d.platform == "tpu" for d in x.devices())


_solve_gathered = jax.jit(_solve_batched_einsum,
                          static_argnames=("lam", "n_iter", "tol",
                                           "check_every", "gemm",
                                           "log_domain", "scope",
                                           "with_profile"))


def _staged_width(v_r: int, bucket: int) -> int:
    """Staging width of a chunk whose widest query has ``v_r`` words, in
    the pow2 ``bucket``: the grid of 8 up to 64 words, of 32 above."""
    step = 8 if v_r <= 64 else 32
    return max(8, min(bucket, -(-v_r // step) * step))


def _prepare_query(q, bucket: int, dtype):
    """Host-side support selection + bucket padding for one query row."""
    q = np.asarray(q, dtype=np.float64).reshape(-1)
    idx = np.nonzero(q > 0)[0]
    v_r = idx.size
    if v_r > bucket:
        raise ValueError(f"query v_r={v_r} exceeds bucket {bucket}")
    sup = np.zeros(bucket, np.int32)
    sup[:v_r] = idx
    r = np.ones(bucket, dtype)                # pad rows carry r == 1
    r[:v_r] = (q[idx] / q[idx].sum()).astype(dtype)
    mask = np.zeros(bucket, dtype)
    mask[:v_r] = 1.0
    return sup, r, mask


# docs per candidate tile in search: candidate solves run in tiles of the
# index's doc groups (CorpusIndex.tiles), so their shapes and memory follow
# the group and this count, never how many docs survived the prune. A
# multiple of every block_n (the TPU gather pads docs to whole blocks); a
# step of 20NEWS kNN (~2,000 survivors over four groups) fills about one
# tile per group, and fewer, larger tiles mean fewer dispatches
TILE_DOCS = 512

# a candidate set of at most this many docs (a search step's seeds, or few
# survivors) is solved as one tile across the groups, gathered from the
# corpus' ELL rows at the widest group's width, rather than one mostly
# padded tile per group it touches: at 20NEWS widths its K tile is a
# quarter of the widest group's tile. A multiple of every block_n
FEW_DOCS = 128

# device bytes of gathered candidate tiles that search's solves may hold
# in flight at once: the host reads the oldest tile's result back before it
# dispatches past this, so a candidate solve's memory stays bounded however
# many tiles its candidates fill. One tile queued behind the running one
# keeps the device busy; a 20NEWS tile is at most 302 MB (Q 4, L = B = 192)
INFLIGHT_BYTES = 1 << 28


class SearchResult(NamedTuple):
    """Top-k retrieval result from :meth:`WmdEngine.search`.

    Rows for empty queries (no support) hold ``indices == -1`` and NaN
    distances. ``solved`` counts the documents that went through the exact
    Sinkhorn solve for each query — ``n_docs`` when exhaustive, the
    surviving-candidate count when pruned, and the query's own
    rank-selected pick count (<= ``refine_factor * k``) in
    ``mode="refine"``.
    """

    indices: np.ndarray    # (Q, k) int32 doc ids, ascending distance
    distances: np.ndarray  # (Q, k)
    solved: np.ndarray     # (Q,) int64 exact solves per query


class WmdEngine:
    """Persistent multi-query WMD engine over a frozen :class:`CorpusIndex`.

    Parameters
    ----------
    index:       corpus state from :func:`build_index` (reused across calls)
    lam, n_iter: Sinkhorn strength / iteration count (static per engine)
    impl:        "sparse" (batched einsum; on a TPU its fixed-iteration
                 solves run the VMEM-resident Pallas kernel) or "kernel"
                 (batched Pallas)
    min_bucket:  smallest v_r bucket; queries are padded up to powers of two
    max_batch:   per-solve query cap — larger buckets are chunked so the
                 (Q, B, N, L) gathered tile stays memory-bounded
    pad_q:       round each chunk's Q up to a power of two with inert all-pad
                 queries, bounding the set of compiled shapes under serving
                 traffic (Q buckets x v_r buckets executables total)
    prune_slack: relative safety margin on the prune threshold in
                 :meth:`search` — admissible bounds and exact scores are
                 both fp32, so a candidate is kept unless its bound exceeds
                 the threshold by more than this fraction. Costs a few extra
                 survivors; guards the exact-top-k contract against rounding.
    tol:         convergence-adaptive solve (ISSUE 4): exit the Sinkhorn
                 loop once every live doc's marginal residual
                 ``max|val/t - w_prev|`` (relative to the doc's own
                 marginal scale) is below ``tol``, checked every
                 ``check_every`` iterations. ``None`` (default) keeps the
                 fixed-length loop bit-for-bit; with ``tol`` set,
                 ``n_iter`` becomes a cap (realized counts land on
                 ``1 + k*check_every``). Realized counts:
                 :meth:`iter_stats`.
    scope:       adaptive-exit granularity (ISSUE 5). ``"query"``
                 (default): each query's residual covers only its own
                 live slots, converged queries freeze their x-columns
                 (operand rows zeroed; the loop exits when every live
                 query converged) — one stubborn query no longer holds
                 its chunkmates' realized counts open. In :meth:`search`
                 the survivor solve's scope narrows further to the docs
                 whose bound passed that query's own threshold (the seed
                 solve keeps the union scope: any seed can contend for
                 any query once thresholds exist). ``"chunk"`` keeps
                 ISSUE 4's chunk-global scalar exit. Only consulted when
                 ``tol`` is set.
    warm_start:  survivor solves in :meth:`search` start from the seed
                 solve's converged per-query x profile instead of the
                 uniform init (survivors share the query's gathered
                 columns, so the scaling transfers; docs open at the
                 profile and re-converge in fewer iterations — measured
                 in :meth:`iter_stats_by_stage` as the ``"survivor"``
                 series). Opt-in, and only active with ``tol`` set on
                 the einsum path (``impl="sparse"``): warm starting is
                 sound when the adaptive exit actually CONVERGES (both
                 inits land within ``tol`` of the same fixed point); in
                 a cap-bound regime (``n_iter`` hit first) it changes
                 the truncated values, making survivor distances
                 incomparable with the cold seed stage.
    precision:   :class:`~repro.core.sinkhorn_sparse.SolvePrecision` or
                 its spelling (``"fp32"``, ``"bf16"``, ``"log"``,
                 ``"bf16+log"``) — bf16 GEMMs with fp32 accumulation
                 (tolerance-bounded) and/or the log-domain kernel (exact;
                 makes :class:`LamUnderflowError` impossible at any lam).
    iter_stats_maxlen: bound on the realized-iteration ring
                 (:meth:`iter_stats`); overflow discards the OLDEST record
                 and is counted by :attr:`iter_stats_dropped` so a
                 long-running serve can tell a window from a full history.
    kcache_slots: opt-in cross-request cdist-row cache (ISSUE 10;
                 ``impl="sparse"`` only): keep this many hot words'
                 ``(V,)`` corpus-distance rows device-resident with an
                 LRU clock, so Zipfian serving traffic assembles its
                 ``(Q, V, B)`` K block from cached rows + a misses-only
                 GEMM instead of recomputing the full stacked GEMM per
                 dispatch. Bit-exact against the uncached path (see
                 ``core/kcache.py``); the serving runtime enables it by
                 default. ``None``/``0`` disables.
    kcache_min_hits: dispatch-economy threshold: a chunk with fewer
                 resident rows than this falls back to the one-shot
                 stacked GEMM (cheaper on CPU than gather + miss GEMM +
                 scatter) and warms the cache from its ``mq`` block.
    """

    def __init__(self, index: CorpusIndex, lam: float = 10.0,
                 n_iter: int = 15, impl: str = "sparse",
                 min_bucket: int = 8, max_batch: int = 4,
                 pad_q: bool = True, block_n: int = 128,
                 interpret: bool | None = None, dtype=jnp.float32,
                 prune_slack: float = 1e-3, tol: float | None = None,
                 check_every: int = 4, precision=None,
                 scope: str = "query", warm_start: bool = False,
                 iter_stats_maxlen: int = 4096,
                 kcache_slots: int | None = None,
                 kcache_min_hits: int = 4):
        if impl not in ENGINE_IMPLS:
            raise ValueError(f"impl must be one of {ENGINE_IMPLS}, "
                             f"got {impl!r}")
        if scope not in ("chunk", "query"):
            raise ValueError(f"scope must be 'chunk' or 'query', "
                             f"got {scope!r}")
        if kcache_slots and impl == "kernel":
            raise ValueError(
                "kcache_slots needs impl='sparse': the kernel impl's "
                "staged pair carries no mq block to warm the cache from "
                "(and reconstructs GM in VMEM, bypassing the kq the "
                "cache would assemble)")
        self.index = index
        self.lam = float(lam)
        self.n_iter = int(n_iter)
        self.impl = impl
        self.min_bucket = int(min_bucket)
        self.max_batch = int(max_batch)
        self.pad_q = bool(pad_q)
        self.block_n = int(block_n)
        self.interpret = interpret
        self.dtype = np.dtype(jnp.dtype(dtype).name)
        self.prune_slack = float(prune_slack)
        self.tol = None if tol is None else float(tol)
        self.check_every = int(check_every)
        self.precision = SolvePrecision.parse(precision)
        self.scope = scope
        self.warm_start = bool(warm_start)
        # bounded ring: a long-running service must not leak one device
        # scalar per solve dispatch forever (reset_iter_stats() clears).
        # Saturation is OBSERVABLE (ISSUE 6): the ring silently discarding
        # the oldest record under long-running serve looked like "stats
        # cover everything" when they covered the last 4096 dispatches —
        # iter_stats_dropped counts the discards and the serve JSON
        # surfaces it.
        self._iters_pending: collections.deque = collections.deque(
            maxlen=max(1, int(iter_stats_maxlen)))
        self._iters_dropped = 0
        # cross-request cdist-row cache (ISSUE 10): opt-in here, enabled
        # by default by the serving runtime where Zipfian reuse lives
        self._kcache = None
        self.kcache_min_hits = max(1, int(kcache_min_hits))
        if kcache_slots:
            self.enable_kcache(int(kcache_slots))
        self._lookup = (None, None)     # (groups, group_lookup(groups))
        self.last_solved_ids = np.zeros(0, np.int32)
        self.reset_host_stats()

    # ------------------------------------------------- cross-request cache
    def enable_kcache(self, slots: int) -> bool:
        """Attach a :class:`~repro.core.kcache.KCache` of ``slots``
        resident cdist rows (replacing any existing cache). Returns
        ``False`` on the kernel impl — its staged pair has no ``mq`` to
        warm from — so serving's enable-by-default stays a no-op there.
        Search results are unchanged bit-for-bit (the cache module's
        exactness contract, pinned by the property suite)."""
        if self.impl == "kernel":
            return False
        from .kcache import KCache
        self._kcache = KCache(self.index.vecs, self.index.vecs_sq,
                              int(slots), gemm=self.precision.gemm)
        return True

    def kcache_stats(self) -> dict | None:
        """Hit/miss/eviction counters of the cross-request cache
        (``None`` when no cache is attached)."""
        return None if self._kcache is None else self._kcache.stats()

    def reset_kcache_stats(self) -> None:
        if self._kcache is not None:
            self._kcache.reset_counters()

    # ------------------------------------------------------- host counters
    def host_stats(self) -> dict:
        """Host-side counters since the last :meth:`reset_host_stats`:

        - ``dispatches``: the device layer calls the engine makes (one
          per K block in ``_kq``, and one more where a cached block is
          reordered for the resident solve; one per gather and one per
          solve in ``_solve_group``; one per candidate tile's row gather);
        - ``host_syncs``: the blocking result reads of :meth:`query_batch`
          (one per chunk and doc group);
        - ``resident_solves``: the ``_solve_group`` solves that ran the
          VMEM-resident kernel rather than the einsum (see
          :meth:`_solve_group`);
        - ``row_gathers``: resident solves whose tile came from the
          whole-row gather of :meth:`_kq`'s vocabulary-major kernel;
        - ``search_queries``: queries handed to :meth:`search`;
        - ``pairs_solved``: (live query, real candidate doc) pairs that
          :meth:`search`'s candidate solves scored, seeds and survivors;
        - ``slots_solved``: (live query, doc slot) pairs of the candidate
          tiles those solves ran, pad slots included;
        - ``survivor_tiles``: candidate tile solves dispatched (one per
          staged chunk and tile, seed and survivor stages alike);
        - ``prune_syncs``: the blocking host reads of :meth:`search`'s
          prune stages (seed pick, survivor pass, per-query scopes).

        Counting adds no device work and no sync."""
        return {"dispatches": self._dispatches,
                "host_syncs": self._host_syncs,
                "resident_solves": self._resident_solves,
                "row_gathers": self._row_gathers,
                "search_queries": self._search_queries,
                "pairs_solved": self._pairs_solved,
                "slots_solved": self._slots_solved,
                "survivor_tiles": self._survivor_tiles,
                "prune_syncs": self._prune_syncs}

    def reset_host_stats(self) -> None:
        self._dispatches = 0
        self._host_syncs = 0
        self._resident_solves = 0
        self._row_gathers = 0
        self._search_queries = 0
        self._pairs_solved = 0
        self._slots_solved = 0
        self._survivor_tiles = 0
        self._prune_syncs = 0

    # -------------------------------------------------- realized iterations
    def reset_iter_stats(self) -> None:
        """Drop the accumulated realized-iteration log (and the
        dropped-record counter)."""
        self._iters_pending.clear()
        self._iters_dropped = 0

    @property
    def iter_stats_dropped(self) -> int:
        """Dispatch records discarded by the bounded ring since the last
        :meth:`reset_iter_stats` — nonzero means :meth:`iter_stats` is a
        WINDOW over the most recent ``iter_stats_maxlen`` dispatches, not
        the full history (long-running serve saturates it by design)."""
        return self._iters_dropped

    def _record_iters(self, stage: str, iters, n_live: int | None) -> None:
        """Log one dispatch's realized counts (device values, synced
        lazily in :meth:`iter_stats`): a scalar for chunk-scoped solves,
        a per-query vector for ``scope="query"`` — ``n_live`` trims the
        vector to the chunk's real queries (fillers freeze at the first
        check and would pollute the histogram)."""
        if len(self._iters_pending) == self._iters_pending.maxlen:
            self._iters_dropped += 1    # ring full: oldest record discarded
        self._iters_pending.append((stage, iters, n_live))

    def iter_stats(self, stage: str | None = None) -> np.ndarray:
        """Realized Sinkhorn iteration counts since the last
        :meth:`reset_iter_stats` (device values are synced here, not on
        the hot path; the log keeps the most recent 4096 dispatches).
        Chunk-scoped solves contribute one entry per dispatch; per-query
        solves one entry per LIVE query per dispatch. With ``tol=None``
        every entry equals ``n_iter``; with the adaptive loop this is the
        early-exit histogram the fig10 benchmark reports. ``stage``
        filters to one solve stage (``"batch"`` for exhaustive
        :meth:`query_batch` solves, ``"seed"``/``"survivor"`` for the two
        :meth:`search` solve stages — the warm-start win is the
        ``"survivor"`` series)."""
        out: list[np.ndarray] = []
        for st, dev, n_live in self._iters_pending:
            if stage is not None and st != stage:
                continue
            arr = np.atleast_1d(np.asarray(dev)).astype(np.int64)
            if n_live is not None and arr.size > 1:
                arr = arr[:n_live]
            elif n_live is not None and arr.size == 1:
                # chunk-scoped / fixed dispatch: every live query pays the
                # chunk's exit iteration — replicate so per-query and
                # chunk-scoped histograms measure the same unit (realized
                # iterations PER QUERY) and the fig10 A/B is fair
                arr = np.full(n_live, arr[0], np.int64)
            out.append(arr)
        if not out:
            return np.zeros((0,), np.int64)
        return np.concatenate(out)

    def iter_stats_by_stage(self) -> dict:
        """Realized-iteration log split by solve stage — the serve
        metadata / fig10 view of where iterations actually go (seed
        solves pay the cold init; warm-started survivor solves should
        report strictly fewer)."""
        stages = []
        for st, _, _ in self._iters_pending:
            if st not in stages:
                stages.append(st)
        return {st: self.iter_stats(stage=st) for st in stages}

    def _ext(self, storage_ids) -> np.ndarray:
        """Storage ids -> caller-order doc ids (the output boundary)."""
        return self.index.to_external(np.asarray(storage_ids))

    def query(self, r_full) -> jax.Array:
        """WMD from one full-vocab query histogram to every doc: (N,)."""
        return self.query_batch([r_full])[0]

    # ------------------------------------------------------------ staging
    @functools.partial(jax.profiler.annotate_function, name="wmd.plan")
    def _plan(self, queries: list):
        """Bucket + chunk the query set: [(input positions, width), ...].

        Queries are grouped into power-of-two v_r buckets and SORTED by v_r
        inside each bucket; each ``max_batch``-sized chunk is then trimmed
        to the smallest width of the staging grid covering its members:
        multiples of 8 (the TPU sublane) up to 64 words, multiples of 32
        above (96, 128, 160, 192, ...). The pow2 buckets bound the
        executable count and the sort + trim bounds padding waste to < 8
        rows per query on short queries; the coarser grid above 64 words
        halves the number of widths a long-query deployment compiles
        (11 instead of 23 for 16-192 words) at < 32 rows of padding on
        queries that long. Empty queries (no support) are left out
        entirely.
        """
        vr = [int((q > 0).sum()) for q in queries]
        buckets: dict[int, list[int]] = {}
        for qi in range(len(queries)):
            if vr[qi] == 0:
                continue        # empty marginal: NaN row, never solved
            buckets.setdefault(bucket_size(vr[qi], self.min_bucket),
                               []).append(qi)
        chunks = []
        for b in sorted(buckets):
            members = sorted(buckets[b], key=lambda qi: vr[qi])
            for lo in range(0, len(members), self.max_batch):
                chunk = members[lo:lo + self.max_batch]
                chunks.append((chunk, _staged_width(
                    max(vr[qi] for qi in chunk), b)))
        return vr, chunks

    @functools.partial(jax.profiler.annotate_function, name="wmd.stage")
    def _prep_chunk(self, chunk_queries: list, width: int,
                    q_pad: int | None = None):
        """Stage one chunk: (sup, r, mask) device arrays, q-padded to a
        power of two with inert fillers (no support -> G rows all 0, r == 1)
        when ``pad_q``, or to ``q_pad`` rows when given."""
        prepared = [_prepare_query(q, width, self.dtype)
                    for q in chunk_queries]
        n_live = len(prepared)
        if q_pad is None:
            q_pad = bucket_size(n_live, 1) if self.pad_q else n_live
        filler = (np.zeros(width, np.int32), np.ones(width, self.dtype),
                  np.zeros(width, self.dtype))
        prepared += [filler] * (q_pad - n_live)
        return (jnp.asarray(np.stack([p[0] for p in prepared])),
                jnp.asarray(np.stack([p[1] for p in prepared])),
                jnp.asarray(np.stack([p[2] for p in prepared])))

    @functools.partial(jax.profiler.annotate_function, name="wmd.dispatch")
    def _solve_group(self, kq, r, mask, grp: DocGroup, n_live=None,
                     stage: str = "batch", qdoc_mask=None, x0q=None,
                     want_profile: bool = False, prof_mask=None):
        """Solve one prepared chunk against one doc group (device array,
        not yet synced): gather the group's K columns, run the batched
        solver. Works for index groups and pruned candidate subsets alike —
        the solve stage of the pipeline. ``kq`` is the pair from
        :meth:`_kq`. Realized iteration counts land in :meth:`iter_stats`
        under ``stage`` (device values, synced lazily).

        ``qdoc_mask`` (Q, N_grp) scopes each query's adaptive exit to its
        own candidate docs (``scope="query"``); ``x0q`` (Q, B) warm-starts
        the solve from a per-query profile; ``want_profile=True`` returns
        ``(wmd, profile)`` — the converged profile survivor solves reuse,
        averaged over ``prof_mask`` docs (``None`` on the kernel path,
        which reconstructs GM in VMEM and does not expose x).

        Where :meth:`_kq` handed over the vocabulary-major kernel (on a
        TPU, a fixed-iteration solve: see :attr:`_resident`), the solve
        runs the VMEM-resident kernel
        (:func:`repro.kernels.sddmm_spmm.sinkhorn_resident`) over a tile
        gathered one whole K row per doc word: each doc block's tile is
        read from HBM once, where the einsum rereads it twice per
        iteration. Its distances are the einsum's up to rounding. Every
        other solve, and every solve off a TPU, runs the einsum."""
        kqk, mq = kq
        self._dispatches += 2           # the gather and the solve below
        if kqk.ndim == 2:               # (V, Q*B): the resident hand-over
            if x0q is not None or want_profile:
                raise ValueError("warm starts and profiles need tol set; "
                                 "the resident solve runs without it")
            from repro.kernels.ops import sinkhorn_resident
            g = _gather_g(kqk, grp.docs.idx, layout="qlbn",
                          block_n=self.block_n, q=r.shape[0])
            self._row_gathers += 1
            wmd = sinkhorn_resident(
                g, grp.docs.val, r, mask, self.lam, self.n_iter,
                block_n=self.block_n, interpret=self.interpret,
                gemm=self.precision.gemm,
                log_domain=self.precision.log_domain)
            self._resident_solves += 1
            self._record_iters(stage, np.int32(self.n_iter), n_live)
            return wmd
        layout = "qbnl" if self.impl == "kernel" else "qnlb"
        g = _gather_g(kqk, grp.docs.idx, layout=layout)
        scoped = self.tol is not None and self.scope == "query"
        if self.impl == "kernel":
            from repro.kernels.ops import sinkhorn_fused_all_batched
            wmd, iters = sinkhorn_fused_all_batched(
                g, grp.docs.val, r, self.lam, self.n_iter,
                block_n=self.block_n, interpret=self.interpret,
                tol=self.tol, check_every=self.check_every,
                gemm=self.precision.gemm,
                log_domain=self.precision.log_domain,
                resmask=qdoc_mask if scoped else None, with_iters=True,
                mask=mask)
            # per-block counts -> per-query realized iterations (a query's
            # slowest candidate block is when its columns actually froze)
            self._record_iters(stage,
                               jnp.max(iters, axis=1) if scoped
                               else jnp.max(iters), n_live)
            return (wmd, None) if want_profile else wmd
        out = _solve_gathered(g, mq, grp.docs.idx, grp.docs.val, r,
                              mask, self.lam, self.n_iter, self.tol,
                              self.check_every, self.precision.gemm,
                              self.precision.log_domain,
                              scope=self.scope,
                              qdoc_mask=qdoc_mask if scoped else None,
                              x0q=x0q, with_profile=want_profile,
                              prof_mask=prof_mask)
        wmd, iters = out[0], out[1]
        self._record_iters(stage, iters, n_live)
        if want_profile:
            return wmd, out[2]
        return wmd

    @property
    def _warms_survivors(self) -> bool:
        """Whether :meth:`search`'s survivor solves warm-start from the
        seed solve's profile (``warm_start`` with ``tol`` set)."""
        return self.warm_start and self.tol is not None

    def _solve_profiled(self, kq, r, mask, grp: DocGroup, **kw):
        """:meth:`_solve_group` for :meth:`search`: ``(wmd, profile)``,
        the profile asked for only where :attr:`_warms_survivors` and
        ``None`` otherwise."""
        if not self._warms_survivors:
            return self._solve_group(kq, r, mask, grp, **kw), None
        return self._solve_group(kq, r, mask, grp, want_profile=True, **kw)

    @property
    def _resident(self) -> bool:
        """Whether every solve runs the VMEM-resident kernel: the sparse
        impl, fixed iterations (``tol`` unset, so no warm start or
        profile either: see :attr:`_warms_survivors`), on a TPU."""
        return (self.impl == "sparse" and self.tol is None
                and _on_tpu(self.index.vecs))

    @functools.partial(jax.profiler.annotate_function, name="wmd.dispatch")
    def _kq(self, sup, mask):
        """(kq, mq) for one staged chunk — treat as an opaque pair; the
        solve stage consumes both (kernel gather + distance epilogue).
        The Pallas paths reconstruct GM in VMEM, so their pairs carry
        ``mq=None`` instead of an unused (Q, V, B) buffer: the kernel
        impl's kq is (Q, V, B), and the resident solve's
        (:attr:`_resident`) is the GEMM's vocabulary-major (V, Q*B)
        kernel, whose doc-word gather copies whole rows.

        With a :meth:`enable_kcache` cache attached, chunks whose words
        are mostly resident assemble the pair from cached cdist rows
        (gather + misses-only GEMM) instead of the full stacked GEMM;
        below ``kcache_min_hits`` resident rows the one-shot GEMM is
        cheaper on CPU (dispatch economy — see the ROADMAP refusion
        note) and its ``mq`` block warms the cache for the next request.
        Both paths produce BIT-IDENTICAL pairs (``core/kcache.py``); for
        the resident solve the cached path reorders its kq to (V, Q*B)."""
        self._dispatches += 1           # one K block program, either path
        prec = {"gemm": self.precision.gemm,
                "log_domain": self.precision.log_domain}
        if self.impl == "kernel":
            return _compute_kq(sup, mask, self.index.vecs,
                               self.index.vecs_sq, self.lam, **prec,
                               with_m=False), None
        cache = self._kcache
        if cache is None:
            if self._resident:
                return _compute_kq(sup, mask, self.index.vecs,
                                   self.index.vecs_sq, self.lam, **prec,
                                   vocab_major=True), None
            return _compute_kq(sup, mask, self.index.vecs,
                               self.index.vecs_sq, self.lam, **prec)
        if cache.vecs is not self.index.vecs:
            # anything that swapped the embedding table (a new index, a
            # snapshot reload) invalidates every resident row; append_docs
            # reuses vecs by identity — the vocabulary is frozen — so
            # appends sail through here with the cache intact
            cache = self._kcache = cache.rebind(self.index.vecs,
                                                self.index.vecs_sq)
        sup_np = np.asarray(sup)
        ids = np.unique(sup_np.reshape(-1))
        n_hit = cache.lookup(ids)
        oversize = len(ids) > cache.slots
        if oversize or n_hit < self.kcache_min_hits:
            cache.note_fallback(oversize=oversize)
            pair = _compute_kq(sup, mask, self.index.vecs,
                               self.index.vecs_sq, self.lam, **prec)
            cache.warm(sup_np, pair[1])
        else:
            from .kcache import assemble_kq
            rows = cache.rows(ids)
            inv = jnp.asarray(np.searchsorted(ids, sup_np).astype(np.int32))
            pair = assemble_kq(rows, inv, mask, self.lam,
                               log_domain=self.precision.log_domain)
        if not self._resident:
            return pair
        self._dispatches += 1           # the reorder below
        kq = pair[0]
        return jnp.transpose(kq, (1, 0, 2)).reshape(kq.shape[1], -1), None

    def _raise_if_nan(self, wmd_np: np.ndarray, chunk_queries: list) -> None:
        """Every chunk query has support, so NaN here means the lam-driven
        K underflow — diagnose (host-side, error path only) and raise
        instead of returning NaN distances."""
        bad = np.isnan(wmd_np).any(axis=1)
        if bad.any():
            from .sinkhorn import select_support
            q = chunk_queries[int(np.nonzero(bad)[0][0])]
            _, vecs_sel, _ = select_support(q, self.index.vecs)
            raise LamUnderflowError(underflow_report(
                self.lam, vecs_sel, self.index.vecs, self.index.docs))

    # ----------------------------------------------------------- scoring
    @functools.partial(jax.profiler.annotate_function,
                       name="wmd.query_batch")
    def query_batch(self, queries: Sequence) -> jax.Array:
        """Exhaustive WMD for Q queries (full-vocab histogram rows) ->
        (Q, N). Row order matches the input; a query with no support yields
        a NaN row (WMD is undefined for an empty marginal). Raises
        :class:`LamUnderflowError` if lam underflows K for a corpus word
        (the distances would be NaN).
        """
        queries = [np.asarray(q) for q in queries]
        if not queries:
            return jnp.zeros((0, self.index.n_docs), self.dtype)
        vr, chunks = self._plan(queries)
        # dispatch every chunk before collecting any result: device compute
        # of chunk i overlaps host prep of chunk i+1
        pending = []
        for chunk, width in chunks:
            sup, r, mask = self._prep_chunk([queries[qi] for qi in chunk],
                                            width)
            kq = self._kq(sup, mask)
            parts = [(grp, self._solve_group(kq, r, mask, grp,
                                             n_live=len(chunk)))
                     for grp in self.index.groups]
            pending.append((chunk, parts))
        out = np.zeros((len(queries), self.index.n_docs), self.dtype)
        for qi in range(len(queries)):
            if vr[qi] == 0:
                out[qi] = np.nan
        for chunk, parts in pending:
            for grp, wmd_g in parts:
                with jax.profiler.TraceAnnotation("wmd.collect"):
                    w = np.asarray(wmd_g)[:len(chunk)]
                self._host_syncs += 1
                with jax.profiler.TraceAnnotation("wmd.scatter"):
                    self._raise_if_nan(w, [queries[qi] for qi in chunk])
                    # group cols are STORAGE ids (cluster-major); scatter
                    # into the caller's doc order at this output boundary
                    out[np.ix_(chunk, self._ext(grp.cols))] = w
        with jax.profiler.TraceAnnotation("wmd.return"):
            return jnp.asarray(out)

    # ------------------------------------------------------------ search
    @functools.partial(jax.profiler.annotate_function, name="wmd.search")
    def search(self, queries: Sequence, k: int, prune: object = "rwmd",
               nprobe: int | None = None, mode: str = "exact",
               refine_factor: int = 4) -> SearchResult:
        """Staged top-k retrieval: prune -> solve -> rank.

        ``prune=None`` scores exhaustively (:meth:`query_batch` + argsort,
        bit-for-bit). Otherwise ``prune`` names a lower bound from
        :mod:`repro.core.prune` (``"wcd"``, ``"rwmd"``, ``"wcd+rwmd"``, a
        cascaded ``"ivf+pivot+wcd+rwmd"``) or is a
        :class:`~repro.core.prune.Pruner` /
        :class:`~repro.core.prune.CascadePruner` instance, and per chunk:

        1. *prune*: admissible lower bounds, one batched pass. Full-sweep
           pruners score every (query, doc) pair; a cascade first
           shortlists via the index's IVF clusters (``nprobe`` nearest per
           query; ``None`` = all = exact), bounds only the shortlist, and
           computes each later (costlier) bound only on the docs the
           previous stage could not exclude;
        2. *solve* (seed): exact Sinkhorn on the union of each query's k
           best-bounded docs, in fixed-shape tiles of the index's doc
           groups (:meth:`CorpusIndex.tiles`, :data:`TILE_DOCS` docs each);
           the per-query kth-smallest exact distance becomes the pruning
           threshold t_q — any doc with lb > t_q cannot enter the top-k.
           Seed selection and thresholding run device-side (top_k / sort
           on the bound matrices); only compact id arrays reach the host;
        3. *solve* (survivors): exact Sinkhorn on the docs whose bound
           passes t_q (+ ``prune_slack`` fp margin);
        4. *rank*: merge and argsort the exact distances.

        With an admissible bound the result equals the exhaustive top-k
        (indices and distances, up to tie order) while Sinkhorn runs on a
        strict subset of documents — ``result.solved`` reports how strict,
        and :attr:`last_solved_ids` holds the (caller-order) ids of the
        docs the last call solved.
        The guarantee holds for ``"rwmd"`` (and its compositions), which
        bounds the *computed* truncated-Sinkhorn score; ``"wcd"`` alone
        bounds exact EMD and is exact only up to the iteration's
        query-marginal residual vs ``prune_slack`` — near-exact at
        practical ``n_iter``, see :mod:`repro.core.prune`. A cascade at
        ``nprobe < n_clusters`` is *approximate*: un-probed clusters are
        never scored, recall is measured (monotone in ``nprobe``), and a
        query with fewer than k reachable candidates pads its result row
        with ``-1`` / NaN.

        ``mode="refine"`` (rank-then-refine, LC-RWMD style) trades the
        exact-top-k guarantee for a *bounded solve budget*: instead of
        seed-solve + threshold + survivor-solve, every candidate is RANKED
        by the pruner's tightest lower bound and only each query's best
        ``k' = refine_factor * k`` candidates are Sinkhorn-solved; the
        top-k of those exact distances is returned. Exactness contract:

        - every returned *distance* is still the exact (converged /
          truncated per the engine's solve policy) Sinkhorn score — the
          approximation is only in *which* docs get solved;
        - each query is ranked over its OWN k' picks, and pick sets are
          nested in ``refine_factor``, so recall@k against the exact path
          is monotone in ``refine_factor`` for a fixed query batch
          (measured in ``benchmarks/fig13_pareto.py``);
        - once ``k'`` covers the whole candidate universe (``nprobe``
          permitting), the result equals ``mode="exact"`` at the same
          ``nprobe`` — exactly equal to the exhaustive top-k when
          ``nprobe=None`` (up to tie order);
        - ``result.solved`` reports each query's own solved-candidate
          count (<= ``refine_factor * k``), not the chunk union.

        Failure modes: raises :class:`ValueError` for ``k <= 0``, an
        unknown ``mode``/``prune`` spec, ``refine_factor < 1``, or
        ``mode="refine"`` with ``prune=None`` (no bound to rank by);
        raises :class:`~repro.core.sinkhorn.LamUnderflowError` when
        ``exp(-lam * M)`` underflows for a solved pair (impossible under
        ``precision="log"``).
        """
        queries = [np.asarray(q) for q in queries]
        n = self.index.n_docs
        if k <= 0:
            raise ValueError(f"k must be positive, got {k}")
        if mode not in ("exact", "refine"):
            raise ValueError(f"mode must be 'exact' or 'refine', "
                             f"got {mode!r}")
        if mode == "refine":
            if prune is None:
                raise ValueError(
                    "mode='refine' ranks candidates by a pruner's lower "
                    "bound; prune=None has no bound to rank by — use "
                    "mode='exact' for the exhaustive path")
            if int(refine_factor) < 1:
                raise ValueError(f"refine_factor must be >= 1, "
                                 f"got {refine_factor}")
        k = min(int(k), n)
        nq = len(queries)
        self._search_queries += nq
        self.last_solved_ids = np.zeros(0, np.int32)
        out_i = np.full((nq, k), -1, np.int32)
        out_d = np.full((nq, k), np.nan, self.dtype)
        solved = np.zeros(nq, np.int64)
        if nq == 0 or n == 0:
            return SearchResult(out_i, out_d, solved)

        if prune is None:
            d = np.asarray(self.query_batch(queries))
            with jax.profiler.TraceAnnotation("wmd.rank"):
                for qi in range(nq):
                    if np.isnan(d[qi]).all():
                        continue                  # empty marginal
                    order = np.argsort(d[qi], kind="stable")[:k]
                    out_i[qi], out_d[qi] = order, d[qi, order]
                    solved[qi] = n
            self.last_solved_ids = np.arange(n, dtype=np.int32)
            return SearchResult(out_i, out_d, solved)

        from .prune import CascadePruner, resolve_pruner
        pruner = resolve_pruner(prune, use_kernel=(self.impl == "kernel"),
                                interpret=self.interpret, nprobe=nprobe)
        _, chunks = self._plan(queries)
        if not chunks:
            return SearchResult(out_i, out_d, solved)
        if mode == "refine":
            self._search_refine(queries, k, pruner, nprobe, chunks,
                                int(refine_factor), out_i, out_d, solved)
        elif isinstance(pruner, CascadePruner):
            self._search_cascade(queries, k, pruner, nprobe, chunks,
                                 out_i, out_d, solved)
        else:
            self._search_full(queries, k, pruner, chunks, out_i, out_d,
                              solved)
        return SearchResult(out_i, out_d, solved)

    @staticmethod
    def _pad_qdoc(qmask: np.ndarray, qp: int, n_pad: int) -> jax.Array:
        """Pad a (qc, |ids|) per-query candidate mask to the solve's
        (Qp, N_pad) shape (fillers and pad docs are False — they are
        outside every query's residual scope by construction)."""
        out = np.zeros((qp, n_pad), bool)
        out[:qmask.shape[0], :qmask.shape[1]] = qmask
        return jnp.asarray(out)

    def _scoped(self) -> bool:
        """Per-query residual scoping active for this engine's solves?"""
        return self.tol is not None and self.scope == "query"

    def _thresholds(self, d_seed: np.ndarray, k: int, qp: int) -> jax.Array:
        """Device-side pruning thresholds, (qp,): each live query's kth
        smallest exact distance among the solved seeds (+ the fp slack
        margin); +inf with fewer than k seeds (nothing may be excluded
        yet), -inf on filler rows (they keep nothing). The seed columns
        are padded to ``k * qp`` with +inf, so the sort's shape is fixed
        by k and the staging, not by how many seeds the queries share."""
        qg = d_seed.shape[0]
        d = np.full((qp, max(k * qp, d_seed.shape[1])), np.inf, self.dtype)
        d[:qg, :d_seed.shape[1]] = d_seed
        return _threshold(jnp.asarray(d), qg,
                          np.asarray(self.prune_slack, self.dtype), k=k)

    def _group_lookup(self) -> tuple:
        """:func:`group_lookup` of the index's groups, kept until the
        engine's index changes."""
        groups, lookup = self._lookup
        if groups is not self.index.groups:
            lookup = group_lookup(self.index.groups)
            self._lookup = (self.index.groups, lookup)
        return lookup

    @staticmethod
    def _member_syncs(pm, stages) -> int:
        """Blocking reads :meth:`CascadePruner.bounds` makes for
        ``stages``: one (the probed clusters) where a partial probe
        restricts the RWMD stage."""
        return int(pm is not None and "rwmd" in stages)

    def _stage_chunks(self, queries, chunks, live_q) -> list:
        """Stage every v_r chunk once for the candidate solves: [(rows in
        ``live_q`` order, queries, r, mask, the kq pair)]."""
        row_of = {qi: g for g, qi in enumerate(live_q)}
        staged = []
        for chunk, width in chunks:
            cq = [queries[qi] for qi in chunk]
            sup, r, mask = self._prep_chunk(cq, width)
            staged.append(([row_of[qi] for qi in chunk], cq, r, mask,
                           self._kq(sup, mask)))
        return staged

    def _solve_candidates(self, staged, qg: int, doc_ids: np.ndarray,
                          qmask=None, stage: str = "seed", warm=None,
                          prof=None):
        """Exact solves of the storage ``doc_ids`` for every staged chunk,
        in fixed-shape tiles of the index's doc groups (each tile's shapes
        are the group's width and :data:`TILE_DOCS`, whatever the count of
        ``doc_ids``; at most :data:`FEW_DOCS` ids are one tile across the
        groups). Tiles are dispatched ahead of their reads, with at
        most :data:`INFLIGHT_BYTES` of gathered tiles not yet read back.

        ``qmask``/``prof`` (qg, |ids|) are the per-query residual scopes
        and warm-profile docs; ``warm`` the per-chunk start profiles.
        Returns ((qg, |ids|) host distances, NaN-checked; per chunk, the
        warm-start profile averaged over its own docs across the tiles,
        or None)."""
        out = np.empty((qg, doc_ids.size), self.dtype)
        tiles = self.index.tiles(doc_ids, TILE_DOCS, storage=True,
                                 lookup=self._group_lookup(), few=FEW_DOCS)
        self._dispatches += len(tiles)           # one row gather per tile
        sel = prof if prof is not None else qmask
        pending = []
        unread = collections.deque()     # [entry, gathered tile bytes]
        held = 0
        for ci, (rows, cq, r, mask, kq) in enumerate(staged):
            qp, n_live = r.shape[0], len(rows)
            for grp, part in tiles:
                t = grp.docs.idx.shape[0]
                qm = (None if qmask is None else
                      self._pad_qdoc(qmask[np.ix_(rows, part)], qp, t))
                pm = (None if prof is None else
                      self._pad_qdoc(prof[np.ix_(rows, part)], qp, t))
                w, xp = self._solve_profiled(
                    kq, r, mask, grp, n_live=n_live, stage=stage,
                    qdoc_mask=qm, x0q=None if warm is None else warm[ci],
                    prof_mask=pm)
                self._survivor_tiles += 1
                self._pairs_solved += n_live * part.size
                self._slots_solved += n_live * t
                entry = [ci, part, w, xp]
                pending.append(entry)
                nbytes = 4 * qp * grp.docs.idx.shape[1] * r.shape[1] * t
                unread.append((entry, nbytes))
                held += nbytes
                # a gathered tile is freed once its solve has run: read the
                # oldest results back while the tiles dispatched and not
                # yet solved could hold more than INFLIGHT_BYTES
                while held > INFLIGHT_BYTES and len(unread) > 1:
                    old, old_bytes = unread.popleft()
                    old[2] = np.asarray(old[2])
                    held -= old_bytes
        profs = [None] * len(staged)
        sums = [None] * len(staged)
        for ci, part, w, xp in pending:
            rows = staged[ci][0]
            out[np.ix_(rows, part)] = np.asarray(w)[:len(rows), :part.size]
            if xp is not None:
                # the profile is a mean over each query's own docs: weigh
                # each tile's mean by how many of them the tile holds
                cnt = (np.full(len(rows), part.size, np.float64)
                       if sel is None else
                       sel[np.ix_(rows, part)].sum(axis=1).astype(np.float64))
                x = np.asarray(xp, np.float64)[:len(rows)] * cnt[:, None]
                if profs[ci] is None:
                    profs[ci], sums[ci] = np.zeros_like(x), np.zeros_like(cnt)
                profs[ci] += x
                sums[ci] += cnt
        for ci, (rows, cq, r, _, _) in enumerate(staged):
            self._raise_if_nan(out[rows], cq)
            if profs[ci] is not None:
                x = np.zeros((r.shape[0], r.shape[1]), self.dtype)
                x[:len(rows)] = profs[ci] / np.maximum(sums[ci], 1.0)[:, None]
                profs[ci] = jnp.asarray(x)
        return out, profs

    def _search_full(self, queries, k, pruner, chunks, out_i, out_d,
                     solved):
        """Full-sweep pruner search (``"wcd"``, ``"rwmd"``, ``"wcd+rwmd"``),
        per v_r chunk: the pruner's (Qp, N) bound matrix, then seeds,
        threshold and survivors (:meth:`_prune_full`), then rank."""
        solved_ids = []
        for chunk, width in chunks:
            cq = [queries[qi] for qi in chunk]
            qc = len(chunk)
            sup, r, mask = self._prep_chunk(cq, width)
            staged = [(list(range(qc)), cq, r, mask, self._kq(sup, mask))]
            cand, d_cand = self._prune_full(pruner, sup, r, mask, qc, k,
                                            staged)
            with jax.profiler.TraceAnnotation("wmd.rank"):
                cand_ext = self._ext(cand)    # storage -> caller doc ids
                for ci, qi in enumerate(chunk):
                    order = np.argsort(d_cand[ci], kind="stable")[:k]
                    out_i[qi, :order.size] = cand_ext[order]
                    out_d[qi, :order.size] = d_cand[ci, order]
                    solved[qi] = cand.size
            solved_ids.append(cand_ext)
        self.last_solved_ids = np.unique(np.concatenate(solved_ids))

    def _prune_full(self, pruner, sup, r, mask, qc, k, staged):
        """PR 2's full-sweep prune stage, with seed selection and
        thresholding moved device-side: (Qc, N) argpartition/partition
        become top_k/sort on the device bound matrix, and only compact id
        arrays (seeds, the survivor bitmap) cross to the host.

        With per-query scoping (ISSUE 5): the SEED solve's residual
        covers the union of real seed docs — any chunkmate's seed can
        contend for any query's top-k once thresholds are known, so its
        distance must be converged for every query that might read it —
        while each query still FREEZES individually (the win). The
        query's OWN k picks drive only its warm-start profile; the
        threshold keeps PR 2's chunk-union tightening (every seed
        distance is now converged for every query, so it is sound). The
        SURVIVOR solve's residual narrows further, to the docs whose
        bound passed that query's threshold — a survivor outside that
        scope is admissibly excluded from its top-k at any truncation
        (RWMD lower-bounds the computed score, so its unconverged value
        stays above the threshold)."""
        from .prune import _keep_any
        scoped = self._scoped()
        lb = pruner.lower_bounds(self.index, sup, r, mask)   # (Qp, N) dev
        # seed: each query's k best-bounded docs (chunk union — extra
        # exact distances only tighten the other queries' thresholds)
        with jax.profiler.TraceAnnotation("wmd.seed"):
            _, seed_pos = jax.lax.top_k(-lb, k)
            seed_pos = np.asarray(seed_pos)[:qc]
            self._prune_syncs += 1
            seed = np.unique(seed_pos).astype(np.int32)
        qmask_seed = None
        if scoped:
            qmask_seed = np.stack([np.isin(seed, seed_pos[qi])
                                   for qi in range(qc)])
        d_seed, xprof = self._solve_candidates(staged, qc, seed, None,
                                               "seed", prof=qmask_seed)
        with jax.profiler.TraceAnnotation("wmd.threshold"):
            thresh = self._thresholds(d_seed, k, sup.shape[0])
        with jax.profiler.TraceAnnotation("wmd.survivors"):
            surv = np.nonzero(np.asarray(_keep_any(lb, thresh)))[0] \
                .astype(np.int32)
            self._prune_syncs += 1
            surv = surv[~np.isin(surv, seed)]
        cand = np.concatenate([seed, surv])
        if not surv.size:
            return cand, d_seed
        qmask_surv = None
        if scoped:
            qmask_surv = (np.asarray(lb)[:qc, surv]
                          <= np.asarray(thresh)[:qc, None])
            self._prune_syncs += 1
        warm = xprof if self._warms_survivors else None
        d_surv, _ = self._solve_candidates(staged, qc, surv, qmask_surv,
                                           "survivor", warm=warm)
        return cand, np.concatenate([d_seed, d_surv], axis=1)

    def _search_refine(self, queries, k, pruner, nprobe, chunks,
                       refine_factor, out_i, out_d, solved):
        """Rank-then-refine driver (``mode="refine"``): ONE bound pass
        ranks the whole candidate universe, then exactly one solve covers
        the union of each query's top ``k' = refine_factor * k`` picks.

        Ranking bound: a cascade's TIGHTEST stage (its last — RWMD in the
        default specs) over the probed clusters' members; a full-sweep
        pruner's own bound over every doc. Each query is ranked over its
        OWN picks only, so pick sets are nested in ``refine_factor`` and
        recall against the exact path is monotone; at a ``k'`` covering
        the candidate universe this IS the exact path's answer (every
        candidate solved, ranked by exact distance)."""
        from .prune import CascadePruner
        index = self.index
        live_q = [qi for chunk, _ in chunks for qi in chunk]
        qg = len(live_q)
        width_g = max(width for _, width in chunks)
        sup_g, r_g, mask_g = self._prep_chunk(
            [queries[qi] for qi in live_q], width_g)
        with jax.profiler.TraceAnnotation("wmd.seed"):
            if isinstance(pruner, CascadePruner):
                # the tightest stage over the probed clusters' members
                # (+inf at each query's non-candidates)
                _, pm, qcent = pruner.probe(index, sup_g, r_g, mask_g,
                                            nprobe)
                (lb,) = pruner.bounds(index, sup_g, mask_g, qcent, pm,
                                      pruner.stages[-1:],
                                      self._group_lookup())
                self._prune_syncs += self._member_syncs(pm, pruner.stages[-1:])
            else:
                lb = pruner.lower_bounds(index, sup_g, r_g, mask_g)
            kp = min(refine_factor * k, index.n_docs)
            neg, pos = jax.device_get(jax.lax.top_k(-lb[:qg], kp))
            self._prune_syncs += 1
        # per-query own picks; -inf bounds are non-candidates (a query
        # whose probed universe holds fewer than k' docs)
        own = [np.unique(pos[g][np.isfinite(neg[g])]).astype(np.int32)
               for g in range(qg)]
        ids = np.unique(np.concatenate(own))
        if ids.size == 0:
            return
        qmask_own = np.stack([np.isin(ids, o) for o in own])
        staged = self._stage_chunks(queries, chunks, live_q)
        d, _ = self._solve_candidates(
            staged, qg, ids, qmask_own if self._scoped() else None,
            "refine")
        # rank each query over its OWN picks only — batch-mates' union
        # candidates are excluded so the pick-set nesting (and with it
        # the recall monotonicity) holds per query, not just per batch
        with jax.profiler.TraceAnnotation("wmd.rank"):
            dm = np.where(qmask_own, d, np.inf)
            ids_ext = self._ext(ids)
            for g, qi in enumerate(live_q):
                n_own = int(qmask_own[g].sum())
                order = np.argsort(dm[g], kind="stable")[:min(k, n_own)]
                out_i[qi, :order.size] = ids_ext[order]
                out_d[qi, :order.size] = d[g, order]
                solved[qi] = n_own
        self.last_solved_ids = ids_ext

    def _search_cascade(self, queries, k, pruner, nprobe, chunks,
                        out_i, out_d, solved):
        """CascadePruner search — ONE prune pass for the whole query set.

        The bound stages don't need the solve's v_r bucketing (they read
        the (Q, B) support arrays directly), so all live queries are staged
        once at the widest chunk's width and at a fixed Q pad (at least
        ``max_batch`` rows), and every prune dispatch covers the full set.
        Every prune program's shapes follow the index, k and that staging,
        never the candidate counts. Flow:

        1. ``wmd.probe``: cluster probe (one (Q, C) GEMM);
        2. ``wmd.seed``: every stage's bounds of every candidate doc ->
           per-query best-k seeds by the tightest (one blocking read) ->
           exact seed solve (per solve chunk, in tiles);
        3. ``wmd.threshold``: threshold t_q, on the device;
        4. ``wmd.survivors``: ``pruner.survivors``, the cluster-radius and
           per-doc bounds against t_q (one blocking read);
        5. exact solve on the survivors (in tiles), ``wmd.rank``.
        """
        index = self.index
        live_q = [qi for chunk, _ in chunks for qi in chunk]
        qg = len(live_q)
        qp = bucket_size(qg, self.max_batch)
        width_g = max(width for _, width in chunks)
        with jax.profiler.TraceAnnotation("wmd.probe"):
            sup_g, r_g, mask_g = self._prep_chunk(
                [queries[qi] for qi in live_q], width_g, q_pad=qp)
            cdists, pm, qcent = pruner.probe(index, sup_g, r_g, mask_g,
                                             nprobe)
        lookup = self._group_lookup()
        with jax.profiler.TraceAnnotation("wmd.seed"):
            lbs = pruner.bounds(index, sup_g, mask_g, qcent, pm,
                                lookup=lookup)
            pos, real = pruner.seeds(lbs, k)
            self._prune_syncs += 1 + self._member_syncs(pm, pruner.stages)
            pos, real = pos[:qg], real[:qg]
            seed = np.unique(pos[real]).astype(np.int32)
        if seed.size == 0:
            return
        scoped = self._scoped()
        qmask_seed = None
        if scoped:
            # per-query seed membership: q's own finite top-k picks
            qmask_seed = np.stack([np.isin(seed, pos[g][real[g]])
                                   for g in range(qg)])

        # solve stage stays v_r-bucketed: per-chunk staging, reused for
        # the seed and survivor solves
        staged = self._stage_chunks(queries, chunks, live_q)

        # seed residual scope = the union of real seed docs (any of them
        # can contend for any query once thresholds exist); own picks
        # drive only the warm profile — see _prune_full
        d_seed, xprofs = self._solve_candidates(staged, qg, seed, None,
                                                "seed", prof=qmask_seed)
        with jax.profiler.TraceAnnotation("wmd.threshold"):
            thresh = self._thresholds(d_seed, k, qp)
        with jax.profiler.TraceAnnotation("wmd.survivors"):
            # with per-query scoping, also which query needs each
            # survivor: the same bounds against each query's threshold
            surv = pruner.survivors(index, sup_g, r_g, mask_g, cdists, pm,
                                    qcent, thresh, exclude=seed, lbs=lbs,
                                    per_query=scoped, lookup=lookup)
            self._prune_syncs += 1
            qmask_surv = None
            if scoped:
                surv, qmask_surv = surv[0], surv[1][:qg]
        cand = np.concatenate([seed, surv])
        if surv.size:
            warm = xprofs if self._warms_survivors else None
            d_surv, _ = self._solve_candidates(staged, qg, surv, qmask_surv,
                                               "survivor", warm=warm)
            d_cand = np.concatenate([d_seed, d_surv], axis=1)
        else:
            d_cand = d_seed
        with jax.profiler.TraceAnnotation("wmd.rank"):
            cand_ext = self._ext(cand)       # storage -> caller doc ids
            for g, qi in enumerate(live_q):
                order = np.argsort(d_cand[g], kind="stable")[:k]
                out_i[qi, :order.size] = cand_ext[order]
                out_d[qi, :order.size] = d_cand[g, order]
                solved[qi] = cand.size
        self.last_solved_ids = cand_ext


@functools.partial(jax.jit, static_argnames=("k",))
def _threshold(d, n_live, slack, k: int):
    """Per row of the padded (Qp, S) seed distances: the kth smallest plus
    the fp slack margin; rows from ``n_live`` on (fillers) get -inf."""
    t = jnp.sort(d, axis=1)[:, k - 1]
    t = t + slack * (jnp.abs(t) + 1.0)
    return jnp.where(jnp.arange(d.shape[0]) < n_live, t, -jnp.inf)
