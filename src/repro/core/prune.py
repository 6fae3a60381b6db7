"""Pluggable admissible lower bounds for the staged retrieval pipeline.

``WmdEngine.search`` runs *prune -> solve -> rank*: a cheap lower bound on
every (query, doc) pair first, the O(v_r * V * n_iter) Sinkhorn solve only
on candidates the bound cannot exclude (Atasu et al., LC-RWMD,
arXiv:1711.07227; Werner & Laber, arXiv:1912.00509; Kusner et al.'s
prefetch-and-prune). Each bound implements the small :class:`Pruner`
protocol, so stages are pluggable and composable (:class:`MaxPruner` takes
the elementwise max of several admissible bounds, which is itself
admissible).

Admissibility — what "lower bound" means *here*. The engine's score is not
exact EMD but ``<P, M>`` for the plan the truncated Sinkhorn iteration
produces. That plan satisfies the **document-side marginal exactly** (the
distance line recomputes ``w = val / (G^T u)``, so column sums equal
``val`` by construction) while the query-side marginal holds only
approximately. Hence:

``RwmdPruner`` (doc-side relaxed WMD)
    ``lb[q, n] = sum_l val[n, l] * min_k M[k, idx[n, l]]`` — every unit of
    doc mass pays at least its distance to the *nearest* query word. Since
    the engine's plan transports exactly ``val[n, l]`` out of each doc word,
    ``lb <= <P, M>`` holds for the *computed* score (up to fp rounding —
    covered by the engine's ``prune_slack``). This is the default pruner
    and the one the exact-top-k guarantee rests on.

``WcdPruner`` (word-centroid distance)
    ``lb[q, n] = ||sum_k r_k vec_k - centroid_n||`` — one GEMM per query
    chunk against centroids frozen in the :class:`~.index.CorpusIndex`.
    Admissible w.r.t. exact EMD (Jensen), but w.r.t. the truncated-Sinkhorn
    score only up to the query-marginal residual of the unconverged
    iteration — at very small ``n_iter`` that residual can exceed the
    engine's ``prune_slack`` and exclude a true top-k doc. WCD alone is
    therefore *near*-exact, not guaranteed; the exact-top-k contract rests
    on RWMD. Use WCD composed (``"wcd+rwmd"``, still guaranteed: MaxPruner
    keeps every doc RWMD keeps... see below) or standalone when approximate
    top-k at converged ``n_iter`` is acceptable.

    (Note on composition: ``max(wcd, rwmd) <= score`` requires *both*
    bounds admissible, so at tiny ``n_iter`` the same caveat applies to the
    composite; at practical iteration counts the residual is far below the
    slack — see ``test_bounds_below_engine_scores``.)

Bounds are in raw distance units (no lam): they bound the transport-cost
part ``<P, M>``, which is exactly what the solve stage returns.

``CascadePruner`` (ISSUE 3) runs these stages *cheapest-first* over a
shrinking candidate set — IVF cluster shortlist, pivot triangle bound,
WCD on the shortlist, RWMD only on WCD survivors (and only over the
survivors' own vocabulary) — instead of computing every bound on every
document; see its docstring for the exactness-vs-``nprobe`` contract.

Spec resolution (runnable — the CI ``docs`` job executes this as a
doctest)::

    >>> from repro.core.prune import PRUNERS, resolve_pruner
    >>> "ivf+pivot+wcd+rwmd" in PRUNERS
    True
    >>> type(resolve_pruner("ivf+pivot+wcd+rwmd")).__name__
    'CascadePruner'
    >>> resolve_pruner("ivf+pivot+wcd+rwmd").stages
    ('pivot', 'wcd', 'rwmd')
    >>> resolve_pruner("rwmd").name
    'rwmd'
"""
from __future__ import annotations

import functools
from typing import Protocol, Sequence, runtime_checkable

import numpy as np
import jax
import jax.numpy as jnp

from .sinkhorn import sq_dists


@runtime_checkable
class Pruner(Protocol):
    """One prune stage: admissible lower bounds for a prepared query chunk.

    ``sup``/``r``/``mask`` are the engine's bucketed chunk layout
    ((Qp, B) support word ids, normalized frequencies with pad rows == 1,
    and the live-row mask) — the same arrays the solve stage consumes, so
    a pruner slots in front of any solve without re-staging queries.
    Returns (Qp, N) bounds; rows past the live queries are don't-care.
    """

    name: str

    def lower_bounds(self, index, sup: jax.Array, r: jax.Array,
                     mask: jax.Array) -> jax.Array: ...


@jax.jit
def _wcd_bounds(qcent: jax.Array, centroids: jax.Array) -> jax.Array:
    return jnp.sqrt(jnp.maximum(sq_dists(qcent, centroids), 0.0))


@jax.jit
def _query_centroids(sup, r, mask, vecs):
    a = jnp.take(vecs, sup, axis=0)                  # (Qp, B, w)
    return jnp.einsum("qb,qbw->qw", r * mask, a)     # pad rows (r==1) masked


class WcdPruner:
    """Word-centroid distance: one (Qp, w) x (w, N) GEMM per chunk."""

    name = "wcd"

    def lower_bounds(self, index, sup, r, mask):
        return _wcd_bounds(_query_centroids(sup, r, mask, index.vecs),
                           index.centroids)


# XLA fallback for kernels.rwmd: the kernels' oracle IS the implementation
# (single source of truth; ref.py imports only jax, so no core<->ops cycle)
from repro.kernels.ref import rwmd_min_cdist_ref

_min_cdist_xla = jax.jit(rwmd_min_cdist_ref)


@jax.jit
def _min_cdist_subset_xla(sup, mask, vecs, vids):
    """Candidate-vocab min-cdist with the support/vocab gathers fused in
    (one dispatch; the XLA twin of kernels.rwmd.rwmd_min_cdist_subset)."""
    return rwmd_min_cdist_ref(jnp.take(vecs, sup, axis=0), mask,
                              jnp.take(vecs, vids, axis=0))


@jax.jit
def _rwmd_gather(minm: jax.Array, idx: jax.Array, val: jax.Array):
    """Own jit on purpose: XLA CPU would otherwise fuse the cdist producer
    into the gather and recompute it per element (see ROADMAP note)."""
    g = jnp.take(minm, idx, axis=1)                  # (Qp, N, L)
    return jnp.einsum("qnl,nl->qn", g, val)


class RwmdPruner:
    """Doc-side relaxed WMD — tight, provably <= the engine's score.

    ``use_kernel=True`` computes the masked min-cdist with the query-grid
    Pallas kernel (:mod:`repro.kernels.rwmd`) so the prune stage is as
    TPU-resident as the solve stage; the O(nnz) gather stays in XLA either
    way (same boundary as the solve's G gather).
    """

    name = "rwmd"

    def __init__(self, use_kernel: bool = False,
                 interpret: bool | None = None):
        self.use_kernel = use_kernel
        self.interpret = interpret

    def lower_bounds(self, index, sup, r, mask):
        a = jnp.take(index.vecs, sup, axis=0)        # (Qp, B, w)
        if self.use_kernel:
            from repro.kernels import ops
            minm = ops.rwmd_min_cdist(a, mask, index.vecs,
                                      interpret=self.interpret)
        else:
            minm = _min_cdist_xla(a, mask, index.vecs)
        # all-pad filler rows have minm == +inf; inf * 0-mass stays out of
        # live rows, and callers slice fillers off anyway
        return _rwmd_gather(jnp.where(jnp.isfinite(minm), minm, 0.0),
                            index.docs.idx, index.docs.val)


class MaxPruner:
    """Elementwise max of several admissible bounds (still admissible)."""

    def __init__(self, pruners: Sequence[Pruner]):
        self.pruners = tuple(pruners)
        self.name = "+".join(p.name for p in self.pruners)

    def lower_bounds(self, index, sup, r, mask):
        bounds = [p.lower_bounds(index, sup, r, mask) for p in self.pruners]
        return functools.reduce(jnp.maximum, bounds)


# ---------------------------------------------------------------- cascade
def _pad_pow2_ids(ids: np.ndarray, min_size: int = 8) -> np.ndarray:
    """Pow2-pad an id array (pad slots get id 0 — a valid row whose
    computed bounds are garbage the candidacy masks exclude) so
    data-dependent candidate counts hit a bounded set of compiled shapes."""
    n_pad = min_size
    while n_pad < ids.size:
        n_pad *= 2
    out = np.zeros(n_pad, np.int32)
    out[:ids.size] = ids
    return out


# Fused per-stage jits: each cascade stage is ONE device dispatch (bounds +
# candidacy fold), plus one tiny dispatch for the threshold compare — the
# stage arrays are small post-shortlist, so op-by-op dispatch overhead would
# otherwise dominate the stage compute (measured ~4x on CPU at N=8k).

@jax.jit
def _wcd_stage(qcent, centroids, ids_pad, qmask):
    """Centroid bounds for a candidate id array, qmask folded to +inf:
    gather candidate centroids -> cdist vs the (probe-computed) query
    centroids -> mask."""
    cand = jnp.take(centroids, ids_pad, axis=0)          # (Sp, w)
    d2 = sq_dists(qcent, cand)
    return jnp.where(qmask, jnp.sqrt(jnp.maximum(d2, 0.0)), jnp.inf)


@jax.jit
def _wcd_dense_keep_all(qcent, centroids, thresh):
    """Exhaustive-probe variant of :func:`_wcd_dense_keep`: every doc is a
    candidate of every query, so the doc -> probed-cluster lookup drops
    out of the dispatch entirely."""
    qc = thresh.shape[0]
    d2 = jnp.maximum(sq_dists(qcent[:qc], centroids), 0.0)
    return jnp.any(d2 <= jnp.square(thresh)[:, None], axis=0)


@jax.jit
def _wcd_dense_keep(qcent, centroids, pm, assign, thresh):
    """Dense WCD threshold pass, ONE dispatch end to end: per-doc centroid
    bounds over the whole corpus (no candidate gather, query centroids
    reused from the probe, squared-distance compare — sqrt is monotone),
    candidacy via the doc -> probed-cluster lookup, keep = any live
    query's bound passes. The dispatch-economy twin of the gathered
    :func:`_wcd_stage` path — the survivor pass picks by surviving-cluster
    mass (a (Q, N) GEMM beats gather + mask dispatch chains once most docs
    survive the cluster filter)."""
    qc = thresh.shape[0]
    d2 = jnp.maximum(sq_dists(qcent[:qc], centroids), 0.0)
    cand = jnp.take(pm[:qc], assign, axis=1)             # (qc, N) candidacy
    return jnp.any(cand & (d2 <= jnp.square(thresh)[:, None]), axis=0)


@jax.jit
def _pivot_stage(qd, dd, ids_pad, qmask):
    """Pivot triangle bounds for a candidate id array, one dispatch:
    gather candidate pivot-distance rows -> ``max_p |d(q,p) - d(n,p)|``
    (reverse triangle inequality in the embedding metric, so it
    lower-bounds the WCD) -> candidacy fold to +inf."""
    cand = jnp.take(dd, ids_pad, axis=0)                 # (Sp, P)
    lb = jnp.max(jnp.abs(qd[:, None, :] - cand[None, :, :]), axis=-1)
    return jnp.where(qmask, lb, jnp.inf)


@jax.jit
def _pivot_dense_keep(qd, dd, pm, assign, thresh):
    """Dense pivot threshold pass over the whole corpus, one dispatch —
    the pivot twin of :func:`_wcd_dense_keep`, at O(P) per pair instead
    of the WCD GEMM's O(w)."""
    qc = thresh.shape[0]
    lb = jnp.max(jnp.abs(qd[:qc, None, :] - dd[None, :, :]), axis=-1)
    cand = jnp.take(pm[:qc], assign, axis=1)             # (qc, N) candidacy
    return jnp.any(cand & (lb <= thresh[:, None]), axis=0)


@jax.jit
def _pivot_dense_keep_all(qd, dd, thresh):
    """Exhaustive-probe variant of :func:`_pivot_dense_keep`."""
    qc = thresh.shape[0]
    lb = jnp.max(jnp.abs(qd[:qc, None, :] - dd[None, :, :]), axis=-1)
    return jnp.any(lb <= thresh[:, None], axis=0)


@jax.jit
def _rwmd_epilogue(minm, rel, val, qmask):
    """RWMD gather + doc-mass contraction + candidacy fold, one dispatch.
    Separate from the min-cdist producer on purpose (the XLA CPU
    producer-into-gather refusion hazard — see the ROADMAP note)."""
    g = jnp.take(jnp.where(jnp.isfinite(minm), minm, 0.0), rel, axis=1)
    lb = jnp.einsum("qnl,nl->qn", g, val)
    return jnp.where(qmask, lb, jnp.inf)


@jax.jit
def _rwmd_keep(minm, rel, val, pm, assign_ids, n_real, thresh):
    """:func:`_rwmd_epilogue` fused with candidacy lookup and the
    threshold test — the post-threshold RWMD stage in one dispatch after
    the min-cdist producer."""
    qc = thresh.shape[0]
    g = jnp.take(jnp.where(jnp.isfinite(minm), minm, 0.0), rel, axis=1)
    lb = jnp.einsum("qnl,nl->qn", g[:qc], val)
    cand = (jnp.take(pm[:qc], assign_ids, axis=1)
            & (jnp.arange(assign_ids.shape[0])[None, :] < n_real))
    return jnp.any(cand & (lb <= thresh[:, None]), axis=0)


@jax.jit
def _rwmd_keep_all(minm, rel, val, n_real, thresh):
    """Exhaustive-probe variant of :func:`_rwmd_keep` (no cluster
    candidacy lookup; only the pad tail is masked)."""
    qc = thresh.shape[0]
    g = jnp.take(jnp.where(jnp.isfinite(minm), minm, 0.0), rel, axis=1)
    lb = jnp.einsum("qnl,nl->qn", g[:qc], val)
    keep = jnp.any(lb <= thresh[:, None], axis=0)
    return keep & (jnp.arange(rel.shape[0]) < n_real)


@jax.jit
def _keep_any(lbm, thresh):
    """Columns any live query still needs: lbm (Qp, Sp) with +inf at
    non-candidates, thresh (qc,) margined thresholds -> (Sp,) bool."""
    return jnp.any(lbm[:thresh.shape[0]] <= thresh[:, None], axis=0)


@jax.jit
def _cluster_keep_fused(cdists, radii, pm, thresh):
    """Cluster-radius filter, one dispatch: triangle bound + candidacy +
    threshold test -> (C,) bool of clusters some live query still needs."""
    lbm = jnp.where(pm, cdists - radii[None, :], jnp.inf)
    return jnp.any(lbm[:thresh.shape[0]] <= thresh[:, None], axis=0)


@jax.jit
def _cluster_keep_all(cdists, radii, thresh):
    """Exhaustive-probe variant of :func:`_cluster_keep_fused`."""
    lbm = cdists - radii[None, :]
    return jnp.any(lbm[:thresh.shape[0]] <= thresh[:, None], axis=0)


@jax.jit
def _probe_dists(sup, r, mask, vecs, centers):
    """Query centroids + cluster-center distances, one dispatch:
    (cdists (Qp, C), qcent (Qp, w) — reused by the dense WCD pass)."""
    qcent = jnp.einsum("qb,qbw->qw", r * mask, jnp.take(vecs, sup, axis=0))
    return jnp.sqrt(jnp.maximum(sq_dists(qcent, centers), 0.0)), qcent


@functools.partial(jax.jit, static_argnames=("nprobe",))
def _probe_mask(cdists, nprobe: int):
    """(Qp, C) bool: True at each query's ``nprobe`` nearest clusters."""
    _, idx = jax.lax.top_k(-cdists, nprobe)
    rows = jnp.arange(cdists.shape[0])[:, None]
    return jnp.zeros(cdists.shape, bool).at[rows, idx].set(True)


@jax.jit
def _ids_qmask(pm, assign_ids, n_real):
    """Per-query candidacy for a padded doc-id array: the doc's cluster
    must be probed by the query, and the slot must be real (``n_real`` is
    traced, so shape bucketing stays data-independent)."""
    sub = jnp.take(pm, assign_ids, axis=1)
    return sub & (jnp.arange(assign_ids.shape[0])[None, :] < n_real)


class CascadePruner:
    """Cheapest-first cascade over a shrinking candidate set: IVF cluster
    probe + cluster-radius filter -> pivot triangle bounds -> per-doc WCD
    -> RWMD min-cdist.

    Unlike the full-sweep pruners above (one (Q, N) bound matrix), the
    cascade's per-doc work is sub-O(N):

    1. *ivf probe*: one (Q, n_clusters) GEMM against the frozen k-means
       centers. ``nprobe`` nearest clusters per query define the candidate
       universe (all clusters when ``nprobe=None`` — the exact mode). Seed
       docs come from each query's nearest probed clusters (just enough to
       cover k members), so even seed selection never sweeps the corpus.
    2. *ivf radius filter*: after the seed solve fixes the threshold t_q,
       the triangle inequality ``wcd(q, n) >= ||qcent - center_c|| -
       radius_c`` (:class:`~.index.IvfClusters` ``radii``) drops whole
       clusters against t_q — their members are never touched again.
    3. *pivot* (optional, the cheapest per-doc rung — Werner & Laber,
       arXiv:1912.00509): ``max_p |d(q, p) - d(n, p)|`` over the
       ``n_pivots`` reference words frozen at ``build_index``, using the
       precomputed ``doc_pivot_d`` table — O(P) per pair vs the WCD
       GEMM's O(w). The reverse triangle inequality makes it a lower
       bound on WCD, so it inherits WCD's admissibility (and WCD's
       truncated-iteration caveat) while touching no embeddings. Spelled
       ``"ivf+pivot+wcd+rwmd"``; requires an index built with
       ``n_pivots > 0`` (the default).
    4. *wcd*: the centroid bound, only on surviving clusters' members.
    5. *rwmd*: the tight bound, only on WCD survivors — and only over the
       vocabulary those survivors actually use, so the min-cdist block
       shrinks from (Q*B, V) to (Q*B, V_survivors)
       (:func:`repro.kernels.rwmd.rwmd_min_cdist_subset`).

    Admissibility: the radius bound under-estimates WCD (triangle
    inequality), so at ``nprobe = n_clusters`` the drop set is contained
    in the ``"wcd+rwmd"`` :class:`MaxPruner`'s-with-cluster-bounds and the
    exact-top-k story is identical to ``"wcd+rwmd"`` — guaranteed through
    the RWMD stage, near-exact through WCD's truncated-iteration caveat
    above (the cluster bound inherits the same caveat: it lower-bounds
    WCD). At smaller ``nprobe`` un-probed clusters are skipped entirely:
    approximate retrieval with *measured* recall, monotone in ``nprobe``
    for a fixed query batch (probe sets are nested, and every returned
    doc carries its exact distance — the result contains at least the
    top-k of the query's own probed universe, plus any batch-mates' union
    candidates that rank better, which can only raise recall).

    Sharded serving (:class:`~repro.core.shard_index.ShardedWmdEngine`)
    runs one cascade PER SHARD over that shard's own clusters, so
    ``nprobe`` is a per-shard knob: each shard probes its ``nprobe``
    nearest owned clusters (clamped to the shard's cluster count by the
    ``np_eff`` clamp in :meth:`probe`), and a doc is reachable iff its
    cluster ranks among its OWNING shard's probes. ``nprobe=None``
    therefore stays globally exact (every shard probes everything and
    the merge is a true global top-k), and the recall-vs-``nprobe``
    monotonicity above holds per shard count — but the probed universes
    at a fixed finite ``nprobe`` differ between shard counts (S shards
    probe up to ``S * nprobe`` clusters globally, drawn shard-locally).

    The driver is :meth:`WmdEngine.search <repro.core.index.WmdEngine>`;
    this class owns the stage computations.
    """

    def __init__(self, stages: Sequence[str] = ("wcd", "rwmd"),
                 nprobe: int | None = None, use_kernel: bool = False,
                 interpret: bool | None = None):
        stages = tuple(stages)
        if not stages or any(s not in ("pivot", "wcd", "rwmd")
                             for s in stages):
            raise ValueError(f"cascade stages must be drawn from "
                             f"('pivot', 'wcd', 'rwmd'), got {stages!r}")
        self.stages = stages
        self.nprobe = nprobe
        self.use_kernel = use_kernel
        self.interpret = interpret
        self.name = "+".join(("ivf",) + stages)

    # -------------------------------------------------------- stage 0: ivf
    def probe(self, index, sup, r, mask, nprobe: int | None = None):
        """Cluster probe for one query staging: (cdists (Qp, C) device,
        pm (Qp, C) device bool — True at each query's probed clusters,
        qcent (Qp, w) query centroids for downstream reuse).
        ``nprobe=None`` uses the pruner's default, which itself defaults
        to all clusters."""
        cl = index.clusters
        if cl is None:
            raise ValueError(
                "CorpusIndex has no IVF clusters — rebuild with "
                "build_index() (clusters are built by default)")
        if nprobe is None:
            nprobe = self.nprobe
        c = cl.n_clusters
        np_eff = c if nprobe is None else max(1, min(int(nprobe), c))
        cdists, qcent = _probe_dists(sup, r, mask, index.vecs, cl.centers)
        # pm None == exhaustive probe: every cluster is every query's
        # candidate, and the hot stages skip the candidacy lookups
        pm = None if np_eff == c else _probe_mask(cdists, np_eff)
        return cdists, pm, qcent

    def seed_candidates(self, index, cdists, mask, k: int,
                        pm) -> np.ndarray:
        """Seed-candidate doc ids: per live query, walk probed clusters
        nearest-first until they cover k members; the union across the
        chunk is returned (host — O(Q * C), never O(N))."""
        cl = index.clusters
        sizes = cl.sizes
        cd = np.asarray(cdists)
        pm_np = None if pm is None else np.asarray(pm)
        live = np.asarray(mask).sum(axis=1) > 0
        chosen = np.zeros(cl.n_clusters, bool)
        for q in np.nonzero(live)[0]:
            covered = 0
            for c in np.argsort(cd[q], kind="stable"):
                if (pm_np is not None and not pm_np[q, c]) or sizes[c] == 0:
                    continue
                chosen[c] = True
                covered += sizes[c]
                if covered >= k:
                    break
        picked = np.nonzero(chosen)[0]
        if picked.size == 0:
            return np.zeros(0, np.int32)
        # cluster-sorted storage ids: with the index's cluster-major layout
        # (ISSUE 4) this concat of per-cluster slices is a near-contiguous
        # run of storage rows — exactly what subset()'s gather wants
        return np.concatenate(
            [cl.order[cl.starts[c]:cl.starts[c + 1]] for c in picked])

    def id_qmask(self, index, pm, ids_pad: np.ndarray, n_real: int,
                 qp: int | None = None) -> jax.Array:
        """(Qp, Sp) candidacy for a padded id array (see _ids_qmask).
        ``pm=None`` (exhaustive probe) needs ``qp`` to shape the valid-slot
        mask."""
        if pm is None:
            valid = jnp.arange(ids_pad.size) < n_real
            return jnp.broadcast_to(valid[None, :], (qp, ids_pad.size))
        assign_ids = jnp.asarray(
            index.clusters.assign[ids_pad].astype(np.int32))
        return _ids_qmask(pm, assign_ids, n_real)

    def cluster_keep(self, index, cdists, pm, thresh) -> np.ndarray:
        """(C,) host bool: clusters some live query still needs, by the
        cluster-radius triangle bound against the threshold."""
        radii = index.clusters.radii.astype(np.float32)
        if pm is None:
            return np.asarray(_cluster_keep_all(cdists, radii, thresh))
        return np.asarray(_cluster_keep_fused(cdists, radii, pm, thresh))

    def cluster_members(self, index, keep_c: np.ndarray) -> np.ndarray:
        """Cluster-sorted doc ids of the kept clusters (host slice concat —
        a near-contiguous storage run under the cluster-major layout)."""
        cl = index.clusters
        kept = np.nonzero(keep_c[:cl.n_clusters])[0]
        if kept.size == 0:
            return np.zeros(0, np.int32)
        return np.concatenate(
            [cl.order[cl.starts[c]:cl.starts[c + 1]] for c in kept])

    # --------------------------------------- post-threshold survivor pass
    def survivors(self, index, sup, r, mask, cdists, pm, qcent, thresh,
                  exclude: np.ndarray | None = None,
                  dense_cutoff: float = 0.25) -> np.ndarray:
        """The post-threshold prune pass, cheapest-first: cluster-radius
        filter, then the per-doc stages on what remains. Returns surviving
        doc ids (``exclude`` — typically the already-solved seeds —
        removed). Shared by ``WmdEngine._prune_cascade`` and the fig9
        prune-stage benchmark, so the measured pass IS the serving pass.

        When the cluster filter keeps most of the corpus (loose clusters,
        or simply a hard query), the gathered per-doc WCD stage is replaced
        by :func:`_wcd_dense_keep` — one dense dispatch over all docs beats
        gather + mask dispatch chains precisely when the gather wouldn't
        shrink the problem (the radius bound under-estimates every
        member's WCD, so the dense threshold test subsumes the cluster
        filter)."""
        cl = index.clusters
        radii = cl.radii.astype(np.float32)
        stages = self.stages
        # dispatch the cluster filter and the (speculative) dense
        # first-stage pass back to back, then sync once — the dense result
        # is discarded in the rare tight-cluster case where the gather
        # path wins, but the serial dispatch->sync->dispatch latency it
        # saves dominates its (Q, N) cost on every other call. The pivot
        # stage gets the same treatment as WCD (its dense pass is O(P)
        # per pair, cheaper still).
        qd = None
        if stages[0] == "pivot":
            if index.pivots is None:
                raise ValueError("cascade has a 'pivot' stage but the "
                                 "index has no pivot words — rebuild with "
                                 "build_index(n_pivots > 0)")
            from .index import _pivot_dists
            qd = _pivot_dists(qcent, index.pivots)
        if pm is None:
            keep_c_dev = _cluster_keep_all(cdists, radii, thresh)
            if stages[0] == "wcd":
                keep_d_dev = _wcd_dense_keep_all(qcent, index.centroids,
                                                 thresh)
            elif qd is not None:
                keep_d_dev = _pivot_dense_keep_all(qd, index.doc_pivot_d,
                                                   thresh)
            else:
                keep_d_dev = None
        else:
            keep_c_dev = _cluster_keep_fused(cdists, radii, pm, thresh)
            if stages[0] == "wcd":
                keep_d_dev = _wcd_dense_keep(qcent, index.centroids, pm,
                                             cl.assign_dev, thresh)
            elif qd is not None:
                keep_d_dev = _pivot_dense_keep(qd, index.doc_pivot_d, pm,
                                               cl.assign_dev, thresh)
            else:
                keep_d_dev = None
        keep_c = np.asarray(keep_c_dev)
        kept_docs = int(cl.sizes[keep_c[:cl.n_clusters]].sum())
        if (keep_d_dev is not None
                and kept_docs >= dense_cutoff * index.n_docs):
            surv = np.nonzero(np.asarray(keep_d_dev))[0].astype(np.int32)
            stages = stages[1:]
        else:
            surv = self.cluster_members(index, keep_c)
        if exclude is not None and exclude.size and surv.size:
            surv = surv[~np.isin(surv, exclude)]
        for stage in stages:
            if surv.size == 0:
                break
            sp = _pad_pow2_ids(surv)
            if stage == "rwmd":
                prep = self._rwmd_prep(index, sup, mask, sp, surv.size)
                if prep is None:
                    break
                minm, rel, val = prep
                rel, val = jnp.asarray(rel), jnp.asarray(val)
                if pm is None:
                    keep = np.asarray(_rwmd_keep_all(
                        minm, rel, val, surv.size, thresh))
                else:
                    assign_ids = jnp.asarray(cl.assign[sp].astype(np.int32))
                    keep = np.asarray(_rwmd_keep(
                        minm, rel, val, pm, assign_ids, surv.size, thresh))
            else:
                lbm = self.stage_bounds(
                    stage, index, sup, r, mask, sp, surv.size,
                    self.id_qmask(index, pm, sp, surv.size,
                                  qp=sup.shape[0]), qcent=qcent)
                keep = np.asarray(_keep_any(lbm, thresh))
            surv = surv[keep[:surv.size]]
        return surv

    # ----------------------------------------------------- bounded stages
    def stage_bounds(self, stage: str, index, sup, r, mask,
                     ids_pad: np.ndarray, n_real: int, qmask: jax.Array,
                     qcent: jax.Array | None = None) -> jax.Array:
        """Masked lower bounds for one cascade stage on a candidate id
        array: (Qp, Sp) device, +inf wherever ``qmask`` is False (pad
        slots and per-query non-candidates). One fused dispatch per stage
        (plus the min-cdist producer for RWMD). Pass the ``qcent`` the
        probe already computed to skip recomputing query centroids."""
        if stage in ("wcd", "pivot"):
            if qcent is None:
                qcent = _query_centroids(sup, r, mask, index.vecs)
            if stage == "pivot":
                if index.pivots is None:
                    raise ValueError(
                        "cascade has a 'pivot' stage but the index has no "
                        "pivot words — rebuild with build_index("
                        "n_pivots > 0)")
                from .index import _pivot_dists
                return _pivot_stage(_pivot_dists(qcent, index.pivots),
                                    index.doc_pivot_d,
                                    jnp.asarray(ids_pad), qmask)
            return _wcd_stage(qcent, index.centroids,
                              jnp.asarray(ids_pad), qmask)
        return self._rwmd_subset(index, sup, mask, ids_pad, n_real, qmask)

    def _rwmd_prep(self, index, sup, mask, ids_pad, n_real):
        """Shared RWMD-subset prep: gather candidate rows host-side (like
        ``CorpusIndex.subset``), remap their word ids into the compact
        candidate-vocab space, min-cdist only those embedding rows — the
        (Q*B, V) block shrinks to (Q*B, V_survivors). Returns
        (minm device, rel np, val np) or None when the subset is empty."""
        idx = index.docs_host.idx[ids_pad]
        val = index.docs_host.val[ids_pad].copy()
        val[n_real:] = 0.0                    # pad rows out of the vocab
        nnz = (val > 0).sum(axis=1)
        lg = max(1, int(nnz.max(initial=0)))
        lg = min(-(-lg // 8) * 8, idx.shape[1])
        idx, val = idx[:, :lg], val[:, :lg]
        live = val > 0
        vids = np.unique(idx[live])
        if vids.size == 0:
            return None
        rel = np.searchsorted(vids, idx).astype(np.int32)
        rel[~live] = 0
        # pow2-bucket the candidate vocab so data-dependent survivor sets
        # don't compile a fresh min-cdist per step (pad ids repeat vids[0];
        # the padded columns are computed but never gathered)
        vids_pad = _pad_pow2_ids(vids, min_size=128)
        vids_pad[vids.size:] = vids[0]
        if self.use_kernel:
            from repro.kernels import ops
            minm = ops.rwmd_min_cdist(
                jnp.take(index.vecs, sup, axis=0), mask, index.vecs,
                interpret=self.interpret,
                vocab_ids=jnp.asarray(vids_pad, jnp.int32))
        else:
            minm = _min_cdist_subset_xla(sup, mask, index.vecs,
                                         jnp.asarray(vids_pad, jnp.int32))
        return minm, rel, val

    def _rwmd_subset(self, index, sup, mask, ids_pad, n_real, qmask):
        """Masked RWMD bounds on a candidate subset (see _rwmd_prep)."""
        prep = self._rwmd_prep(index, sup, mask, ids_pad, n_real)
        if prep is None:
            return jnp.where(qmask, 0.0, jnp.inf)
        minm, rel, val = prep
        return _rwmd_epilogue(minm, jnp.asarray(rel), jnp.asarray(val),
                              qmask)


PRUNERS = ("wcd", "rwmd", "wcd+rwmd", "ivf", "ivf+wcd", "ivf+rwmd",
           "ivf+wcd+rwmd", "ivf+pivot+wcd+rwmd", "ivf+pivot+rwmd")


def resolve_pruner(spec, use_kernel: bool = False,
                   interpret: bool | None = None,
                   nprobe: int | None = None):
    """Turn a spec (``"wcd"``, ``"rwmd"``, ``"wcd+rwmd"``, a cascaded
    ``"ivf[+wcd][+rwmd]"``, or a :class:`Pruner`/:class:`CascadePruner`
    instance) into a pruner instance. ``nprobe`` applies to cascades only
    (``None`` probes every cluster — the exact mode)."""
    if isinstance(spec, str):
        parts = [p.strip() for p in spec.replace(",", "+").split("+") if p]
        if parts and parts[0] == "ivf":
            stages = tuple(parts[1:]) or ("wcd", "rwmd")
            return CascadePruner(stages=stages, nprobe=nprobe,
                                 use_kernel=use_kernel, interpret=interpret)
        if nprobe is not None:
            raise ValueError(
                f"nprobe={nprobe} only applies to ivf cascades; "
                f"{spec!r} sweeps every document")
        made = []
        for p in parts:
            if p == "wcd":
                made.append(WcdPruner())
            elif p == "rwmd":
                made.append(RwmdPruner(use_kernel=use_kernel,
                                       interpret=interpret))
            elif p == "pivot":
                raise ValueError(
                    "the pivot prestage reads the index's precomputed "
                    "doc_pivot_d table and runs inside the ivf cascade — "
                    "spell it 'ivf+pivot+...'")
            else:
                raise ValueError(
                    f"unknown pruner {p!r}; pick from {PRUNERS} or pass a "
                    f"Pruner instance")
        if not made:
            raise ValueError(f"empty pruner spec {spec!r}")
        return made[0] if len(made) == 1 else MaxPruner(made)
    if isinstance(spec, CascadePruner):
        if nprobe is not None and spec.nprobe != nprobe:
            raise ValueError(
                f"nprobe={nprobe} conflicts with the CascadePruner's own "
                f"nprobe={spec.nprobe}; set it on the pruner")
        return spec
    if isinstance(spec, Pruner):
        if nprobe is not None:
            raise ValueError(
                f"nprobe={nprobe} only applies to ivf cascades; "
                f"{type(spec).__name__} sweeps every document")
        return spec
    raise TypeError(f"prune must be a str, None, or Pruner, got {spec!r}")
