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

``CascadePruner`` adds an IVF cluster probe and a
cluster-radius bound, and holds each (query, doc) pair to every stage at
once (pivot triangle, WCD, RWMD) in passes of fixed shape, so a search
step compiles nothing whatever its candidate counts; see its docstring
for the exactness-vs-``nprobe`` contract. Every RWMD bound, of the
full sweep and of the cascade, comes from :func:`rwmd_bounds`.

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

from repro.kernels.precision import FP32_GEMM

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
    return jnp.einsum("qb,qbw->qw", r * mask, a,     # pad rows (r==1) masked
                      precision=FP32_GEMM)


class WcdPruner:
    """Word-centroid distance: one (Qp, w) x (w, N) GEMM per chunk."""

    name = "wcd"

    def lower_bounds(self, index, sup, r, mask):
        return _wcd_bounds(_query_centroids(sup, r, mask, index.vecs),
                           index.centroids)


# XLA fallback for kernels.rwmd: the kernels' oracle IS the implementation
# (single source of truth; ref.py imports only jax, so no core<->ops cycle)
from repro.kernels.ref import rwmd_min_cdist_ref

# Docs per gathered tile when the RWMD bound covers only some docs (a
# cascade that probes fewer than all clusters): each tile is (Qp, T, L_g)
# gathered distances, 12.6 MB at Qp = 8, L_g = 192.
BOUND_TILE = 2048


@jax.jit
def _finite(minm):
    # all-pad filler rows have minm == +inf; their bounds are don't-care
    return jnp.where(jnp.isfinite(minm), minm, 0.0)


@jax.jit
def _rwmd_minm(sup, mask, vecs):
    """(Qp, V) distance from every vocabulary word to its nearest live
    query word, the support gather fused in: the RWMD bound's producer."""
    return _finite(rwmd_min_cdist_ref(jnp.take(vecs, sup, axis=0), mask,
                                      vecs))


@jax.jit
def _rwmd_rows(lb, minm, pieces):
    """Write the RWMD bounds of ELL rows into columns of ``lb`` (Qp, N):
    each piece is ``(idx, val, cols)``, a doc group's or a gathered tile's
    (n, L) rows and their (n,) storage ids, where an id >= N is a pad slot
    and is dropped. Elementwise in fp32: a TPU would round a contraction's
    operands to bf16, and the bound must not exceed the solve's score by
    more than ``prune_slack``. Separate from the min-cdist producer on
    purpose: XLA CPU would otherwise fuse the producer into the gather and
    recompute it per element (see ROADMAP note)."""
    for idx, val, cols in pieces:
        g = jnp.take(minm, idx, axis=1)                  # (Qp, n, L)
        lb = lb.at[:, cols].set(jnp.sum(g * val[None], axis=-1),
                                mode="drop")
    return lb


def rwmd_bounds(index, sup, mask, doc_ids=None, use_kernel: bool = False,
                interpret: bool | None = None, lookup=None) -> jax.Array:
    """(Qp, N) device RWMD bounds for one query staging, the one
    implementation every pruner and the cascade share.

    With ``doc_ids`` None every doc is bounded, one dispatch over the
    index's nnz groups. Otherwise only the storage ``doc_ids`` are (+inf
    at every other doc), in fixed tiles of :data:`BOUND_TILE` docs of
    their groups (:meth:`~.index.CorpusIndex.tiles`, which takes the
    caller's ``lookup``), so the work follows the ids while the compiled
    shapes follow only the groups. ``use_kernel`` computes the min-cdist
    with the query-grid Pallas kernel (:mod:`repro.kernels.rwmd`)."""
    if use_kernel:
        from repro.kernels import ops
        minm = _finite(ops.rwmd_min_cdist(jnp.take(index.vecs, sup, axis=0),
                                          mask, index.vecs,
                                          interpret=interpret))
    else:
        minm = _rwmd_minm(sup, mask, index.vecs)
    shape = (minm.shape[0], index.n_docs)
    if doc_ids is None:
        return _rwmd_rows(jnp.zeros(shape, minm.dtype), minm, tuple(
            (g.docs.idx, g.docs.val, g.cols) for g in index.groups))
    lb = jnp.full(shape, jnp.inf, minm.dtype)
    for grp, part in index.tiles(doc_ids, BOUND_TILE, storage=True,
                                 lookup=lookup):
        cols = np.full(grp.docs.idx.shape[0], index.n_docs, np.int32)
        cols[:part.size] = grp.cols
        lb = _rwmd_rows(lb, minm,
                        ((grp.docs.idx, grp.docs.val, jnp.asarray(cols)),))
    return lb


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
        return rwmd_bounds(index, sup, mask, use_kernel=self.use_kernel,
                           interpret=self.interpret)


class MaxPruner:
    """Elementwise max of several admissible bounds (still admissible)."""

    def __init__(self, pruners: Sequence[Pruner]):
        self.pruners = tuple(pruners)
        self.name = "+".join(p.name for p in self.pruners)

    def lower_bounds(self, index, sup, r, mask):
        bounds = [p.lower_bounds(index, sup, r, mask) for p in self.pruners]
        return functools.reduce(jnp.maximum, bounds)


# ---------------------------------------------------------------- cascade
# Stage programs of the cascade: each bounds every doc of the corpus (the
# RWMD stage, under a partial probe, only the probed clusters' members, in
# fixed tiles), so each program's shapes are fixed by the index and the
# staging (Qp query rows, B words) and never by how many docs a stage lets
# through -- nothing compiles per search step.

@jax.jit
def _keep_any(lbm, thresh):
    """Columns any live query still needs: lbm (Qp, Sp) with +inf at
    non-candidates, thresh (qc,) margined thresholds -> (Sp,) bool."""
    return jnp.any(lbm[:thresh.shape[0]] <= thresh[:, None], axis=0)


@jax.jit
def _pivot_dense(qd, dd):
    """(Qp, N) pivot triangle bounds of every doc."""
    return jnp.max(jnp.abs(qd[:, None, :] - dd[None, :, :]), axis=-1)


@jax.jit
def _candidates_only(lb, pm, assign):
    """``lb`` (Qp, N) at +inf wherever the doc's cluster is not probed by
    the query."""
    return jnp.where(jnp.take(pm, assign, axis=1), lb, jnp.inf)


@jax.jit
def _probed_clusters(pm, mask):
    """(C,) bool: clusters some live query row probes."""
    return jnp.any(pm & jnp.any(mask > 0, axis=1)[:, None], axis=0)


@functools.partial(jax.jit, static_argnames=("k",))
def _seed_pick(lbs, k: int):
    """Each query row's ``k`` best docs by the tightest of the stage
    bounds ``lbs`` (their max): (neg bounds, positions), both (Qp, k);
    non-candidates bound +inf, so a query with fewer than k candidates
    reads -inf in its tail."""
    return jax.lax.top_k(-functools.reduce(jnp.maximum, lbs), k)


@jax.jit
def _cascade_keep(lbs, cdists, radii, assign, thresh, exclude):
    """Docs some live query still needs: ((N,) bool, the (Qp, N) bool per
    query). A doc survives for query q when its cluster's radius bound
    ``cdist(q, c) - radius_c`` and every stage bound are all at most
    ``thresh[q]`` (non-candidates bound +inf); pad query rows carry
    ``thresh = -inf`` and keep nothing. ``exclude`` (N,) drops the docs
    already solved."""
    ok = (cdists - radii[None, :])[:, assign] <= thresh[:, None]
    for lb in lbs:
        ok &= lb <= thresh[:, None]
    return jnp.any(ok, axis=0) & ~exclude, ok


@jax.jit
def _probe_dists(sup, r, mask, vecs, centers):
    """Query centroids + cluster-center distances, one dispatch:
    (cdists (Qp, C), qcent (Qp, w) — reused by the dense WCD pass)."""
    qcent = jnp.einsum("qb,qbw->qw", r * mask, jnp.take(vecs, sup, axis=0),
                       precision=FP32_GEMM)
    return jnp.sqrt(jnp.maximum(sq_dists(qcent, centers), 0.0)), qcent


@functools.partial(jax.jit, static_argnames=("nprobe",))
def _probe_mask(cdists, nprobe: int):
    """(Qp, C) bool: True at each query's ``nprobe`` nearest clusters."""
    _, idx = jax.lax.top_k(-cdists, nprobe)
    rows = jnp.arange(cdists.shape[0])[:, None]
    return jnp.zeros(cdists.shape, bool).at[rows, idx].set(True)


class CascadePruner:
    """IVF cascade: cluster probe, seed pick, then one survivor pass that
    holds every candidate doc to the cluster-radius bound and to each
    per-doc stage (pivot triangle -> WCD -> RWMD) at once.

    1. *ivf probe*: one (Q, n_clusters) GEMM against the frozen k-means
       centers. ``nprobe`` nearest clusters per query define the candidate
       universe (all clusters when ``nprobe=None`` — the exact mode).
    2. *seed pick* (:meth:`seeds`): every stage's bound over every
       candidate doc, and each query's k best docs by the tightest of
       them; the engine solves them exactly and takes the kth distance as
       the threshold t_q (the tighter the seeds' bounds, the lower t_q
       and the fewer survivors).
    3. *survivor pass* (:meth:`survivors`): a doc survives for query q when
       the cluster-radius triangle bound ``||qcent - center_c|| - radius_c
       <= wcd(q, n)`` (:class:`~.index.IvfClusters` ``radii``) and every
       stage bound are all at most t_q; the stages are
       - *pivot* (optional — Werner & Laber, arXiv:1912.00509):
         ``max_p |d(q, p) - d(n, p)|`` over the ``n_pivots`` reference
         words frozen at ``build_index`` (the precomputed ``doc_pivot_d``
         table), O(P) per pair; the reverse triangle inequality makes it
         a lower bound on WCD, so it inherits WCD's admissibility (and
         WCD's truncated-iteration caveat). Spelled
         ``"ivf+pivot+wcd+rwmd"``; requires an index built with
         ``n_pivots > 0`` (the default);
       - *wcd*: the centroid bound, one (Q, w) x (w, N) GEMM;
       - *rwmd*: the tight bound, each doc's mass times its words'
         distances to the nearest query word (one (Q*B, V) min-cdist,
         then :func:`rwmd_bounds` over the candidates' ELL rows).

    :meth:`bounds` computes each stage for every doc in one dispatch; the
    RWMD stage, the one whose work grows with the docs' words, bounds
    only the probed clusters' members when ``nprobe`` leaves some out
    (one blocking read of the probed clusters, then fixed tiles), so a
    reduced probe saves its per-doc work. Every program's shapes follow
    only the index, ``k`` and the query staging, so a search step
    compiles nothing new whatever its candidate counts. The seed pick
    and the survivor pass each end in one blocking read of compact host
    arrays (seed ids; the survivor bitmap).

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
        # candidate, and the stages skip the candidacy lookups
        pm = None if np_eff == c else _probe_mask(cdists, np_eff)
        return cdists, pm, qcent

    def bounds(self, index, sup, mask, qcent, pm, stages=None,
               lookup=None) -> tuple:
        """(Qp, N) device bounds, one array per stage (the pruner's own
        by default), +inf wherever the doc's cluster is not probed by the
        query. ``lookup`` is the engine's kept ``group_lookup`` for the
        RWMD stage's tiles under a partial probe, where that stage costs
        one blocking read."""
        out = []
        for stage in self.stages if stages is None else stages:
            if stage == "wcd":
                lb = _wcd_bounds(qcent, index.centroids)
            elif stage == "pivot":
                if index.pivots is None:
                    raise ValueError(
                        "cascade has a 'pivot' stage but the index has no "
                        "pivot words — rebuild with build_index("
                        "n_pivots > 0)")
                from .index import _pivot_dists
                lb = _pivot_dense(_pivot_dists(qcent, index.pivots),
                                  index.doc_pivot_d)
            else:
                lb = rwmd_bounds(
                    index, sup, mask,
                    None if pm is None else self.probed_members(index, pm,
                                                                mask),
                    self.use_kernel, self.interpret, lookup)
            out.append(lb if pm is None else _candidates_only(
                lb, pm, index.clusters.assign_dev))
        return tuple(out)

    def probed_members(self, index, pm, mask) -> np.ndarray:
        """Storage ids (ascending) of the clusters some live query probes;
        one blocking read of a (C,) bool."""
        cl = index.clusters
        kept = np.nonzero(np.asarray(_probed_clusters(pm, mask)))[0]
        if kept.size == 0:
            return np.zeros(0, np.int32)
        return np.sort(np.concatenate(
            [cl.order[cl.starts[c]:cl.starts[c + 1]] for c in kept]))

    def seeds(self, lbs, k: int):
        """Each query row's ``k`` best docs by the tightest of the stage
        :meth:`bounds` ``lbs``: (positions (Qp, k) host storage ids, real
        (Qp, k) host bool — False where a query has fewer than k
        candidates). One dispatch, one blocking read."""
        neg, pos = jax.device_get(_seed_pick(tuple(lbs), k=k))
        return pos, np.isfinite(neg)

    # --------------------------------------- post-threshold survivor pass
    def survivors(self, index, sup, r, mask, cdists, pm, qcent, thresh,
                  exclude: np.ndarray | None = None, lbs: tuple = (),
                  per_query: bool = False, lookup=None):
        """The post-threshold prune pass: storage ids (ascending, so
        cluster-major) of the docs some live query still needs, with
        ``exclude`` — typically the already-solved seeds — removed.
        ``thresh`` holds one threshold per live query row; rows past it
        (fillers) keep nothing. ``lbs`` are the :meth:`bounds` of the
        pruner's first stages when the caller has them already.
        ``per_query`` also returns the (Qp, |ids|) host bool of which
        query needs each survivor. Shared by
        ``WmdEngine._search_cascade`` and the fig9 prune-stage benchmark,
        so the measured pass IS the serving pass. One dispatch chain, one
        blocking read."""
        cl = index.clusters
        qp = sup.shape[0]
        if thresh.shape[0] < qp:
            thresh = jnp.concatenate([thresh, jnp.full(
                (qp - thresh.shape[0],), -jnp.inf, thresh.dtype)])
        excl = np.zeros(index.n_docs, bool)
        if exclude is not None:
            excl[exclude] = True
        lbs = tuple(lbs)
        if len(lbs) < len(self.stages):
            lbs += self.bounds(index, sup, mask, qcent, pm,
                               self.stages[len(lbs):], lookup)
        keep, ok = _cascade_keep(
            lbs, cdists, jnp.asarray(cl.radii, jnp.float32), cl.assign_dev,
            thresh, jnp.asarray(excl))
        surv = np.nonzero(np.asarray(keep))[0].astype(np.int32)
        if per_query:
            return surv, np.asarray(ok)[:, surv]
        return surv


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
