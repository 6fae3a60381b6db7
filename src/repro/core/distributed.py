"""Multi-chip Sinkhorn-WMD via shard_map — the paper's parallelization at pod
scale (DESIGN.md §3).

Two distribution schemes, mirroring the paper's baseline->optimized arc:

``dense`` (paper-faithful distributed baseline)
    Vocabulary V sharded over the ``model`` axis, documents N over ``data``
    (and ``pod`` when present). Per iteration: Kᵀ@u and the c-mask are local;
    the contraction x = K_over_r @ v crosses the V sharding -> one psum of a
    (v_r, N_local) tile over ``model`` per iteration. This is the distributed
    analogue of the paper's shared-memory dense kernel.

``sparse`` (production path)
    After precompute, the ELL iteration touches only per-document state, so
    documents are sharded over *all* mesh axes (N / n_chips docs per chip)
    and the loop runs with ZERO collectives — the pod-scale version of the
    paper's observation that threads own disjoint nnz ranges. Precompute in
    the baseline recomputes cdist per chip (replicated V); the optimized
    variant (``sparse_vshard``) shards cdist over ``model`` and assembles G
    with one psum — see EXPERIMENTS.md §Perf.

Load balance across shards (the paper's nnz binary-search) is handled at
ingest by ``repro.data.corpus.shard_balanced``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from .sinkhorn import LamUnderflowError, cdist, underflow_report
from .sinkhorn_sparse import (adaptive_loop_scoped,
                              marginal_residual_per_query, reconstruct_gm)
from .sparse import PaddedDocs


def _pvary(x, axes):
    """Mark a scan carry as varying over the doc-shard axes (shard_map's
    varying-manual-axes types require it)."""
    return lax.pcast(x, axes, to="varying")


def _doc_axes(mesh: Mesh) -> tuple[str, ...]:
    """All mesh axes, used jointly to shard the document dimension."""
    return tuple(mesh.axis_names)


def _data_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a != "model")


# --------------------------------------------------------------------------
# dense distributed (paper-faithful baseline)
# --------------------------------------------------------------------------

def sinkhorn_wmd_dense_distributed(r, vecs_sel, vecs, c, lam: float,
                                   n_iter: int, mesh: Mesh):
    """Dense Alg. 1 with V over ``model`` and N over the data axes.

    Inputs: r (v_r,) vecs_sel (v_r, w) vecs (V, w) c (V, N).
    V and N must divide the respective mesh axis sizes.
    """
    data_axes = _data_axes(mesh)
    v_spec = P("model")               # vocab-sharded
    c_spec = P("model", data_axes)
    out_spec = P(data_axes)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(), P(), v_spec, c_spec),
        out_specs=out_spec)
    def run(r, vecs_sel, vecs_loc, c_loc):
        m = cdist(vecs_sel, vecs_loc)            # (v_r, V_loc)
        k = jnp.exp(-lam * m)
        k_over_r = k / r[:, None]
        km = k * m
        v_r = r.shape[0]
        n_loc = c_loc.shape[1]
        x = jnp.full((v_r, n_loc), 1.0 / v_r, dtype=k.dtype)
        x = _pvary(x, tuple(data_axes))  # carry varies over doc shards

        def body(x, _):
            u = 1.0 / x
            v = c_loc * (1.0 / (k.T @ u))        # local (V_loc, N_loc)
            # contraction over V crosses the model sharding -> one psum/iter
            x = lax.psum(k_over_r @ v, "model")
            return x, None

        x, _ = lax.scan(body, x, None, length=n_iter)
        u = 1.0 / x
        v = c_loc * (1.0 / (k.T @ u))
        return lax.psum(jnp.sum(u * (km @ v), axis=0), "model")

    return run(r, vecs_sel, vecs, c)


# --------------------------------------------------------------------------
# sparse distributed (production path)
# --------------------------------------------------------------------------

def _check_underflow(out, lam, vecs_sel, vecs, docs, mesh: Mesh = None,
                     doc_ids=None):
    """Host-side lam-hygiene guard shared by the distributed solvers: a K
    underflow poisons every affected shard's distances with NaN — raise the
    same diagnosed :class:`LamUnderflowError` the engine raises instead of
    returning (and all-reducing) NaN. Batched (Q, v_r, w) support stacks
    are flattened for the report (it diagnoses per support word).

    With ``mesh`` the report names the OWNING SHARD(S) of the poisoned
    doc positions (docs are dealt to shards in contiguous mesh-order
    blocks, so ownership is position // block), and with ``doc_ids`` it
    quotes EXTERNAL doc ids instead of storage positions — a poisoned
    request's diagnosis stays actionable on the sharded path, mirroring
    the batched-path fix (storage positions are meaningless to callers
    once the cluster-major permutation and the shard deal are applied).
    """
    import numpy as np

    if vecs_sel.shape[0] > 0 and np.isnan(np.asarray(out)).any():
        sel2 = jnp.reshape(vecs_sel, (-1, vecs_sel.shape[-1]))
        msg = underflow_report(lam, sel2, vecs, docs)
        out_np = np.asarray(out)
        nan_docs = np.nonzero(
            np.isnan(out_np).any(axis=0) if out_np.ndim == 2
            else np.isnan(out_np))[0]
        if nan_docs.size:
            ids = (np.asarray(doc_ids)[nan_docs] if doc_ids is not None
                   else nan_docs)
            shown = ids[:8].tolist()
            tail = ", ..." if ids.size > 8 else ""
            kind = "external doc ids" if doc_ids is not None \
                else "doc positions"
            where = f"{nan_docs.size} poisoned docs ({kind} {shown}{tail})"
            if mesh is not None:
                n_shards = int(mesh.devices.size)
                block = max(1, out_np.shape[-1] // n_shards)
                owners = sorted({int(d // block) for d in nan_docs})
                where = (f"owning shard(s) {owners} of {n_shards} on mesh "
                         f"{dict(mesh.shape)}; " + where)
            msg = f"{where} — {msg}"
        raise LamUnderflowError(msg)
    return out


def sinkhorn_wmd_sparse_distributed(r, vecs_sel, vecs, docs: PaddedDocs,
                                    lam: float, n_iter: int, mesh: Mesh,
                                    vshard_precompute: bool = True,
                                    check_underflow: bool = True,
                                    tol: float | None = None,
                                    check_every: int = 4,
                                    qmask=None,
                                    return_iters: bool = False,
                                    doc_ids=None):
    """ELL fused Sinkhorn with docs sharded over every mesh axis.

    ``vshard_precompute=False``: baseline — every chip computes the full
    (v_r, V) cdist and gathers its docs' columns locally (replicated
    compute, zero collectives).

    ``vshard_precompute=True`` (beyond-paper optimized): cdist is sharded
    over ``model`` (each chip owns V/model_size vocab columns), each chip
    gathers the columns it owns for *its* docs and one psum over ``model``
    assembles G — cutting precompute FLOPs/chip by the model-axis size at
    the cost of a single (v_r, N_loc, L) all-reduce before the loop. (GM is
    reconstructed from G after the collective — each ELL entry is owned by
    exactly one vocab shard, so the scattered G is exact — which halves the
    assembly traffic versus shipping G and GM.)

    Both variants guard lam hygiene like the engine: NaN distances from a
    ``K = exp(-lam*M)`` underflow raise :class:`LamUnderflowError` with a
    diagnosis (``check_underflow=False`` opts out — the check syncs the
    sharded result).

    Batched queries (ISSUE 5): ``r`` may be (Q, v_r) with ``vecs_sel``
    (Q, v_r, w) — the solve runs all Q queries against the shared doc
    shards in one launch and returns (Q, N). ``qmask`` (Q, v_r) marks
    live support rows when queries were padded to a common ``v_r``
    (padded rows: ``r == 1``, ``qmask == 0``; their G rows are zeroed so
    they stay inert, the engine's padding contract).

    ``tol`` enables the convergence-adaptive loop: every ``check_every``
    iterations each shard reduces its local doc-marginal residual to a
    PER-QUERY (Q,) vector and ONE ``lax.pmax`` over the doc axes
    all-reduces that vector — still a single collective per check (ISSUE
    4's scalar became ISSUE 5's (Q,) vector). Every shard therefore
    freezes the same queries at the same (earliest safe) iteration:
    converged queries' x-columns stop updating while stubborn batch-mates
    run on, and the loop exits when all live queries converged or the
    ``n_iter`` cap hits (realized counts land on ``1 + k*check_every``,
    overshooting the cap by at most ``check_every - 1``).
    ``return_iters=True`` also returns the per-query realized counts
    ((Q,) int32; scalar-shaped (1,) for a single query).

    ``doc_ids`` (N,) optionally names each doc position's EXTERNAL id in
    the underflow diagnosis (see :func:`_check_underflow`) — callers that
    permuted or shard-dealt storage should pass it so a poisoned
    request's report quotes ids the caller can act on.
    """
    doc_axes = _doc_axes(mesh)
    docs_spec = P(doc_axes)
    batched = jnp.ndim(r) == 2
    out_spec = P(None, doc_axes) if batched else P(doc_axes)
    # the adaptive path's lax.while_loop has no shard_map replication rule
    # (jax #workaround) — drop the rep check only when it is in play
    rep = {} if tol is None else {"check_vma": False}

    def finish(out_iters):
        out, iters = out_iters
        if check_underflow:
            _check_underflow(out, lam, vecs_sel, vecs, docs, mesh=mesh,
                             doc_ids=doc_ids)
        return (out, iters) if return_iters else out

    if not vshard_precompute:
        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(P(), P(), P(), docs_spec, docs_spec),
            out_specs=(out_spec, P()), **rep)
        def run(r, vecs_sel, vecs_full, idx_loc, val_loc):
            sel2 = vecs_sel.reshape(-1, vecs_sel.shape[-1])
            m = cdist(sel2, vecs_full)            # replicated (Q*v_r, V)
            k = jnp.exp(-lam * m)
            g = jnp.take(k, idx_loc, axis=1)      # (Q*v_r, N_loc, L)
            if batched:
                g = g.reshape(r.shape + idx_loc.shape)
            out, iters = _ell_loop(r, g, val_loc, lam, n_iter, doc_axes,
                                   tol=tol, check_every=check_every,
                                   qmask=qmask)
            return (out if batched else out[0]), iters

        return finish(run(r, vecs_sel, vecs, docs.idx, docs.val))

    # optimized: vocab-sharded precompute, psum_scatter-assembled gather.
    # Docs enter sharded over the data axes and REPLICATED over model; each
    # model shard gathers the K columns it owns for every doc in the data
    # shard, then one psum_scatter over model simultaneously (a) sums the
    # per-vocab-shard contributions and (b) deals each model shard its
    # 1/model_size slice of the docs — after which the loop owns docs over
    # data x model jointly, same as the baseline.
    n_model = mesh.shape["model"]
    v = vecs.shape[0]
    v_loc_size = v // n_model
    data_axes = _data_axes(mesh)
    vs_out = (P(None, data_axes + ("model",)) if batched
              else P(data_axes + ("model",)))

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(), P(), P("model"), P(data_axes), P(data_axes)),
        out_specs=(vs_out, P()), **rep)
    def run(r, vecs_sel, vecs_loc, idx_loc, val_loc):
        midx = lax.axis_index("model")
        lo = midx * v_loc_size
        sel2 = vecs_sel.reshape(-1, vecs_sel.shape[-1])
        m = cdist(sel2, vecs_loc)                 # (Q*v_r, V_loc)
        k = jnp.exp(-lam * m)
        # gather only ids this chip owns; others contribute zeros to the sum
        rel = idx_loc - lo
        mine = (rel >= 0) & (rel < v_loc_size)
        rel = jnp.where(mine, rel, 0)
        g = jnp.where(mine[None], jnp.take(k, rel, axis=1), 0.0)
        # assemble + redistribute docs over the model axis in one collective;
        # GM is rebuilt from the assembled G, so it never crosses the wire
        g = lax.psum_scatter(g, "model", scatter_dimension=1, tiled=True)
        n_slice = val_loc.shape[0] // n_model
        val_my = lax.dynamic_slice_in_dim(val_loc, midx * n_slice, n_slice, 0)
        if batched:
            g = g.reshape(r.shape + (n_slice, idx_loc.shape[1]))
        out, iters = _ell_loop(r, g, val_my, lam, n_iter,
                               data_axes + ("model",), tol=tol,
                               check_every=check_every, qmask=qmask)
        return (out if batched else out[0]), iters

    return finish(run(r, vecs_sel, vecs, docs.idx, docs.val))


def _ell_loop(r, g, val, lam, n_iter, vary_axes=(), tol=None,
              check_every: int = 4, qmask=None):
    """The collective-free fused SDDMM_SpMM iteration (per shard).

    Accepts one query (``g`` (v_r, N_loc, L), ``r`` (v_r,)) or a batch
    (``g`` (Q, v_r, N_loc, L), ``r`` (Q, v_r)); internally everything is
    the batched layout (a single query is Q == 1) so there is ONE copy of
    the loop. Returns ((Q, N_loc) wmd, (Q,) realized iterations).

    With ``tol`` set, the fixed scan becomes the per-query
    :func:`~repro.core.sinkhorn_sparse.adaptive_loop_scoped`: every
    ``check_every`` iterations each shard reduces its local doc-marginal
    residual ``max|val/t - w_prev|`` per query and one (Q,)-vector
    ``lax.pmax`` over ``vary_axes`` agrees on them globally — all shards
    freeze the same queries at the same iteration, so the carries stay
    consistent for the final distance line.
    """
    if g.ndim == 3:
        g, r = g[None], jnp.reshape(r, (1, -1))
    q, v_r, n_loc, length = g.shape
    g_over_r = g / r[:, :, None, None]
    if qmask is not None:
        # padded support rows are structurally inert: G rows zeroed, u
        # rows masked (their x decays to 0 after one iteration)
        g = g * qmask[:, :, None, None]
        g_over_r = g_over_r * qmask[:, :, None, None]
    live = val > 0
    n_live = (jnp.sum(qmask, axis=1) if qmask is not None
              else jnp.full((q,), v_r, g.dtype))
    x0 = 1.0 / jnp.maximum(n_live, 1.0)
    x = jnp.broadcast_to(x0[:, None, None], (q, v_r, n_loc)).astype(g.dtype)
    if qmask is not None:
        x = x * qmask[:, :, None]
    if vary_axes:
        x = _pvary(x, tuple(vary_axes))  # match shard-varying carry type

    def u_of(x):
        if qmask is None:
            return 1.0 / x   # raw: a K underflow must surface as NaN
        return jnp.where(qmask[:, :, None] > 0, 1.0 / jnp.where(
            qmask[:, :, None] > 0, x, 1.0), 0.0)

    def step(carry, _):
        x, _ = carry
        u = u_of(x)
        t = jnp.einsum("qknl,qkn->qnl", g, u)
        w = jnp.where(live[None], val[None] / t, 0.0)
        x = jnp.einsum("qknl,qnl->qkn", g_over_r, w)
        return (x, w), None

    if tol is None:
        # x-only carry — bit-identical to the pre-adaptive loop
        x, _ = lax.scan(lambda x, _: (step((x, None), None)[0][0], None),
                        x, None, length=n_iter)
        iters = jnp.full((q,), n_iter, jnp.int32)
    else:
        # the one collective in the loop: a (Q,) vector all-reduce so
        # every shard freezes the same queries at the same check
        all_reduce = ((lambda res: lax.pmax(res, tuple(vary_axes)))
                      if vary_axes else None)
        live_q = (jnp.sum(qmask, axis=1) > 0 if qmask is not None
                  else jnp.ones((q,), bool))
        resmask = jnp.broadcast_to(live[None], (q,) + val.shape)

        def step_active(x, active):
            # frozen queries' update rows are dropped via the u mask
            u = u_of(x) * active[:, None, None].astype(g.dtype)
            t = jnp.einsum("qknl,qkn->qnl", g, u)
            w = jnp.where(live[None], val[None] / t, 0.0)
            return jnp.einsum("qknl,qnl->qkn", g_over_r, w), w

        x, iters = adaptive_loop_scoped(
            step_active,
            lambda w, wp: marginal_residual_per_query(w, wp, resmask),
            x, n_iter, tol, check_every, live_q, all_reduce=all_reduce)
    u = u_of(x)
    t = jnp.einsum("qknl,qkn->qnl", g, u)
    w = jnp.where(live[None], val[None] / t, 0.0)
    wmd = jnp.einsum("qkn,qknl,qnl->qn", u, reconstruct_gm(g, lam), w)
    return wmd, iters


def sharded_inputs(mesh: Mesh, r, vecs_sel, vecs, docs: PaddedDocs,
                   for_impl: str = "sparse"):
    """Device_put inputs with the shardings the distributed solvers expect."""
    doc_axes = _doc_axes(mesh)
    if for_impl == "sparse":
        specs = dict(vecs=P(), idx=P(doc_axes), val=P(doc_axes))
    else:
        specs = dict(vecs=P("model"), idx=None, val=None)

    def put(x, s):
        return jax.device_put(x, NamedSharding(mesh, s))
    out = dict(r=put(r, P()), vecs_sel=put(vecs_sel, P()),
               vecs=put(vecs, specs["vecs"]))
    if for_impl == "sparse":
        out["docs"] = PaddedDocs(idx=put(docs.idx, specs["idx"]),
                                 val=put(docs.val, specs["val"]))
    return out
