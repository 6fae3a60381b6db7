"""Sparse-kernel Sinkhorn-Knopp WMD — the paper's contribution (§4), TPU form.

The paper's transformation: the dense hot line
``v = c.multiply(1 / (K.T @ u))`` computes a (V, N) GEMM and then throws away
99.996% of it; SDDMM computes only the nnz(c) dot products, and SDDMM_SpMM
fuses the following ``x = K_over_r @ v`` so ``v`` never round-trips memory.

TPU adaptation (see DESIGN.md §4): CSR loops become ELL-format einsums. With
``G[k, j, l] = K[k, idx[j, l]]`` gathered once before the loop (K is
loop-invariant — the same observation the paper uses to hoist K, K.T,
K_over_r), each iteration is

    t[j, l] = sum_k G[k, j, l] * u[k, j]        # SDDMM
    w[j, l] = val[j, l] / t[j, l]               # sparse selection
    x[k, j] = sum_l G[k, j, l] / r[k] * w[j, l] # SpMM (fused: same G tile)

which is 4*N*L*v_r flops/iter versus the dense 4*N*V*v_r — a V/L ~ 2800x
work reduction at the paper's corpus statistics, with zero gather traffic
inside the loop. The Pallas kernel (:mod:`repro.kernels.sddmm_spmm`) executes
the same schedule tile-by-tile out of VMEM.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.sddmm_spmm import reconstruct_gm  # re-exported
from .sinkhorn import LamUnderflowError, cdist, underflow_report
from .sparse import PaddedDocs


class SolvePrecision(NamedTuple):
    """Solve-stage numeric policy (ISSUE 4): which dtype the GEMMs run in
    and whether the kernel matrix is kept in the log domain.

    ``gemm="bf16"`` runs the cdist and SDDMM/SpMM contractions with bf16
    inputs and fp32 accumulation (``preferred_element_type``); ``x`` and
    the marginals stay fp32, so only the GEMM operand traffic is halved —
    the Atasu et al. (LC-RWMD) mixed-precision lever, tolerance-bounded.

    ``log_domain=True`` keeps ``log K = -lam*M`` unexponentiated through
    the gather and max-subtracts per gathered column before the solve
    (:func:`precompute_sparse_log`): every column's largest entry becomes
    exactly 1, so an all-zero K column — the :class:`LamUnderflowError`
    failure mode — is structurally impossible at any ``lam``. The Sinkhorn
    iteration is invariant under per-column rescaling of G (the factor
    cancels between the SDDMM and SpMM lines), and the distance line picks
    up the closed-form correction ``-(1/lam) sum_l shift*val`` — exact, not
    an approximation (see :func:`log_shift_correction`).

    Spellings accepted by :meth:`parse` (engine/serve/CLI knob):
    ``"fp32"``, ``"bf16"``, ``"log"``, ``"bf16+log"`` (order-insensitive).
    """

    gemm: str = "fp32"        # "fp32" | "bf16"
    log_domain: bool = False

    @classmethod
    def parse(cls, spec) -> "SolvePrecision":
        if isinstance(spec, cls):
            return spec
        if spec is None:
            return cls()
        parts = [p.strip() for p in str(spec).split("+") if p.strip()]
        gemm, log_domain = "fp32", False
        for p in parts:
            if p in ("fp32", "bf16"):
                gemm = p
            elif p == "log":
                log_domain = True
            else:
                raise ValueError(
                    f"unknown precision token {p!r} in {spec!r}; spell it "
                    f"from {{'fp32', 'bf16', 'log'}} joined by '+'")
        return cls(gemm=gemm, log_domain=log_domain)

    @property
    def gemm_dtype(self):
        return jnp.bfloat16 if self.gemm == "bf16" else None

    @property
    def name(self) -> str:
        return self.gemm + ("+log" if self.log_domain else "")


class SparsePrecompute(NamedTuple):
    """Loop-invariant gathered tiles: everything the iteration touches.

    Only TWO nnz-sized arrays: the (K*M) gather the distance line needs is
    reconstructable from G (``GM = -G*log(G)/lam`` since ``G`` holds gathered
    ``K = exp(-lam*M)`` entries), so it is never materialized — see
    :func:`reconstruct_gm`.
    """

    G: jax.Array          # (v_r, N, L)  K columns at each doc's words
    G_over_r: jax.Array   # (v_r, N, L)  diag(1/r) G
    val: jax.Array        # (N, L)       normalized frequencies (0 = pad)


class SparsePrecomputeLog(NamedTuple):
    """Log-domain variant of :class:`SparsePrecompute` (ISSUE 4).

    ``G`` holds ``exp(log K - shift)`` with ``shift[n, l] = max_k
    (-lam * M[k, idx[n, l]])`` — each gathered column is rescaled so its
    largest entry is exactly 1. The iteration consumes it unchanged (the
    rescale cancels between SDDMM and SpMM); only the distance line needs
    ``shift`` back (see :func:`log_shift_correction`).
    """

    G: jax.Array          # (v_r, N, L)  exp(-lam*M - shift), col-max == 1
    G_over_r: jax.Array   # (v_r, N, L)  diag(1/r) G
    val: jax.Array        # (N, L)       normalized frequencies (0 = pad)
    shift: jax.Array      # (N, L)       per-column max of -lam*M (<= 0)


def log_shift_correction(shift: jax.Array, val: jax.Array,
                         lam) -> jax.Array:
    """Exact distance-line correction for the log-domain rescale.

    With ``G' = G * exp(-shift)`` per column, the converged selection
    satisfies ``t' * w' = val`` (the doc marginal holds by construction),
    so the rescale's contribution to ``<P, M>`` collapses to
    ``-(1/lam) sum_l shift[n, l] * val[n, l]`` — a per-doc constant, no
    approximation. Returns (N,)."""
    return -jnp.sum(shift * val, axis=-1) / lam


def precompute_sparse(r: jax.Array, vecs_sel: jax.Array, vecs: jax.Array,
                      docs: PaddedDocs, lam: float,
                      gemm_dtype=None) -> SparsePrecompute:
    """cdist -> K -> gather doc columns. One pass over (v_r, V), then O(nnz).

    ``gemm_dtype`` (e.g. ``jnp.bfloat16``) runs the cdist GEMM with
    reduced-precision inputs and fp32 accumulation (the
    :class:`SolvePrecision` bf16 policy)."""
    M = cdist(vecs_sel, vecs, gemm_dtype=gemm_dtype)       # (v_r, V)
    K = jnp.exp(-lam * M)
    G = jnp.take(K, docs.idx, axis=1)            # (v_r, N, L)
    return SparsePrecompute(G=G, G_over_r=G / r[:, None, None], val=docs.val)


def precompute_sparse_log(r: jax.Array, vecs_sel: jax.Array, vecs: jax.Array,
                          docs: PaddedDocs, lam: float,
                          gemm_dtype=None) -> SparsePrecomputeLog:
    """Log-domain precompute: ``log K = -lam*M`` is gathered UNexponentiated
    and max-subtracted per column, so no column can underflow to all-zero
    (its max entry exponentiates to exactly 1) — large-``lam`` configs like
    the paper's ``lam=9`` run without the :class:`LamUnderflowError` guard
    ever tripping."""
    M = cdist(vecs_sel, vecs, gemm_dtype=gemm_dtype)       # (v_r, V)
    lg = jnp.take(-lam * M, docs.idx, axis=1)    # (v_r, N, L) log K gathered
    shift = jnp.max(lg, axis=0)                  # (N, L), <= 0
    G = jnp.exp(lg - shift[None])
    return SparsePrecomputeLog(G=G, G_over_r=G / r[:, None, None],
                               val=docs.val, shift=shift)


def _gemm_cast(a, gemm_dtype):
    return a if gemm_dtype is None else a.astype(gemm_dtype)


def _sddmm(g, u, gemm_dtype=None):
    """t[n, l] = sum_k G[k, n, l] u[k, n] with fp32 accumulation."""
    return jnp.einsum("knl,kn->nl", _gemm_cast(g, gemm_dtype),
                      _gemm_cast(u, gemm_dtype),
                      preferred_element_type=jnp.float32)


def _spmm(g_over_r, w, gemm_dtype=None):
    """x[k, n] = sum_l G_over_r[k, n, l] w[n, l] with fp32 accumulation."""
    return jnp.einsum("knl,nl->kn", _gemm_cast(g_over_r, gemm_dtype),
                      _gemm_cast(w, gemm_dtype),
                      preferred_element_type=jnp.float32)


def marginal_residual(w, w_prev, mask):
    """Per-doc relative doc-marginal residual, the adaptive loops' shared
    exit statistic: ``max_doc max_slot |w - w_prev| / max_slot |w|`` over
    ``mask``-live slots (the last axis is the slot axis; leading axes are
    docs and, for the batched engine, queries). Masked slots contribute 0
    to both the diff and the scale, so padded docs/queries can neither
    stall the loop nor release it early; an all-masked doc's 0/1e-30 is
    exactly 0."""
    diff = jnp.max(jnp.where(mask, jnp.abs(w - w_prev), 0.0), axis=-1)
    scale = jnp.max(jnp.where(mask, jnp.abs(w), 0.0), axis=-1)
    return jnp.max(diff / jnp.maximum(scale, 1e-30))


def marginal_residual_per_query(w, w_prev, mask):
    """Per-QUERY residual vector (ISSUE 5): the same statistic as
    :func:`marginal_residual` — each DOC's diff is normalized by that
    doc's own marginal scale (the last axis is the slot axis) BEFORE any
    cross-doc reduction; mixing a near doc's diff with a far doc's much
    larger marginal scale would release the exit spuriously early — but
    reduced only over each query's own axes: ``w`` is (Q, ..., L) with a
    leading query axis, and the doc-ratio max keeps it, returning (Q,).
    ``mask`` is the per-query residual scope: fold the query's
    *candidate* docs into it and far (query, doc) pairs the ranking
    never needs can no longer hold that query's exit open. A query whose
    scope is empty (an all-pad filler, or no candidates) reduces to
    exactly 0 and converges at the first check."""
    diff = jnp.max(jnp.where(mask, jnp.abs(w - w_prev), 0.0), axis=-1)
    scale = jnp.max(jnp.where(mask, jnp.abs(w), 0.0), axis=-1)
    ratio = diff / jnp.maximum(scale, 1e-30)
    return jnp.max(ratio, axis=tuple(range(1, ratio.ndim)))


def adaptive_loop(step, residual, x0, n_iter: int, tol: float,
                  check_every: int, all_reduce=None):
    """Shared convergence-adaptive driver for every Sinkhorn variant
    (einsum engine, single-query sparse, distributed shards — ONE copy
    of the exit machinery).

    ``step(x) -> (x, w)`` runs one iteration; ``residual(w, w_prev)``
    reduces to the scalar exit statistic (:func:`marginal_residual` with
    the variant's own mask); ``all_reduce`` (optional) agrees on the
    residual across shards (the distributed ``lax.pmax``). The window
    is SEEDED with one real iteration — against ``w_prev == 0`` the
    first residual would be exactly 1.0 and a whole check period would
    be wasted — so realized counts land on ``1 + k*check_every`` with
    ``n_iter`` the cap (overshot by at most ``check_every - 1``).
    Returns (x, iters)."""
    def window(x, w):
        out, _ = lax.scan(lambda c, _: (step(c[0]), None), (x, w), None,
                          length=check_every)
        return out

    def cond(state):
        i, _, _, res = state
        return (i < n_iter) & (res > tol)

    def body(state):
        i, x, w_prev, _ = state
        x, w = window(x, w_prev)
        res = residual(w, w_prev)
        if all_reduce is not None:
            res = all_reduce(res)
        return (i + check_every, x, w, res)

    x, w_seed = step(x0)
    state = (jnp.asarray(1, jnp.int32), x, w_seed,
             jnp.asarray(jnp.inf, jnp.float32))
    iters, x, _, _ = lax.while_loop(cond, body, state)
    return x, iters


def adaptive_loop_scoped(step, residual, x0, n_iter: int, tol: float,
                         check_every: int, live_q, all_reduce=None):
    """Per-QUERY convergence-adaptive driver (ISSUE 5).

    Where :func:`adaptive_loop` reduces the exit statistic to one
    chunk-global scalar, this driver keeps a (Q,) residual VECTOR and a
    per-query convergence state:

    - ``step(x, active) -> (x, w)`` runs one iteration with the (Q,) bool
      ``active`` mask folded into the update — frozen queries' operand
      rows are ZEROED (semantically dropped; a dense einsum/GEMM still
      executes at full chunk width, so the saving is the earlier
      per-query EXIT and the honest per-query iteration accounting, not
      fewer FLOPs per remaining iteration — on TPU the Pallas path's
      per-block exit is where frozen work is genuinely skipped);
    - ``residual(w, w_prev) -> (Q,)`` is the per-query exit statistic
      (:func:`marginal_residual_per_query` with the variant's own scope
      mask — fold each query's CANDIDATE docs in and far pairs the
      ranking never needs stop holding its exit open);
    - queries FREEZE their x-columns once converged (``x`` keeps the
      frozen value through every later window; convergence is sticky);
    - the loop exits when every ``live_q`` query has converged or the
      ``n_iter`` cap hits; ``all_reduce`` (the distributed ``lax.pmax``
      over the (Q,) vector — still ONE collective) agrees on the
      residuals across shards so every shard freezes the same queries.

    The query axis is axis 0 of ``x``. The window is seeded with one real
    iteration like the scalar driver, so per-query realized counts land
    on ``1 + k*check_every`` with ``n_iter`` the cap. Returns
    ``(x, iters_q)`` with ``iters_q`` (Q,) int32 — the iterations each
    query's x actually absorbed (fillers stay at the seed count)."""
    bshape = (-1,) + (1,) * (x0.ndim - 1)

    def window(x, w, active):
        act_b = active.reshape(bshape)

        def inner(carry, _):
            x, _ = carry
            x_new, w_new = step(x, active)
            return (jnp.where(act_b, x_new, x), w_new), None

        (x, w), _ = lax.scan(inner, (x, w), None, length=check_every)
        return x, w

    def cond(state):
        i, _, _, conv, _ = state
        return (i < n_iter) & jnp.any(live_q & ~conv)

    def body(state):
        i, x, w_prev, conv, iters_q = state
        active = live_q & ~conv
        x, w = window(x, w_prev, active)
        res = residual(w, w_prev)
        if all_reduce is not None:
            res = all_reduce(res)
        i_new = i + check_every
        iters_q = jnp.where(active, i_new, iters_q)
        conv = conv | (active & (res <= tol))
        return (i_new, x, w, conv, iters_q)

    x, w_seed = step(x0, live_q)
    q = live_q.shape[0]
    state = (jnp.asarray(1, jnp.int32), x, w_seed,
             jnp.zeros((q,), bool), jnp.ones((q,), jnp.int32))
    _, x, _, _, iters_q = lax.while_loop(cond, body, state)
    return x, iters_q


def _inv(x, guarded: bool):
    """``1/x``; the guarded form maps non-positive entries to 0 instead of
    inf/NaN. The LINEAR path keeps the raw division on purpose — an
    underflowed K column must surface as NaN so the
    :class:`LamUnderflowError` guard can trip; the LOG path uses the
    guarded form because column underflow is structurally impossible there
    and a fully-underflowed *row* (a query word beyond the fp32 horizon of
    every doc word) should drop out like its linear-domain K row would."""
    if not guarded:
        return 1.0 / x
    return jnp.where(x > 0, 1.0 / jnp.where(x > 0, x, 1.0), 0.0)


def _select(live, val, t, guarded: bool):
    """Sparse selection ``w = val/t`` on live slots (0 elsewhere)."""
    if not guarded:
        return jnp.where(live, val / t, 0.0)
    ok = live & (t > 0)
    return jnp.where(ok, val / jnp.where(ok, t, 1.0), 0.0)


def _iterate(pre: SparsePrecompute, n_iter: int, gemm_dtype=None,
             guarded: bool = False):
    v_r = pre.G.shape[0]
    n = pre.G.shape[1]
    live = pre.val > 0
    x = jnp.full((v_r, n), 1.0 / v_r, dtype=jnp.float32)

    def body(x, _):
        u = _inv(x, guarded)
        t = _sddmm(pre.G, u, gemm_dtype)                   # SDDMM
        w = _select(live, pre.val, t, guarded)
        x = _spmm(pre.G_over_r, w, gemm_dtype)             # SpMM (fused)
        return x, None

    x, _ = lax.scan(body, x, None, length=n_iter)
    return x, jnp.asarray(n_iter, jnp.int32)


def _iterate_adaptive(pre, n_iter: int, tol: float, check_every: int,
                      gemm_dtype=None, guarded: bool = False,
                      doc_mask=None):
    """Convergence-adaptive Sinkhorn: a ``lax.while_loop`` that checks the
    doc-marginal residual ``max|val/t - w_prev|`` every ``check_every``
    iterations and exits once every live column is below ``tol``.

    ``n_iter`` becomes a CAP (realized counts land on ``1 + k *
    check_every`` — the window is seeded with one real iteration so even
    the first check can exit — overshooting the cap by at most
    ``check_every - 1``). The residual is RELATIVE to each doc's own
    marginal scale and costs nothing extra: ``w`` falls out of the
    chunk's last inner iteration and is carried between checks. Padded
    slots (``val == 0``) are masked out of the residual, so inert docs
    can neither stall the loop nor release it early. ``doc_mask`` (N,)
    additionally scopes the exit test to the docs the caller actually
    needs (ISSUE 5's residual scoping from this single-query solver's
    perspective): non-candidate docs keep iterating but cannot hold the
    loop open. Returns (x, iters)."""
    v_r = pre.G.shape[0]
    live = pre.val > 0
    resmask = live if doc_mask is None else live & doc_mask[:, None]
    x0 = jnp.full((v_r, pre.val.shape[0]), 1.0 / v_r, dtype=jnp.float32)

    def step(x):
        u = _inv(x, guarded)
        t = _sddmm(pre.G, u, gemm_dtype)
        w = _select(live, pre.val, t, guarded)
        return _spmm(pre.G_over_r, w, gemm_dtype), w

    return adaptive_loop(step,
                         lambda w, wp: marginal_residual(w, wp, resmask),
                         x0, n_iter, tol, check_every)


@functools.partial(jax.jit, static_argnames=("n_iter", "tol", "check_every",
                                             "precision"))
def _sinkhorn_wmd_sparse(r: jax.Array, vecs_sel: jax.Array, vecs: jax.Array,
                         docs: PaddedDocs, lam: float, n_iter: int,
                         tol=None, check_every: int = 4,
                         precision: SolvePrecision = SolvePrecision(),
                         doc_mask=None):
    gd = precision.gemm_dtype
    guarded = precision.log_domain
    if precision.log_domain:
        pre = precompute_sparse_log(r, vecs_sel, vecs, docs, lam, gd)
    else:
        pre = precompute_sparse(r, vecs_sel, vecs, docs, lam, gd)
    if tol is None:
        x, iters = _iterate(pre, n_iter, gd, guarded)
    else:
        x, iters = _iterate_adaptive(pre, n_iter, tol, check_every, gd,
                                     guarded, doc_mask)
    u = _inv(x, guarded)
    t = _sddmm(pre.G, u, gd)
    w = _select(pre.val > 0, pre.val, t, guarded)
    # wmd[j] = sum_k u[k,j] * sum_l GM[k,j,l] w[j,l]   (paper's final line);
    # GM reconstructed from G, never stored
    wmd = jnp.einsum("kn,knl,nl->n", u, reconstruct_gm(pre.G, lam), w)
    if precision.log_domain:
        wmd = wmd + log_shift_correction(pre.shift, pre.val, lam)
    return wmd, iters


def sinkhorn_wmd_sparse(r: jax.Array, vecs_sel: jax.Array, vecs: jax.Array,
                        docs: PaddedDocs, lam: float, n_iter: int,
                        check_underflow: bool = True, tol=None,
                        check_every: int = 4, precision=None,
                        return_iters: bool = False, doc_mask=None):
    """Sparse fused Sinkhorn WMD: identical result to the dense Alg. 1.

    Padding entries (val == 0) produce w == 0 and therefore contribute
    nothing — exactly the entries the dense version masks away with c.

    ``tol`` switches the fixed-length scan to the convergence-adaptive
    ``lax.while_loop`` (``n_iter`` becomes a cap; realized counts land on
    ``1 + k*check_every``); ``precision`` is a :class:`SolvePrecision` (or its
    string spelling) selecting bf16 GEMMs and/or the log-domain kernel —
    the log-domain path cannot underflow, so the guard below never trips on
    it. ``return_iters=True`` also returns the realized iteration count.
    ``doc_mask`` (N,) bool scopes the adaptive exit test to the caller's
    candidate docs (ISSUE 5): this solver IS one query, so per-query
    residual scoping means its residual covers only the docs whose
    distances the caller will read — distances of masked-out docs are
    still returned, just not allowed to delay the exit.

    Like the engine and ``one_to_many``, a ``K = exp(-lam*M)`` underflow
    raises :class:`~repro.core.sinkhorn.LamUnderflowError` with a host-side
    diagnosis instead of returning NaN distances. The check syncs the (N,)
    result; pass ``check_underflow=False`` to keep dispatch async (callers
    that run their own guard, e.g. ``one_to_many``, do).
    """
    precision = SolvePrecision.parse(precision)
    out, iters = _sinkhorn_wmd_sparse(
        r, vecs_sel, vecs, docs, lam, n_iter,
        tol=None if tol is None else float(tol),
        check_every=int(check_every), precision=precision,
        doc_mask=None if doc_mask is None else jnp.asarray(doc_mask, bool))
    if (check_underflow and r.shape[0] > 0
            and bool(jnp.isnan(out).any())):
        raise LamUnderflowError(underflow_report(lam, vecs_sel, vecs, docs))
    return (out, iters) if return_iters else out


@functools.partial(jax.jit, static_argnames=("n_iter",))
def sinkhorn_wmd_sparse_unfused(r: jax.Array, vecs_sel: jax.Array,
                                vecs: jax.Array, docs: PaddedDocs, lam: float,
                                n_iter: int) -> jax.Array:
    """Paper-faithful *unfused* sparse variant (separate SDDMM then SpMM,
    re-reading K from HBM each iteration — the paper's Fig. 3 pair before the
    SDDMM_SpMM fusion). Used by benchmarks to measure the fusion win."""
    M = cdist(vecs_sel, vecs)
    K = jnp.exp(-lam * M)
    K_over_r = K / r[:, None]
    KM = K * M
    v_r = r.shape[0]
    n, length = docs.idx.shape
    live = docs.val > 0
    x = jnp.full((v_r, n), 1.0 / v_r, dtype=K.dtype)

    def body(x, _):
        u = 1.0 / x
        # SDDMM with per-iteration gather (no hoisted G):
        g = jnp.take(K, docs.idx, axis=1)                  # (v_r, N, L)
        t = jnp.einsum("knl,kn->nl", g, u)
        w = jnp.where(live, docs.val / t, 0.0)
        # separate SpMM, gathering K_over_r again:
        gor = jnp.take(K_over_r, docs.idx, axis=1)
        x = jnp.einsum("knl,nl->kn", gor, w)
        return x, None

    x, _ = lax.scan(body, x, None, length=n_iter)
    u = 1.0 / x
    g = jnp.take(K, docs.idx, axis=1)
    t = jnp.einsum("knl,kn->nl", g, u)
    w = jnp.where(live, docs.val / t, 0.0)
    gm = jnp.take(KM, docs.idx, axis=1)
    return jnp.einsum("kn,knl,nl->n", u, gm, w)
