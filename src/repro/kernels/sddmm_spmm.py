"""Pallas TPU kernels for the fused SDDMM_SpMM Sinkhorn iteration (paper §4).

Two kernels, in increasing fusion depth:

``sddmm_spmm_step``
    One Sinkhorn iteration: SDDMM (t = sum_k G u), sparse selection
    (w = val/t), SpMM (x' = sum_l (G/r) w) — the paper's Fig. 4 kernel in ELL
    form. G streams HBM->VMEM once per call; the intermediate ``w`` lives
    only in VREGs (that is the paper's fusion: "output values from SDDMM can
    be fed directly to the SpMM and would not need to be stored in memory").

``sinkhorn_fused_all``
    Beyond-paper: the ENTIRE solver (all iterations + the final distance
    line) for a block of documents with the G tile *resident in VMEM*. The
    paper's appendix notes the kernel remains memory-bound without tiling
    ("if we assume that all matrices can be loaded from cache, the runtime
    ... can be improved further"); on TPU the G tile (v_r x block_n x L
    ~ 1 MB) comfortably fits the ~16 MB VMEM, so HBM traffic drops from
    (2 reads of G per iteration) to (1 read of G total) and the iteration
    becomes compute-bound. This is the TPU analogue of the
    adaptive-sparse-tiling improvement the paper cites as future work [5].

    The distance line needs GM = (K*M) gathered at the doc words, but since
    K = exp(-lam*M) we have GM = -G*log(G)/lam: GM is *reconstructed in
    VMEM* from the already-resident G tile instead of being materialized in
    HBM — halving both the solver's HBM reads and the nnz-sized precompute
    footprint (G==0 pad entries are guarded to 0).

``sinkhorn_fused_all_batched``
    The multi-query engine kernel (:mod:`repro.core.index`): identical
    per-document schedule, with the grid extended by a leading query
    dimension. A bucket of Q shape-padded queries shares one ``val`` tile
    stream and one compiled executable, so per-query dispatch and
    recompilation cost is amortized across the batch.

Layout note (paper: "data could be transposed on the fly to ensure
unit-stride data accesses"): G is laid out (v_r, N, L) so both reductions —
over k (sublane) for SDDMM and over l (lane) for SpMM — are unit-stride in
VMEM; no transposes are materialized.

Padding contract (see ops.py): padded query rows carry G == 0 and padded
doc slots carry val == 0; the ``where`` guards make both inert, so kernel
results on padded problems equal the unpadded oracle exactly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# single source of truth for the GM = -G*log(G)/lam rebuild and the
# adaptive-exit machinery; pure jnp/lax, so they trace inside Pallas
# kernel bodies too
from repro.core.sinkhorn_sparse import (adaptive_loop, marginal_residual,
                                        reconstruct_gm)


def _safe_inv(x):
    return jnp.where(x > 0, 1.0 / jnp.where(x > 0, x, 1.0), 0.0)


def _step_kernel(g_ref, gor_ref, val_ref, x_ref, xout_ref):
    g = g_ref[...]                        # (v_r, bn, L)
    gor = gor_ref[...]                    # (v_r, bn, L)
    val = val_ref[...]                    # (bn, L)
    x = x_ref[...]                        # (v_r, bn)
    u = _safe_inv(x)
    t = jnp.sum(g * u[:, :, None], axis=0)             # SDDMM   (bn, L)
    w = val * _safe_inv(t)                             # sparse selection
    xout_ref[...] = jnp.sum(gor * w[None, :, :], axis=2)  # SpMM  (v_r, bn)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def sddmm_spmm_step(g: jax.Array, g_over_r: jax.Array, val: jax.Array,
                    x: jax.Array, block_n: int = 128,
                    interpret: bool = False) -> jax.Array:
    """One fused SDDMM_SpMM Sinkhorn iteration. g, g_over_r: (v_r, N, L);
    val: (N, L); x: (v_r, N) -> new x (v_r, N)."""
    v_r, n, length = g.shape
    assert n % block_n == 0, (n, block_n)
    grid = (n // block_n,)
    g_spec = pl.BlockSpec((v_r, block_n, length), lambda i: (0, i, 0))
    return pl.pallas_call(
        _step_kernel,
        grid=grid,
        in_specs=[g_spec, g_spec,
                  pl.BlockSpec((block_n, length), lambda i: (i, 0)),
                  pl.BlockSpec((v_r, block_n), lambda i: (0, i))],
        out_specs=pl.BlockSpec((v_r, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((v_r, n), g.dtype),
        interpret=interpret,
    )(g, g_over_r, val, x)


def _sddmm_with(g, u):
    """t[j, l] = sum_k g[k, j, l] u[k, j]: (v_r, bn, L) x (v_r, bn)."""
    return jnp.sum(g * u[:, :, None], axis=0)


def _solve_block(g, val, r, n_iter: int, lam: float, tol=None,
                 check_every: int = 4, gemm: str = "fp32",
                 log_domain: bool = False, resmask=None):
    """Shared solver body: one (v_r, bn, L) G tile resident in VMEM.

    g (v_r, bn, L); val (bn, L); r (v_r, 1). Returns (wmd (1, bn), iters).
    Every array value stays at rank 2 or more (doc vectors are (1, bn)
    rows or (bn, 1) columns): Mosaic lays out rank-1 vectors poorly and
    its compiler aborts on some of them.

    ``tol`` switches the fixed ``fori_loop`` to a ``lax.while_loop`` with
    a residual epilogue: the doc-marginal residual ``max|val/t - w_prev|``
    (relative to each doc's own marginal scale, live slots only) is
    checked every ``check_every`` iterations and each grid block exits
    independently — inert pad blocks (w == 0 throughout) exit at the
    first check. ``gemm="bf16"`` runs both reductions with bf16 operands
    and fp32 accumulation. ``log_domain=True`` takes ``g`` as
    UNexponentiated log K (pad rows -inf), column-stabilizes it in VMEM,
    and adds the exact shift correction to the distance line.

    ``resmask`` (bn, 1) scopes the exit test to the CALLER'S candidate
    docs (per-query residual scoping on the kernel path: in the
    batched kernel each grid block holds exactly one query's rows, so a
    block whose scope excludes its far docs exits — freezing that query's
    rows — as soon as the docs the query actually needs are stationary).
    Masked-out docs keep iterating while the block is live but cannot
    hold its exit open; a block with an empty scope exits at the first
    check like a pad block.
    """
    shift = None
    if log_domain:
        shift = jnp.max(g, axis=0)                     # (bn, L)
        shift = jnp.where(jnp.isfinite(shift), shift, 0.0)
        g = jnp.where(jnp.isfinite(g), jnp.exp(g - shift[None]), 0.0)
    gor = g * _safe_inv(r)[:, :, None]    # padded rows: r inv -> 0 is fine,
    # but r pad is 1.0 by contract; g pad rows are 0 so gor pad rows are 0.
    v_r = g.shape[0]
    bn = g.shape[1]
    live = (val > 0).astype(g.dtype)
    rowmask = jnp.sum(jnp.sum(jnp.abs(g), axis=2), axis=1,
                      keepdims=True) > 0                      # (v_r, 1)
    n_rows = jnp.sum(rowmask.astype(g.dtype), axis=0, keepdims=True)
    x0 = jnp.where(rowmask, 1.0 / n_rows, 0.0)                # (v_r, 1)
    x = jnp.broadcast_to(x0, (v_r, bn)).astype(g.dtype)

    # bf16 policy = bf16-ROUNDED OPERANDS with fp32 products/accumulation
    # (cast through bf16, multiply in fp32 — matching the einsum paths'
    # preferred_element_type semantics; rounding each product to bf16
    # would drift further for long docs)
    gd = jnp.bfloat16 if gemm == "bf16" else None
    gb = g if gd is None else g.astype(gd).astype(jnp.float32)
    gorb = gor if gd is None else gor.astype(gd).astype(jnp.float32)

    def _rnd(a):
        return a if gd is None else a.astype(gd).astype(jnp.float32)

    def _sddmm(u):
        return _sddmm_with(gb, _rnd(u))

    def _spmm(w):
        return jnp.sum(gorb * _rnd(w)[None, :, :], axis=2)

    def one(x):
        u = _safe_inv(x)
        t = _sddmm(u)
        w = val * _safe_inv(t) * live
        return _spmm(w), w

    resm = live > 0
    if resmask is not None:
        resm = resm & (resmask > 0)
    if tol is None:
        x = jax.lax.fori_loop(0, n_iter, lambda _, x: one(x)[0], x)
        iters = jnp.asarray(n_iter, jnp.int32)
    else:
        x, iters = adaptive_loop(
            one, lambda w, wp: marginal_residual(w, wp, resm),
            x, n_iter, tol, check_every, use_fori=True)

    u = _safe_inv(x)
    t = _sddmm(u)
    w = val * _safe_inv(t) * live
    gm = reconstruct_gm(g, lam)           # in VMEM; never touches HBM
    # final line: wmd[j] = sum_l w[j,l] * sum_k u[k,j] GM[k,j,l] — the
    # k-sum first (elementwise across vregs), then one lane reduce
    pw = _sddmm_with(gm, u) * w                               # (bn, L)
    if log_domain:
        # exact rescale correction (t*w == val on live slots)
        pw = pw - shift * val / lam
    # the lane reduce over a unit leading axis yields the (1, bn) row
    return jnp.sum(pw[None, :, :], axis=2), iters


def _fused_kernel(g_ref, val_ref, r_ref, *refs, n_iter: int,
                  lam: float, tol, check_every: int, gemm: str,
                  log_domain: bool, with_resmask: bool):
    if with_resmask:
        rm_ref, wmd_ref, it_ref = refs
        rm = rm_ref[0]
    else:
        (wmd_ref, it_ref), rm = refs, None
    wmd, iters = _solve_block(g_ref[0], val_ref[...], r_ref[0], n_iter, lam,
                              tol, check_every, gemm, log_domain,
                              resmask=rm)
    wmd_ref[0] = wmd
    it_ref[...] = jnp.full(it_ref.shape, iters, jnp.int32)


def _compiler_params(block_bytes: int):
    """Scoped-VMEM budget for one grid step: the double-buffered G block
    plus the solver body's G-sized temporaries (G/r, the broadcast
    products, the rebuilt GM), with headroom, inside v5e's 128 MiB VMEM.
    The compiler's default scoped limit (16 MiB) is too small for the
    adaptive loop over a (64, 128, 128) fp32 tile, which needs ~24 MiB."""
    return pltpu.CompilerParams(
        vmem_limit_bytes=int(min(max(8 * block_bytes, 32 << 20), 100 << 20)))


@functools.partial(jax.jit,
                   static_argnames=("lam", "n_iter", "block_n", "interpret",
                                    "tol", "check_every", "gemm",
                                    "log_domain"))
def sinkhorn_fused_all(g: jax.Array, val: jax.Array, r: jax.Array, lam: float,
                       n_iter: int, block_n: int = 128,
                       interpret: bool = False, tol=None,
                       check_every: int = 4, gemm: str = "fp32",
                       log_domain: bool = False, resmask=None):
    """Whole Sinkhorn solve + WMD for all docs; one HBM pass over G.

    g: (v_r, N, L); val: (N, L); r: (v_r,) with padded rows == 1.0 and
    padded G rows == 0 (or -inf when ``log_domain`` — ``g`` then holds
    log K); lam: the K = exp(-lam*M) strength (static; needed to
    reconstruct GM in VMEM). Returns (wmd (N,), iters (N // block_n,)) —
    realized iteration count per doc block (== ``n_iter`` for the fixed
    loop; see :func:`_solve_block` for the adaptive/precision knobs).
    ``resmask`` (N,) float/bool scopes each block's adaptive exit to the
    caller's candidate docs (ignored without ``tol``). This is
    the batched solver at Q == 1.
    """
    wmd, iters = sinkhorn_fused_all_batched(
        g[None], val, r[None], lam, n_iter, block_n=block_n,
        interpret=interpret, tol=tol, check_every=check_every, gemm=gemm,
        log_domain=log_domain,
        resmask=None if resmask is None else jnp.asarray(resmask)[None])
    return wmd[0], iters[0]


@functools.partial(jax.jit,
                   static_argnames=("lam", "n_iter", "block_n", "interpret",
                                    "tol", "check_every", "gemm",
                                    "log_domain"))
def sinkhorn_fused_all_batched(g: jax.Array, val: jax.Array, r: jax.Array,
                               lam: float, n_iter: int, block_n: int = 128,
                               interpret: bool = False, tol=None,
                               check_every: int = 4, gemm: str = "fp32",
                               log_domain: bool = False, resmask=None):
    """Batched solver: Q queries against one shared corpus in one launch.

    g: (Q, v_r, N, L) per-query gathered kernels (log K when
    ``log_domain``); val: (N, L) shared corpus frequencies; r: (Q, v_r)
    with the same padding contract as :func:`sinkhorn_fused_all` per query
    row. Returns (wmd (Q, N), iters (Q, N // block_n)) — each grid block
    records its own realized iteration count, and with ``tol`` set each
    block EXITS independently (per-block early exit; inert pad blocks exit
    at the first residual check).

    Per-query residual scoping (ISSUE 5): each grid block holds exactly
    one query's rows, so the per-block exit IS a per-query-row freeze —
    ``resmask`` (Q, N) narrows each query's exit test to its own
    candidate docs, letting a block stop burning iterations on far docs
    its ranking never reads (ignored without ``tol``).

    Grid is (Q, N // block_n): the doc axis varies fastest so each query's
    corpus sweep is contiguous; ``val`` blocks depend only on the doc index
    and are revisited per query (resident after the first pass on TPU).

    Mosaic takes a block whose last two dimensions are (8, 128)-aligned or
    span the array's own, so the per-block outputs and the mask carry unit
    axes: wmd is produced as (Q, 1, N), iters as (Q, N // block_n, 1, 1)
    and ``resmask`` is passed as (Q, N, 1).
    """
    q, v_r, n, length = g.shape
    assert n % block_n == 0, (n, block_n)
    nb = n // block_n
    with_resmask = resmask is not None and tol is not None
    in_specs = [pl.BlockSpec((1, v_r, block_n, length),
                             lambda qi, i: (qi, 0, i, 0)),
                pl.BlockSpec((block_n, length), lambda qi, i: (i, 0)),
                pl.BlockSpec((1, v_r, 1), lambda qi, i: (qi, 0, 0))]
    args = [g, val, r.reshape(q, v_r, 1)]
    if with_resmask:
        in_specs.append(pl.BlockSpec((1, block_n, 1),
                                     lambda qi, i: (qi, i, 0)))
        args.append(jnp.asarray(resmask, g.dtype).reshape(q, n, 1))
    wmd, iters = pl.pallas_call(
        functools.partial(_fused_kernel, n_iter=n_iter, lam=lam,
                          tol=tol, check_every=check_every, gemm=gemm,
                          log_domain=log_domain, with_resmask=with_resmask),
        grid=(q, nb),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, 1, block_n), lambda qi, i: (qi, 0, i)),
                   pl.BlockSpec((1, 1, 1, 1), lambda qi, i: (qi, i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((q, 1, n), g.dtype),
                   jax.ShapeDtypeStruct((q, nb, 1, 1), jnp.int32)],
        compiler_params=_compiler_params(
            v_r * block_n * length * g.dtype.itemsize),
        interpret=interpret,
    )(*args)
    return wmd.reshape(q, n), iters.reshape(q, nb)
