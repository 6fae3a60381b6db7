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
    The multi-query kernel of the engine's ``impl="kernel"``: identical
    per-document schedule, with the grid extended by a leading query
    dimension. A bucket of Q shape-padded queries shares one ``val`` tile
    stream and one compiled executable, so per-query dispatch and
    recompilation cost is amortized across the batch.

``sinkhorn_resident``
    The same grid and the same solver body over the tile the engine's
    default path gathers on a TPU: the fixed-iteration solve of
    :class:`repro.core.index.WmdEngine` there.

Layout note (paper: "data could be transposed on the fly to ensure
unit-stride data accesses"): one body, :func:`_solve_block`, serves two
tile orders (:class:`Layout`). ``sinkhorn_fused_all_batched`` takes
(v_r, N, L) per query, the SDDMM's k-sum over the leading axis and the
SpMM's l-sum over the lanes; ``sinkhorn_resident`` takes (L, v_r, N),
the docs on the lanes, the k-sum over the sublanes and the l-sum over
the leading axis. No transposes are materialized in either.

Padding contract (see ops.py): padded query rows carry G == 0 and padded
doc slots carry val == 0; the ``where`` guards make both inert, so kernel
results on padded problems equal the unpadded oracle exactly.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

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


class Layout(NamedTuple):
    """Axis order of one query's G tile in VMEM: ``k`` is the query-word
    axis, ``l`` the doc-word axis, and the remaining axis holds the docs.

    Doc vectors keep the tile's rank, so every broadcast and reduction is
    along one axis of the tile: x-shaped values (x, u, r) are the tile
    summed over ``l`` with the axis kept, t-shaped ones (t, w, val, the
    residual mask) the tile summed over ``k``."""
    k: int
    l: int

    @property
    def j(self) -> int:
        return 3 - self.k - self.l


KJL = Layout(k=0, l=2)   # (v_r, bn, L): the kernel impl's tile
LKJ = Layout(k=1, l=0)   # (L, B, bn): the engine's tile, docs on the lanes


def _solve_block(g, val, r, n_iter: int, lam: float, tol=None,
                 check_every: int = 4, gemm: str = "fp32",
                 log_domain: bool = False, resmask=None,
                 layout: Layout = KJL, rowmask=None):
    """Shared solver body: one G tile resident in VMEM.

    g is one query's tile in ``layout`` order; val and ``resmask`` are
    t-shaped, r and ``rowmask`` x-shaped (see :class:`Layout`). Returns
    (wmd, iters):
    wmd (1, bn) is the tile summed over k and l. Every array value stays
    at rank 2 or more: Mosaic lays out rank-1 vectors poorly and its
    compiler aborts on some of them.

    ``tol`` switches the fixed ``fori_loop`` to a ``lax.while_loop`` with
    a residual epilogue: the doc-marginal residual ``max|val/t - w_prev|``
    (relative to each doc's own marginal scale, live slots only) is
    checked every ``check_every`` iterations and each grid block exits
    independently — inert pad blocks (w == 0 throughout) exit at the
    first check. The residual reads the slots on the last axis, so
    ``tol`` needs :data:`KJL`. ``gemm="bf16"`` runs both reductions with
    bf16 operands and fp32 accumulation. ``log_domain=True`` takes ``g``
    as UNexponentiated log K (pad rows -inf), column-stabilizes it in
    VMEM, and adds the exact shift correction to the distance line.

    ``resmask`` scopes the exit test to the CALLER'S candidate
    docs (per-query residual scoping on the kernel path: in the
    batched kernel each grid block holds exactly one query's rows, so a
    block whose scope excludes its far docs exits — freezing that query's
    rows — as soon as the docs the query actually needs are stationary).
    Masked-out docs keep iterating while the block is live but cannot
    hold its exit open; a block with an empty scope exits at the first
    check like a pad block.

    ``rowmask`` (nonzero on the query's live words) seeds x as the
    einsum path does; without it the live words are the rows of G that
    are nonzero somewhere in the block. In the linear domain a live doc
    word whose K column is 0 for every query word (t == 0: exp(-lam*M)
    underflowed) makes its doc's distance NaN, as the einsum path's raw
    ``val / t`` does, so the engine raises its underflow error. Only the
    caller's mask tells a live query whose K underflowed over the whole
    block from an all-pad filler query, which stays inert.
    """
    k, l = layout
    assert tol is None or layout == KJL, "the residual needs slots last"
    shift = None
    if log_domain:
        shift = jnp.max(g, axis=k, keepdims=True)
        shift = jnp.where(jnp.isfinite(shift), shift, 0.0)
        g = jnp.where(jnp.isfinite(g), jnp.exp(g - shift), 0.0)
    # r pad rows are 1.0 by contract and their G rows 0, so they stay 0
    rinv = _safe_inv(r)
    live = (val > 0).astype(g.dtype)
    if rowmask is None:
        rowmask = jnp.sum(jnp.sum(jnp.abs(g), axis=l, keepdims=True),
                          axis=layout.j, keepdims=True) > 0
    else:
        rowmask = rowmask > 0
    n_rows = jnp.sum(rowmask.astype(g.dtype), axis=k, keepdims=True)
    x0 = jnp.where(rowmask, 1.0 / n_rows, 0.0)
    xshape = g.shape[:l] + (1,) + g.shape[l + 1:]
    x = jnp.broadcast_to(x0, xshape).astype(g.dtype)

    # bf16 policy = bf16-ROUNDED OPERANDS with fp32 products/accumulation
    # (cast through bf16, multiply in fp32 — matching the einsum paths'
    # preferred_element_type semantics; rounding each product to bf16
    # would drift further for long docs)
    gd = jnp.bfloat16 if gemm == "bf16" else None
    gb = g if gd is None else g.astype(gd).astype(jnp.float32)

    def _rnd(a):
        return a if gd is None else a.astype(gd).astype(jnp.float32)

    def _sddmm(gt, u):
        return jnp.sum(gt * u, axis=k, keepdims=True)

    def _spmm(w):
        # diag(1/r) is applied after the l-sum, as the einsum path does,
        # so no G/r tile is kept
        return jnp.sum(gb * _rnd(w), axis=l, keepdims=True) * rinv

    def one(x):
        t = _sddmm(gb, _rnd(_safe_inv(x)))
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
    t = _sddmm(gb, _rnd(u))
    w = val * _safe_inv(t) * live
    gm = reconstruct_gm(g, lam)           # in VMEM; never touches HBM
    # final line: wmd[j] = sum_l w[j,l] * sum_k u[k,j] GM[k,j,l] — the
    # k-sum first, then the l-sum
    pw = _sddmm(gm, u) * w
    if log_domain:
        # exact rescale correction (t*w == val on live slots)
        pw = pw - shift * val / lam
    wmd = jnp.sum(pw, axis=l)
    if not log_domain:
        # the guard is applied to the (1, bn) row: Mosaic cannot broadcast
        # the block's live-row count over both axes of a (1, bn, L) tile
        dead = jnp.sum(((live > 0) & (t <= 0)).astype(g.dtype), axis=l) > 0
        wmd = jnp.where(dead & (n_rows.reshape(1, 1) > 0), jnp.nan, wmd)
    return wmd, iters


def _fused_kernel(g_ref, val_ref, r_ref, *refs, layout: Layout,
                  n_iter: int, lam: float, tol, check_every: int, gemm: str,
                  log_domain: bool, with_rowmask: bool, with_resmask: bool):
    *masks, wmd_ref, it_ref = refs
    rowmask = masks.pop(0)[0] if with_rowmask else None
    rm = masks.pop(0)[...] if with_resmask else None
    wmd, iters = _solve_block(g_ref[0], val_ref[...], r_ref[0], n_iter,
                              lam, tol, check_every, gemm, log_domain,
                              resmask=rm, layout=layout, rowmask=rowmask)
    wmd_ref[0] = wmd
    it_ref[...] = jnp.full(it_ref.shape, iters, jnp.int32)


def _doc_block(shape, doc_axis: int, block_n: int, per_query: bool):
    """BlockSpec of ``block_n`` docs along ``doc_axis`` (and one query
    along axis 0 when ``per_query``), whole along every other axis."""
    block = list(shape)
    block[doc_axis] = block_n
    if per_query:
        block[0] = 1

    def index(qi, i):
        at = [0] * len(shape)
        at[doc_axis] = i
        if per_query:
            at[0] = qi
        return tuple(at)
    return pl.BlockSpec(tuple(block), index)


def _compiler_params(block_bytes: int):
    """Scoped-VMEM budget for one grid step: the double-buffered G block
    plus the solver body's G-sized temporaries (the broadcast products,
    the rebuilt GM), with headroom, inside v5e's 128 MiB VMEM.
    The compiler's default scoped limit (16 MiB) is too small for the
    adaptive loop over a (64, 128, 128) fp32 tile, which needs ~24 MiB."""
    return pltpu.CompilerParams(
        vmem_limit_bytes=int(min(max(8 * block_bytes, 32 << 20), 100 << 20)))


def _fused_call(g, val, r, resmask, layout: Layout, *, lam: float,
                n_iter: int, block_n: int, interpret: bool, tol,
                check_every: int, gemm: str, log_domain: bool,
                rowmask=None):
    """The (Q, N // block_n) grid of :func:`_fused_kernel`: one query's
    block of ``block_n`` docs per step, its G tile resident in VMEM for
    the whole solve.

    g (Q, ...) holds each query's (3-axis) tile in ``layout`` order; val
    is the t-shaped doc plane, (1, N, L) under :data:`KJL` and
    (L, 1, N) under :data:`LKJ`; r and ``rowmask`` (Q, v_r), ``rowmask``
    or None; ``resmask`` (Q, N) or None. Returns (wmd (Q, N), iters
    (Q, N // block_n)).

    Mosaic takes a block whose last two dimensions are (8, 128)-aligned
    or span the array's own, so the small operands and outputs carry
    unit axes: r and ``rowmask`` arrive x-shaped, ``resmask`` t-shaped,
    wmd leaves as (Q, 1, N) and iters as (Q, N // block_n, 1, 1).
    """
    q, n = g.shape[0], g.shape[1 + layout.j]
    assert n % block_n == 0, (n, block_n)
    nb = n // block_n
    xshape = (q,) + tuple(-1 if ax == layout.k else 1 for ax in range(3))
    per_query = pl.BlockSpec((1,) + r.reshape(xshape).shape[1:],
                             lambda qi, i: (qi, 0, 0, 0))
    in_specs = [_doc_block(g.shape, 1 + layout.j, block_n, True),
                _doc_block(val.shape, layout.j, block_n, False), per_query]
    args = [g, val, r.reshape(xshape)]
    if rowmask is not None:
        in_specs.append(per_query)
        args.append(jnp.asarray(rowmask, g.dtype).reshape(xshape))
    with_resmask = resmask is not None and tol is not None
    if with_resmask:
        rm = jnp.asarray(resmask, g.dtype)
        rm = rm.reshape((q, n, 1) if layout.j == 1 else (q, 1, n))
        in_specs.append(_doc_block(rm.shape, layout.j, block_n, True))
        args.append(rm)
    wmd, iters = pl.pallas_call(
        functools.partial(_fused_kernel, layout=layout, n_iter=n_iter,
                          lam=lam, tol=tol, check_every=check_every,
                          gemm=gemm, log_domain=log_domain,
                          with_rowmask=rowmask is not None,
                          with_resmask=with_resmask),
        grid=(q, nb),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, 1, block_n), lambda qi, i: (qi, 0, i)),
                   pl.BlockSpec((1, 1, 1, 1), lambda qi, i: (qi, i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((q, 1, n), g.dtype),
                   jax.ShapeDtypeStruct((q, nb, 1, 1), jnp.int32)],
        compiler_params=_compiler_params(
            math.prod(in_specs[0].block_shape) * g.dtype.itemsize),
        interpret=interpret,
    )(*args)
    return wmd.reshape(q, n), iters.reshape(q, nb)


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
                               log_domain: bool = False, resmask=None,
                               mask=None):
    """Batched solver: Q queries against one shared corpus in one launch.

    g: (Q, v_r, N, L) per-query gathered kernels (log K when
    ``log_domain``); val: (N, L) shared corpus frequencies; r: (Q, v_r)
    with the same padding contract as :func:`sinkhorn_fused_all` per query
    row; ``mask`` (Q, v_r), nonzero on live query words, or None (see
    :func:`_solve_block`). Returns (wmd (Q, N), iters (Q, N // block_n)) — each grid block
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

    The tile is (v_r, block_n, L) per query: the SDDMM's k-sum runs over
    the leading axis and the SpMM's l-sum over the lanes.
    """
    return _fused_call(g, val[None], r, resmask, KJL, lam=lam,
                       n_iter=n_iter, block_n=block_n, interpret=interpret,
                       tol=tol, check_every=check_every, gemm=gemm,
                       log_domain=log_domain, rowmask=mask)


@functools.partial(jax.jit,
                   static_argnames=("lam", "n_iter", "block_n", "interpret",
                                    "gemm", "log_domain"))
def sinkhorn_resident(g: jax.Array, val: jax.Array, r: jax.Array,
                      mask: jax.Array, lam: float, n_iter: int,
                      block_n: int = 128,
                      interpret: bool = False, gemm: str = "fp32",
                      log_domain: bool = False) -> jax.Array:
    """Fixed-iteration batched solve over the engine's gathered tile.

    g: (Q, L, B, N_pad) — each query's K rows gathered at the doc words
    (log K under ``log_domain``), N_pad a multiple of ``block_n`` whose
    tail docs are inert; val: (N, L) with N <= N_pad; r: (Q, B), padded
    rows r == 1 and G == 0 (-inf); mask: (Q, B), nonzero on live query
    words, so that a live query whose K underflowed over a whole block
    still reads NaN there (see :func:`_solve_block`). Returns wmd (Q, N).

    Each (L, B, block_n) tile is read from HBM once and stays in VMEM
    for every iteration and the distance line (GM rebuilt from G in
    VMEM). The docs lie on the lanes and the query words on the
    sublanes, which is the order the TPU lays out the gather's output
    in, so the tile reaches the kernel without a copy. Both reductions
    stay off the lanes: the SDDMM's k-sum runs over the sublanes and the
    SpMM's l-sum over the leading axis.
    """
    n = val.shape[0]
    n_pad = g.shape[3]
    valt = jnp.pad(val, ((0, n_pad - n), (0, 0))).T[:, None]  # (L, 1, N_pad)
    wmd, _ = _fused_call(g, valt, r, None, LKJ, lam=lam, n_iter=n_iter,
                         block_n=block_n, interpret=interpret, tol=None,
                         check_every=1, gemm=gemm, log_domain=log_domain,
                         rowmask=mask)
    return wmd[:, :n]
