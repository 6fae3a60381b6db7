"""Pallas TPU kernels for the fused SDDMM_SpMM Sinkhorn iteration (paper §4).

Two kernels, in increasing fusion depth:

``sddmm_spmm_step``
    One Sinkhorn iteration: SDDMM (t = sum_k G u), sparse selection
    (w = val/t), SpMM (x' = sum_l (G/r) w) — the paper's Fig. 4 kernel in ELL
    form. G streams HBM->VMEM once per call; the intermediate ``w`` lives
    only in VREGs (that is the paper's fusion: "output values from SDDMM can
    be fed directly to the SpMM and would not need to be stored in memory").

``sinkhorn_resident``
    Beyond-paper: the ENTIRE solver (all iterations + the final distance
    line) for a block of documents with the G tile *resident in VMEM*,
    over a grid of (query, doc block). The paper's appendix notes the
    kernel remains memory-bound without tiling ("if we assume that all
    matrices can be loaded from cache, the runtime ... can be improved
    further"); on TPU the tile fits VMEM, so HBM traffic drops from (2
    reads of G per iteration) to (1 read of G total). This is the TPU
    analogue of the adaptive-sparse-tiling improvement the paper cites as
    future work [5], and the fixed-iteration solve of
    :class:`repro.core.index.WmdEngine` on a TPU.

    The distance line needs GM = (K*M) gathered at the doc words, but since
    K = exp(-lam*M) we have GM = -G*log(G)/lam: GM is *reconstructed in
    VMEM* (:func:`reconstruct_gm`) from the already-resident G tile instead
    of being materialized in HBM (G==0 pad entries are guarded to 0).

Tile order (paper: "data could be transposed on the fly to ensure
unit-stride data accesses"): the resident tile is (L, B, block_n), the
docs on the lanes, so the SDDMM's k-sum runs over the sublanes and the
SpMM's l-sum over the leading axis; no transpose is materialized.

Padding contract (see ops.py): padded query rows carry G == 0 and padded
doc slots carry val == 0; the ``where`` guards make both inert, so kernel
results on padded problems equal the unpadded oracle exactly.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _safe_inv(x):
    return jnp.where(x > 0, 1.0 / jnp.where(x > 0, x, 1.0), 0.0)


def reconstruct_gm(G: jax.Array, lam) -> jax.Array:
    """(K*M) gathered == -G*log(G)/lam; G == 0 entries (padding or exp
    underflow) map to 0, matching the materialized gather. Pure jnp, so
    it traces inside the resident kernel's body as well as in XLA."""
    safe = jnp.where(G > 0, G, 1.0)
    return jnp.where(G > 0, -G * jnp.log(safe), 0.0) / lam


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


def _solve_block(g, val, r, rowmask, n_iter: int, lam: float,
                 gemm: str = "fp32", log_domain: bool = False):
    """Solver body: one query's (L, B, bn) G tile resident in VMEM.

    val (L, 1, bn) holds the doc words' frequencies; r and ``rowmask``
    (1, B, 1) the query's marginal and its live words. Vectors keep the
    tile's rank, so every broadcast and reduction is along one axis: the
    k-sum over axis 1 gives t-shaped (L, 1, bn) values, the l-sum over
    axis 0 x-shaped (1, B, bn) ones. Returns wmd (1, bn). Every array
    value stays at rank 2 or more: Mosaic lays out rank-1 vectors poorly
    and its compiler aborts on some of them.

    ``gemm="bf16"`` runs both reductions with bf16 operands and fp32
    accumulation. ``log_domain=True`` takes ``g`` as UNexponentiated log
    K (pad rows -inf), column-stabilizes it in VMEM, and adds the exact
    shift correction to the distance line.

    ``rowmask`` (nonzero on the query's live words) seeds x as the
    einsum path does. In the linear domain a live doc word whose K
    column is 0 for every query word (t == 0: exp(-lam*M) underflowed)
    makes its doc's distance NaN, as the einsum path's raw ``val / t``
    does, so the engine raises its underflow error. Only the caller's
    mask tells a live query whose K underflowed over the whole block
    from an all-pad filler query, which stays inert.
    """
    shift = None
    if log_domain:
        shift = jnp.max(g, axis=1, keepdims=True)
        shift = jnp.where(jnp.isfinite(shift), shift, 0.0)
        g = jnp.where(jnp.isfinite(g), jnp.exp(g - shift), 0.0)
    # r pad rows are 1.0 by contract and their G rows 0, so they stay 0
    rinv = _safe_inv(r)
    live = (val > 0).astype(g.dtype)
    rowmask = rowmask > 0
    n_rows = jnp.sum(rowmask.astype(g.dtype), axis=1, keepdims=True)
    x0 = jnp.where(rowmask, 1.0 / n_rows, 0.0)
    x = jnp.broadcast_to(x0, (1,) + g.shape[1:]).astype(g.dtype)

    # bf16 policy = bf16-ROUNDED OPERANDS with fp32 products/accumulation
    # (cast through bf16, multiply in fp32 — matching the einsum paths'
    # preferred_element_type semantics; rounding each product to bf16
    # would drift further for long docs)
    gd = jnp.bfloat16 if gemm == "bf16" else None
    gb = g if gd is None else g.astype(gd).astype(jnp.float32)

    def _rnd(a):
        return a if gd is None else a.astype(gd).astype(jnp.float32)

    def _sddmm(gt, u):
        return jnp.sum(gt * u, axis=1, keepdims=True)

    def _spmm(w):
        # diag(1/r) is applied after the l-sum, as the einsum path does,
        # so no G/r tile is kept
        return jnp.sum(gb * _rnd(w), axis=0, keepdims=True) * rinv

    def one(_, x):
        t = _sddmm(gb, _rnd(_safe_inv(x)))
        return _spmm(val * _safe_inv(t) * live)

    x = jax.lax.fori_loop(0, n_iter, one, x)

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
    wmd = jnp.sum(pw, axis=0)
    if not log_domain:
        # the guard is applied to the (1, bn) row: Mosaic cannot broadcast
        # the block's live-row count over both axes of an (L, 1, bn) tile
        dead = jnp.sum(((live > 0) & (t <= 0)).astype(g.dtype), axis=0) > 0
        wmd = jnp.where(dead & (n_rows.reshape(1, 1) > 0), jnp.nan, wmd)
    return wmd


def _fused_kernel(g_ref, val_ref, r_ref, mask_ref, wmd_ref, *,
                     n_iter: int, lam: float, gemm: str, log_domain: bool):
    rowmask = mask_ref[0]
    wmd_ref[0] = _solve_block(g_ref[0], val_ref[...], r_ref[0], rowmask,
                              n_iter, lam, gemm, log_domain)


def _compiler_params(block_bytes: int):
    """Scoped-VMEM budget for one grid step: the double-buffered G block
    plus the solver body's G-sized temporaries (the broadcast products,
    the rebuilt GM), with headroom, inside v5e's 128 MiB VMEM.
    The compiler's default scoped limit (16 MiB) is too small for a
    (64, 128, 128) fp32 tile's temporaries."""
    return pltpu.CompilerParams(
        vmem_limit_bytes=int(min(max(8 * block_bytes, 32 << 20), 100 << 20)))


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

    The grid is (Q, N_pad // block_n), the doc blocks fastest; ``val``'s
    blocks depend only on the doc block. Each (L, B, block_n) tile is
    read from HBM once and stays in VMEM for every iteration and the
    distance line (GM rebuilt from G in VMEM). The docs lie on the lanes
    and the query words on the sublanes, which is the order the TPU lays
    out the gather's output in, so the tile reaches the kernel without a
    copy. Both reductions stay off the lanes: the SDDMM's k-sum runs
    over the sublanes and the SpMM's l-sum over the leading axis.

    Mosaic takes a block whose last two dimensions are (8, 128)-aligned
    or span the array's own, so the small operands and the output carry
    unit axes: r and mask arrive as (Q, 1, B, 1), val as (L, 1, N_pad),
    and wmd leaves as (Q, 1, N_pad).
    """
    q, length, b, n_pad = g.shape
    n = val.shape[0]
    assert n_pad % block_n == 0, (n_pad, block_n)
    valt = jnp.pad(val, ((0, n_pad - n), (0, 0))).T[:, None]  # (L, 1, N_pad)
    per_query = pl.BlockSpec((1, 1, b, 1), lambda qi, i: (qi, 0, 0, 0))
    g_block = (1, length, b, block_n)
    wmd = pl.pallas_call(
        functools.partial(_fused_kernel, n_iter=n_iter, lam=lam,
                          gemm=gemm, log_domain=log_domain),
        grid=(q, n_pad // block_n),
        in_specs=[pl.BlockSpec(g_block, lambda qi, i: (qi, 0, 0, i)),
                  pl.BlockSpec((length, 1, block_n), lambda qi, i: (0, 0, i)),
                  per_query, per_query],
        out_specs=pl.BlockSpec((1, 1, block_n), lambda qi, i: (qi, 0, i)),
        out_shape=jax.ShapeDtypeStruct((q, 1, n_pad), g.dtype),
        compiler_params=_compiler_params(
            math.prod(g_block) * g.dtype.itemsize),
        interpret=interpret,
    )(g, valt, r.reshape(q, 1, b, 1),
      jnp.asarray(mask, g.dtype).reshape(q, 1, b, 1))
    return wmd.reshape(q, n_pad)[:, :n]
