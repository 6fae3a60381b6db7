"""jit'd user-facing wrappers around the Pallas kernels.

Handles TPU-alignment padding (the kernels' shape contract) and exposes
``sinkhorn_wmd_kernel`` — the full WMD pipeline on the kernel path, result
bit-identical (up to fp reassociation) to ``repro.core`` oracles.

Off the TPU the kernels execute with ``interpret=True``; on a TPU the same
call sites compile to Mosaic. :func:`resolve_interpret` picks the mode from
the platform at call time, and never interprets on a TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.sparse import PaddedDocs
from . import cdist_exp as _cdist_exp
from . import rwmd as _rwmd
from . import sddmm_spmm as _sddmm_spmm

def resolve_interpret(interpret: bool | None = None) -> bool:
    """Pallas interpret mode for a call: ``None`` follows the platform
    (interpret everywhere but a TPU). On a TPU the kernels always compile
    to Mosaic, so an explicit ``True`` there is refused rather than
    silently run through the interpreter."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("Pallas interpret mode is not used on a TPU; "
                         "pass interpret=None to compile to Mosaic")
    return bool(interpret)


def pad_to(x: jax.Array, axis: int, multiple: int, value=0.0) -> jax.Array:
    size = x.shape[axis]
    target = -(-size // multiple) * multiple
    if target == size:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - size)
    return jnp.pad(x, pads, constant_values=value)


def cdist_exp(a, b, r, lam: float, block_v: int = 512,
              interpret: bool | None = None, k_only: bool = False,
              gemm: str = "fp32", log_k: bool = False):
    """Fused (M, K, K_over_r) with auto-padding. a (v_r, w), b (V, w).
    ``k_only=True`` returns just K and skips the two dead HBM stores;
    ``gemm``/``log_k`` plumb the SolvePrecision policy (bf16 MXU operands
    / unexponentiated log K for the log-domain solve)."""
    interpret = resolve_interpret(interpret)
    v_r, w = a.shape
    v = b.shape[0]
    ap = pad_to(pad_to(a, 1, 128), 0, 8)
    bp = pad_to(pad_to(b, 1, 128), 0, block_v)
    rp = pad_to(r, 0, 8, value=1.0)          # pad rows divide by 1
    if k_only:
        k = _cdist_exp.cdist_exp(ap, bp, rp, lam, block_v=block_v,
                                 interpret=interpret, k_only=True,
                                 gemm=gemm, log_k=log_k)
        return k[:v_r, :v]
    m, k, kr = _cdist_exp.cdist_exp(ap, bp, rp, lam,
                                    block_v=block_v, interpret=interpret)
    return m[:v_r, :v], k[:v_r, :v], kr[:v_r, :v]


def rwmd_min_cdist(a, mask, b, block_v: int = 512,
                   interpret: bool | None = None):
    """Masked min-over-support cdist with auto-padding (the RWMD prune
    stage). a (Q, B, w), mask (Q, B), b (V, w) -> minM (Q, V)."""
    interpret = resolve_interpret(interpret)
    ap = pad_to(pad_to(a, 2, 128), 1, 8)
    maskp = pad_to(mask, 1, 8)               # pad support rows masked out
    v = b.shape[0]
    bp = pad_to(pad_to(b, 1, 128), 0, block_v)
    minm = _rwmd.rwmd_min_cdist(ap, maskp, bp, block_v=block_v,
                                interpret=interpret)
    return minm[:, :v]


def sddmm_spmm_step(g, g_over_r, val, x, block_n: int = 128,
                    interpret: bool | None = None):
    interpret = resolve_interpret(interpret)
    v_r, n, length = g.shape
    gp = pad_to(pad_to(pad_to(g, 2, 128), 1, block_n), 0, 8)
    gorp = pad_to(pad_to(pad_to(g_over_r, 2, 128), 1, block_n), 0, 8)
    valp = pad_to(pad_to(val, 1, 128), 0, block_n)
    xp = pad_to(pad_to(x, 1, block_n), 0, 8)
    out = _sddmm_spmm.sddmm_spmm_step(gp, gorp, valp, xp, block_n=block_n,
                                      interpret=interpret)
    return out[:v_r, :n]


def sinkhorn_fused_all(g, val, r, lam: float, n_iter: int, block_n: int = 128,
                       interpret: bool | None = None, tol=None,
                       check_every: int = 4, gemm: str = "fp32",
                       log_domain: bool = False, resmask=None,
                       with_iters: bool = False):
    """Fused solver with auto-padding; ``with_iters=True`` also returns the
    per-block realized iteration counts. ``log_domain`` pads query rows
    with -inf (a 0 would be a VALID log-K entry — distance 0 — and the
    pad row would stop being inert). ``resmask`` (N,) scopes each block's
    adaptive exit test to the caller's candidate docs (pad docs are
    masked out, matching the val padding)."""
    interpret = resolve_interpret(interpret)
    v_r, n, length = g.shape
    row_pad = -jnp.inf if log_domain else 0.0
    gp = pad_to(pad_to(pad_to(g, 2, 128), 1, block_n), 0, 8, value=row_pad)
    valp = pad_to(pad_to(val, 1, 128), 0, block_n)
    rp = pad_to(r, 0, 8, value=1.0)
    rmp = None
    if resmask is not None:
        rmp = pad_to(jnp.asarray(resmask, gp.dtype), 0, block_n)
    wmd, iters = _sddmm_spmm.sinkhorn_fused_all(
        gp, valp, rp, lam, n_iter, block_n=block_n, interpret=interpret,
        tol=tol, check_every=check_every, gemm=gemm, log_domain=log_domain,
        resmask=rmp)
    return (wmd[:n], iters) if with_iters else wmd[:n]


def sinkhorn_fused_all_batched(g, val, r, lam: float, n_iter: int,
                               block_n: int = 128,
                               interpret: bool | None = None, tol=None,
                               check_every: int = 4, gemm: str = "fp32",
                               log_domain: bool = False, resmask=None,
                               with_iters: bool = False, mask=None):
    """Batched fused solver with auto-padding. g (Q, v_r, N, L); val (N, L);
    r (Q, v_r) -> wmd (Q, N). Padded query rows carry r == 1, G == 0
    (G == -inf under ``log_domain`` — see :func:`sinkhorn_fused_all`) and
    ``mask`` (Q, v_r) == 0 when given.
    ``with_iters=True`` also returns the (Q, N-blocks) realized iteration
    counts (per-block early exit under ``tol``). ``resmask`` (Q, N)
    scopes each query's exit test to its own candidate docs — each grid
    block holds one query's rows, so the per-block exit is a
    per-query-row freeze (ISSUE 5)."""
    interpret = resolve_interpret(interpret)
    q, v_r, n, length = g.shape
    row_pad = -jnp.inf if log_domain else 0.0
    gp = pad_to(pad_to(pad_to(g, 3, 128), 2, block_n), 1, 8, value=row_pad)
    valp = pad_to(pad_to(val, 1, 128), 0, block_n)
    rp = pad_to(r, 1, 8, value=1.0)
    rmp = None
    if resmask is not None:
        rmp = pad_to(jnp.asarray(resmask, gp.dtype), 1, block_n)
    mp = None if mask is None else pad_to(jnp.asarray(mask, gp.dtype), 1, 8)
    wmd, iters = _sddmm_spmm.sinkhorn_fused_all_batched(
        gp, valp, rp, lam, n_iter, block_n=block_n, interpret=interpret,
        tol=tol, check_every=check_every, gemm=gemm, log_domain=log_domain,
        resmask=rmp, mask=mp)
    return (wmd[:, :n], iters) if with_iters else wmd[:, :n]


def sinkhorn_resident(g, val, r, mask, lam: float, n_iter: int,
                      block_n: int = 128, interpret: bool | None = None,
                      gemm: str = "fp32", log_domain: bool = False):
    """Resident fixed-iteration solve over the engine's (Q, L, B, N_pad)
    gathered tile; val (N, L), r and mask (Q, B) -> wmd (Q, N). The
    caller's gather already pads the docs to whole blocks, so nothing is
    padded here."""
    return _sddmm_spmm.sinkhorn_resident(
        g, val, r, mask, lam, n_iter, block_n=block_n,
        interpret=resolve_interpret(interpret), gemm=gemm,
        log_domain=log_domain)


@functools.partial(jax.jit, static_argnames=("lam", "n_iter", "interpret",
                                             "tol", "check_every",
                                             "precision"))
def sinkhorn_wmd_kernel(r, vecs_sel, vecs, docs: PaddedDocs, lam: float,
                        n_iter: int, interpret: bool | None = None,
                        tol=None, check_every: int = 4, precision=None):
    """Full kernel-path WMD: cdist_exp -> gather (XLA) -> fused solver.

    The gather between the two kernels stays in XLA (TPU gather over the
    vocab axis); everything else runs in Pallas. GM is reconstructed from G
    inside the solver, so only one (v_r, N, L) array is ever materialized.

    ``tol``/``check_every`` select the convergence-adaptive loop;
    ``precision`` (a ``SolvePrecision`` or its string spelling) plumbs the
    bf16-GEMM and log-domain policies through ``cdist_exp``'s epilogue and
    the fused solver — under ``log_domain`` the kernel emits
    UNexponentiated log K, so no column can underflow at any lam.
    """
    from repro.core.sinkhorn_sparse import SolvePrecision
    precision = SolvePrecision.parse(precision)
    k = cdist_exp(vecs_sel, vecs, r, lam, interpret=interpret, k_only=True,
                  gemm=precision.gemm, log_k=precision.log_domain)
    g = jnp.take(k, docs.idx, axis=1)          # (v_r, N, L)
    return sinkhorn_fused_all(g, docs.val, r, lam, n_iter,
                              interpret=interpret, tol=tol,
                              check_every=check_every, gemm=precision.gemm,
                              log_domain=precision.log_domain)
