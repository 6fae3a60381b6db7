"""jit'd user-facing wrappers around the Pallas kernels.

Handles TPU-alignment padding (the kernels' shape contract); the kernel
path's full WMD pipeline is :func:`repro.core.wmd.one_to_many` with
``impl="kernel"``.

Off the TPU the kernels execute with ``interpret=True``; on a TPU the same
call sites compile to Mosaic. :func:`resolve_interpret` picks the mode from
the platform at call time, and never interprets on a TPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import cdist_exp as _cdist_exp
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
