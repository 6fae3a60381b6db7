"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .precision import FP32_GEMM


def cdist_exp_ref(a, b, r, lam: float):
    """Oracle for kernels.cdist_exp: (M, K, K_over_r)."""
    a2 = jnp.sum(a * a, axis=1)[:, None]
    b2 = jnp.sum(b * b, axis=1)[None, :]
    ab = jnp.matmul(a, b.T, precision=FP32_GEMM)
    d2 = jnp.maximum(a2 + b2 - 2.0 * ab, 0.0)
    m = jnp.sqrt(d2)
    k = jnp.exp(-lam * m)
    return m, k, k / r[:, None]


def _safe_inv(x):
    return jnp.where(x > 0, 1.0 / jnp.where(x > 0, x, 1.0), 0.0)


def sddmm_spmm_step_ref(g, g_over_r, val, x):
    """Oracle for kernels.sddmm_spmm_step (one fused iteration)."""
    u = _safe_inv(x)
    t = jnp.einsum("knl,kn->nl", g, u)
    w = val * _safe_inv(t)
    return jnp.einsum("knl,nl->kn", g_over_r, w)


def sinkhorn_fused_all_materialized_ref(g, gm, val, r, n_iter: int):
    """Explicit-GM oracle (the pre-reconstruction formulation): used to prove
    the in-VMEM GM reconstruction equals the materialized gather."""
    rowmask = jnp.sum(jnp.abs(g), axis=(1, 2)) > 0
    v_r_true = jnp.sum(rowmask.astype(g.dtype))
    x0 = jnp.where(rowmask, 1.0 / v_r_true, 0.0)
    x = jnp.broadcast_to(x0[:, None], (g.shape[0], g.shape[1]))
    gor = g * _safe_inv(r)[:, None, None]
    live = (val > 0).astype(g.dtype)

    def body(_, x):
        u = _safe_inv(x)
        t = jnp.einsum("knl,kn->nl", g, u)
        w = val * _safe_inv(t) * live
        return jnp.einsum("knl,nl->kn", gor, w)

    x = jax.lax.fori_loop(0, n_iter, body, x)
    u = _safe_inv(x)
    t = jnp.einsum("knl,kn->nl", g, u)
    w = val * _safe_inv(t) * live
    return jnp.einsum("kn,knl,nl->n", u, gm, w)


def reconstruct_gm_ref(g, lam: float):
    """Oracle for kernels.sddmm_spmm.reconstruct_gm: GM = -G*log(G)/lam."""
    safe = jnp.where(g > 0, g, 1.0)
    return jnp.where(g > 0, -g * jnp.log(safe) / lam, 0.0)


def sinkhorn_fused_all_ref(g, val, r, lam: float, n_iter: int):
    """Oracle for one query of kernels.sinkhorn_resident (full solve +
    distance; GM reconstructed from G exactly as the kernel does). g is
    the query's (v_r, N, L) tile, val (N, L), r (v_r,)."""
    return sinkhorn_fused_all_materialized_ref(g, reconstruct_gm_ref(g, lam),
                                               val, r, n_iter)
