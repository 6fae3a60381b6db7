"""Pallas TPU kernel: fused GEMM-shaped Euclidean distance + exp + scale.

Paper §6: restructure ``cdist`` as a blocked matrix-multiplication-like
kernel and fuse the ``K = exp(-lam*M)`` and ``K_over_r = K / r`` follow-ups
so M, K, K_over_r are produced in ONE pass over the output tiles ("we use the
modified matrix-multiplication-like kernel to not only compute matrix M but
also K and K_over_r matrices at once"). On TPU this maps naturally:

  - the ``a @ b.T`` contraction runs on the MXU per (v_r, blockV) tile;
  - the sqrt/exp/divide epilogue runs on the VPU while the tile is still in
    VMEM/VREGs — the three outputs never round-trip HBM between stages;
  - ``b`` (the big V x w embedding matrix) is streamed tile-by-tile from HBM
    exactly once, which is the §6 bandwidth-reduction goal.

Grid: 1-D over V tiles. ``a`` (v_r x w, "tall-and-skinny" per the paper) and
``r`` stay resident in VMEM across the whole grid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .precision import FP32_GEMM


def _kernel(a_ref, b_ref, r_ref, *out_refs, lam: float, k_only: bool,
            gemm: str, log_k: bool):
    a = a_ref[...]                       # (v_r, w)   resident
    b = b_ref[...]                       # (bv, w)    streamed tile
    r = r_ref[...]                       # (v_r, 1)
    if gemm == "bf16":                   # bf16 operands, fp32 accumulation
        ab = jax.lax.dot_general(a.astype(jnp.bfloat16),
                                 b.astype(jnp.bfloat16),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    else:
        ab = jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                                 precision=FP32_GEMM,
                                 preferred_element_type=jnp.float32)  # MXU
    a2 = jnp.sum(a * a, axis=1, keepdims=True)        # (v_r, 1)
    b2 = jnp.sum(b * b, axis=1)[None, :]              # (1, bv)
    d2 = jnp.maximum(a2 + b2 - 2.0 * ab, 0.0)
    m = jnp.sqrt(d2)
    # log_k: emit UNexponentiated log K = -lam*M (the log-domain solve
    # stabilizes per gathered column, so exp never underflows a column)
    k = -lam * m if log_k else jnp.exp(-lam * m)
    if k_only:
        (k_ref,) = out_refs
        k_ref[...] = k
        return
    m_ref, k_ref, kr_ref = out_refs
    m_ref[...] = m
    k_ref[...] = k
    kr_ref[...] = k / r


@functools.partial(jax.jit,
                   static_argnames=("lam", "block_v", "interpret", "k_only",
                                    "gemm", "log_k"))
def cdist_exp(a: jax.Array, b: jax.Array, r: jax.Array, lam: float,
              block_v: int = 512, interpret: bool = False,
              k_only: bool = False, gemm: str = "fp32",
              log_k: bool = False):
    """Fused (M, K, K_over_r) for query embeddings ``a`` (v_r, w), vocabulary
    embeddings ``b`` (V, w), query frequencies ``r`` (v_r,).

    V must divide by ``block_v``; pad ``w``/``v_r`` via
    :func:`repro.kernels.ops.pad_to` (zero-padding embedding width is exact —
    zeros add nothing to the distance).

    ``k_only=True`` writes ONLY the K output (returned alone): consumers
    that reconstruct GM from G (the fused solver path) would otherwise pay
    HBM stores for two dead (v_r, V) buffers — Pallas outputs can't be
    dead-code-eliminated by XLA.

    ``gemm="bf16"`` runs the MXU contraction with bf16 operands and fp32
    accumulation; ``log_k=True`` (with ``k_only``) emits ``-lam*M``
    unexponentiated for the log-domain solve.
    """
    v_r, w = a.shape
    v = b.shape[0]
    assert v % block_v == 0, (v, block_v)
    grid = (v // block_v,)
    out_spec = pl.BlockSpec((v_r, block_v), lambda i: (0, i))
    n_out = 1 if k_only else 3
    out = pl.pallas_call(
        functools.partial(_kernel, lam=lam, k_only=k_only, gemm=gemm,
                          log_k=log_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((v_r, w), lambda i: (0, 0)),      # a resident
            pl.BlockSpec((block_v, w), lambda i: (i, 0)),  # b streamed
            pl.BlockSpec((v_r, 1), lambda i: (0, 0)),      # r resident
        ],
        out_specs=[out_spec] * n_out,
        out_shape=[jax.ShapeDtypeStruct((v_r, v), a.dtype)] * n_out,
        interpret=interpret,
    )(a, b, r.reshape(-1, 1))
    return out[0] if k_only else out
