"""Pallas TPU kernel for the RWMD prune stage: query-grid masked min-cdist.

The staged retrieval pipeline (``WmdEngine.search``: prune -> solve -> rank)
needs, per query q, the distance from every vocabulary word v to the
*nearest* query word:

    minM[q, v] = min_{k : mask[q, k] > 0} ||a[q, k] - b[v]||

The doc-side relaxed WMD lower bound is then ``sum_l val[n, l] *
minM[q, idx[n, l]]`` — an O(nnz) gather the caller keeps in XLA (same
split as the solve stage: cdist-shaped work in Pallas, the gather at the
kernel boundary).

This is the same blocked GEMM-shaped schedule as :mod:`.cdist_exp` (the
``a @ b.T`` contraction on the MXU, the sqrt epilogue on the VPU while the
tile is in VMEM/VREGs) with two changes mirroring the multi-query engine:

  - a leading *query* grid dimension, so a whole shape-bucketed chunk of
    queries runs in one launch (one executable per bucket shape, like
    ``sinkhorn_fused_all_batched``);
  - the epilogue reduces over the support axis (masked min) instead of
    storing the full (B, block_v) tile, so the kernel's HBM output is the
    small (Q, V) bound matrix — the (Q*B, V) distance block never exists
    outside VMEM.

Padding contract: padded support rows carry ``mask == 0`` and are excluded
from the min via a +inf select; zero-padding the embedding width is exact
(zeros add nothing to the distance); padded vocabulary tiles produce
garbage columns the wrapper slices off.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .precision import FP32_GEMM


def _kernel(a_ref, mask_ref, b_ref, out_ref):
    a = a_ref[0]                          # (B, w)   this query's support
    mask = mask_ref[0]                    # (B, 1)
    b = b_ref[...]                        # (bv, w)  streamed vocab tile
    ab = jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                             precision=FP32_GEMM,
                             preferred_element_type=jnp.float32)  # MXU
    a2 = jnp.sum(a * a, axis=1, keepdims=True)       # (B, 1)
    b2 = jnp.sum(b * b, axis=1)[None, :]             # (1, bv)
    d = jnp.sqrt(jnp.maximum(a2 + b2 - 2.0 * ab, 0.0))
    d = jnp.where(mask > 0, d, jnp.inf)              # pad rows out of the min
    out_ref[0] = jnp.min(d, axis=0, keepdims=True)   # (1, bv)


@functools.partial(jax.jit, static_argnames=("block_v", "interpret"))
def rwmd_min_cdist(a: jax.Array, mask: jax.Array, b: jax.Array,
                   block_v: int = 512, interpret: bool = False) -> jax.Array:
    """Masked min-over-support distances for a query chunk.

    ``a`` (Q, B, w) support embeddings, ``mask`` (Q, B) with 0 marking padded
    support rows, ``b`` (V, w) vocabulary embeddings. V must divide by
    ``block_v``; pad B/w via :func:`repro.kernels.ops.pad_to` (the ops
    wrapper does). Returns ``minM`` (Q, V); rows whose mask is all zero
    (inert filler queries) come out +inf.
    """
    q, bq, w = a.shape
    v = b.shape[0]
    assert v % block_v == 0, (v, block_v)
    grid = (q, v // block_v)
    # the output carries a unit middle axis: a (1, block_v) block of a
    # (Q, V) array is not (8, 128)-aligned on its query axis, while a
    # (1, 1, block_v) block of (Q, 1, V) spans that axis whole
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, w), lambda qi, i: (qi, 0, 0)),   # resident
            pl.BlockSpec((1, bq, 1), lambda qi, i: (qi, 0, 0)),
            pl.BlockSpec((block_v, w), lambda qi, i: (i, 0)),     # streamed
        ],
        out_specs=pl.BlockSpec((1, 1, block_v), lambda qi, i: (qi, 0, i)),
        out_shape=jax.ShapeDtypeStruct((q, 1, v), a.dtype),
        interpret=interpret,
    )(a, mask.reshape(q, bq, 1), b)
    return out.reshape(q, v)


@functools.partial(jax.jit, static_argnames=("block_v", "interpret"))
def rwmd_min_cdist_subset(a: jax.Array, mask: jax.Array, b: jax.Array,
                          vocab_ids: jax.Array, block_v: int = 512,
                          interpret: bool = False) -> jax.Array:
    """Candidate-vocab min-cdist: the cascade's RWMD stage only needs the
    words that actually appear in the surviving documents, so the caller
    passes their (padded) id array and the streamed vocab side shrinks from
    (V, w) to (Vc, w) — the (Q*B, V) distance block becomes (Q*B, Vc).

    The gather sits at the kernel boundary (XLA gather feeding the Pallas
    launch, same split as the solve stage's G gather). ``vocab_ids`` (Vc,)
    must be ``block_v``-aligned — pad with any valid id; padded columns are
    garbage the caller's compact gather never reads. Returns (Q, Vc).
    """
    return rwmd_min_cdist(a, mask, jnp.take(b, vocab_ids, axis=0),
                          block_v=block_v, interpret=interpret)
