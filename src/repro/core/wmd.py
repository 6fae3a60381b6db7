"""End-to-end Word Mover's Distance pipeline (public API).

    wmd = one_to_many(query_counts, corpus_docs, vecs, lam=..., n_iter=...,
                      impl="sparse")
    res = search(queries, corpus_docs, vecs, k=10, prune="rwmd")

Implementations (all produce identical distances, tested against each other
and against the exact-LP oracle):

  dense             paper Fig. 2 transliteration (the "python" baseline)
  dense_stabilized  log-domain dense (beyond-paper; large-lam safe in fp32)
  sparse            fused SDDMM_SpMM formulation, gather-once (paper §4 + TPU
                    adaptation) — the production path
  sparse_unfused    separate SDDMM / SpMM with per-iteration gathers (paper
                    Fig. 3 before fusion; for the fusion ablation)
  kernel            Pallas path (TPU target; interpret-mode on CPU): the
                    Pallas cdist, then the engine's row gather and its
                    VMEM-resident solve (``sinkhorn_resident``) at Q = 1

Top-k retrieval goes through the staged pipeline (prune -> solve -> rank,
:meth:`repro.core.index.WmdEngine.search`); :func:`search` is the one-shot
convenience wrapper (index built per call — hold a ``WmdEngine`` to amortize
the corpus freeze across query batches).
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from .sinkhorn import (LamUnderflowError, select_support, sinkhorn_wmd_dense,
                       sinkhorn_wmd_dense_stabilized, underflow_report)
from .sinkhorn_sparse import (SolvePrecision, sinkhorn_wmd_sparse,
                              sinkhorn_wmd_sparse_unfused)
from .sparse import PaddedDocs, padded_docs_to_dense

IMPLS = ("dense", "dense_stabilized", "sparse", "sparse_unfused", "kernel")


@functools.partial(jax.jit, static_argnames=("lam", "n_iter", "precision",
                                             "interpret"))
def _kernel_wmd(r, vecs_sel, vecs, docs: PaddedDocs, lam: float,
                n_iter: int, precision=None, interpret: bool | None = None):
    """The kernel path: Pallas cdist -> the engine's whole-row gather ->
    the resident solve, for one query of v_r words against ``docs``.

    The query is staged as the engine stages a chunk of one: its rows
    padded to a multiple of 8 with r = 1, K = 0 (-inf under the log
    domain) and mask 0, and K handed over vocabulary-major, (V, B).
    ``precision`` (a ``SolvePrecision`` or its spelling) sets the bf16
    operands and the log domain of both kernels; under ``log_domain``
    the cdist emits UNexponentiated log K, so no column can underflow at
    any lam. Returns (N,) distances."""
    from repro.kernels import ops
    from .index import _gather_g
    precision = SolvePrecision.parse(precision)
    k = ops.cdist_exp(vecs_sel, vecs, r, lam, interpret=interpret,
                      k_only=True, gemm=precision.gemm,
                      log_k=precision.log_domain)          # (v_r, V)
    row_pad = -jnp.inf if precision.log_domain else 0.0
    kt = ops.pad_to(k, 0, 8, value=row_pad).T              # (V, B)
    block_n = 128
    g = _gather_g(kt, docs.idx, layout="qlbn", block_n=block_n, q=1)
    rp = ops.pad_to(r, 0, 8, value=1.0)
    mask = ops.pad_to(jnp.ones_like(r), 0, 8)
    return ops.sinkhorn_resident(
        g, docs.val, rp[None], mask[None], lam, n_iter, block_n=block_n,
        interpret=interpret, gemm=precision.gemm,
        log_domain=precision.log_domain)[0]


# the impls that solve on the ELL docs directly
_ELL_SOLVES = {
    # one_to_many's own underflow check covers this impl: skip the solver's
    "sparse": functools.partial(sinkhorn_wmd_sparse, check_underflow=False),
    "sparse_unfused": sinkhorn_wmd_sparse_unfused,
    "kernel": _kernel_wmd,
}


def one_to_many(r_full, docs: PaddedDocs, vecs, lam: float = 10.0,
                n_iter: int = 15, impl: str = "sparse",
                dtype=jnp.float32, check_underflow: bool = True):
    """WMD from one query (full-vocab count/frequency vector ``r_full``) to
    every document in ``docs``. Returns (N,) distances.

    ``check_underflow`` (all impls except the log-domain one): raise
    :class:`LamUnderflowError` with a diagnosis when ``K = exp(-lam*M)``
    underflowed and the distances came out NaN, instead of returning them.
    The check syncs the result — pass ``False`` to keep dispatch async.
    """
    if impl not in IMPLS:
        raise ValueError(f"impl must be one of {IMPLS}, got {impl!r}")
    vecs = jnp.asarray(vecs, dtype)
    r, vecs_sel, _ = select_support(r_full, vecs, dtype)

    if impl in _ELL_SOLVES:
        out = _ELL_SOLVES[impl](r, vecs_sel, vecs, docs, lam, n_iter)
    else:
        c = jnp.asarray(padded_docs_to_dense(docs, vecs.shape[0]), dtype)
        if impl == "dense":
            out = sinkhorn_wmd_dense(r, vecs_sel, vecs, c, lam, n_iter)
        else:
            return sinkhorn_wmd_dense_stabilized(r, vecs_sel, vecs, c, lam,
                                                 n_iter)
    if (check_underflow and r.shape[0] > 0
            and bool(jnp.isnan(out).any())):
        raise LamUnderflowError(underflow_report(lam, vecs_sel, vecs, docs))
    return out


def many_to_many(queries: list[np.ndarray], docs: PaddedDocs, vecs,
                 lam: float = 10.0, n_iter: int = 15, impl: str = "sparse",
                 batched: bool = True):
    """Paper Fig. 6 workload: multiple source documents at once.

    Default path: the batched multi-query engine (:mod:`repro.core.index`) —
    one persistent corpus index, one solve per power-of-two ``v_r`` bucket.
    ``batched=False`` keeps the original per-query Python loop (the naive
    baseline the engine is benchmarked against); dense impls always loop.
    """
    if batched and impl in ("sparse", "kernel"):
        from .index import WmdEngine, build_index
        engine = WmdEngine(build_index(docs, vecs), lam=lam, n_iter=n_iter)
        out = engine.query_batch(queries)
        return [out[i] for i in range(out.shape[0])]
    return [one_to_many(q, docs, vecs, lam, n_iter, impl) for q in queries]


def search(queries, docs: PaddedDocs, vecs, k: int = 10, lam: float = 10.0,
           n_iter: int = 15, prune: object = "rwmd"):
    """One-shot top-k retrieval through the staged pipeline: freeze an index,
    prune with an admissible lower bound, Sinkhorn-solve the survivors, rank.
    Returns a :class:`repro.core.index.SearchResult`. ``prune=None`` scores
    every document (exhaustive oracle path)."""
    from .index import WmdEngine, build_index
    engine = WmdEngine(build_index(docs, vecs), lam=lam, n_iter=n_iter)
    return engine.search(queries, k, prune=prune)
