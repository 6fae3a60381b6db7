"""Dense one-to-many Sinkhorn-Knopp WMD solver (paper Algorithm 1 / Fig. 2).

This module is the *paper-faithful* baseline: a direct JAX transliteration of
the python implementation in Fig. 2 of the paper (which itself implements
Cuturi'13 Algorithm 1 specialized to WMD). All matrices are dense; the hot
kernel is the dense ``K.T @ u`` followed by the sparse elementwise selection —
exactly the formulation the paper profiles in Table 1 and then replaces with
sparse kernels (see :mod:`repro.core.sinkhorn_sparse`).

Shapes follow the paper's notation:
  V    vocabulary size
  v_r  number of unique words in the query/source document (nnz of r)
  N    number of target documents
  w    word-embedding width

Conventions: ``lam`` is the positive regularization strength; the kernel is
``K = exp(-lam * M)`` (the paper negates lambda before the call; we negate
inside).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.precision import FP32_GEMM


# ln(fp32 min normal) ~ -87.3: exp(-x) flushes to exactly 0 beyond this,
# and an all-zero gathered K column turns the Sinkhorn 1/(K^T u) line into
# inf/NaN for every document containing that word.
MAX_NEG_EXP = 87.0


class LamUnderflowError(FloatingPointError):
    """``K = exp(-lam*M)`` underflowed to all-zero for some corpus word.

    Raised by the engine / ``one_to_many`` instead of silently returning
    (and benchmarking!) NaN distances — the failure mode the seed fig6
    config was timing at lam=9 on a distance-scale-10 corpus.
    """


def underflow_report(lam: float, vecs_sel, vecs, docs) -> str:
    """Host-side diagnosis for :class:`LamUnderflowError` (error path only).

    Finds the corpus words whose K column is all-zero — i.e. words farther
    than ``MAX_NEG_EXP / lam`` from *every* query word — and counts the
    documents containing one, so the message names the actual culprit
    instead of a bare NaN.
    """
    import numpy as np

    a = np.asarray(vecs_sel, np.float64)
    b = np.asarray(vecs, np.float64)
    d2 = (np.sum(a * a, 1)[:, None] + np.sum(b * b, 1)[None, :]
          - 2.0 * (a @ b.T))
    mincol = np.sqrt(np.maximum(d2, 0.0)).min(axis=0)     # (V,) to nearest
    dead = lam * mincol > MAX_NEG_EXP                     # query word
    idx = np.asarray(docs.idx)
    live = np.asarray(docs.val) > 0
    hit = dead[idx] & live
    n_docs = int(hit.any(axis=1).sum())
    scale = float(np.median(mincol[np.isfinite(mincol)]))
    return (
        f"K = exp(-lam*M) underflowed to an all-zero column for "
        f"{int(dead[np.unique(idx[hit])].size)} corpus word(s) in {n_docs} "
        f"document(s) at lam={lam:g} (fp32 cutoff: lam*dist > ~{MAX_NEG_EXP:.0f}; "
        f"max lam*min-dist here = {lam * float(mincol.max()):.0f}). The "
        f"Sinkhorn division by these columns would make every affected "
        f"distance NaN. Reduce lam (corpus min-distance scale ~{scale:.1f} "
        f"-> lam <~ {MAX_NEG_EXP / max(scale, 1e-9):.1f}), or opt into the "
        f"log-domain solve — precision='log' on WmdEngine / "
        f"sinkhorn_wmd_sparse (underflow-free at any lam), or "
        f"impl='dense_stabilized' for the dense path."
    )


def sq_dists(a: jax.Array, b: jax.Array, a2=None, b2=None,
             gemm_dtype=None) -> jax.Array:
    """Squared distances in GEMM form: (m, w) x (n, w) -> (m, n) of
    ``|a_i|^2 + |b_j|^2 - 2 a_i.b_j``, unclamped (the cancellation can
    leave small negatives). ``a2``/``b2`` are the squared norms when the
    caller already holds them. Every cdist GEMM goes through here, so
    this is where :data:`FP32_GEMM` applies; ``gemm_dtype`` (e.g.
    ``jnp.bfloat16``) instead casts only the operands, accumulating in
    fp32."""
    if a2 is None:
        a2 = jnp.sum(a * a, axis=-1)
    if b2 is None:
        b2 = jnp.sum(b * b, axis=-1)
    if gemm_dtype is None:
        ab = jnp.matmul(a, b.T, precision=FP32_GEMM)
    else:
        ab = jnp.matmul(a.astype(gemm_dtype), b.astype(gemm_dtype).T,
                        preferred_element_type=jnp.float32)
    return a2[:, None] + b2[None, :] - 2.0 * ab


def cdist(a: jax.Array, b: jax.Array, gemm_dtype=None) -> jax.Array:
    """Pairwise Euclidean distance, GEMM-shaped (paper §6).

    ``m[i, j] = sqrt(|a_i|^2 + |b_j|^2 - 2 a_i.b_j)`` — one big matmul plus
    rank-1 corrections instead of a broadcast-subtract (which would
    materialize an (v_r, V, w) intermediate). This is the paper's
    "matrix-multiplication-like" Euclidean distance restructuring.

    ``gemm_dtype`` (e.g. ``jnp.bfloat16``) casts ONLY the matmul operands;
    the accumulation and the rank-1 norms stay fp32 (the
    :class:`~repro.core.sinkhorn_sparse.SolvePrecision` bf16 policy).
    """
    return jnp.sqrt(jnp.maximum(sq_dists(a, b, gemm_dtype=gemm_dtype), 0.0))


class SinkhornPrecompute(NamedTuple):
    """Loop-invariant matrices (paper: "can be pre-computed once and reused")."""

    M: jax.Array          # (v_r, V) transport cost
    K: jax.Array          # (v_r, V) exp(-lam*M)
    K_over_r: jax.Array   # (v_r, V) diag(1/r) K
    KM: jax.Array         # (v_r, V) K * M


def precompute(r: jax.Array, vecs_sel: jax.Array, vecs: jax.Array,
               lam: float) -> SinkhornPrecompute:
    """Compute M, K, K_over_r, KM for the selected query words.

    ``r``        (v_r,)   normalized word frequencies of the query (nnz only)
    ``vecs_sel`` (v_r, w) embeddings of the query words
    ``vecs``     (V, w)   full vocabulary embeddings
    """
    M = cdist(vecs_sel, vecs)
    K = jnp.exp(-lam * M)
    return SinkhornPrecompute(M=M, K=K, K_over_r=K / r[:, None], KM=K * M)


@functools.partial(jax.jit, static_argnames=("n_iter",))
def sinkhorn_wmd_dense(r: jax.Array, vecs_sel: jax.Array, vecs: jax.Array,
                       c: jax.Array, lam: float, n_iter: int) -> jax.Array:
    """Paper Fig. 2, dense: WMD of one query against N target documents.

    ``c`` (V, N) column-normalized word-frequency matrix of the targets,
    *dense* here (the paper's python baseline stores it sparse but the
    compute is dense GEMM + elementwise mask — identical arithmetic).

    Returns ``wmd`` (N,).
    """
    pre = precompute(r, vecs_sel, vecs, lam)
    v_r = r.shape[0]
    n_docs = c.shape[1]
    x = jnp.full((v_r, n_docs), 1.0 / v_r, dtype=pre.K.dtype)

    def body(x, _):
        u = 1.0 / x
        # Table 1 hot line: v = c.multiply(1 / (K.T @ u))  (91.9% of runtime)
        kt_u = pre.K.T @ u                       # (V, N) dense GEMM
        v = c * (1.0 / kt_u)                     # sparse selection, dense here
        x = pre.K_over_r @ v                     # (v_r, N) "SpMM" line
        return x, None

    x, _ = lax.scan(body, x, None, length=n_iter)
    u = 1.0 / x
    v = c * (1.0 / (pre.K.T @ u))
    return jnp.sum(u * (pre.KM @ v), axis=0)


@functools.partial(jax.jit, static_argnames=("n_iter",))
def sinkhorn_wmd_dense_stabilized(r: jax.Array, vecs_sel: jax.Array,
                                  vecs: jax.Array, c: jax.Array, lam: float,
                                  n_iter: int) -> jax.Array:
    """Beyond-paper: log-domain Sinkhorn (numerically stable for large lam).

    The paper runs fp64 on CPU; on TPU (fp32/bf16) large ``lam`` underflows
    ``exp(-lam*M)``. The log-domain iteration replaces scaling vectors with
    dual potentials f, g and matmuls with logsumexp reductions.

    Solves the same fixed point: P = diag(exp(f*lam)) K diag(exp(g*lam)).
    """
    M = cdist(vecs_sel, vecs)                    # (v_r, V)
    v_r = r.shape[0]
    n_docs = c.shape[1]
    log_r = jnp.log(r)                           # (v_r,)
    # columns with c==0 contribute -inf log-mass
    log_c = jnp.where(c > 0, jnp.log(jnp.where(c > 0, c, 1.0)), -jnp.inf)

    f = jnp.zeros((v_r, n_docs), M.dtype)        # potential per (word, doc)
    g = jnp.zeros_like(c)                        # (V, N)

    def body(carry, _):
        f, g = carry
        # g update: column marginal  (logsumexp over query words)
        s = -lam * M[:, :, None] + f[:, None, :]            # (v_r, V, N)
        g = log_c - jax.nn.logsumexp(s, axis=0)             # (V, N)
        g = jnp.where(jnp.isneginf(log_c), -jnp.inf, g)
        # f update: row marginal (logsumexp over vocabulary)
        t = -lam * M[:, :, None] + g[None, :, :]            # (v_r, V, N)
        f = log_r[:, None] - jax.nn.logsumexp(t, axis=1)    # (v_r, N)
        return (f, g), None

    (f, g), _ = lax.scan(body, (f, g), None, length=n_iter)
    # transport plan P[k, i, n] = exp(f + g - lam*M); WMD = <P, M>
    log_p = f[:, None, :] + g[None, :, :] - lam * M[:, :, None]
    p = jnp.exp(jnp.where(jnp.isneginf(log_p), -jnp.inf, log_p))
    return jnp.sum(p * M[:, :, None], axis=(0, 1))


def select_support(r_full, vecs, dtype=jnp.float32):
    """Host-side support selection (paper: ``sel = r.squeeze() > 0``).

    Dynamic-shape step, so it runs outside jit. Returns (r_sel, vecs_sel, idx).
    """
    import numpy as np

    r_full = np.asarray(r_full).reshape(-1)
    idx = np.nonzero(r_full > 0)[0]
    r_sel = r_full[idx].astype(dtype)
    r_sel = r_sel / r_sel.sum()
    return jnp.asarray(r_sel), jnp.asarray(np.asarray(vecs)[idx], dtype=dtype), idx
