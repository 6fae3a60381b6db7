"""Plain reference of the Sinkhorn Word Mover's Distance.

Straight from the paper's algorithm (Tithi & Petrini, arXiv:2005.06727,
after Cuturi 2013), one query against every document, with nothing of
the program under test: no index, bucketing, pruning, cache or kernel.
For a query of weights r over its words and a doc of weights c over its
words, with M the Euclidean distances between their embeddings and
K = exp(-lam * M):

    x = 1 / v_r
    repeat n_iter:  u = 1 / x;  v = c / (K^T u);  x = (K v) / r
    u = 1 / x;  v = c / (K^T u);  wmd = sum(u * ((K * M) v))

float32 throughout, every contraction at ``Precision.HIGHEST`` (a TPU
otherwise rounds float32 matmul operands to bfloat16). Docs are taken in
blocks of ``BLOCK``, sorted by length and padded to a power of two, so
the reference fits beside nothing else on the chip; a padded query row
or doc slot carries zero weight and drops out.
"""
from __future__ import annotations

import functools

import numpy as np

BLOCK = 512


def _pow2(n: int, floor: int = 8) -> int:
    b = floor
    while b < n:
        b *= 2
    return b


@functools.lru_cache(maxsize=None)
def _solver(lam: float, n_iter: int):
    import jax
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST

    @jax.jit
    def solve(vecs, q_ids, r, d_ids, c):
        """q_ids, r: (B,) padded query; d_ids, c: (N, L) padded docs."""
        a = vecs[q_ids]                                   # (B, w)
        d = vecs[d_ids]                                   # (N, L, w)
        d2 = (jnp.sum(d * d, -1)[..., None] + jnp.sum(a * a, -1)
              - 2 * jnp.einsum("nlw,bw->nlb", d, a, precision=hi))
        m = jnp.sqrt(jnp.maximum(d2, 0.0))                # (N, L, B)
        live_q = r > 0
        k = jnp.where(live_q, jnp.exp(-lam * m), 0.0)
        x = jnp.broadcast_to(jnp.where(live_q, 1.0 / jnp.sum(live_q), 0.0),
                             (d_ids.shape[0], r.shape[0]))
        rinv = jnp.where(live_q, 1.0 / jnp.where(live_q, r, 1.0), 0.0)

        def inv(z):
            return jnp.where(z > 0, 1.0 / jnp.where(z > 0, z, 1.0), 0.0)

        def v_of(u):
            t = jnp.einsum("nlb,nb->nl", k, u, precision=hi)
            return jnp.where(c > 0, c / jnp.where(c > 0, t, 1.0), 0.0)

        def body(_, x):
            v = v_of(inv(x))
            return jnp.einsum("nlb,nl->nb", k, v, precision=hi) * rinv

        x = lax.fori_loop(0, n_iter, body, x)
        u = inv(x)
        v = v_of(u)
        return jnp.einsum("nb,nlb,nl->n", u, k * m, v, precision=hi)

    return solve


def wmd_rows(vecs, doc_ids: np.ndarray, doc_val: np.ndarray,
             doc_nnz: np.ndarray, queries, lam: float,
             n_iter: int) -> np.ndarray:
    """(len(queries), N) distances from each query to every doc.

    ``vecs`` (V, w) device array; docs as zero-padded (N, L) ids and
    weights with their word counts; ``queries`` a list of (ids, weights).
    """
    import jax.numpy as jnp

    solve = _solver(float(lam), int(n_iter))
    order = np.argsort(doc_nnz, kind="stable")
    blocks = []
    for lo in range(0, len(order), BLOCK):
        rows = order[lo:lo + BLOCK]
        live = int(doc_nnz[rows].max())
        width = _pow2(live)
        ids = np.zeros((BLOCK, width), np.int32)
        val = np.zeros((BLOCK, width), np.float32)
        ids[:len(rows), :live] = doc_ids[rows, :live]
        val[:len(rows), :live] = doc_val[rows, :live]
        blocks.append((rows, jnp.asarray(ids), jnp.asarray(val)))
    out = np.zeros((len(queries), len(order)), np.float32)
    for qi, (q_ids, q_w) in enumerate(queries):
        b = _pow2(len(q_ids))
        ids = np.zeros(b, np.int32)
        r = np.zeros(b, np.float32)
        ids[:len(q_ids)] = q_ids
        r[:len(q_ids)] = q_w
        ids, r = jnp.asarray(ids), jnp.asarray(r)
        parts = [(rows, solve(vecs, ids, r, d_ids, c))
                 for rows, d_ids, c in blocks]
        for rows, dist in parts:
            out[qi, rows] = np.asarray(dist)[:len(rows)]
    return out
