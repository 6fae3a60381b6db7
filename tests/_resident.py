"""Run the resident Pallas solve on per-query (v_r, N, L) tiles.

The oracles in ``repro.kernels.ref`` take one query's tile as
(v_r, N, L); ``ops.sinkhorn_resident`` takes the engine's gathered
(Q, L, v_r, N_pad) tile, the docs padded to whole blocks. ``solve_knl``
hands it the transpose of the same tiles.
"""
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops


def solve_knl(g, val, r, lam: float, n_iter: int, mask=None,
              block_n: int = 128, log_domain: bool = False, **kw):
    """``ops.sinkhorn_resident`` on g (Q, v_r, N, L) -> wmd (Q, N).

    The docs are padded to a multiple of ``block_n`` as the engine's
    gather pads them: with K columns (here 0.5, its log under
    ``log_domain``) that the solve gives val 0. ``mask`` (Q, v_r)
    defaults to the query rows that hold a K entry (nonzero, or finite
    under ``log_domain``) anywhere in the tile."""
    g = jnp.asarray(g, jnp.float32)
    q, v_r, n, length = g.shape
    if mask is None:
        live = jnp.isfinite(g) if log_domain else g > 0
        mask = jnp.any(live, axis=(2, 3)).astype(jnp.float32)
    tile = jnp.transpose(g, (0, 3, 1, 2))               # (Q, L, v_r, N)
    pad = np.log(0.5) if log_domain else 0.5
    tile = ops.pad_to(tile, 3, block_n, value=pad)
    return ops.sinkhorn_resident(tile, jnp.asarray(val, jnp.float32),
                                 jnp.asarray(r, jnp.float32),
                                 jnp.asarray(mask, jnp.float32), lam,
                                 n_iter, block_n=block_n,
                                 log_domain=log_domain, **kw)
