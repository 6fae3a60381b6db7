"""Per-kernel allclose vs pure-jnp oracles, shape/dtype sweeps (interpret
mode on CPU; same call sites compile to Mosaic on TPU)."""
import numpy as np
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st
from _resident import solve_knl

from repro.kernels import ops
from repro.kernels.ref import (cdist_exp_ref, sddmm_spmm_step_ref,
                               sinkhorn_fused_all_ref)


def _rand(rng, *shape, dtype=np.float32):
    return jnp.asarray(rng.standard_normal(shape).astype(dtype))


# ---------------------------------------------------------------- cdist_exp
@pytest.mark.parametrize("v_r,v,w,block_v", [
    (8, 256, 128, 128), (19, 512, 300, 256), (43, 384, 64, 128),
    (5, 128, 32, 128), (64, 1024, 256, 512),
])
def test_cdist_exp_shapes(rng, v_r, v, w, block_v):
    a, b = _rand(rng, v_r, w), _rand(rng, v, w)
    r = jnp.asarray(rng.uniform(0.01, 1.0, v_r).astype(np.float32))
    lam = 5.0
    m, k, kr = ops.cdist_exp(a, b, r, lam, block_v=block_v)
    mr, kref, krr = cdist_exp_ref(a, b, r, lam)
    assert m.shape == (v_r, v)
    np.testing.assert_allclose(m, mr, rtol=2e-3, atol=5e-3)
    np.testing.assert_allclose(k, kref, rtol=2e-3, atol=5e-3)
    np.testing.assert_allclose(kr, krr, rtol=2e-3, atol=5e-2)


def test_cdist_exp_k_only_matches_full(rng):
    """k_only mode (fused-solver path: no dead M/K_over_r stores) returns
    the same K as the full three-output kernel."""
    a, b = _rand(rng, 16, 128), _rand(rng, 256, 128)
    r = jnp.asarray(rng.uniform(0.1, 1.0, 16).astype(np.float32))
    _, k_full, _ = ops.cdist_exp(a, b, r, 4.0)
    k_only = ops.cdist_exp(a, b, r, 4.0, k_only=True)
    np.testing.assert_array_equal(np.asarray(k_only), np.asarray(k_full))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_cdist_exp_dtypes(rng, dtype):
    # skip on the actual capability probe, not a hardcoded marker: a box
    # running with JAX_ENABLE_X64=1 exercises the float64 path for real
    # instead of silently skipping it (ISSUE 5 hygiene fix)
    import jax
    if dtype == jnp.float64 and not jax.config.jax_enable_x64:
        pytest.skip("jax_enable_x64 is off on this box (fp32 is the TPU "
                    "target dtype); enable JAX_ENABLE_X64=1 to run this")
    a, b = _rand(rng, 16, 128), _rand(rng, 256, 128)
    r = jnp.asarray(rng.uniform(0.1, 1.0, 16).astype(np.float32))
    m, k, kr = ops.cdist_exp(a.astype(dtype), b.astype(dtype),
                             r.astype(dtype), 3.0)
    assert k.dtype == dtype


# ------------------------------------------------------------ sddmm_spmm step
@pytest.mark.parametrize("v_r,n,length,block_n", [
    (8, 128, 128, 128), (19, 64, 40, 32), (32, 256, 64, 128), (3, 32, 8, 32),
])
def test_sddmm_spmm_step_shapes(rng, v_r, n, length, block_n):
    g = jnp.abs(_rand(rng, v_r, n, length)) + 0.1
    gor = g * 1.7
    val = jnp.abs(_rand(rng, n, length))
    val = jnp.where(val > 0.8, val, 0.0)          # sparse pattern
    x = jnp.abs(_rand(rng, v_r, n)) + 0.5
    out = ops.sddmm_spmm_step(g, gor, val, x, block_n=block_n)
    ref = sddmm_spmm_step_ref(g, gor, val, x)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


# ------------------------------------------------------ resident full solver
def _rand_g(rng, *shape):
    """G entries as the solver sees them: gathered K = exp(-lam*M) in (0, 1]."""
    return jnp.asarray(rng.uniform(0.02, 1.0, shape).astype(np.float32))


SHAPES = [(19, 128, 40, 15, 64), (8, 64, 16, 5, 32), (43, 256, 64, 25, 128)]


@pytest.mark.parametrize("v_r,n,length,n_iter,block_n", SHAPES)
def test_sinkhorn_fused_all_shapes(rng, v_r, n, length, n_iter, block_n):
    """The resident solve on the (1, L, v_r, N_pad) transpose of one
    query's tile, unaligned v_r and L included, against the oracle."""
    g = _rand_g(rng, v_r, n, length)
    val = jnp.abs(_rand(rng, n, length))
    val = jnp.where(val > 0.5, val, 0.0)
    val = val.at[:, 0].set(1.0)                   # every doc has >=1 word
    r = jnp.asarray(rng.uniform(0.1, 1.0, v_r).astype(np.float32))
    lam = 7.0
    out = solve_knl(g[None], val, r[None], lam, n_iter, block_n=block_n)[0]
    ref = sinkhorn_fused_all_ref(g, val, r, lam, n_iter)
    np.testing.assert_allclose(out, ref, rtol=5e-5, atol=5e-5)


@pytest.mark.parametrize("v_r,n,length,n_iter,block_n", SHAPES)
def test_resident_shapes_multi_query(rng, v_r, n, length, n_iter, block_n):
    """The same shape grid at Q = 3: each query's row of the multi-query
    grid equals the oracle on that query's tile."""
    q = 3
    g = _rand_g(rng, q, v_r, n, length)
    val = jnp.abs(_rand(rng, n, length))
    val = jnp.where(val > 0.5, val, 0.0)
    val = val.at[:, 0].set(1.0)
    r = jnp.asarray(rng.uniform(0.1, 1.0, (q, v_r)).astype(np.float32))
    lam = 7.0
    out = solve_knl(g, val, r, lam, n_iter, block_n=block_n)
    assert out.shape == (q, n)
    for qi in range(q):
        ref = sinkhorn_fused_all_ref(g[qi], val, r[qi], lam, n_iter)
        np.testing.assert_allclose(out[qi], ref, rtol=5e-5, atol=5e-5)


def test_fused_all_handles_padded_rows(rng):
    """Padded query rows (G row == 0, r == 1) must be exactly inert."""
    v_r, n, length = 10, 64, 16
    g = _rand_g(rng, 1, v_r, n, length)
    val = jnp.where(jnp.abs(_rand(rng, n, length)) > 0.5, 1.0, 0.0)
    val = val.at[:, 0].set(1.0)
    r = jnp.asarray(rng.uniform(0.1, 1.0, (1, v_r)).astype(np.float32))
    base = solve_knl(g, val, r, 5.0, 10)
    # append 6 dead rows
    zpad = jnp.zeros((1, 6, n, length))
    g2 = jnp.concatenate([g, zpad], axis=1)
    r2 = jnp.concatenate([r, jnp.ones((1, 6))], axis=1)
    padded = solve_knl(g2, val, r2, 5.0, 10)
    np.testing.assert_allclose(padded, base, rtol=1e-6, atol=1e-6)


def test_resident_log_domain_padded_rows_inert(rng):
    """Under ``log_domain`` a padded query row holds log K = -inf (a 0
    would be a valid entry, distance 0) with r == 1 and mask 0: the
    distances equal those without the rows."""
    v_r, n, length, lam = 10, 64, 16, 5.0
    logk = jnp.log(_rand_g(rng, 1, v_r, n, length))
    val = jnp.where(jnp.abs(_rand(rng, n, length)) > 0.5, 1.0, 0.0)
    val = val.at[:, 0].set(1.0)
    r = jnp.asarray(rng.uniform(0.1, 1.0, (1, v_r)).astype(np.float32))
    base = solve_knl(logk, val, r, lam, 10, log_domain=True)
    g2 = jnp.concatenate([logk, jnp.full((1, 6, n, length), -jnp.inf)],
                         axis=1)
    r2 = jnp.concatenate([r, jnp.ones((1, 6))], axis=1)
    padded = solve_knl(g2, val, r2, lam, 10, log_domain=True)
    assert bool(jnp.all(jnp.isfinite(padded)))
    np.testing.assert_allclose(padded, base, rtol=1e-6, atol=1e-6)


# ------------------------------------------------------- property-based sweep
@settings(max_examples=15, deadline=None)
@given(v_r=st.integers(2, 24), n=st.integers(1, 6), length=st.integers(2, 24),
       seed=st.integers(0, 2**31 - 1))
def test_step_kernel_property(v_r, n, length, seed):
    rng = np.random.default_rng(seed)
    n *= 32
    g = jnp.asarray(np.abs(rng.standard_normal((v_r, n, length))) + 0.1,
                    dtype=jnp.float32)
    gor = g * 0.5
    val = jnp.asarray(
        np.where(rng.random((n, length)) > 0.6,
                 rng.random((n, length)), 0).astype(np.float32))
    x = jnp.asarray(np.abs(rng.standard_normal((v_r, n))) + 0.5,
                    dtype=jnp.float32)
    out = ops.sddmm_spmm_step(g, gor, val, x, block_n=32)
    ref = sddmm_spmm_step_ref(g, gor, val, x)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_kernel_path_equals_library_path(small_corpus):
    from repro.core import one_to_many
    q = small_corpus.queries[0]
    a = one_to_many(q, small_corpus.docs, small_corpus.vecs, 9.0, 30,
                    impl="sparse")
    b = one_to_many(q, small_corpus.docs, small_corpus.vecs, 9.0, 30,
                    impl="kernel")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=5e-4, atol=5e-4)


# ------------------------------------------------------------- BSR kernel
@pytest.mark.parametrize("v,n,bv,bn,density", [
    (256, 128, 64, 32, 0.0008), (512, 256, 128, 128, 0.00004),
])
def test_bsr_sddmm(rng, v, n, bv, bn, density):
    """Block-sparse SDDMM (DESIGN.md §4 tile-granular adaptation) matches
    the dense product at retained tiles; zero tiles are never computed."""
    from repro.core.sparse import block_sparse_from_dense, block_density
    from repro.kernels.bsr_sddmm import bsr_sddmm, bsr_sddmm_ref
    c = np.where(rng.random((v, n)) < density,
                 rng.random((v, n)), 0.0).astype(np.float32)
    c_bsr = block_sparse_from_dense(c, bv, bn)
    assert block_density(c, bv, bn) < 1.0          # actually sparse in tiles
    v_r = 24
    kt = jnp.asarray(rng.standard_normal((v, v_r)).astype(np.float32))
    u = jnp.asarray(rng.standard_normal((v_r, n)).astype(np.float32))
    got = bsr_sddmm(kt, u, c_bsr, interpret=True)
    want = bsr_sddmm_ref(kt, u, c_bsr)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
