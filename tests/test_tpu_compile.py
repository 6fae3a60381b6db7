"""Compile the main path's device programs for a described TPU v5e chip.

Nothing runs: each case lowers and compiles at the paper's widths (V=100k
vocabulary, w=300 embeddings, 43-word documents, 64-row query buckets,
Q=8 queries per chunk) for one chip of a described ``v5e:2x2`` topology.
The TPU compiler refuses here what interpret mode accepts: block shapes
that are not (8, 128)-aligned, layouts Mosaic cannot relayout, and
kernels that do not fit the chip's scoped VMEM.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

V, W, N_DOCS, L, VR, Q = 100_000, 300, 5_120, 43, 64, 8
N_GROUP = 1_250      # one of build_index's four nnz groups of paper_corpus


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described device are written to the persistent cache
    # but cannot be read back without the chip; keep them out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _cdist_exp():
    from repro.kernels.ops import cdist_exp
    fn = functools.partial(cdist_exp, lam=1.0, k_only=True, interpret=False)
    return fn, [((VR, W), jnp.float32), ((V, W), jnp.float32),
                ((VR,), jnp.float32)]


def _rwmd_min_cdist():
    from repro.kernels.ops import rwmd_min_cdist
    fn = functools.partial(rwmd_min_cdist, interpret=False)
    return fn, [((Q, VR, W), jnp.float32), ((Q, VR), jnp.float32),
                ((V, W), jnp.float32)]


def _fused_batched(tol):
    from repro.kernels.ops import sinkhorn_fused_all_batched

    def fn(g, val, r, resmask, mask):
        return sinkhorn_fused_all_batched(
            g, val, r, 1.0, 15, interpret=False, tol=tol, resmask=resmask,
            with_iters=True, mask=mask)
    return fn, [((Q, VR, N_DOCS, L), jnp.float32), ((N_DOCS, L), jnp.float32),
                ((Q, VR), jnp.float32), ((Q, N_DOCS), jnp.float32),
                ((Q, VR), jnp.float32)]


def _compute_kq():
    from repro.core.index import _compute_kq
    fn = functools.partial(_compute_kq, lam=1.0)
    return fn, [((Q, VR), jnp.int32), ((Q, VR), jnp.float32),
                ((V, W), jnp.float32), ((V,), jnp.float32)]


def _gather_g():
    from repro.core.index import _gather_g
    return _gather_g, [((Q, V, VR), jnp.float32), ((N_GROUP, L), jnp.int32)]


def _solve_gathered(tol):
    from repro.core.index import _solve_gathered

    def fn(g, mq, idx, val, r, mask, qdoc_mask):
        return _solve_gathered(g, mq, idx, val, r, mask, 1.0, 15, tol=tol,
                               scope="query", qdoc_mask=qdoc_mask)
    return fn, [((Q, N_GROUP, L, VR), jnp.float32),
                ((Q, V, VR), jnp.float32), ((N_GROUP, L), jnp.int32),
                ((N_GROUP, L), jnp.float32), ((Q, VR), jnp.float32),
                ((Q, VR), jnp.float32), ((Q, N_GROUP), jnp.bool_)]


CASES = {
    "cdist_exp": (_cdist_exp, True),
    "rwmd_min_cdist": (_rwmd_min_cdist, True),
    "sinkhorn_fused_all_batched": (functools.partial(_fused_batched, None),
                                   True),
    "sinkhorn_fused_all_batched_tol": (functools.partial(_fused_batched,
                                                         1e-3), True),
    "compute_kq": (_compute_kq, False),
    "gather_g": (_gather_g, False),
    "solve_gathered": (functools.partial(_solve_gathered, None), False),
    "solve_gathered_tol": (functools.partial(_solve_gathered, 1e-3), False),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_compiles_for_one_v5e_chip(case, one_chip):
    make, is_kernel = CASES[case]
    fn, shapes = make()
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    # a Pallas kernel reaches the chip as a Mosaic custom call; the
    # interpret-mode lowering would have inlined it as plain XLA instead
    assert ("tpu_custom_call" in text) == is_kernel, case
    stats = compiled.memory_analysis()
    assert stats.argument_size_in_bytes > 0


def _compile_resident(vocab, n_group, length, b, q, one_chip):
    """Compile the gather and the resident solve of one nnz group of
    ``n_group`` docs of ELL width ``length`` against a chunk of ``q``
    queries staged at width ``b``: the gather hands its tile over in the
    layout the kernel reads, so no copy of the tile lies between them."""
    from repro.core.index import _gather_g
    from repro.kernels.sddmm_spmm import sinkhorn_resident

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    gather = jax.jit(functools.partial(_gather_g, layout="qlbn",
                                       block_n=128)).lower(
        spec((q, vocab, b)), spec((n_group, length), jnp.int32)).compile()
    tile = gather.out_info
    assert tile.shape == (q, length, b, -(-n_group // 128) * 128)
    solve = jax.jit(functools.partial(sinkhorn_resident, lam=10.0,
                                      n_iter=15)).lower(
        spec(tile.shape), spec((n_group, length)), spec((q, b)),
        spec((q, b))).compile()
    assert "tpu_custom_call" in solve.as_text()
    handed, taken = gather.output_formats, solve.input_formats[0][0]
    assert handed.layout == taken.layout, (handed, taken)
    assert taken.layout.major_to_minor == (0, 1, 2, 3)


@pytest.mark.parametrize("q", [1, 2, 4])
@pytest.mark.parametrize("b", [24, 32, 40, 48])
@pytest.mark.parametrize("length", [25, 32, 42, 96])
def test_resident_solve_compiles_for_one_v5e_chip(length, b, q, one_chip):
    """The engine's TPU solve at the one-to-many scan's shapes: one nnz
    group of 1,250 docs, each of its ELL widths, each staged query width
    and each padded chunk size."""
    _compile_resident(V, N_GROUP, length, b, q, one_chip)


@pytest.mark.parametrize("q", [1, 2, 4])
@pytest.mark.parametrize("b", [128, 192])
def test_resident_solve_compiles_at_news_widths(b, q, one_chip):
    """The resident solve's largest tiles: Kusner's 20NEWS deployment
    (29,671 words, 11,293 docs in four nnz groups of 2,824, the widest of
    ELL width 192) against queries staged at 128 and 192 words, the
    widest of the two largest buckets. A (192, 192, 128) fp32 block is 18.9 MB, so this checks that
    two buffers of it and the body's temporaries fit the scoped VMEM."""
    _compile_resident(29_671, 2_824, 192, b, q, one_chip)


def test_shard_merge_compiles_for_four_v5e_chips(topo):
    """Sharded search's cross-shard top-k merge over the 2x2 host: one
    all-gather and no other collective."""
    import re

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core.shard_index import _build_merge
    n_shards, k = 4, 10
    mesh = Mesh(np.asarray(topo.devices[:n_shards]), ("shard",))
    packed = jax.ShapeDtypeStruct((n_shards, Q, 2 * k), jnp.float32,
                                  sharding=NamedSharding(mesh, P("shard")))
    text = _build_merge(mesh, n_shards, k).lower(packed).compile().as_text()
    ops = re.findall(r"= \S+ (all-gather|all-reduce|all-to-all|"
                     r"reduce-scatter|collective-permute)(?:-start)?\(", text)
    assert ops == ["all-gather"], ops
