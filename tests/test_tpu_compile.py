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


def _kernel_route(precision):
    """``one_to_many(impl="kernel")``: the Pallas cdist, the row gather
    and the resident solve for one query of 43 words (padded to 48)."""
    from repro.core.sparse import PaddedDocs
    from repro.core.wmd import _kernel_wmd

    def fn(r, vecs_sel, vecs, idx, val):
        return _kernel_wmd(r, vecs_sel, vecs, PaddedDocs(idx, val), 1.0, 15,
                           precision=precision, interpret=False)
    return fn, [((43,), jnp.float32), ((43, W), jnp.float32),
                ((V, W), jnp.float32), ((N_DOCS, L), jnp.int32),
                ((N_DOCS, L), jnp.float32)]


def _compute_kq():
    from repro.core.index import _compute_kq
    fn = functools.partial(_compute_kq, lam=1.0)
    return fn, [((Q, VR), jnp.int32), ((Q, VR), jnp.float32),
                ((V, W), jnp.float32), ((V,), jnp.float32)]


def _gather_g():
    from repro.core.index import _gather_g
    return _gather_g, [((Q, V, VR), jnp.float32), ((N_GROUP, L), jnp.int32)]


def _compute_kq_rows():
    from repro.core.index import _compute_kq
    fn = functools.partial(_compute_kq, lam=1.0, vocab_major=True)
    return fn, [((Q, VR), jnp.int32), ((Q, VR), jnp.float32),
                ((V, W), jnp.float32), ((V,), jnp.float32)]


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
    "kernel_route": (functools.partial(_kernel_route, "fp32"), True),
    "kernel_route_log": (functools.partial(_kernel_route, "log"), True),
    "kernel_route_bf16": (functools.partial(_kernel_route, "bf16"), True),
    "compute_kq": (_compute_kq, False),
    "compute_kq_rows": (_compute_kq_rows, False),
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
    queries staged at width ``b``: the gather copies whole (Q*B) rows of
    the K block's vocabulary-major (V, Q*B) hand-over, and hands its tile
    over in the layout the kernel reads, so no copy of the tile lies
    between them."""
    import re

    from repro.core.index import _gather_g
    from repro.kernels.sddmm_spmm import sinkhorn_resident

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    gather = jax.jit(functools.partial(_gather_g, layout="qlbn",
                                       block_n=128, q=q)).lower(
        spec((vocab, q * b)), spec((n_group, length), jnp.int32)).compile()
    slices = re.findall(r"= \S+ gather\(.*slice_sizes=\{([\d,]+)\}",
                        gather.as_text())
    assert slices == [f"1,{q * b}"], slices
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


# Kusner's 20NEWS deployment: 29,671 words, 11,293 docs in four nnz groups
# of 2,824 (the last 2,821) of ELL widths 45, 64, 89 and 192, searched
# in candidate tiles of 512 docs by chunks of up to 4 queries staged at
# up to 192 words, with k = 19
NEWS_V, NEWS_N, NEWS_TILE, NEWS_K, NEWS_C = 29_671, 11_293, 512, 19, 106
NEWS_GROUPS = ((2_824, 45), (2_824, 64), (2_824, 89), (2_821, 192))


@pytest.mark.parametrize("length", [length for _, length in NEWS_GROUPS])
def test_news_survivor_tile_compiles(length, one_chip):
    """One survivor tile of each 20NEWS doc group at B = 192, Q = 4: the
    tile's rows gathered from its group, the gather and the resident
    solve."""
    from repro.core.index import _tile_rows

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    n_group = dict((l, n) for n, l in NEWS_GROUPS)[length]
    rows = jax.jit(_tile_rows).lower(
        spec((n_group, length), jnp.int32), spec((n_group, length)),
        spec((NEWS_TILE,), jnp.int32), spec((), jnp.int32)).compile()
    assert [o.shape for o in rows.out_info] == [(NEWS_TILE, length)] * 2
    _compile_resident(NEWS_V, NEWS_TILE, length, 192, 4, one_chip)


def test_news_few_docs_tile_compiles(one_chip):
    """A step's seeds as one tile of FEW_DOCS docs across the 20NEWS
    groups, its rows gathered from the corpus' ELL arrays at the widest
    group's width (192), at B = 192 and Q = 4."""
    from repro.core.index import FEW_DOCS, _tile_rows

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    rows = _tile_rows.lower(
        spec((NEWS_N, 192), jnp.int32), spec((NEWS_N, 192)),
        spec((FEW_DOCS,), jnp.int32), spec((), jnp.int32),
        width=192).compile()
    assert [o.shape for o in rows.out_info] == [(FEW_DOCS, 192)] * 2
    _compile_resident(NEWS_V, FEW_DOCS, 192, 192, 4, one_chip)


def _news_groups(spec):
    return tuple((spec((n, length), jnp.int32), spec((n, length)),
                  spec((n,), jnp.int32)) for n, length in NEWS_GROUPS)


def test_news_cascade_bounds_compile(one_chip):
    """The cascade's bound programs for 20NEWS at a staging width of 192
    words and a Q pad of 4: probe, seed pick, threshold, and the dense
    survivor pass (WCD, RWMD min-cdist and bounds, the fused keep)."""
    from repro.core.index import _threshold
    from repro.core.prune import (_cascade_keep, _probe_dists, _rwmd_minm,
                                  _rwmd_rows, _seed_pick, _wcd_bounds)

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    q, b, n = 4, 192, NEWS_N
    sup, mask = spec((q, b), jnp.int32), spec((q, b))
    vecs = spec((NEWS_V, W))
    cases = [
        (_probe_dists, (sup, mask, mask, vecs, spec((NEWS_C, W)))),
        (_wcd_bounds, (spec((q, W)), spec((n, W)))),
        (_rwmd_minm, (sup, mask, vecs)),
        (_rwmd_rows, (spec((q, n)), spec((q, NEWS_V)), _news_groups(spec))),
        (functools.partial(_seed_pick, k=NEWS_K),
         ((spec((q, n)), spec((q, n))),)),
        (functools.partial(_threshold, k=NEWS_K),
         (spec((q, NEWS_K * q)), spec((), jnp.int32), spec(()))),
        (_cascade_keep,
         ((spec((q, n)), spec((q, n))), spec((q, NEWS_C)), spec((NEWS_C,)),
          spec((n,), jnp.int32), spec((q,)), spec((n,), jnp.bool_))),
    ]
    for fn, args in cases:
        compiled = jax.jit(fn).lower(*args).compile()
        assert compiled.memory_analysis().argument_size_in_bytes > 0
