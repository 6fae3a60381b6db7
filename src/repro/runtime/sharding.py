"""Sharding rules: params / optimizer state / activations / caches ->
PartitionSpecs for the production mesh (DESIGN.md §6).

Megatron-style TP over ``model``; DP over ``data`` (+ ``pod``); vocab-sharded
embeddings and logits; expert parallelism for MoE; sequence-sharded KV cache
for the long-context decode cells. A ``stage`` axis hook is reserved for PP
(unused at 512 chips — DP x TP covers every assigned arch).

Rules are name-based over the param pytree paths — one table instead of
per-module annotations, auditable in one screen.
"""
from __future__ import annotations

import re
from typing import Any

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

# (regex over 'a/b/c' path, spec builder(ndim) -> PartitionSpec)
# Specs are written for the LAST dims; leading stacked layer/group dims are
# replicated (None-padded on the left automatically).
_RULES: list[tuple[str, tuple]] = [
    (r"embed$",                    ("model", None)),
    (r"lm_head$",                  (None, "model")),
    # attention
    (r"attn/w[qkv]$",              (None, "model")),
    (r"attn/wo$",                  ("model", None)),
    (r"attn/b[qkv]$",              ("model",)),
    # dense mlp / shared expert / rwkv channel-mix
    (r"(mlp|cmix|shared)/w_(gate|up|in)$", (None, "model")),
    (r"(mlp|cmix|shared)/w_(down|out)$",   ("model", None)),
    # moe: experts over model (EP); router replicated
    (r"moe/router$",               (None, None)),
    (r"moe/w_(gate|up)$",          ("model", None, None)),
    (r"moe/w_down$",               ("model", None, None)),
    # mamba2: heads/d_inner over model; B/C small -> replicated
    (r"mamba/w_(z|x)$",            (None, "model")),
    (r"mamba/w_bc$",               (None, None)),
    (r"mamba/w_dt$",               (None, "model")),
    (r"mamba/conv_x$",             (None, "model")),
    (r"mamba/conv_bias_x$",        ("model",)),
    (r"mamba/(conv_bc|conv_bias_bc)$", (None,)),
    (r"mamba/(a_log|d_skip|dt_bias)$", ("model",)),
    (r"mamba/norm_scale$",         ("model",)),
    (r"mamba/out_proj$",           ("model", None)),
    # rwkv6 time-mix
    (r"tmix/w[rkvg]$",             (None, "model")),
    (r"tmix/wo$",                  ("model", None)),
    (r"tmix/w0$",                  ("model",)),
    (r"tmix/w1$",                  (None, None)),
    (r"tmix/w2$",                  (None, "model")),
    (r"tmix/u$",                   ("model", None)),
    (r"tmix/ln_scale$",            ("model",)),
    (r"tmix/mu$",                  (None, None)),
    # norms & everything small
    (r".*",                        ()),
]


def make_mesh(axis_shapes, axis_names, devices=None) -> Mesh:
    """``jax.make_mesh`` with Auto axes. JAX 0.9 makes Explicit axes the
    default, under which every jit over the mesh must run inside
    ``jax.set_mesh`` and ops such as ``jnp.repeat`` need an explicit
    ``out_sharding``; this code base shards through ``shard_map`` and
    ``NamedSharding`` and lets the compiler propagate the rest."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def _path_str(path) -> str:
    parts = []
    for e in path:
        if hasattr(e, "key"):
            parts.append(str(e.key))
        elif hasattr(e, "idx"):
            parts.append(str(e.idx))
    return "/".join(parts)


def param_spec_for(path: str, ndim: int) -> P:
    for pat, spec in _RULES:
        if re.search(pat, path):
            spec = tuple(spec)
            if len(spec) > ndim:          # scalar-ish leaf
                spec = spec[-ndim:] if ndim else ()
            pad = (None,) * (ndim - len(spec))
            return P(*(pad + spec))
    return P()


def param_specs(params, fsdp_axes: tuple = ()) -> Any:
    """Pytree of PartitionSpec matching the params pytree.

    ``fsdp_axes`` (e.g. ('data',) or ('pod','data')): additionally shard
    every large leaf over these axes on its first still-unsharded,
    divisible dim — ZeRO-3/FSDP. XLA all-gathers weights per layer inside
    the scan (the MaxText pattern); required for the >=14B archs where
    params+opt exceed HBM under TP-only sharding (DESIGN.md §6)."""
    import numpy as np

    def nshards(axes) -> int:
        n = 1
        for a in axes:
            n *= _AXIS_SIZES.get(a, 1)
        return n

    def spec_of(path, x):
        base = param_spec_for(_path_str(path), x.ndim)
        if not fsdp_axes or int(np.prod(x.shape)) < (1 << 20):
            return base
        need = nshards(fsdp_axes)
        entries = list(base) + [None] * (x.ndim - len(base))
        # search from the LAST dim: leading dims of stacked per-layer params
        # are the lax.scan axis — sharding the scan axis forces XLA to
        # re-gather the whole stack inside inner loops (measured 9.9 TB of
        # all-gathers on qwen2.5 before this fix; EXPERIMENTS.md §Perf #1)
        for i in reversed(range(len(entries))):
            if entries[i] is None and x.shape[i] % need == 0 \
                    and x.shape[i] >= need:
                entries[i] = fsdp_axes if len(fsdp_axes) > 1 else fsdp_axes[0]
                return P(*entries)
        return base

    return jax.tree_util.tree_map_with_path(spec_of, params)


# set by launchers before building specs (mesh axis name -> size)
_AXIS_SIZES: dict[str, int] = {"pod": 2, "data": 16, "model": 16}


def set_axis_sizes(mesh: Mesh) -> None:
    global _AXIS_SIZES
    _AXIS_SIZES = dict(zip(mesh.axis_names, mesh.devices.shape))


def opt_state_specs(params_specs, zero1: bool = False) -> Any:
    """AdamW state specs: step replicated; m/v mirror the params.

    ``zero1=True`` additionally shards any replicated-leading-dim moment
    over 'data' (ZeRO-1-style optimizer state partitioning, beyond-paper
    memory optimization; params stay as-is, update gathers are XLA's).
    """
    from repro.optim.adamw import AdamWState

    def z1(spec: P) -> P:
        if not zero1 or len(spec) == 0:
            return spec
        if spec[0] is None:
            return P(*(("data",) + tuple(spec[1:])))
        return spec

    mv = jax.tree.map(z1, params_specs,
                      is_leaf=lambda x: isinstance(x, P))
    return AdamWState(step=P(), m=mv, v=mv)


def batch_spec(mesh: Mesh) -> P:
    """(B, T) token batches: batch over every data-ish axis."""
    axes = tuple(a for a in mesh.axis_names if a != "model")
    return P(axes)


def activation_spec(mesh: Mesh) -> P:
    axes = tuple(a for a in mesh.axis_names if a != "model")
    return P(axes, None, None)


def cache_specs(cache, mesh: Mesh, seq_shard: bool = False) -> Any:
    """Serve-cache specs. KV caches (L, B, H_kv, S, D): batch over data,
    heads over model. ``seq_shard=True`` (long_500k, batch=1): shard the
    cache SEQUENCE dim over data instead (sequence parallelism)."""
    data_axes = tuple(a for a in mesh.axis_names if a != "model")

    def spec(path, x):
        name = _path_str(path)
        nd = x.ndim
        if name in ("k", "v"):
            if seq_shard:
                # (L?, B, H, S, D) -> S over data, H over model
                s = [None] * nd
                s[-2] = data_axes
                s[-3] = "model"
                return P(*s)
            s = [None] * nd
            s[-4] = data_axes
            s[-3] = "model"
            return P(*s)
        if name in ("wkv", "ssm", "ssm_rem"):
            # (..., B, H, N/D, P): B over data, H over model
            s = [None] * nd
            s[-4] = data_axes if not seq_shard else None
            s[-3] = "model"
            return P(*s)
        if name in ("conv", "conv_rem", "shift"):
            s = [None] * nd
            s[-3] = data_axes if not seq_shard else None
            return P(*s)
        return P()

    return jax.tree_util.tree_map_with_path(spec, cache)


def shardings(mesh: Mesh, tree_of_specs) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_of_specs,
                        is_leaf=lambda x: isinstance(x, P))


# ------------------------------------------------------- corpus doc sharding
def ensure_host_devices(n: int) -> int:
    """Make at least ``n`` devices visible.

    Under ``JAX_PLATFORMS=cpu`` this forces ``n`` host-platform CPU
    devices: XLA only honors ``--xla_force_host_platform_device_count``
    if it is set BEFORE the backend initializes, so this merges the flag
    into ``XLA_FLAGS`` and then touches ``jax.devices()``; call it before
    the first jax array operation (``launch/serve.py --shards N`` and
    ``examples/wmd_search.py --shards N`` do). On any other platform it
    forces nothing: the real devices must number at least ``n``.
    Raises if too few devices are visible — on the CPU because the
    backend was already initialized (the flag cannot apply
    retroactively). Returns the visible device count.
    """
    import os

    n = int(n)
    host_only = (jax.config.jax_platforms or "") == "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if host_only and n > 1 \
            and "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}".strip())
    count = jax.device_count()
    if count < n:
        hint = (f"set XLA_FLAGS=--xla_force_host_platform_device_count={n} "
                f"in the environment before the process does any jax work"
                if host_only else
                "fake host devices are only made under JAX_PLATFORMS=cpu")
        raise RuntimeError(
            f"need {n} devices but the {jax.default_backend()} backend "
            f"has {count}; {hint}")
    return count


def corpus_mesh(n_shards: int, devices=None) -> Mesh:
    """1-D mesh over the doc-shard axis for
    :class:`repro.core.shard_index.ShardedCorpusIndex` — distinct from
    the LM param mesh above: corpus serving shards DATA (docs), nothing
    model-parallel."""
    import numpy as np

    devs = (list(devices) if devices is not None
            else jax.devices()[:int(n_shards)])
    if len(devs) < int(n_shards):
        raise RuntimeError(f"corpus_mesh({n_shards}) needs {n_shards} "
                           f"devices, found {len(devs)}; see "
                           f"ensure_host_devices")
    return Mesh(np.asarray(devs[:int(n_shards)]), axis_names=("shard",))
