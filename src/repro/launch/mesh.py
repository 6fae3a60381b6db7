"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before first jax init; everything else
sees the real device count).

Topology: TPU v5e pods of 256 chips (16x16 ICI torus). Single-pod mesh is
(data=16, model=16); multi-pod adds a leading "pod" axis over DCN. TP stays
inside a pod (ICI); only data-parallel gradient reductions cross pods —
the DCN-friendly layout (optionally int8-compressed, runtime/compression).
"""
from __future__ import annotations

import jax

from repro.runtime.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_dev_mesh(n_devices: int | None = None, tp: int = 1):
    """Small mesh for tests/examples on whatever devices exist."""
    n = n_devices or len(jax.devices())
    assert n % tp == 0
    return make_mesh((n // tp, tp), ("data", "model"))


# TPU runtime flags the real launch would set (documented here; no-ops on
# the CPU dry-run container):
TPU_XLA_FLAGS = " ".join([
    "--xla_tpu_enable_latency_hiding_scheduler=true",   # overlap comm/compute
    "--xla_tpu_megacore_fusion_allow_ags=true",
    "--xla_enable_async_collective_permute=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
])
