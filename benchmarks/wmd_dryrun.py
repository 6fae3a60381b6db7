"""Paper-scale distributed WMD dry-run + roofline (the paper's own workload
as a production-mesh cell).

V=100k vocab, w=300 embeddings, N=5120 docs (5000 padded to the 512-chip
doc sharding), v_r=43 (the paper's larger query), 15 iterations — lowered
and compiled for the (16,16) mesh; roofline terms reported like the LM
cells. Run standalone (sets the 512-device flag before jax import):

    PYTHONPATH=src python -m benchmarks.wmd_dryrun
"""
import os

# placeholder CPU devices: the dry-run compiles, it never claims a chip
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=512")

import json

import jax
import jax.numpy as jnp


def main(out=print) -> None:
    from repro.core.distributed import sinkhorn_wmd_sparse_distributed
    from repro.core.sparse import PaddedDocs
    from repro.launch.mesh import make_production_mesh
    from repro.runtime.analysis import (hlo_collective_bytes, jaxpr_cost,
                                        roofline_terms)

    v, w, n, l_max, v_r = 100_000, 300, 5120, 64, 43
    lam, n_iter = 10.0, 15
    mesh = make_production_mesh()
    n_chips = mesh.devices.size

    r = jax.ShapeDtypeStruct((v_r,), jnp.float32)
    vecs_sel = jax.ShapeDtypeStruct((v_r, w), jnp.float32)
    vecs = jax.ShapeDtypeStruct((v, w), jnp.float32)
    docs = PaddedDocs(idx=jax.ShapeDtypeStruct((n, l_max), jnp.int32),
                      val=jax.ShapeDtypeStruct((n, l_max), jnp.float32))

    def run(r, vecs_sel, vecs, idx, val):
        return sinkhorn_wmd_sparse_distributed(
            r, vecs_sel, vecs, PaddedDocs(idx=idx, val=val), lam, n_iter,
            mesh, vshard_precompute=True)

    with mesh:
        lowered = jax.jit(run).lower(r, vecs_sel, vecs, docs.idx, docs.val)
        compiled = lowered.compile()

    cost = jaxpr_cost(run, r, vecs_sel, vecs, docs.idx, docs.val)
    coll = hlo_collective_bytes(compiled.as_text())
    # memory: per chip = cdist slab (v_r x V/16) x3 arrays + G tiles x2 reads
    hbm = (3 * v_r * (v / 16) * 4            # M,K,KM local slabs
           + 3 * v_r * (n / n_chips) * l_max * 4 * 2)
    rt = roofline_terms(cost["flops"], hbm * n_chips,
                        coll["total_bytes_tpu"], n_chips,
                        model_flops=2.0 * v_r * v * w   # cdist is the floor
                        + 4.0 * n * l_max * v_r * n_iter)
    ma = compiled.memory_analysis()
    out(f"wmd.paper_scale.512chips,"
        f"{max(rt['compute_s'], rt['memory_s'], rt['collective_s'])*1e6:.1f},"
        f"dominant={rt['dominant']};collective_bytes="
        f"{coll['total_bytes']/1e6:.1f}MB;mem_gb="
        f"{(ma.argument_size_in_bytes + ma.temp_size_in_bytes)/2**30:.2f}")
    out(json.dumps({k: round(val, 8) if isinstance(val, float) else val
                    for k, val in rt.items()}))


if __name__ == "__main__":
    main()
