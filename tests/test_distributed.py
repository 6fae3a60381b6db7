"""Distributed Sinkhorn correctness on a multi-(fake-)device mesh.

Runs in a subprocess so XLA_FLAGS device-count never pollutes the main test
process (smoke tests must see exactly 1 device).
"""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax, jax.numpy as jnp
    from repro.runtime.sharding import make_mesh
    from repro.data.corpus import make_corpus, shard_balanced
    from repro.core import one_to_many, select_support
    from repro.core.sparse import padded_docs_to_dense
    from repro.core.distributed import (sinkhorn_wmd_dense_distributed,
                                        sinkhorn_wmd_sparse_distributed)

    assert len(jax.devices()) == 8
    c = make_corpus(vocab_size=512, embed_dim=16, n_docs=64, n_queries=1,
                    seed=2)
    q = c.queries[0]
    ref = np.asarray(one_to_many(q, c.docs, c.vecs, lam=8.0, n_iter=40,
                                 impl="sparse"))
    r, vs, _ = select_support(q, c.vecs)

    for shape, names in (((2, 4), ("data", "model")),
                         ((2, 2, 2), ("pod", "data", "model"))):
        mesh = make_mesh(shape, names)
        cd = jnp.asarray(padded_docs_to_dense(c.docs, 512))
        dd = np.asarray(sinkhorn_wmd_dense_distributed(
            r, vs, jnp.asarray(c.vecs), cd, 8.0, 40, mesh))
        assert np.abs(dd - ref).max() < 1e-3, ("dense", names)
        for vp in (False, True):
            ds = np.asarray(sinkhorn_wmd_sparse_distributed(
                r, vs, jnp.asarray(c.vecs), c.docs, 8.0, 40, mesh,
                vshard_precompute=vp))
            assert np.abs(ds - ref).max() < 1e-3, ("sparse", names, vp)

    # nnz-balanced sharding preserves the distance multiset
    sb = shard_balanced(c.docs, 8)
    mesh = make_mesh((2, 4), ("data", "model"))
    db = np.asarray(sinkhorn_wmd_sparse_distributed(
        r, vs, jnp.asarray(c.vecs), sb, 8.0, 40, mesh,
        vshard_precompute=True))
    assert np.allclose(np.sort(db), np.sort(ref), atol=1e-3)
    print("DISTRIBUTED_OK")
""")


@pytest.mark.slow
def test_distributed_all_variants():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "DISTRIBUTED_OK" in res.stdout, res.stdout + res.stderr
