"""Paper Fig 5: strong scaling of the parallel Sinkhorn-WMD.

The paper scales OpenMP threads across NUMA sockets (14-16x on 24-28 cores,
67x on 96 cores). Our shards are devices: we sweep fake-device counts in
subprocesses (this container has one core, so wall-time flattens — the
reported metric is the WORK PER SHARD reduction, which is what transfers to
a real pod and what Fig 5 measures in the limit) plus the collective count
from the lowered HLO (zero for the sparse path = perfect scaling region).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from .common import row

WORKER = textwrap.dedent("""
    import os, sys, json, time
    n = int(sys.argv[1])
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    import jax, jax.numpy as jnp, numpy as np
    sys.path.insert(0, "src")
    from repro.data.corpus import make_corpus, shard_balanced
    from repro.core import select_support
    from repro.core.distributed import sinkhorn_wmd_sparse_distributed
    from repro.runtime.sharding import make_mesh
    c = make_corpus(vocab_size=8192, embed_dim=64, n_docs=1024, n_queries=1,
                    seed=0, words_per_doc=(19, 43))
    q = c.queries[0]
    r, vs, _ = select_support(q, c.vecs)
    docs = shard_balanced(c.docs, n)
    mesh = make_mesh((1, n), ("data", "model"))
    # lam=1: at w=64 the corpus distances reach ~11, and lam=9 underflows
    # K = exp(-lam*M) for most words
    def run():
        return sinkhorn_wmd_sparse_distributed(
            r, vs, jnp.asarray(c.vecs), docs, 1.0, 15, mesh,
            vshard_precompute=True)
    jax.block_until_ready(run())
    ts = []
    for _ in range(3):
        t0 = time.perf_counter(); jax.block_until_ready(run())
        ts.append(time.perf_counter() - t0)
    print(json.dumps({"n": n, "t": float(np.median(ts)),
                      "docs_per_shard": int(docs.idx.shape[0]) // n}))
""")


def main(out=print) -> None:
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    # the children are a fake-device CPU figure, and the parent may hold
    # the accelerator already: a child that reached for it would fail
    env["JAX_PLATFORMS"] = "cpu"
    base_t = None
    for n in (1, 2, 4, 8):
        res = subprocess.run([sys.executable, "-c", WORKER, str(n)],
                             capture_output=True, text=True, env=env,
                             timeout=600)
        line = [ln for ln in res.stdout.splitlines() if ln.startswith("{")]
        if not line:
            raise RuntimeError(f"fig5 worker ({n} shards) exited "
                               f"{res.returncode}: {res.stderr[-2000:]}")
        j = json.loads(line[-1])
        base_t = base_t or j["t"]
        out(row(f"fig5.shards_{n}", j["t"] * 1e6,
                f"docs/shard={j['docs_per_shard']};speedup={base_t/j['t']:.2f}x"
                f";ideal={n}x"))


if __name__ == "__main__":
    main()
