"""Platform resolution at the entry points: the persistent compile cache's
placement, Pallas interpret mode, and host-device forcing.

The cache cases run in subprocesses: enabling the cache is process-global,
and the test session itself keeps it off.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CACHE_SCRIPT = textwrap.dedent("""
    import os, sys
    import jax, jax.numpy as jnp
    from repro.runtime import compile_cache
    if sys.argv[2]:
        compile_cache.REPO_CACHE_DIR = compile_cache.Path(sys.argv[2])
    where = compile_cache.enable_compile_cache()
    assert where == sys.argv[1], (where, sys.argv[1])
    jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()
    print("CACHE_AT", where)
""")


@pytest.mark.parametrize("from_env", [True, False],
                         ids=["env_dir", "repo_dir"])
def test_compile_cache_lands_in_one_place(tmp_path, from_env):
    """With ``JAX_COMPILATION_CACHE_DIR`` set, entries land there and the
    fixed repo directory stays untouched; unset, they land in the fixed
    directory (redirected to ``tmp_path`` here)."""
    env_dir, repo_dir = tmp_path / "env", tmp_path / "repo"
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    want = env_dir if from_env else repo_dir
    res = subprocess.run(
        [sys.executable, "-c", CACHE_SCRIPT, str(want), str(repo_dir)],
        env=env, capture_output=True, text=True, timeout=300, cwd=ROOT)
    assert f"CACHE_AT {want}" in res.stdout, res.stdout + res.stderr
    assert want.is_dir() and any(want.iterdir())
    other = repo_dir if from_env else env_dir
    assert not other.exists()


def test_interpret_follows_the_platform(monkeypatch):
    from repro.kernels import ops
    assert ops.resolve_interpret(None) is True          # CPU: interpret
    assert ops.resolve_interpret(False) is False
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    assert ops.resolve_interpret(None) is False         # TPU: Mosaic
    with pytest.raises(ValueError, match="interpret"):
        ops.resolve_interpret(True)


def test_host_devices_are_forced_only_on_cpu(monkeypatch):
    from repro.runtime import sharding
    assert sharding.ensure_host_devices(1) == jax.device_count()
    monkeypatch.setenv("XLA_FLAGS", "")
    # the backend is up already, so the setting is read and nothing else
    prev = jax.config.jax_platforms
    jax.config.update("jax_platforms", "tpu")
    try:
        with pytest.raises(RuntimeError, match="only made under"):
            sharding.ensure_host_devices(jax.device_count() + 1)
    finally:
        jax.config.update("jax_platforms", prev)
    assert "device_count" not in os.environ["XLA_FLAGS"]
