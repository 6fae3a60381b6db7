"""``correct`` comes out false for the control and for a broken timed
path, and true for the program as configured.

Every run here goes through ``harness.run_cell`` exactly as ``run.py``
drives it, minus its look for a TPU, at a size a CPU test can hold. The
limits are the chip cells' own (``limits/*.json``), so what fails here
is what those limits catch.
"""
import json
import time
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

import harness

HERE = Path(__file__).resolve().parent.parent
SEED = 2**31 + 101

# the chip cell's regime at a CPU size: word distances near 2.45, so
# lam * M near 24.5, where the bfloat16 control's error shows as on the chip
TINY = {
    "name": "tiny", "vocab_size": 2000, "embed_dim": 32, "n_docs": 300,
    "doc_words": {"dist": "lognormal", "mean": 12, "sigma": 0.4,
                  "min": 4, "max": 30},
    "query_words": {"dist": "uniform", "min": 5, "max": 20},
    "zipf_a": 1.0,
    "embedding": {"kind": "gaussian", "scale": 0.3},
    "engine": {"impl": "sparse", "lam": 10.0, "n_iter": 15,
               "precision": "fp32"},
}


def _limits(workload):
    with open(HERE / "limits" / f"{workload}.json") as f:
        return json.load(f)


def _cell(kind):
    with open(HERE / "traffic" / f"{kind}.json") as f:
        traffic = json.load(f)
    limits = dict(_limits(f"paper.{kind}"), checked_min=8)
    return harness.Cell(f"tiny.{kind}", TINY, traffic, limits, 1,
                        {0: [], 1: []})


def _run(kind, **kw):
    return harness.run_cell(_cell(kind), SEED, 1.5, False,
                            time.perf_counter(), **kw)


@pytest.mark.parametrize("kind", ["scan"])
def test_program_is_correct(kind):
    res = _run(kind)
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("kind", ["scan"])
def test_control_is_not_correct(kind):
    """The program's own bfloat16 path, the nearest precision below the
    configured float32."""
    res = _run(kind, engine_override={"precision": "bf16"})
    assert not res["correct"], res["checks"]


def _scan_half_batch(orig):
    def query_batch(self, queries):
        out = np.asarray(orig(self, queries[:(len(queries) + 1) // 2]))
        return jnp.asarray(np.concatenate([out, out])[:len(queries)])
    return query_batch


def _scan_altered(orig):
    def query_batch(self, queries):
        out = np.array(orig(self, queries))
        out[:, 0] *= 1.01
        return jnp.asarray(out)
    return query_batch


def _scan_stale(orig):
    def query_batch(self, queries):
        # every step answered with the first step's distances
        first = getattr(self, "_first_out", None)
        out = np.asarray(orig(self, queries))
        if first is None or first.shape != out.shape:
            self._first_out = first = out
        return jnp.asarray(first)
    return query_batch


@pytest.mark.parametrize("kind,method,fault", [
    ("scan", "query_batch", _scan_half_batch),
    ("scan", "query_batch", _scan_altered),
    ("scan", "query_batch", _scan_stale),
])
def test_broken_timed_path_is_not_correct(monkeypatch, kind, method, fault):
    from repro.core import WmdEngine
    monkeypatch.setattr(WmdEngine, method,
                        fault(getattr(WmdEngine, method)))
    res = _run(kind)
    assert not res["correct"], res["checks"]
