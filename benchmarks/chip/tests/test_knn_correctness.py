"""``correct`` for the kNN cell comes out true for the program as
configured, and false for the control and for each fault a search can
have.

Every run here goes through ``harness.run_cell`` exactly as ``run.py``
drives it, minus its look for a TPU, at a size a CPU test can hold, with
the chip cell's limits (``limits/news.knn.json``).
"""
import json
import time
from pathlib import Path

import numpy as np
import pytest

import harness

HERE = Path(__file__).resolve().parent.parent
SEED = 2**31 + 107

# the chip cell's regime at a CPU size: 300-wide topical word vectors of
# its scales (word distances near 2 within a topic and 10 across), lam 1
TINY = {
    "name": "tiny-news", "vocab_size": 3000, "embed_dim": 300,
    "n_docs": 400,
    "doc_words": {"dist": "lognormal", "mean": 24, "sigma": 0.5,
                  "min": 8, "max": 64},
    "query_words": {"dist": "lognormal", "mean": 14, "sigma": 0.5,
                    "min": 8, "max": 24},
    "zipf_a": 1.0,
    "embedding": {"kind": "topical", "topics": 20, "centre_scale": 0.41,
                  "noise_scale": 0.08, "topic_mix": 0.8},
    "engine": {"impl": "sparse", "lam": 1.0, "n_iter": 15,
               "precision": "fp32"},
}


def _cell():
    with open(HERE / "traffic" / "knn.json") as f:
        traffic = json.load(f)
    with open(HERE / "limits" / "news.knn.json") as f:
        limits = json.load(f)
    return harness.Cell("tiny.knn", TINY, traffic, limits, 1, {0: [], 1: []})


def _run(**kw):
    return harness.run_cell(_cell(), SEED, 1.5, False, time.perf_counter(),
                            **kw)


def test_program_is_correct():
    res = _run()
    assert res["correct"], res["checks"]
    assert res["checks"]["topk_miss"]["value"] == 0


def test_control_is_not_correct():
    """The program's own bfloat16 path, the nearest precision below the
    configured float32."""
    res = _run(engine_override={"precision": "bf16"})
    assert not res["correct"], res["checks"]


def _survivors_dropped(orig):
    def survivors(self, *args, **kw):
        # the top-k of the seeds alone
        return np.zeros(0, np.int32)
    return survivors


def _distance_altered(orig):
    def search(self, queries, *args, **kw):
        res = orig(self, queries, *args, **kw)
        return res._replace(distances=res.distances * 1.01)
    return search


def _stale(orig):
    def search(self, queries, *args, **kw):
        # every step answered with the first step's result
        res = orig(self, queries, *args, **kw)
        first = getattr(self, "_first_res", None)
        if first is None or first.indices.shape != res.indices.shape:
            self._first_res = first = res
        return first
    return search


@pytest.mark.parametrize("owner,method,fault", [
    ("CascadePruner", "survivors", _survivors_dropped),
    ("WmdEngine", "search", _distance_altered),
    ("WmdEngine", "search", _stale),
])
def test_broken_search_is_not_correct(monkeypatch, owner, method, fault):
    import repro.core as core
    cls = getattr(core, owner)
    monkeypatch.setattr(cls, method, fault(getattr(cls, method)))
    res = _run()
    assert not res["correct"], res["checks"]
