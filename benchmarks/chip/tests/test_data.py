"""The data generator makes what the configuration files state."""
import json
from pathlib import Path

import numpy as np
import pytest

import data

CONFIGS = Path(__file__).resolve().parent.parent / "configs"
SEED = 2**31 + 7


def _config(name):
    with open(CONFIGS / f"{name}.json") as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["paper-dbpedia-5k", "kusner-20news"])
def test_sizes_and_word_counts_match_the_config(name):
    cfg = _config(name)
    corpus = data.make_corpus(cfg, SEED)
    docs = corpus.docs
    assert corpus.vecs.shape == (cfg["vocab_size"], cfg["embed_dim"])
    assert docs.ids.shape[0] == cfg["n_docs"]
    # every doc holds exactly its drawn number of unique words
    live = (docs.val > 0).sum(axis=1)
    assert np.array_equal(live, docs.nnz)
    for row in range(0, cfg["n_docs"], 997):
        ids = docs.ids[row, :docs.nnz[row]]
        assert len(set(ids.tolist())) == docs.nnz[row]
    assert np.allclose(docs.val.sum(axis=1), 1.0, atol=1e-5)
    spec = cfg["doc_words"]
    assert docs.nnz.min() >= spec["min"] and docs.nnz.max() <= spec["max"]
    if spec["dist"] == "lognormal":
        assert abs(docs.nnz.mean() / spec["mean"] - 1) < 0.03
    q = data.make_queries(cfg, corpus.vocab, SEED, 256)
    qs = cfg["query_words"]
    assert q.nnz.min() >= qs["min"] and q.nnz.max() <= qs["max"]


def test_lengths_are_one_multiset_for_every_seed():
    cfg = _config("paper-dbpedia-5k")
    vocab = data.make_vocab(cfg, 1)
    a = data.make_queries(cfg, vocab, 1, 64)
    b = data.make_queries(cfg, vocab, 2, 64)
    assert sorted(a.nnz) == sorted(b.nnz)
    assert not np.array_equal(a.ids, b.ids)
    # the scan's pool: the same lengths in the same order, other words
    a = data.make_queries(cfg, vocab, 1, 64, fixed_order=True)
    b = data.make_queries(cfg, vocab, 2, 64, fixed_order=True)
    assert np.array_equal(a.nnz, b.nnz)
    assert not np.array_equal(a.ids, b.ids)


def test_same_seed_same_data():
    cfg = _config("kusner-20news")
    cfg = dict(cfg, vocab_size=2000, n_docs=50)
    a, b = data.make_corpus(cfg, 5), data.make_corpus(cfg, 5)
    assert np.array_equal(a.docs.ids, b.docs.ids)
    assert np.array_equal(np.asarray(a.vecs), np.asarray(b.vecs))


def _solved(cfg, seed, n_queries=8, k=19):
    from repro.core import WmdEngine, build_index
    from repro.core.sparse import PaddedDocs
    corpus = data.make_corpus(cfg, seed)
    engine = WmdEngine(build_index(PaddedDocs(idx=corpus.docs.ids,
                                              val=corpus.docs.val),
                                   corpus.vecs), **cfg["engine"])
    q = data.make_queries(cfg, corpus.vocab, seed, n_queries)
    rows = [q.dense(i, cfg["vocab_size"]) for i in range(n_queries)]
    res = engine.search(rows, k, prune="ivf+wcd+rwmd", nprobe=None)
    return int(res.solved.sum())


def test_topical_embeddings_let_the_cascade_prune():
    """kusner-20news's generator at a CPU size against an i.i.d. Gaussian
    build of the same sizes and distance scale: the cascade solves fewer
    docs on the topical one."""
    news = _config("kusner-20news")
    small = dict(news, vocab_size=3000, n_docs=600,
                 doc_words=dict(news["doc_words"], max=96),
                 query_words=dict(news["query_words"], max=96))
    # the same word-distance scale as the topical build's cross-topic one
    gauss = dict(small, embedding={"kind": "gaussian", "scale": 0.41})
    topical, vacuous = _solved(small, 3), _solved(gauss, 3)
    print(f"cascade solved {topical} (topical) vs {vacuous} (Gaussian) "
          f"of {8 * small['n_docs']} query-doc pairs")
    assert topical < 0.5 * vacuous
