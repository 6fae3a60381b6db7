"""Seeded corpora and queries for the chip benchmark.

A configuration file (``configs/<name>.json``) fixes the deployment's
sizes; this module turns it and ``--seed`` into data:

- the embedding table, made on the device in one jitted call from the
  seed (``gaussian``: i.i.d. N(0, scale^2); ``topical``: one centre per
  topic plus per-word noise);
- documents and queries as bags of unique words, drawn on the host with
  Zipf popularity over the vocabulary. A text of ``n`` unique words is
  a token stream cut where its ``n``-th distinct word appears; the
  weights are the token counts.

The lengths are a fixed multiset, a quantile grid of the configured
distribution, and the seed only shuffles them: every seed gives the same
amount of work, in another order and on other words.
"""
from __future__ import annotations

from statistics import NormalDist
from typing import NamedTuple

import numpy as np


def seed_u32(seed: int, *stream: int) -> int:
    """A 32-bit key for JAX from a seed of any size and a sub-stream."""
    return int(np.random.SeedSequence([int(seed), *stream]).generate_state(1)[0])


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *stream])


# sub-streams of one seed
DOCS, QUERIES, WARMUP, SAMPLE, TOPICS, ORDER = range(6)


def length_grid(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at evenly spaced quantiles of ``spec`` (not shuffled).

    ``{"dist": "uniform", "min", "max"}`` or
    ``{"dist": "lognormal", "mean", "sigma", "min", "max"}``; lognormal
    lengths are clipped to [min, max]."""
    p = (np.arange(n) + 0.5) / n
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "uniform":
        out = lo + np.floor(p * (hi - lo + 1))
    elif spec["dist"] == "lognormal":
        sigma = float(spec["sigma"])
        mu = np.log(float(spec["mean"])) - sigma * sigma / 2
        z = np.array([NormalDist().inv_cdf(x) for x in p])
        out = np.round(np.exp(mu + sigma * z))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(out, lo, hi).astype(np.int64)


class Vocab(NamedTuple):
    """Host-side word statistics: Zipf popularity and topic membership."""

    cdf: np.ndarray            # (V,) cumulative popularity, global draw
    topic: np.ndarray | None   # (V,) topic id per word
    topic_words: list | None   # per topic: its word ids
    topic_cdf: list | None     # per topic: cumulative popularity


def make_vocab(cfg: dict, seed: int) -> Vocab:
    v = int(cfg["vocab_size"])
    rng = rng_for(seed, TOPICS)
    # popularity rank of each word id: a permutation, so frequent words
    # are spread over the id space
    rank = rng.permutation(v)
    pop = 1.0 / (rank + 1.0) ** float(cfg["zipf_a"])
    cdf = np.cumsum(pop / pop.sum())
    emb = cfg["embedding"]
    if emb["kind"] != "topical":
        return Vocab(cdf, None, None, None)
    topic = rng.integers(0, int(emb["topics"]), v)
    words, cdfs = [], []
    for t in range(int(emb["topics"])):
        w = np.nonzero(topic == t)[0]
        p = pop[w]
        words.append(w)
        cdfs.append(np.cumsum(p / p.sum()))
    return Vocab(cdf, topic, words, cdfs)


def _draw_tokens(rng, vocab: Vocab, m: int, topic: int | None,
                 mix: float) -> np.ndarray:
    u = rng.random(m)
    ids = np.minimum(np.searchsorted(vocab.cdf, u, side="right"),
                     len(vocab.cdf) - 1)
    if topic is None:
        return ids
    own = rng.random(m) < mix
    w, c = vocab.topic_words[topic], vocab.topic_cdf[topic]
    pick = np.minimum(np.searchsorted(c, u[own], side="right"), len(w) - 1)
    ids[own] = w[pick]
    return ids


def draw_text(rng, vocab: Vocab, n: int, topic: int | None = None,
              mix: float = 0.0):
    """One text of exactly ``n`` unique words: (sorted ids, counts)."""
    toks = np.zeros(0, np.int64)
    while True:
        toks = np.concatenate([toks, _draw_tokens(rng, vocab, 4 * n + 16,
                                                  topic, mix)])
        uniq, first = np.unique(toks, return_index=True)
        if uniq.size >= n:
            cut = np.sort(first)[n - 1]
            ids, counts = np.unique(toks[:cut + 1], return_counts=True)
            return ids.astype(np.int32), counts.astype(np.float64)


class Texts(NamedTuple):
    """Bags of words: ids (n, L) int32, weights (n, L) float32 summing
    to 1 per row, zero-padded; ``nnz`` (n,)."""

    ids: np.ndarray
    val: np.ndarray
    nnz: np.ndarray

    def dense(self, i: int, vocab_size: int) -> np.ndarray:
        """Row ``i`` as the full-vocabulary histogram the program takes."""
        row = np.zeros(vocab_size, np.float32)
        k = int(self.nnz[i])
        row[self.ids[i, :k]] = self.val[i, :k]
        return row


def draw_texts(cfg: dict, vocab: Vocab, lengths: np.ndarray,
               rng) -> Texts:
    emb = cfg["embedding"]
    topical = emb["kind"] == "topical"
    n = len(lengths)
    width = int(lengths.max())
    ids = np.zeros((n, width), np.int32)
    val = np.zeros((n, width), np.float32)
    topics = (rng.integers(0, int(emb["topics"]), n) if topical
              else [None] * n)
    mix = float(emb.get("topic_mix", 0.0))
    for i, n_words in enumerate(lengths):
        w, c = draw_text(rng, vocab, int(n_words), topics[i], mix)
        ids[i, :w.size] = w
        val[i, :w.size] = (c / c.sum()).astype(np.float32)
    return Texts(ids, val, np.asarray(lengths, np.int64))


def make_docs(cfg: dict, vocab: Vocab, seed: int) -> Texts:
    rng = rng_for(seed, DOCS)
    lengths = rng.permutation(length_grid(cfg["doc_words"],
                                          int(cfg["n_docs"])))
    return draw_texts(cfg, vocab, lengths, rng)


def make_queries(cfg: dict, vocab: Vocab, seed: int, n: int,
                 *stream: int, fixed_order: bool = False) -> Texts:
    """``n`` queries whose lengths are the quantile grid, shuffled;
    ``stream`` picks another sub-stream than the window's queries.
    ``fixed_order`` shuffles the lengths the same way for every seed,
    so that only the words change: a closed loop that hands the queries
    out in steps then does the same work, step for step, on any seed."""
    rng = rng_for(seed, *(stream or (QUERIES,)))
    grid = length_grid(cfg["query_words"], n)
    lengths = (rng_for(0, QUERIES) if fixed_order else rng).permutation(grid)
    return draw_texts(cfg, vocab, lengths, rng)


def make_vecs(cfg: dict, vocab: Vocab, seed: int):
    """The (V, w) float32 embedding table, made on the device."""
    import jax
    import jax.numpy as jnp

    v, w = int(cfg["vocab_size"]), int(cfg["embed_dim"])
    emb = cfg["embedding"]
    key = jax.random.key(seed_u32(seed, TOPICS))
    if emb["kind"] == "gaussian":
        scale = float(emb["scale"])

        @jax.jit
        def gen(key):
            return jax.random.normal(key, (v, w), jnp.float32) * scale
        return gen(key)
    if emb["kind"] == "topical":
        n_topics = int(emb["topics"])
        sc, sn = float(emb["centre_scale"]), float(emb["noise_scale"])

        @jax.jit
        def gen(key, topic):
            kc, kn = jax.random.split(key)
            centres = jax.random.normal(kc, (n_topics, w), jnp.float32) * sc
            noise = jax.random.normal(kn, (v, w), jnp.float32) * sn
            return centres[topic] + noise
        return gen(key, jnp.asarray(vocab.topic, jnp.int32))
    raise ValueError(f"unknown embedding kind {emb['kind']!r}")


class Corpus(NamedTuple):
    vocab: Vocab
    vecs: object          # (V, w) device array
    docs: Texts


def make_corpus(cfg: dict, seed: int) -> Corpus:
    vocab = make_vocab(cfg, seed)
    return Corpus(vocab, make_vecs(cfg, vocab, seed),
                  make_docs(cfg, vocab, seed))
