"""Seeded inputs of a run: seed streams, the corpus generator, arrivals.

Frozen copies, kept with the benchmark so that a later change to the
program cannot move the yardstick:

* ``lda_corpus`` copies ``repro.data.corpus.generate_lda_corpus`` (LDA
  generative process over a Zipf base measure, frequency-ordered word
  ids), with the document lengths passed in rather than drawn Poisson.
* ``doc_lengths`` and ``arrival_gaps`` give every seed the same multiset
  of sizes and gaps (stratified quantiles of the distribution) and let the
  seed only shuffle them, so two seeds do the same work in another order.

Everything here is numpy on the host; nothing imports the program.
"""
from __future__ import annotations

import numpy as np
from scipy.special import ndtri

# named streams drawn from one --seed
STREAMS = {"corpus": 1, "order": 2, "keys": 3, "arrivals": 4, "sample": 5,
           "model": 6, "requests": 7}


def seed_seq(seed: int, stream: str) -> np.random.SeedSequence:
    """Seed sequence of one named stream; any whole number is a seed."""
    s = int(seed)
    entropy = [abs(s) & 0xFFFFFFFF, (abs(s) >> 32) & 0xFFFFFFFF,
               (abs(s) >> 64) & 0xFFFFFFFF, int(s < 0)]
    return np.random.SeedSequence(entropy, spawn_key=(STREAMS[stream],))


def rng(seed: int, stream: str) -> np.random.Generator:
    return np.random.default_rng(seed_seq(seed, stream))


def key_ints(seed: int, stream: str, n: int) -> np.ndarray:
    """``n`` non-negative int32 values for ``jax.random.PRNGKey``."""
    return (seed_seq(seed, stream).generate_state(n) & 0x7FFFFFFF).astype(
        np.int64)


def _strata(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def doc_lengths(n_docs: int, length: dict, seed: int) -> np.ndarray:
    """Document lengths: the same multiset for every seed, in seeded order.

    ``length`` = {"dist": "lognormal", "mean": m, "sigma": s, "min": lo}:
    the stratified quantiles of a lognormal of mean ``m`` (a heavy right
    tail), rounded, at least ``lo``.
    """
    if length["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {length['dist']!r}")
    sigma = float(length["sigma"])
    mu = np.log(float(length["mean"])) - sigma * sigma / 2
    lens = np.exp(mu + sigma * ndtri(_strata(n_docs)))
    lens = np.maximum(np.rint(lens), int(length["min"])).astype(np.int64)
    return rng(seed, "order").permutation(lens)


def arrival_gaps(n: int, rate_per_s: float, seed: int) -> np.ndarray:
    """Poisson arrivals: stratified exponential gaps of mean 1/rate, the
    same multiset for every seed, in seeded order (seconds)."""
    gaps = -np.log1p(-_strata(n)) / float(rate_per_s)
    return rng(seed, "arrivals").permutation(gaps)


def lda_corpus(seed: int, doc_lens: np.ndarray, vocab_size: int,
               num_topics: int, zipf_exponent: float,
               doc_topic_alpha: float, topic_concentration: float,
               doc_block: int = 4096, stream: str = "corpus") -> dict:
    """Tokens of the LDA generative process over a Zipf base measure.

    Returns {"w", "d", "doc_start", "doc_len", "word_freq"}: flat int32
    arrays grouped by document, word ids ranked by corpus frequency (rank
    0 the most frequent), which is the layout the sampler consumes.
    """
    g = rng(seed, stream)
    base = 1.0 / np.arange(1, vocab_size + 1) ** zipf_exponent
    base /= base.sum()
    phi = g.dirichlet(base * topic_concentration, size=num_topics)   # [T, V]

    num_docs = doc_lens.shape[0]
    d = np.repeat(np.arange(num_docs, dtype=np.int32), doc_lens)
    starts = np.concatenate([[0], np.cumsum(doc_lens)])

    # z | theta_d, a block of documents at a time (inverse CDF)
    z = np.empty(d.shape[0], np.int32)
    for b0 in range(0, num_docs, doc_block):
        b1 = min(b0 + doc_block, num_docs)
        theta = g.dirichlet(np.full(num_topics, doc_topic_alpha),
                            size=b1 - b0)
        cdf = np.cumsum(theta, axis=1)
        cdf[:, -1] = 1.0
        row = np.arange(b1 - b0)
        t0, t1 = starts[b0], starts[b1]
        local = d[t0:t1] - b0
        hit = np.searchsorted((cdf + row[:, None]).ravel(),
                              g.random(t1 - t0) + local, side="right")
        z[t0:t1] = np.minimum(hit - local * num_topics, num_topics - 1)

    # w | phi_z, a topic at a time
    w = np.empty(d.shape[0], np.int32)
    order = np.argsort(z, kind="stable")
    bounds = np.concatenate([[0], np.cumsum(np.bincount(
        z, minlength=num_topics))])
    for k in range(num_topics):
        tok = order[bounds[k]:bounds[k + 1]]
        if tok.size:
            cdf = np.cumsum(phi[k])
            cdf[-1] = 1.0
            w[tok] = np.minimum(np.searchsorted(
                cdf, g.random(tok.size), side="right"), vocab_size - 1)

    # frequency-ordered vocabulary (paper section 3.2)
    freq = np.bincount(w, minlength=vocab_size)
    order = np.argsort(-freq, kind="stable")
    rank_of = np.empty_like(order)
    rank_of[order] = np.arange(vocab_size)
    return {"w": rank_of[w].astype(np.int32), "d": d,
            "doc_start": starts[:-1].astype(np.int32),
            "doc_len": doc_lens.astype(np.int32),
            "word_freq": freq[order]}


def config_corpus(cfg: dict, seed: int, num_docs: int,
                  stream: str = "corpus") -> dict:
    """The configuration's corpus: its length distribution and generative
    parameters, ``num_docs`` documents."""
    gen = cfg["generator"]
    lens = doc_lengths(num_docs, cfg["length"], seed)
    return lda_corpus(seed, lens, cfg["vocab"], gen["topics"],
                      gen["zipf_exponent"], gen["doc_topic_alpha"],
                      gen["topic_concentration"], stream=stream)
