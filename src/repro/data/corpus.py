"""Synthetic Zipfian corpus pipeline (ClueWeb12 stand-in).

The paper's corpus statistics that matter to the *system* are (a) the
Zipfian word-frequency distribution (paper Fig. 4) -- it drives the implicit
load-balancing argument -- and (b) scale.  This module generates LDA-
distributed corpora whose empirical word frequencies are Zipfian, and
produces the exact data layout the sampler consumes:

  * vocabulary ids are **frequency-ordered** (rank 0 = most common word),
    which is the paper's section 3.2 trick that makes cyclic partitioning
    load-balanced;
  * tokens are flattened (w, d) arrays grouped by document, with doc offset
    tables, padded to block/shard boundaries;
  * held-out docs are split half/half for fold-in perplexity evaluation.

Generation is host-side numpy (a data pipeline, not a model), as it would
be in production (CPU feeders, TPU consumers).
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import numpy as np


@dataclasses.dataclass
class Corpus:
    """Flattened corpus, frequency-ordered vocabulary."""

    w: np.ndarray          # [N] word ids
    d: np.ndarray          # [N] doc ids
    doc_start: np.ndarray  # [D]
    doc_len: np.ndarray    # [D]
    vocab_size: int
    word_freq: np.ndarray  # [V] corpus frequency of each word id (desc.)

    @property
    def num_tokens(self) -> int:
        return int(self.w.shape[0])

    @property
    def num_docs(self) -> int:
        return int(self.doc_len.shape[0])

    def subset(self, frac: float, seed: int = 0) -> "Corpus":
        """Take the first ``frac`` of documents (the paper's 2.5%-10%
        subset experiments scale the corpus this way)."""
        ndocs = max(1, int(self.num_docs * frac))
        end = int(self.doc_start[ndocs - 1] + self.doc_len[ndocs - 1])
        return reindex(self.w[:end], self.d[:end], self.vocab_size)


def reindex(w: np.ndarray, d: np.ndarray, vocab_size: int) -> Corpus:
    """Rebuild offsets + frequency ordering for a token list."""
    # frequency-order the vocabulary (paper section 3.2)
    freq = np.bincount(w, minlength=vocab_size)
    order = np.argsort(-freq, kind="stable")
    rank_of = np.empty_like(order)
    rank_of[order] = np.arange(vocab_size)
    w = rank_of[w].astype(np.int32)
    freq = freq[order]

    # compact doc ids, grouped
    d_new = _compact_docs(d)
    if not _is_sorted(d):
        sort = np.argsort(d_new, kind="stable")
        w, d_new = w[sort], d_new[sort]
    doc_len = np.bincount(d_new).astype(np.int32)
    doc_start = _starts_of(doc_len)
    return Corpus(w, d_new, doc_start, doc_len, vocab_size, freq)


def generate_lda_corpus(seed: int, num_docs: int, mean_doc_len: int,
                        vocab_size: int, num_topics: int,
                        zipf_exponent: float = 1.05,
                        doc_topic_alpha: float = 0.08,
                        topic_concentration: float = 2000.0,
                        doc_block: int = 4096) -> Corpus:
    """Generate a corpus from the LDA generative process with a Zipfian base
    measure, so empirical frequencies follow Zipf's law (paper Fig. 4).

    Vectorised by inverse-CDF draws: every token's topic is drawn for a
    block of ``doc_block`` documents at once (one ``searchsorted`` over
    the block's concatenated θ CDFs), then every token of a topic draws
    its word in one ``searchsorted`` on that topic's φ CDF.  Host cost is
    O(N log V + K·V), so a NYTimes-sized corpus (~10^8 tokens) takes about
    a minute instead of hours of per-document ``rng.choice`` calls."""
    rng = np.random.default_rng(seed)

    # Zipfian base measure over the vocabulary.
    base = 1.0 / np.arange(1, vocab_size + 1) ** zipf_exponent
    base /= base.sum()

    # Topic-word distributions: Dirichlet around the Zipf base (sparse-ish
    # topics that still mix to a Zipfian marginal).
    phi = rng.dirichlet(base * topic_concentration, size=num_topics)  # [K, V]

    doc_lens = np.maximum(rng.poisson(mean_doc_len, size=num_docs), 4)
    d = np.repeat(np.arange(num_docs, dtype=np.int32), doc_lens)
    starts = np.concatenate([[0], np.cumsum(doc_lens)])

    # z | θ_d, block by block: row r of the block's CDF spans (r, r+1]
    z = np.empty(d.shape[0], np.int32)
    for b0 in range(0, num_docs, doc_block):
        b1 = min(b0 + doc_block, num_docs)
        theta = rng.dirichlet(np.full(num_topics, doc_topic_alpha),
                              size=b1 - b0)
        cdf = np.cumsum(theta, axis=1)
        cdf[:, -1] = 1.0
        row = np.arange(b1 - b0)
        t0, t1 = starts[b0], starts[b1]
        local = d[t0:t1] - b0
        hit = np.searchsorted((cdf + row[:, None]).ravel(),
                              rng.random(t1 - t0) + local, side="right")
        z[t0:t1] = np.minimum(hit - local * num_topics, num_topics - 1)

    # w | φ_z, topic by topic
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
                cdf, rng.random(tok.size), side="right"), vocab_size - 1)

    return reindex(w, d, vocab_size)


def synthetic_corpus(num_docs: int, vocab_size: int, *,
                     true_topics: Optional[int] = None,
                     model_topics: Optional[int] = None,
                     mean_doc_len: int = 60, seed: int = 0,
                     log_fn=None) -> Corpus:
    """The canonical synthetic-corpus recipe for examples/ and benchmarks/.

    Every demo and benchmark used to hand-roll its own
    ``generate_lda_corpus`` call with near-identical arguments; this is
    the single front door.  ``true_topics`` is the generative topic
    count; when omitted it defaults to half the *model's* topic count
    (``max(4, model_topics // 2)`` -- the convention the benchmarks
    converged on) or 16 if neither is given.  ``log_fn`` optionally
    prints the one-line corpus summary every caller used to format
    itself.
    """
    if true_topics is None:
        true_topics = max(4, model_topics // 2) if model_topics else 16
    corp = generate_lda_corpus(seed=seed, num_docs=num_docs,
                               mean_doc_len=mean_doc_len,
                               vocab_size=vocab_size,
                               num_topics=true_topics)
    if log_fn is not None:
        log_fn(f"corpus: {corp.num_tokens} tokens, {corp.num_docs} docs, "
               f"V={corp.vocab_size}")
    return corp


def corpus_from_docs(docs, vocab_size: Optional[int] = None) -> Corpus:
    """Build a ``Corpus`` from an iterable of token-id documents.

    The entry point behind ``LDAJob(docs=...)``.  NOTE: word ids are
    re-ranked by corpus frequency (``reindex`` -- the section-3.2
    contract every downstream component assumes); keep your own id->rank
    map if you need to translate back.  Empty documents are dropped.
    """
    ws: List[np.ndarray] = []
    ds: List[np.ndarray] = []
    for i, doc in enumerate(docs):
        a = np.asarray(doc, dtype=np.int64).ravel()
        if a.size == 0:
            continue
        ws.append(a)
        ds.append(np.full(a.size, i, np.int64))
    if not ws:
        raise ValueError("docs yielded no tokens; pass at least one "
                         "non-empty document")
    w = np.concatenate(ws)
    d = np.concatenate(ds)
    if w.min() < 0:
        raise ValueError("negative token ids in docs")
    if vocab_size is None:
        vocab_size = int(w.max()) + 1
    elif int(w.max()) >= vocab_size:
        raise ValueError(f"token id {int(w.max())} out of range for "
                         f"vocab_size={vocab_size}")
    return reindex(w, d, vocab_size)


def train_heldout_split(corpus: Corpus, heldout_frac: float = 0.1,
                        seed: int = 1) -> Tuple[Corpus, Corpus]:
    """Split documents into train/held-out sets."""
    rng = np.random.default_rng(seed)
    ndocs = corpus.num_docs
    held = rng.random(ndocs) < heldout_frac
    held_tok = held[corpus.d]
    train = reindex(corpus.w[~held_tok], corpus.d[~held_tok], corpus.vocab_size)
    heldout = reindex(corpus.w[held_tok], corpus.d[held_tok], corpus.vocab_size)
    # NOTE: reindex re-sorts each split's vocabulary by its own frequencies;
    # for evaluation the two must share word ids, so instead keep the parent
    # corpus ordering for the held-out split:
    heldout = Corpus(corpus.w[held_tok].astype(np.int32),
                     _compact_docs(corpus.d[held_tok]),
                     *_offsets(corpus.d[held_tok]),
                     corpus.vocab_size, corpus.word_freq)
    train = Corpus(corpus.w[~held_tok].astype(np.int32),
                   _compact_docs(corpus.d[~held_tok]),
                   *_offsets(corpus.d[~held_tok]),
                   corpus.vocab_size, corpus.word_freq)
    return train, heldout


def _is_sorted(d: np.ndarray) -> bool:
    return bool(np.all(d[1:] >= d[:-1]))


def _compact_docs(d: np.ndarray) -> np.ndarray:
    """Doc ids -> dense ranks 0..D-1 (``np.unique``'s inverse).  Token
    arrays grouped by document are already sorted, and then the ranks are
    a running count of id changes: O(N) instead of a sort."""
    d = np.asarray(d)
    if d.size and _is_sorted(d):
        return np.concatenate([[0], np.cumsum(d[1:] != d[:-1])]).astype(
            np.int32)
    _, inv = np.unique(d, return_inverse=True)
    return inv.astype(np.int32)


def _starts_of(doc_len: np.ndarray) -> np.ndarray:
    """Offsets from lengths; an empty doc set has *empty* offsets (not a
    phantom [0] entry -- the doc_start/doc_len lengths must always agree)."""
    if doc_len.shape[0] == 0:
        return np.zeros(0, np.int32)
    return np.concatenate([[0], np.cumsum(doc_len)[:-1]]).astype(np.int32)


def _offsets(d: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    dc = _compact_docs(d)
    doc_len = np.bincount(dc).astype(np.int32)
    return _starts_of(doc_len), doc_len


def fold_eval_split(corpus: Corpus, seed: int = 2
                    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Alternate tokens of each held-out doc into fold-in vs eval halves.
    Returns boolean masks (fold_mask, eval_mask) plus (w, d) unchanged."""
    rng = np.random.default_rng(seed)
    coin = rng.random(corpus.num_tokens) < 0.5
    return corpus.w, corpus.d, coin, ~coin


def packed_fold_eval_split(corpus: Corpus, seed: int = 2
                           ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """``fold_eval_split`` in a [D, L] document-major layout (L the
    longest document): word ids, fold-in mask, eval mask.  Padding has
    word 0 and both masks False.  Tokens must be grouped by document
    (every ``Corpus`` is)."""
    w, d, fold, ev = fold_eval_split(corpus, seed)
    shape = (corpus.num_docs, int(corpus.doc_len.max(initial=0)))
    pos = np.arange(corpus.num_tokens) - corpus.doc_start[d]
    out = [np.zeros(shape, np.int32), np.zeros(shape, bool),
           np.zeros(shape, bool)]
    for dst, src in zip(out, (w, fold, ev)):
        dst[d, pos] = src
    return tuple(out)


def shard_tokens(corpus: Corpus, num_shards: int, block_tokens: int
                 ) -> List[Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray]]:
    """Partition documents across data-parallel workers (Spark partitions,
    paper Fig. 3).  Documents are assigned round-robin by size (greedy LPT)
    so token counts balance; each shard's arrays are padded to
    ``block_tokens``.  Returns per-shard (w, d_local, valid, doc_start,
    doc_len)."""
    order = np.argsort(-corpus.doc_len, kind="stable")
    loads = np.zeros(num_shards, dtype=np.int64)
    assign = np.empty(corpus.num_docs, dtype=np.int32)
    for doc in order:
        s = int(np.argmin(loads))
        assign[doc] = s
        loads[s] += corpus.doc_len[doc]

    shards = []
    for s in range(num_shards):
        docs = np.where(assign == s)[0]
        tok_mask = np.isin(corpus.d, docs)
        w = corpus.w[tok_mask]
        d = _compact_docs(corpus.d[tok_mask])
        doc_start, doc_len = _offsets(corpus.d[tok_mask])
        # every shard pads to at least one full block -- an empty shard
        # (num_shards > num_docs) still yields block-shaped, all-invalid
        # arrays, so downstream per-shard reshapes never see length 0
        pad = (-len(w)) % block_tokens
        if len(w) + pad == 0:
            pad = block_tokens
        valid = np.concatenate([np.ones(len(w), bool), np.zeros(pad, bool)])
        w = np.concatenate([w, np.zeros(pad, np.int32)])
        d = np.concatenate([d, np.zeros(pad, np.int32)])
        shards.append((w.astype(np.int32), d.astype(np.int32), valid,
                       doc_start, doc_len))
    return shards


def doc_term_matrix(corpus: Corpus, docs: np.ndarray) -> np.ndarray:
    """Dense doc-term counts for a batch of docs (online-VB pipeline)."""
    out = np.zeros((len(docs), corpus.vocab_size), np.float32)
    for i, doc in enumerate(docs):
        s, l = corpus.doc_start[doc], corpus.doc_len[doc]
        np.add.at(out[i], corpus.w[s:s + l], 1.0)
    return out
