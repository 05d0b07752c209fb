"""Plain reference of the SPMD plane's sweep, for deciding ``correct``.

Straightforward numpy and jax.numpy, importing nothing of the program:
the chain and Vose's build are ``ref_lda``'s, the partition and the
counts are taken here from the corpus and the assignments.

What it reproduces is the plane's documented semantics (the paper's
section 2.2 layout; LightLDA's sampler as ``ref_lda`` states it):

* **Partition.**  Whole documents go to ``workers`` workers, longest
  first, each to the worker holding the fewest tokens so far (ties to
  the lowest worker; greedy LPT).  A worker holds its documents' tokens
  contiguously, in corpus order, its documents numbered from 0 in that
  order, padded with invalid tokens to a multiple of the block size (at
  least one block); then every worker is padded to the longest
  worker's token count, and its document arrays to the most documents.
* **Sweep.**  Worker ``j`` resamples its blocks in order with the key
  ``split(split(sweep_key, workers)[j], n_blocks)[g]`` for block ``g``
  (one block per exchange: staleness 0).  Block ``g``'s start state is:
  the word counts and alias tables of the sweep's input assignments over
  all workers (the snapshot pulled at the sweep's start); ``n_k`` of the
  sweep's input plus every worker's changes of blocks before ``g`` (the
  push merges all workers after every block); the worker's own ``n_dk``
  and the doc proposal's topics with its blocks before ``g`` new and the
  rest old.

So a correct program makes the same decisions wherever float32 rounding
cannot tip one; the ties are ``ref_lda``'s.  ``dtype`` selects the
arithmetic: float32 is the reference, bfloat16 the control.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

import ref_lda


class Partition(NamedTuple):
    """Every worker's padded arrays, stacked: ``[workers, ntok]`` tokens,
    ``[workers, dmax]`` documents (numpy)."""

    w: np.ndarray
    d: np.ndarray
    valid: np.ndarray
    doc_start: np.ndarray
    doc_len: np.ndarray


def partition(w: np.ndarray, d: np.ndarray, doc_len: np.ndarray,
              workers: int, block: int) -> Partition:
    """The plane's partition of a corpus given as tokens ``w`` of
    documents ``d`` (grouped by document, in document order) and the
    documents' lengths ``doc_len``."""
    doc_len = np.asarray(doc_len, np.int64)
    owner = np.empty(doc_len.shape[0], np.int64)
    load = [0] * workers
    for doc in np.argsort(-doc_len, kind="stable"):
        j = load.index(min(load))
        owner[doc] = j
        load[j] += int(doc_len[doc])
    tok_owner = owner[d]
    parts = []
    for j in range(workers):
        docs = np.nonzero(owner == j)[0]
        lens = doc_len[docs]
        mine = tok_owner == j
        wj = np.asarray(w)[mine]
        pad = max(-wj.shape[0] % block, block if wj.shape[0] == 0 else 0)
        parts.append((wj, np.searchsorted(docs, np.asarray(d)[mine]),
                      np.concatenate([[0], np.cumsum(lens)[:-1]]), lens,
                      wj.shape[0] + pad))
    ntok = max(p[4] for p in parts)
    dmax = max(p[3].shape[0] for p in parts)

    def stack(i, size, dtype):
        out = np.zeros((workers, size), dtype)
        for j, p in enumerate(parts):
            out[j, :p[i].shape[0]] = p[i]
        return out

    valid = np.zeros((workers, ntok), bool)
    for j, p in enumerate(parts):
        valid[j, :p[0].shape[0]] = True
    return Partition(stack(0, ntok, np.int32), stack(1, ntok, np.int32),
                     valid, stack(2, dmax, np.int32),
                     stack(3, dmax, np.int32))


def word_counts(part: Partition, z: np.ndarray, vocab_size: int,
                num_topics: int):
    """(n_wk [V, K], n_k [K]) of assignments ``z`` [workers, ntok] over
    every worker: plain histograms (numpy, int64)."""
    v = part.valid.reshape(-1)
    w, zz = part.w.reshape(-1)[v], z.reshape(-1)[v]
    nwk = np.bincount(w.astype(np.int64) * num_topics + zz,
                      minlength=vocab_size * num_topics)
    return (nwk.reshape(vocab_size, num_topics),
            np.bincount(zz, minlength=num_topics))


def doc_counts(part: Partition, z: np.ndarray, j: int, num_topics: int):
    """n_dk [dmax, K] of worker ``j``'s documents (numpy, int64)."""
    v = part.valid[j]
    dmax = part.doc_len.shape[1]
    return np.bincount(part.d[j][v].astype(np.int64) * num_topics
                       + z[j][v], minlength=dmax * num_topics).reshape(
        dmax, num_topics)


@partial(jax.jit, static_argnames=("block", "n_blocks", "num_topics",
                                   "vocab_size", "mh_steps", "alpha", "beta",
                                   "dtype"))
def block_resample(w, d, valid, doc_start, doc_len, z_cur, nk, key, g,
                   tab: ref_lda.Tables, *, block, n_blocks, num_topics,
                   vocab_size, mh_steps, alpha, beta, dtype=jnp.float32):
    """Resample block ``g`` of one worker whose tokens hold ``z_cur``
    (its blocks before ``g`` new, the rest old), against the all-worker
    topic totals ``nk`` at the block's start; ``key`` is the worker's
    sweep key.  Returns (z_block, tie_block)."""
    one = valid.astype(jnp.int32)
    num_docs = doc_len.shape[0]
    ndk = jnp.zeros((num_docs, num_topics), jnp.int32).at[d, z_cur].add(one)
    sl = lambda a: jax.lax.dynamic_slice_in_dim(a, g * block, block)
    w_b, d_b, z0 = sl(w), sl(d), sl(z_cur)

    key_g = jax.random.split(key, n_blocks)[g]
    kw, kwa, kd, kda = jax.random.split(key_g, 4)
    shape = (mh_steps, block)
    nd = jnp.take(doc_len, d_b).astype(jnp.float32)
    starts = jnp.take(doc_start, d_b)

    def doc_draw(k):
        k1, k2, k3 = jax.random.split(k, 3)
        p_ = (jax.random.uniform(k1, d_b.shape)
              * jnp.maximum(nd, 1.0)).astype(jnp.int32)
        p_ = jnp.minimum(p_, jnp.maximum(nd.astype(jnp.int32) - 1, 0))
        z_tok = jnp.take(z_cur, starts + p_)
        z_unif = jax.random.randint(k2, d_b.shape, 0, num_topics,
                                    dtype=jnp.int32)
        use_tok = (jax.random.uniform(k3, d_b.shape)
                   * (nd + num_topics * alpha) < nd)
        return jnp.where(use_tok, z_tok, z_unif)

    z_doc = jax.vmap(doc_draw)(jax.random.split(kd, mh_steps))
    u_w = jax.random.uniform(kw, shape)
    u_wa = jax.random.uniform(kwa, shape)
    u_da = jax.random.uniform(kda, shape)
    return ref_lda._chain(z0, tab.row_of[w_b], lambda k: ndk[d_b, k], nk,
                          tab, u_w, u_wa, z_doc, u_da, alpha, beta,
                          vocab_size * beta, num_topics, False, dtype)


def replay(part: Partition, z_in: np.ndarray, z_out: np.ndarray,
           sweep_key, picks, *, block, num_topics, vocab_size, mh_steps,
           alpha, beta, control: bool = False) -> dict:
    """Replay block ``picks[j]`` of every worker ``j`` of one sweep from
    its input ``z_in`` and output ``z_out`` ([workers, ntok] numpy);
    count the decided tokens whose topic differs from ``z_out`` (and,
    with ``control``, those where the bfloat16 reference differs from the
    float32 one)."""
    workers, ntok = part.w.shape
    n_blocks = ntok // block
    nwk, nk_in = word_counts(part, z_in, vocab_size, num_topics)
    words = np.concatenate([part.w[j, b * block:(b + 1) * block]
                            for j, b in enumerate(picks)])
    tab = ref_lda.build_tables(jnp.asarray(nwk.astype(np.int32)),
                               jnp.asarray(nk_in.astype(np.int32)), words,
                               beta)
    del nwk
    keys = jax.random.split(sweep_key, workers)
    kw = dict(block=block, n_blocks=n_blocks, num_topics=num_topics,
              vocab_size=vocab_size, mh_steps=mh_steps, alpha=alpha,
              beta=beta)
    pos = np.arange(ntok)
    out = {"checked": 0, "ties": 0, "mismatch": 0, "control": 0}
    for j, b in enumerate(picks):
        z_cur = np.where(pos[None, :] < b * block, z_out, z_in)
        nk = np.bincount(z_cur[part.valid], minlength=num_topics)
        args = (jnp.asarray(part.w[j]), jnp.asarray(part.d[j]),
                jnp.asarray(part.valid[j]), jnp.asarray(part.doc_start[j]),
                jnp.asarray(part.doc_len[j]), jnp.asarray(z_cur[j]),
                jnp.asarray(nk.astype(np.int32)), keys[j], int(b), tab)
        z_ref, tie = block_resample(*args, **kw)
        sl = slice(b * block, (b + 1) * block)
        valid = part.valid[j, sl]
        decided = valid & ~np.asarray(tie)
        z_ref = np.asarray(z_ref)
        out["checked"] += int(decided.sum())
        out["ties"] += int((valid & np.asarray(tie)).sum())
        out["mismatch"] += int((decided & (z_out[j, sl] != z_ref)).sum())
        if control:
            z_c, _ = block_resample(*args, dtype=jnp.bfloat16, **kw)
            out["control"] += int((decided & (np.asarray(z_c) != z_ref))
                                  .sum())
    return out
