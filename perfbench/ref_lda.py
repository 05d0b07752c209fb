"""Plain reference of the LightLDA sampler, for deciding ``correct``.

Straightforward jax.numpy and numpy, importing nothing of the program and
taking nothing it made: the counts it samples against are histograms it
takes itself of the assignments, and it builds its own alias tables.

What it reproduces is the sampler's published semantics at the step the
program takes them (LightLDA, Yuan et al. 2015; the paper's Alg. 1):

* the word proposal draws from a Vose alias table of
  q_w(k) = (n_wk + beta) / (n_k + V beta), one uniform per draw (the
  integer part of u K picks the bucket, the rest is the coin);
* the doc proposal is the topic of a uniformly drawn token of the
  document, or a uniform topic with probability K alpha / (N_d + K alpha);
* each proposal is accepted with the Metropolis-Hastings ratio of the
  collapsed posterior (n_dk + alpha)(n_wk + beta)/(n_k + V beta), the
  token itself excluded (in fold-in only from n_dk);
* a training sweep resamples blocks of ``block_tokens`` tokens in order,
  each against the counts at the block's start, the word counts and
  alias tables taken once at the sweep's start; fold-in runs
  ``num_sweeps`` sweeps per document and averages n_dk after burn-in.

The random numbers are those of the program's documented key schedule
(``jax.random`` threefry keys split per block, per step and per document),
so a correct program and this reference make the same draws, and the same
decisions wherever f32 rounding cannot tip one.  A decision whose two
sides lie within rounding of each other is a tie: an MH ratio within
``RATIO_TIE`` (relative) of its coin, an alias coin within ``COIN_TIE`` of
its probability, or a draw from an alias entry that Vose's build placed
after one of its own threshold tests came within rounding (a weight
within ``Q_TIE`` of 1 when the stacks were filled, a donor's residual
within ``RESIDUAL_TIE`` times its first weight of 1): the program's row
sums may round the other
way, and its table then differs from that entry on.  A token (or a
fold-in document) with a tie is left out of the comparison and counted
apart.

``dtype`` selects the arithmetic: float32 is the reference, bfloat16 the
control that the comparison must reject.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

RATIO_TIE = 1e-6      # relative: a few f32 ulps of the MH ratio
COIN_TIE = 1e-6       # absolute, on an alias acceptance probability in [0, 1]
Q_TIE = 1e-6          # relative: a few ulps of a row's normalising sum
RESIDUAL_TIE = 1e-6   # relative to the donor's first weight, whose
                      # rounding its residual carries
TINY = 1e-30


class Tables(NamedTuple):
    """Alias tables and word counts for a subset of the vocabulary.

    ``row_of[w]`` is the row of word ``w`` in the [R, K] arrays (0 for
    words not held, which are never looked up)."""

    row_of: jax.Array   # [V] int32
    nwk: jax.Array      # [R, K] float32 word-topic counts
    prob: jax.Array     # [R, K] float32
    alias: jax.Array    # [R, K] int32
    unsure: jax.Array   # [R, K] bool: placed after a tied threshold test


# ---------------------------------------------------------------------------
# Vose alias tables
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("beta",))
def _scaled_rows(nwk, nk, rows, beta):
    """q = w K / sum(w) of the given rows, w = (n_wk + beta)/(n_k + V beta),
    written over the whole [V, K] table as the proposal is defined."""
    v, k = nwk.shape
    w = (nwk.astype(jnp.float32) + beta) / (
        nk.astype(jnp.float32)[None, :] + v * beta)
    q = w * (k / jnp.maximum(w.sum(-1, keepdims=True), TINY))
    return q[rows]


def vose(q: np.ndarray):
    """Vose's two-stack construction for every row of ``q`` [R, K], float32
    weights scaled to mean 1.  Smalls (q < 1) and larges (the rest) are
    stacked in ascending index order and popped from the top; a large
    whose residual falls below 1 moves to the small stack.  Entries never
    retired keep probability 1 and alias themselves.  Returns (prob,
    alias, unsure), ``unsure`` marking the entries placed after a test
    that came within rounding of its threshold."""
    q = np.array(q, np.float32)
    q0 = q.copy()
    r_n, k = q.shape
    never = 2 * k
    retired = np.full((r_n, k), never, np.int64)
    # -1: the stacks themselves are in doubt, so every entry is
    unsure_after = np.where((np.abs(q - 1.0) <= Q_TIE).any(axis=1), -1,
                            never)
    idx = np.arange(k)
    is_small = q < np.float32(1.0)
    small = np.argsort(np.where(is_small, idx, idx + k), axis=1,
                       kind="stable").astype(np.int64)
    large = np.argsort(np.where(~is_small, idx, idx + k), axis=1,
                       kind="stable").astype(np.int64)
    ns = is_small.sum(1).astype(np.int64)
    nl = k - ns
    prob = np.ones((r_n, k), np.float32)
    alias = np.tile(idx.astype(np.int32), (r_n, 1))
    one = np.float32(1.0)
    for t in range(2 * k):
        act = np.nonzero((ns > 0) & (nl > 0))[0]
        if act.size == 0:
            break
        s = small[act, ns[act] - 1]
        l = large[act, nl[act] - 1]
        q_s = q[act, s]
        q_l = (q[act, l] + q_s) - one
        prob[act, s] = q_s
        alias[act, s] = l
        retired[act, s] = t
        # a residual near 1 tips the rest of the row, unless both ways
        # end the build here (no small left, or this was the last large)
        goes_on = (ns[act] > 1) | (nl[act] > 1)
        tol = RESIDUAL_TIE * np.maximum(q0[act, l], one)
        near = act[(np.abs(q_l - one) <= tol) & goes_on]
        unsure_after[near] = np.minimum(unsure_after[near], t)
        q[act, l] = q_l
        ns[act] -= 1
        dem = q_l < one
        da = act[dem]
        nl[da] -= 1
        small[da, ns[da]] = l[dem]
        ns[da] += 1
    return (np.clip(prob, 0.0, 1.0), alias,
            retired > unsure_after[:, None])


def build_tables(nwk_full: jax.Array, nk: jax.Array, words: np.ndarray,
                 beta: float) -> Tables:
    """Alias tables and counts of ``words`` from the full count table."""
    words = np.unique(np.asarray(words))
    v = nwk_full.shape[0]
    q = np.asarray(_scaled_rows(nwk_full, nk, jnp.asarray(words), beta))
    prob, alias, unsure = vose(q)
    row_of = np.zeros(v, np.int32)
    row_of[words] = np.arange(words.size, dtype=np.int32)
    rows = jnp.asarray(words)
    nwk = jax.jit(lambda n, r: n[r].astype(jnp.float32))(nwk_full, rows)
    return Tables(jnp.asarray(row_of), nwk, jnp.asarray(prob),
                  jnp.asarray(alias), jnp.asarray(unsure))


# ---------------------------------------------------------------------------
# The Metropolis-Hastings chain (one token per lane, scalars gathered)
# ---------------------------------------------------------------------------

def _chain(z0, w_row, ndk_at, nk, tab: Tables, u_w, u_wa, z_doc, u_da,
           alpha, beta, vbeta, num_topics, frozen, dtype):
    """``mh_steps`` x (word proposal, doc proposal) for a flat batch.

    ``ndk_at(k)`` gives each token's document count of topic k (int or
    float); ``nk`` the [K] topic totals; ``w_row`` each token's row in
    ``tab``.  Returns (z, tie) with ``tie`` True where a decision was
    within rounding of its threshold."""
    ct = lambda x: x.astype(dtype)

    def nwk_at(k):
        return ct(tab.nwk[w_row, k])

    def nk_at(k):
        return ct(nk[k].astype(jnp.float32))

    def p(k):
        e = ct((k == z0).astype(jnp.float32))
        e_wk = ct(jnp.zeros_like(z0, jnp.float32)) if frozen else e
        return ((ct(ndk_at(k).astype(jnp.float32)) - e + alpha)
                * (nwk_at(k) - e_wk + beta) / (nk_at(k) - e_wk + vbeta))

    def q_word(k):
        return (nwk_at(k) + beta) / (nk_at(k) + vbeta)

    def q_doc(k):
        return ct(ndk_at(k).astype(jnp.float32)) + alpha

    def decide(u, ratio, tie):
        r = ratio.astype(jnp.float32)
        tie = tie | (jnp.abs(u - r) <= RATIO_TIE * jnp.abs(r))
        return u < r, tie

    z = z0
    tie = jnp.zeros(z0.shape, bool)
    for s in range(u_w.shape[0]):
        scaled = u_w[s] * num_topics
        bucket = jnp.minimum(scaled.astype(jnp.int32), num_topics - 1)
        coin = scaled - bucket.astype(jnp.float32)
        pa = ct(tab.prob[w_row, bucket]).astype(jnp.float32)
        al = tab.alias[w_row, bucket]
        tie = (tie | (jnp.abs(coin - pa) <= COIN_TIE)
               | tab.unsure[w_row, bucket])
        z_prop = jnp.where(coin < pa, bucket, al)
        ratio = (p(z_prop) * q_word(z)) / (
            jnp.maximum(p(z), TINY) * jnp.maximum(q_word(z_prop), TINY))
        acc, tie = decide(u_wa[s], ratio, tie)
        z = jnp.where(acc, z_prop, z)

        z_prop = z_doc[s]
        ratio = (p(z_prop) * q_doc(z)) / (
            jnp.maximum(p(z), TINY) * jnp.maximum(q_doc(z_prop), TINY))
        acc, tie = decide(u_da[s], ratio, tie)
        z = jnp.where(acc, z_prop, z)
    return z, tie


# ---------------------------------------------------------------------------
# Training: one block of a sweep, its inputs rebuilt from the assignments
# ---------------------------------------------------------------------------

class Corpus(NamedTuple):
    """The padded token arrays as the benchmark made them (on device)."""

    w: jax.Array          # [N] int32, padding 0
    d: jax.Array          # [N] int32, padding 0
    valid: jax.Array      # [N] bool
    doc_start: jax.Array  # [D] int32
    doc_len: jax.Array    # [D] int32


@partial(jax.jit, static_argnames=("num_docs", "num_topics", "vocab_size"))
def sweep_counts(w, d, valid, z, num_docs, num_topics, vocab_size):
    """(n_wk, n_k, n_dk) of assignments ``z``: plain histograms."""
    one = valid.astype(jnp.int32)
    nwk = jnp.zeros((vocab_size, num_topics), jnp.int32).at[w, z].add(one)
    nk = jnp.zeros((num_topics,), jnp.int32).at[z].add(one)
    ndk = jnp.zeros((num_docs, num_topics), jnp.int32).at[d, z].add(one)
    return nwk, nk, ndk


@partial(jax.jit, static_argnames=("block", "n_blocks", "num_docs",
                                   "num_topics", "vocab_size", "mh_steps",
                                   "alpha", "beta", "dtype"))
def block_resample(corp: Corpus, z_in, z_out, key, g, tab: Tables, *,
                   block, n_blocks, num_docs, num_topics, vocab_size,
                   mh_steps, alpha, beta, dtype=jnp.float32):
    """Resample block ``g`` of a sweep from ``z_in`` (the sweep's input)
    and ``z_out`` (its output), which fix the block's inputs: blocks
    before ``g`` hold their new topics, the rest their old ones.
    Returns (z_block, tie_block)."""
    n = corp.w.shape[0]
    pos = jnp.arange(n)
    z_cur = jnp.where(pos < g * block, z_out, z_in)
    one = corp.valid.astype(jnp.int32)
    nk = jnp.zeros((num_topics,), jnp.int32).at[z_cur].add(one)
    ndk = jnp.zeros((num_docs, num_topics), jnp.int32).at[
        corp.d, z_cur].add(one)

    sl = lambda a: jax.lax.dynamic_slice_in_dim(a, g * block, block)
    w_b, d_b, z0 = sl(corp.w), sl(corp.d), sl(z_cur)

    key_g = jax.random.split(key, n_blocks)[g]
    kw, kwa, kd, kda = jax.random.split(key_g, 4)
    shape = (mh_steps, block)
    nd = jnp.take(corp.doc_len, d_b).astype(jnp.float32)
    starts = jnp.take(corp.doc_start, d_b)

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
    z, tie = _chain(z0, tab.row_of[w_b], lambda k: ndk[d_b, k], nk, tab,
                    u_w, u_wa, z_doc, u_da, alpha, beta, vocab_size * beta,
                    num_topics, False, dtype)
    return z, tie


# ---------------------------------------------------------------------------
# Serving: fold-in of documents against frozen counts
# ---------------------------------------------------------------------------

def _doc_randoms(key, z_row, nd, num_topics, alpha, mh_steps):
    shape = (mh_steps, z_row.shape[0])
    kw, kwa, kd, kda = jax.random.split(key, 4)
    k1, k2, k3 = jax.random.split(kd, 3)
    ndf = jnp.maximum(nd.astype(jnp.float32), 1.0)
    pos = (jax.random.uniform(k1, shape) * ndf).astype(jnp.int32)
    pos = jnp.minimum(pos, jnp.maximum(nd - 1, 0))
    z_tok = jnp.take(z_row, pos)
    z_unif = jax.random.randint(k2, shape, 0, num_topics, dtype=jnp.int32)
    use_tok = (jax.random.uniform(k3, shape)
               * (nd.astype(jnp.float32) + num_topics * alpha)
               < nd.astype(jnp.float32))
    return (jax.random.uniform(kw, shape), jax.random.uniform(kwa, shape),
            jnp.where(use_tok, z_tok, z_unif),
            jax.random.uniform(kda, shape))


@partial(jax.jit, static_argnames=("num_topics", "vocab_size", "mh_steps",
                                   "alpha", "beta", "num_sweeps", "burnin",
                                   "dtype"))
def fold_in(w, valid, keys, nk, tab: Tables, *, num_topics, vocab_size,
            mh_steps, alpha, beta, num_sweeps, burnin, dtype=jnp.float32):
    """theta [B, K] of documents packed as [B, L] (tokens left-packed),
    each with its own PRNG key; also [B] True where a decision tied."""
    b, l = w.shape
    nd = jnp.sum(valid.astype(jnp.int32), axis=1)
    z = jax.vmap(lambda k: jax.random.randint(
        jax.random.fold_in(k, 0x1d4), (l,), 0, num_topics,
        dtype=jnp.int32))(keys)
    rows = tab.row_of[w].reshape(b * l)
    nk = nk.astype(jnp.float32)

    def hist(z_):
        oh = jax.nn.one_hot(z_, num_topics, dtype=jnp.int32)
        return jnp.sum(oh * valid[..., None].astype(jnp.int32), axis=1)

    def sweep(s, carry):
        z, acc, tie = carry
        sk = jax.vmap(lambda k: jax.random.fold_in(k, s))(keys)
        u_w, u_wa, z_d, u_da = jax.vmap(
            lambda k, zr, n: _doc_randoms(k, zr, n, num_topics, alpha,
                                          mh_steps))(sk, z, nd)
        flat = lambda r: r.transpose(1, 0, 2).reshape(mh_steps, b * l)
        ndk = hist(z)
        doc = jnp.repeat(jnp.arange(b), l)
        z_new, t = _chain(z.reshape(b * l), rows, lambda k: ndk[doc, k], nk,
                          tab, flat(u_w), flat(u_wa), flat(z_d), flat(u_da),
                          alpha, beta, vocab_size * beta, num_topics, True,
                          dtype)
        z_new = jnp.where(valid, z_new.reshape(b, l), z)
        tie = tie | jnp.any(t.reshape(b, l) & valid, axis=1)
        acc = acc + jnp.where(s >= burnin, hist(z_new), 0)
        return z_new, acc, tie

    _, acc, tie = jax.lax.fori_loop(
        0, num_sweeps, sweep,
        (z, jnp.zeros((b, num_topics), jnp.int32), jnp.zeros((b,), bool)))
    avg = acc.astype(jnp.float32) / (num_sweeps - burnin)
    theta = (avg + alpha) / (nd.astype(jnp.float32)[:, None]
                             + num_topics * alpha)
    return theta, tie
