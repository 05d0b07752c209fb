"""Held-out perplexity (paper Table 1 / Figure 6).

The paper compares perplexity across three inference algorithms; MLlib's
evaluators use point estimates of the topic mixtures.  We use the same
estimator for *all* algorithms so the comparison is internally fair (as the
paper's is):

  θ_dk = (n_dk + α) / (N_d + Kα)        φ_wk = (n_wk + β) / (n_k + Vβ)

  perplexity = exp( - Σ_i log Σ_k θ_{d_i,k} φ_{w_i,k} / N )

Held-out documents are scored by *fold-in*: half of each document's tokens
are used to estimate θ_d (with φ frozen), the other half are scored.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def theta_from_counts(ndk: jax.Array, alpha: float) -> jax.Array:
    k = ndk.shape[-1]
    nd = ndk.sum(-1, keepdims=True)
    return (ndk + alpha) / (nd + k * alpha)


def phi_from_counts(nwk: jax.Array, nk: jax.Array, beta: float) -> jax.Array:
    v = nwk.shape[0]
    return (nwk + beta) / (nk[None, :] + v * beta)


def _token_ll(w, d, valid, theta, phi):
    p = jnp.einsum("ik,ik->i", jnp.take(theta, d, axis=0),
                   jnp.take(phi, w, axis=0))
    return jnp.sum(jnp.where(valid, jnp.log(jnp.maximum(p, 1e-30)), 0.0))


@partial(jax.jit, static_argnames=("num_docs", "chunk"))
def log_likelihood(w: jax.Array, d: jax.Array, valid: jax.Array,
                   theta: jax.Array, phi: jax.Array, num_docs: int,
                   chunk: int = 1 << 16) -> jax.Array:
    """Σ_i log p(w_i | θ_{d_i}, φ) over valid tokens.

    Tokens are scored ``chunk`` at a time: each token reads a [K] row of
    θ and of φ, and at a real corpus size (10^8 tokens x K=1024) the two
    gathered [N, K] operands alone would be hundreds of GB."""
    n = w.shape[0]
    if n <= chunk:
        return _token_ll(w, d, valid, theta, phi)
    pad = (-n) % chunk

    def split(x):
        return jnp.pad(x, (0, pad)).reshape(-1, chunk)

    def body(total, xs):
        return total + _token_ll(*xs, theta, phi), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                            (split(w), split(d), split(valid)))
    return total


@partial(jax.jit, static_argnames=("num_docs", "num_iters"))
def fold_in_theta(w: jax.Array, d: jax.Array, valid: jax.Array,
                  phi: jax.Array, num_docs: int, alpha: float,
                  num_iters: int = 20) -> jax.Array:
    """Estimate θ for held-out docs with φ frozen (EM on responsibilities)."""
    k = phi.shape[1]
    ndk = jnp.ones((num_docs, k), jnp.float32)
    phi_rows = jnp.take(phi, w, axis=0)                      # [N, K]
    wgt = valid.astype(jnp.float32)[:, None]

    def body(_, ndk):
        theta = theta_from_counts(ndk, alpha)
        resp = jnp.take(theta, d, axis=0) * phi_rows
        resp = resp / jnp.maximum(resp.sum(-1, keepdims=True), 1e-30)
        return jnp.zeros_like(ndk).at[d].add(resp * wgt)

    ndk = jax.lax.fori_loop(0, num_iters, body, ndk)
    return theta_from_counts(ndk, alpha)


def heldout_perplexity(fold_w, fold_d, fold_valid, eval_w, eval_d, eval_valid,
                       phi, num_docs: int, alpha: float) -> jax.Array:
    """Fold-in on one half of each held-out doc, score the other half."""
    theta = fold_in_theta(fold_w, fold_d, fold_valid, phi, num_docs, alpha)
    ll = log_likelihood(eval_w, eval_d, eval_valid, theta, phi, num_docs)
    n = jnp.maximum(eval_valid.sum(), 1)
    return jnp.exp(-ll / n)


@partial(jax.jit, static_argnames=("num_iters",))
def heldout_perplexity_packed(w, fold, ev, phi, alpha: float,
                              num_iters: int = 20) -> jax.Array:
    """``heldout_perplexity`` on the [D, L] layout of
    ``data.corpus.packed_fold_eval_split``: the same EM fold-in and
    score, but θ reaches each token by broadcast and n_dk is a sum over
    L, where the flat version gathers θ per token and scatter-adds n_dk
    with ~L duplicate rows per document."""
    k = phi.shape[1]
    phi_rows = jnp.take(phi, w, axis=0)                      # [D, L, K]
    wgt = fold.astype(jnp.float32)[..., None]

    def body(_, ndk):
        theta = theta_from_counts(ndk, alpha)
        resp = theta[:, None, :] * phi_rows
        resp = resp / jnp.maximum(resp.sum(-1, keepdims=True), 1e-30)
        return (resp * wgt).sum(1)

    ndk = jax.lax.fori_loop(0, num_iters, body,
                            jnp.ones((w.shape[0], k), jnp.float32))
    p = (phi_rows * theta_from_counts(ndk, alpha)[:, None, :]).sum(-1)
    ll = jnp.sum(jnp.where(ev, jnp.log(jnp.maximum(p, 1e-30)), 0.0))
    return jnp.exp(-ll / jnp.maximum(ev.sum(), 1))


def training_perplexity(w, d, valid, ndk, nwk_dense, nk,
                        alpha: float, beta: float) -> jax.Array:
    """In-sample perplexity (what paper Fig. 6 tracks over wall-time)."""
    theta = theta_from_counts(ndk.astype(jnp.float32), alpha)
    phi = phi_from_counts(nwk_dense.astype(jnp.float32),
                          nk.astype(jnp.float32), beta)
    ll = log_likelihood(w, d, valid, theta, phi, ndk.shape[0])
    n = jnp.maximum(valid.sum(), 1)
    return jnp.exp(-ll / n)


def stream_training_perplexity(reader, nwk_dense, nk, alpha: float,
                               beta: float) -> float:
    """In-sample perplexity over a whole sharded stream.

    ``phi`` comes from the global count tables; each shard contributes
    its log-likelihood with ``theta`` rebuilt from the shard's persisted
    assignments -- the same "assignments are data, counts are derived"
    discipline the streamed trainer uses.  One pass, one shard resident
    at a time; this is how planes without a resident ``SamplerState``
    (the network plane) evaluate.
    """
    import numpy as np

    phi = phi_from_counts(jnp.asarray(nwk_dense, jnp.float32),
                          jnp.asarray(nk, jnp.float32), beta)
    k = phi.shape[1]
    meta = reader.meta
    pos = np.arange(meta.tokens_per_shard)
    total_ll, total_n = 0.0, 0
    for sid in range(meta.num_shards):
        shard = reader.shard(sid)
        if shard.z is None:
            raise FileNotFoundError(f"shard {sid} has no z file")
        valid_np = pos < shard.n_tokens
        d = np.asarray(shard.d)
        ndk = np.zeros((meta.doc_cap, k), np.int32)
        np.add.at(ndk, (d, np.asarray(shard.z)),
                  valid_np.astype(np.int32))
        theta = theta_from_counts(jnp.asarray(ndk, jnp.float32), alpha)
        ll = log_likelihood(jnp.asarray(shard.w), jnp.asarray(d),
                            jnp.asarray(valid_np), theta, phi,
                            meta.doc_cap)
        total_ll += float(ll)
        total_n += int(shard.n_tokens)
    return float(np.exp(-total_ll / max(total_n, 1)))
