"""Batched fold-in inference against a frozen model (DESIGN.md section 3).

Fold-in estimates θ_d for *unseen* documents by Gibbs/MH-sampling their
topic assignments with the model counts (n_wk, n_k) frozen -- the serving
counterpart of the training sweep in core/lightlda.py, and the sampler
behind the paper's IR use cases (retrieval smoothing, feedback).

The chain reuses LightLDA's O(1) machinery wholesale: because the word
proposal q_w(k) ∝ (n_wk+β)/(n_k+Vβ) depends only on the frozen counts, the
Vose alias tables are built ONCE per snapshot (``lightlda.freeze_model``)
and every request afterwards samples in amortised O(1) per token.  The only
semantic difference from training is the -dw correction: an unseen
document's tokens were never counted into n_wk/n_k, so the exclusion
applies to the local n_dk only (``frozen=True`` in ``mh_chain`` and the
Pallas kernel).

Layout: documents are packed into a dense [B, L] batch (tokens left-packed
per row, right-padded with ``valid=False``).  All randomness is derived
from a *per-document* PRNG key, and every operation in the sweep is
row-wise -- no cross-document reductions -- so a document's θ is a pure
function of (snapshot, tokens, its key, L).  The query engine relies on
this: results are bit-identical no matter how requests are batched
together, which is what makes padding-bucket batching transparent to
callers.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import lightlda as lda
from repro.obs import ObsConfig


@dataclasses.dataclass(frozen=True)
class FoldInConfig:
    """Fold-in chain schedule.

    ``num_sweeps`` full passes over each document's tokens; θ is estimated
    from the average n_dk of the post-``burnin`` sweeps (a Rao-Blackwellised
    point estimate, lower variance than the last sample alone).

    ``obs`` is the serving-side telemetry tri-state (None: inherit the
    installed session; ``ObsConfig(enabled=False)``: suppress the
    engine's spans/metrics locally).  ``ObsConfig`` is frozen and
    hashable, so this config remains a valid jit static argname.
    """

    num_sweeps: int = 30
    burnin: int = 10
    use_kernels: bool = False     # Pallas inference kernel (frozen=True)
    kernel_interpret: Optional[bool] = None  # None: ops.default_interpret
    obs: Optional[ObsConfig] = None

    def __post_init__(self):
        assert 0 <= self.burnin < self.num_sweeps, (self.burnin,
                                                    self.num_sweeps)


def pack_docs(docs: Sequence[np.ndarray], length: int
              ) -> Tuple[np.ndarray, np.ndarray]:
    """Pack token-id lists into the dense [B, L] fold-in layout.

    Tokens are left-packed and right-padded (the layout ``fold_in_batch``
    requires); docs longer than ``length`` are truncated.
    """
    b = len(docs)
    w = np.zeros((b, length), np.int32)
    valid = np.zeros((b, length), bool)
    for i, doc in enumerate(docs):
        n = min(len(doc), length)
        w[i, :n] = np.asarray(doc[:n], np.int32)
        valid[i, :n] = True
    return w, valid


def _doc_randoms(key: jax.Array, z_row: jax.Array, nd: jax.Array,
                 cfg: lda.LDAConfig) -> Tuple[jax.Array, jax.Array,
                                              jax.Array, jax.Array]:
    """Pre-draw one sweep's MH randomness for a single document row.

    Mirrors ``lightlda.draw_mh_randoms`` + ``make_doc_draw`` but scoped to
    one [L] row: the doc proposal q_d(k) ∝ n_dk+α is drawn O(1) by picking
    a uniformly random token of the row's left-packed prefix (the n_dk/N_d
    part) or a uniform topic (the α-branch).  Returns [mh_steps, L] arrays.
    """
    l = z_row.shape[0]
    shape = (cfg.mh_steps, l)
    kw, kwa, kd, kda = jax.random.split(key, 4)
    k1, k2, k3 = jax.random.split(kd, 3)
    ndf = jnp.maximum(nd.astype(jnp.float32), 1.0)
    pos = (jax.random.uniform(k1, shape) * ndf).astype(jnp.int32)
    pos = jnp.minimum(pos, jnp.maximum(nd - 1, 0))
    z_tok = jnp.take(z_row, pos)
    z_unif = jax.random.randint(k2, shape, 0, cfg.K, dtype=jnp.int32)
    use_tok = (jax.random.uniform(k3, shape)
               * (nd.astype(jnp.float32) + cfg.K * cfg.alpha)
               < nd.astype(jnp.float32))
    z_doc = jnp.where(use_tok, z_tok, z_unif)
    return (jax.random.uniform(kw, shape), jax.random.uniform(kwa, shape),
            z_doc, jax.random.uniform(kda, shape))


def _ndk_from_z(z: jax.Array, valid: jax.Array, k: int) -> jax.Array:
    """[B, L] assignments -> [B, K] doc-topic counts (row-wise one-hot sum)."""
    oh = jax.nn.one_hot(z, k, dtype=jnp.int32)
    return jnp.sum(oh * valid[..., None].astype(jnp.int32), axis=1)


@partial(jax.jit, static_argnames=("cfg", "fcfg"))
def fold_in_batch(model: lda.FrozenModel, w: jax.Array, valid: jax.Array,
                  doc_keys: jax.Array, cfg: lda.LDAConfig,
                  fcfg: FoldInConfig) -> jax.Array:
    """Fold a batch of unseen documents into a frozen model; return θ [B, K].

    ``w``/``valid`` are the [B, L] packed layout of ``pack_docs``;
    ``doc_keys`` is a [B] batch of PRNG keys (one per document -- the
    batch-composition-independence contract, see module docstring).

    One sweep resamples every token once against the sweep-start state
    (the serving analogue of the training block: the MH correction makes
    the stale proposals valid, same argument as the paper's asynchrony).
    """
    b, l = w.shape
    w_flat = w.reshape(b * l)
    nd = jnp.sum(valid.astype(jnp.int32), axis=1)                  # [B]

    init_keys = jax.vmap(lambda k: jax.random.fold_in(k, 0x1d4))(doc_keys)
    z = jax.vmap(lambda k: jax.random.randint(k, (l,), 0, cfg.K,
                                              dtype=jnp.int32))(init_keys)

    def sweep(s, carry):
        z, ndk_acc = carry
        with jax.named_scope("mh.chain"):
            sweep_keys = jax.vmap(lambda k: jax.random.fold_in(k, s))(
                doc_keys)
            u_w, u_wa, z_d, u_da = jax.vmap(
                lambda k, zr, n: _doc_randoms(k, zr, n, cfg))(
                    sweep_keys, z, nd)
            # [B, S, L] -> [S, B*L] flat token order
            rng = lda.MHRandoms(*(r.transpose(1, 0, 2)
                                  .reshape(cfg.mh_steps, b * l)
                                  for r in (u_w, u_wa, z_d, u_da)))
        with jax.named_scope("ndk.merge"):
            ndk = _ndk_from_z(z, valid, cfg.K)
        with jax.named_scope("ps.pull"):
            ndk_rows = jnp.broadcast_to(
                ndk[:, None, :], (b, l, cfg.K)).reshape(b * l, cfg.K)
        # the model's row gathers (ps.pull) and the chain (mh.chain) are
        # scoped inside sample_tokens_frozen
        z_new = lda.sample_tokens_frozen(
            model, rng, z.reshape(b * l), w_flat, ndk_rows, cfg,
            use_kernels=fcfg.use_kernels, interpret=fcfg.kernel_interpret)
        with jax.named_scope("mh.chain"):
            z_new = jnp.where(valid, z_new.reshape(b, l), z)
        with jax.named_scope("ndk.merge"):
            ndk_acc = ndk_acc + jnp.where(
                s >= fcfg.burnin, _ndk_from_z(z_new, valid, cfg.K), 0)
        return z_new, ndk_acc

    _, ndk_acc = jax.lax.fori_loop(
        0, fcfg.num_sweeps, sweep, (z, jnp.zeros((b, cfg.K), jnp.int32)))
    samples = fcfg.num_sweeps - fcfg.burnin
    ndk_avg = ndk_acc.astype(jnp.float32) / samples
    return ((ndk_avg + cfg.alpha)
            / (nd.astype(jnp.float32)[:, None] + cfg.K * cfg.alpha))


def fold_in_docs(model: lda.FrozenModel, docs: Sequence[np.ndarray],
                 cfg: lda.LDAConfig, fcfg: FoldInConfig,
                 seeds: Optional[Sequence[int]] = None,
                 length: Optional[int] = None) -> np.ndarray:
    """Convenience one-shot fold-in for a list of docs (no batching policy;
    the query engine adds padding-bucket batching on top)."""
    if length is None:
        length = max((len(d) for d in docs), default=1) or 1
    w, valid = pack_docs(docs, length)
    if seeds is None:
        seeds = range(len(docs))
    keys = jnp.stack([jax.random.PRNGKey(s) for s in seeds])
    theta = fold_in_batch(model, jnp.asarray(w), jnp.asarray(valid), keys,
                          cfg, fcfg)
    return np.asarray(theta)
