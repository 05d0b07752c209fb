"""Distributed LightLDA on the parameter server (paper section 3, Alg. 1).

Collapsed Gibbs sampling for LDA keeps three count statistics

  n_k   -- tokens assigned to topic k               (ps.VectorHandle, replicated)
  n_wk  -- word w assigned to topic k               (ps.MatrixHandle, cyclic over servers)
  n_dk  -- tokens of doc d assigned to topic k      (worker-local, never shared)

and resamples every token's topic ``z`` from the collapsed conditional

  P(z=k) ∝ (n_dk^{-dw} + α) · (n_wk^{-dw} + β) / (n_k^{-dw} + Vβ).

LightLDA factorises this into a *doc-proposal* ``q_d(k) ∝ n_dk + α`` (drawn
O(1) by picking a random token's current assignment, plus the α-branch) and a
*word-proposal* ``q_w(k) ∝ (n_wk + β)/(n_k + Vβ)`` (drawn O(1) from a Vose
alias table), with Metropolis-Hastings acceptance tests between them.

**Staleness model (the paper's asynchrony, made explicit).**  The Spark
implementation samples against counts that are stale by up to one buffer
window (~100k reassignments, paper section 3.3) because pushes are
asynchronous.  Here each *block* of ``block_tokens`` tokens is resampled
vectorised against the block-start snapshot; deltas are aggregated densely
(one-hot matmuls on the MXU -- the generalisation of the paper's hot-word
dense buffer) and merged at the block boundary.  ``block_tokens`` is thus the
exact analogue of the paper's buffer size.  The MH correction makes the
sampler valid for *any* proposal, which is why stale proposals are tolerable
(same argument as LightLDA / the paper).

Doc-topic counts ``n_dk`` are local to the worker that owns the document
(paper section 3: "document-specific and thus local"), and are refreshed at
block boundaries as well.

The per-token proposal/acceptance chain is the compute hot-spot; it is
implemented both as pure jnp (this file, the oracle) and as a Pallas TPU
kernel (kernels/mh_sample.py) selected with ``use_kernels=True``.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import ps
from repro.core import alias as alias_mod


@dataclasses.dataclass(frozen=True)
class LDAConfig:
    num_topics: int
    vocab_size: int
    alpha: float = 0.1            # document-topic Dirichlet prior
    beta: float = 0.01            # topic-word Dirichlet prior
    mh_steps: int = 2             # MH steps per token (LightLDA default)
    block_tokens: int = 8192      # staleness window == paper's push buffer
    num_shards: int = 1           # parameter-server shards (mesh model axis)
    use_kernels: bool = False     # Pallas kernels for MH + delta aggregation
    kernel_interpret: Optional[bool] = None  # None: kernels.ops.default_interpret
                                  # (interpret on CPU, compiled on a TPU)

    @property
    def K(self) -> int:
        return self.num_topics

    @property
    def V(self) -> int:
        return self.vocab_size


class SamplerState(NamedTuple):
    """Full sampler state.  Token arrays are flat and padded to a multiple of
    ``block_tokens`` (padding has ``valid == False``)."""

    w: jax.Array          # [N] word ids (frequency-ordered, paper section 3.2)
    d: jax.Array          # [N] doc ids (local to this worker/shard)
    z: jax.Array          # [N] topic assignments
    valid: jax.Array      # [N] bool, False for padding
    doc_start: jax.Array  # [D] first token index of each doc
    doc_len: jax.Array    # [D] token count of each doc
    nwk: "ps.MatrixHandle"  # (V, K) word-topic counts (PS client handle)
    nk: "ps.VectorHandle"   # (K,)  topic counts (PS client handle)
    ndk: jax.Array          # [D, K] doc-topic counts (worker-local)


# ---------------------------------------------------------------------------
# Initialisation
# ---------------------------------------------------------------------------

def init_state(key: jax.Array, w: jax.Array, d: jax.Array, num_docs: int,
               cfg: LDAConfig, doc_start: Optional[jax.Array] = None,
               doc_len: Optional[jax.Array] = None,
               client: Optional["ps.PSClient"] = None) -> SamplerState:
    """Random topic init + count-table construction.

    Counts are *rebuilt from z* with segment sums -- this same routine is the
    paper's fault-tolerance recovery (section 3.5): checkpoint z, rebuild the
    count tables on the servers.
    """
    n = w.shape[0]
    pad = (-n) % cfg.block_tokens
    z = jax.random.randint(key, (n,), 0, cfg.K, dtype=jnp.int32)
    w = jnp.concatenate([w.astype(jnp.int32), jnp.zeros((pad,), jnp.int32)])
    d = jnp.concatenate([d.astype(jnp.int32), jnp.zeros((pad,), jnp.int32)])
    z = jnp.concatenate([z, jnp.zeros((pad,), jnp.int32)])
    valid = jnp.concatenate([jnp.ones((n,), bool), jnp.zeros((pad,), bool)])

    if doc_start is None or doc_len is None:
        doc_len_ = jnp.zeros((num_docs,), jnp.int32).at[d[:n]].add(1)
        doc_start_ = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), jnp.cumsum(doc_len_)[:-1]])
        doc_start, doc_len = doc_start_, doc_len_

    nwk, nk, ndk = rebuild_counts(w, d, z, valid, num_docs, cfg,
                                  client=client)
    return SamplerState(w, d, z, valid, doc_start, doc_len, nwk, nk, ndk)


def rebuild_counts(w, d, z, valid, num_docs, cfg: LDAConfig,
                   client: Optional["ps.PSClient"] = None
                   ) -> Tuple["ps.MatrixHandle", "ps.VectorHandle", jax.Array]:
    """Rebuild (n_wk, n_k, n_dk) from assignments (paper section 3.5).

    Counts come back as PS client handles (``repro.ps``); pass ``client``
    to place them on a specific backend (default: in-process for
    ``cfg.num_shards`` cyclic shards).
    """
    if client is None:
        client = ps.client_for(cfg)
    one = valid.astype(jnp.int32)
    nwk_dense = jnp.zeros((cfg.V, cfg.K), jnp.int32).at[w, z].add(one)
    nk = jnp.zeros((cfg.K,), jnp.int32).at[z].add(one)
    ndk = jnp.zeros((num_docs, cfg.K), jnp.int32).at[d, z].add(one)
    return client.matrix_from_dense(nwk_dense), client.wrap_vector(nk), ndk


# ---------------------------------------------------------------------------
# Proposal densities and acceptance ratios (LightLDA eqs., paper eq. 1)
# ---------------------------------------------------------------------------

def _gather_cols(mat_rows: jax.Array, k: jax.Array) -> jax.Array:
    """mat_rows: [B, K]; k: [B] -> [B] picking column k_i of row i."""
    return jnp.take_along_axis(mat_rows, k[:, None], axis=-1)[:, 0]


def _posterior_terms(k, z0, nwk_w, ndk_d, nk, alpha, beta, vbeta,
                     frozen: bool = False):
    """Collapsed posterior factors p(k) with the -dw correction.

    The snapshot counts include the token's *block-start* assignment ``z0``;
    excluding the token itself means subtracting 1 exactly where ``k == z0``.
    Returns the three factors of paper eq. (1).

    ``frozen`` is the *fold-in* (inference) mode: the document being sampled
    is unseen, so its tokens were never counted into ``n_wk``/``n_k`` and the
    -dw correction applies only to the local ``n_dk``.
    """
    excl = (k == z0).astype(jnp.float32)
    excl_wk = 0.0 if frozen else excl
    ndk = _gather_cols(ndk_d, k).astype(jnp.float32) - excl
    nwk = _gather_cols(nwk_w, k).astype(jnp.float32) - excl_wk
    nk_ = jnp.take(nk, k).astype(jnp.float32) - excl_wk
    return (ndk + alpha) * (nwk + beta) / (nk_ + vbeta)


def _word_proposal_pmf(k, nwk_w, nk, beta, vbeta):
    """q_w(k) ∝ (n_wk+β)/(n_k+Vβ) evaluated with the *alias snapshot* counts
    (no -dw correction -- the proposal is whatever the table encodes)."""
    nwk = _gather_cols(nwk_w, k).astype(jnp.float32)
    nk_ = jnp.take(nk, k).astype(jnp.float32)
    return (nwk + beta) / (nk_ + vbeta)


def _doc_proposal_pmf(k, z0, ndk_d, alpha):
    """q_d(k) ∝ n_dk+α with block-start counts (what the draw actually uses)."""
    ndk = _gather_cols(ndk_d, k).astype(jnp.float32)
    return ndk + alpha


# ---------------------------------------------------------------------------
# The vectorised MH resampling chain for one block of tokens (jnp oracle).
# ---------------------------------------------------------------------------

class MHRandoms(NamedTuple):
    """Pre-drawn randomness for the MH chain, all shaped [mh_steps, B].

    Pre-drawing is exactly equivalent to drawing inside the chain: the word
    proposal consumes one uniform per step, the acceptance tests one coin
    each, and the doc proposal does not depend on the chain state (it only
    reads block-start quantities), so it can be materialised up-front.  This
    is what lets the Pallas kernel (kernels/mh_sample.py) and this jnp
    oracle share bit-identical semantics.
    """

    u_word: jax.Array    # uniforms for the alias draw
    u_waccept: jax.Array # accept coins, word step
    z_doc: jax.Array     # pre-drawn doc proposals (int32)
    u_daccept: jax.Array # accept coins, doc step


def draw_mh_randoms(key: jax.Array, doc_draw_fn, batch: int,
                    cfg: LDAConfig) -> MHRandoms:
    kw, kwa, kd, kda = jax.random.split(key, 4)
    shape = (cfg.mh_steps, batch)
    z_doc = jax.vmap(doc_draw_fn)(jax.random.split(kd, cfg.mh_steps))
    return MHRandoms(
        u_word=jax.random.uniform(kw, shape),
        u_waccept=jax.random.uniform(kwa, shape),
        z_doc=z_doc,
        u_daccept=jax.random.uniform(kda, shape))


def mh_chain(rng: MHRandoms, z0: jax.Array,
             nwk_rows: jax.Array, ndk_rows: jax.Array, nk: jax.Array,
             aprob_rows: jax.Array, aalias_rows: jax.Array,
             cfg: LDAConfig, frozen: bool = False) -> jax.Array:
    """Run ``cfg.mh_steps`` x (word-proposal, doc-proposal) MH steps for a
    block of B tokens, fully vectorised.

    All count inputs are *pre-gathered per token*:
      nwk_rows  [B, K]  snapshot word-topic rows for each token's word
      ndk_rows  [B, K]  block-start doc-topic rows for each token's doc
      nk        [K]     snapshot topic totals
      aprob/aalias [B,K] alias-table rows (built from the same snapshot)
    This pre-gather + pure-vector-compute split is what the Pallas kernel
    (kernels/mh_sample.py) mirrors tile-by-tile.

    ``frozen=True`` selects fold-in inference semantics (see
    ``_posterior_terms``): the model counts are a frozen snapshot that never
    contained this document.
    """
    alpha, beta = cfg.alpha, cfg.beta
    vbeta = cfg.V * beta

    def p(k):
        # The -dw correction always refers to z0 (what the snapshot contains).
        return _posterior_terms(k, z0, nwk_rows, ndk_rows, nk, alpha, beta,
                                vbeta, frozen=frozen)

    def step(z_cur, xs):
        u_w, u_wa, z_d, u_da = xs

        # --- word proposal (alias table; amortized O(1) per draw) ---
        z_prop = alias_mod.alias_sample(aprob_rows, aalias_rows, u_w)
        ratio = (p(z_prop) * _word_proposal_pmf(z_cur, nwk_rows, nk, beta, vbeta)) / (
            jnp.maximum(p(z_cur), 1e-30) *
            jnp.maximum(_word_proposal_pmf(z_prop, nwk_rows, nk, beta, vbeta), 1e-30))
        z_cur = jnp.where(u_wa < ratio, z_prop, z_cur)

        # --- doc proposal (random token's assignment / α-branch; O(1)) ---
        z_prop = z_d
        ratio = (p(z_prop) * _doc_proposal_pmf(z_cur, z0, ndk_rows, alpha)) / (
            jnp.maximum(p(z_cur), 1e-30) *
            jnp.maximum(_doc_proposal_pmf(z_prop, z0, ndk_rows, alpha), 1e-30))
        z_cur = jnp.where(u_da < ratio, z_prop, z_cur)
        return z_cur, ()

    z_new, _ = jax.lax.scan(step, z0, rng)
    return z_new


def make_doc_draw(key_shape, d_b, z_snapshot, doc_start, doc_len, cfg: LDAConfig):
    """Build the O(1) doc-proposal draw for a block.

    q_d(k) = (n_dk + α) / (N_d + Kα) is sampled *without* touching n_dk:
    with prob N_d/(N_d+Kα) return the assignment of a uniformly random token
    of doc d (that samples k with prob n_dk/N_d); otherwise return a uniform
    topic (the α-part).  ``z_snapshot`` is the block-start assignment array.
    """
    nd = jnp.take(doc_len, d_b).astype(jnp.float32)
    starts = jnp.take(doc_start, d_b)

    def draw(key):
        k1, k2, k3 = jax.random.split(key, 3)
        pos = (jax.random.uniform(k1, d_b.shape) * jnp.maximum(nd, 1.0)).astype(jnp.int32)
        pos = jnp.minimum(pos, jnp.maximum(nd.astype(jnp.int32) - 1, 0))
        z_tok = jnp.take(z_snapshot, starts + pos)
        z_unif = jax.random.randint(k2, d_b.shape, 0, cfg.K, dtype=jnp.int32)
        use_tok = jax.random.uniform(k3, d_b.shape) * (nd + cfg.K * cfg.alpha) < nd
        return jnp.where(use_tok, z_tok, z_unif)

    return draw


# ---------------------------------------------------------------------------
# Frozen-model sampling (serving / fold-in inference, DESIGN.md section 3).
#
# A serving snapshot freezes (n_wk, n_k) -- and therefore the word-proposal
# distribution q_w -- so the Vose alias tables are built ONCE per snapshot
# and amortised over every inference request, not rebuilt per block as in
# training.  ``sample_tokens_frozen`` is the core entry point the
# ``repro.infer`` subsystem drives; it is the same MH chain as training with
# the -dw correction restricted to the local doc counts.
# ---------------------------------------------------------------------------

def build_alias_tables(weights: jax.Array, use_kernels: bool = False,
                       interpret: Optional[bool] = None
                       ) -> alias_mod.AliasTable:
    """Alias tables for [R, K] proposal weights: the Pallas kernel under
    ``use_kernels`` (bitwise the jnp Vose build, and on a TPU at real
    V x K far cheaper), else ``alias.build_alias_rows``."""
    if use_kernels:
        from repro.kernels import ops as kops
        return kops.alias_build(weights, interpret=interpret)
    return alias_mod.build_alias_rows(weights)


def sample_chain(rng: MHRandoms, z0: jax.Array, nwk_rows: jax.Array,
                 ndk_rows: jax.Array, nk: jax.Array, aprob_rows: jax.Array,
                 aalias_rows: jax.Array, cfg: LDAConfig, *,
                 use_kernels: bool, interpret: Optional[bool],
                 frozen: bool = False) -> jax.Array:
    """The MH chain over pre-gathered rows: the Pallas kernel under
    ``use_kernels`` (bitwise ``mh_chain``), else ``mh_chain`` itself.
    ``frozen`` selects the fold-in semantics (see ``_posterior_terms``)."""
    if use_kernels:
        from repro.kernels import ops as kops
        return kops.mh_sample(rng, z0, nwk_rows, ndk_rows, nk, aprob_rows,
                              aalias_rows, cfg, interpret=interpret,
                              frozen=frozen)
    return mh_chain(rng, z0, nwk_rows, ndk_rows, nk, aprob_rows, aalias_rows,
                    cfg, frozen=frozen)


class FrozenModel(NamedTuple):
    """Immutable model snapshot for inference.

    ``nwk``/``nk`` are dense float32 counts (no server layout -- serving
    reads are all local); ``aprob``/``aalias`` are the per-word alias-table
    rows of the word proposal q_w(k) ∝ (n_wk+β)/(n_k+Vβ)."""

    nwk: jax.Array     # [V, K] float32 word-topic counts
    nk: jax.Array      # [K]    float32 topic totals
    aprob: jax.Array   # [V, K] float32 alias acceptance probabilities
    aalias: jax.Array  # [V, K] int32 alias targets


def freeze_model(nwk_dense: jax.Array, nk: jax.Array, cfg: LDAConfig,
                 weights: Optional[jax.Array] = None,
                 use_kernels: bool = False,
                 interpret: Optional[bool] = None) -> FrozenModel:
    """Freeze dense counts into a ``FrozenModel`` (alias tables included).

    This is the expensive, once-per-snapshot step: O(V*K) alias
    construction.  Every fold-in batch afterwards samples in amortised O(1)
    per token against these tables.  ``weights`` lets the caller pass the
    already-computed smoothed φ matrix (q_w and φ are the same quantity);
    otherwise it is computed here.

    ``use_kernels`` routes the alias build through the Pallas kernel
    (``build_alias_tables``), matching the training-side
    ``cfg.use_kernels`` convention.
    """
    from repro.core import perplexity as ppl
    nwk_f = nwk_dense.astype(jnp.float32)
    nk_f = nk.astype(jnp.float32)
    if weights is None:
        weights = ppl.phi_from_counts(nwk_f, nk_f, cfg.beta)
    table = build_alias_tables(weights, use_kernels, interpret)
    return FrozenModel(nwk_f, nk_f, table.prob, table.alias)


def sample_tokens_frozen(model: FrozenModel, rng: MHRandoms, z0: jax.Array,
                         w: jax.Array, ndk_rows: jax.Array, cfg: LDAConfig,
                         use_kernels: bool = False,
                         interpret: Optional[bool] = None) -> jax.Array:
    """Resample a flat batch of tokens against a frozen model.

    ``w``/``z0`` are [B]; ``ndk_rows`` is the per-token gather of the local
    doc-topic counts [B, K].  Selects the Pallas inference kernel with
    ``use_kernels`` (kernels/ops.py ``frozen=True`` wrapper); otherwise the
    jnp oracle chain.
    """
    with jax.named_scope("ps.pull"):
        nwk_rows = jnp.take(model.nwk, w, axis=0)
        aprob_rows = jnp.take(model.aprob, w, axis=0)
        aalias_rows = jnp.take(model.aalias, w, axis=0)
    with jax.named_scope("mh.chain"):
        return sample_chain(rng, z0, nwk_rows, ndk_rows, model.nk,
                            aprob_rows, aalias_rows, cfg,
                            use_kernels=use_kernels, interpret=interpret,
                            frozen=True)


# Dense delta aggregation (paper section 3.3) lives in ps/routes.py now:
# a block's reassignments aggregate through the handle's PushRoute
# (DenseRoute covers the old count_deltas; the executors merge the
# worker-local n_k/n_dk halves with in-place scatter-adds).


# ---------------------------------------------------------------------------
# One full sweep over the local token shard (Alg. 1 of the paper).
# ---------------------------------------------------------------------------

def sweep(state: SamplerState, key: jax.Array, cfg: LDAConfig,
          staleness: int = 0,
          route: Optional["ps.PushRoute"] = None) -> SamplerState:
    """Resample every token once (one Gibbs sweep == one paper "iteration").

    The SPMD collectives come from ``state.nwk``'s client backend
    (``repro.ps``): wrap the counts with ``PSClient.create(axis_name=...,
    model_axis=...)`` to run under shard_map.

    Routed through the asynchronous executor
    (``train.async_exec.snapshot_sweep``); ``staleness`` selects the
    bounded-staleness schedule and ``route`` the push policy --
    ``ps.DenseRoute`` (the default) / ``ps.CooRoute`` / ``ps.HybridRoute``.
    The defaults reproduce the classic per-block synchronous schedule
    exactly -- single-device defaults are the oracle used in tests.
    """
    from repro.train import async_exec
    return async_exec.snapshot_sweep(state, key, cfg, staleness=staleness,
                                     route=route)


def train(state: SamplerState, key: jax.Array, cfg: LDAConfig,
          num_sweeps: int) -> SamplerState:
    """Run ``num_sweeps`` Gibbs sweeps (jit-compiled loop)."""

    @jax.jit
    def one(state, key):
        return sweep(state, key, cfg)

    for i in range(num_sweeps):
        key, sub = jax.random.split(key)
        state = one(state, sub)
    return state


# ---------------------------------------------------------------------------
# Blocked / pipelined sweep (paper section 3.4).
#
# The full-snapshot sweep above replicates n_wk on every worker -- fine when
# V*K fits, but the paper's Web-scale setting cannot (ClueWeb12 vocabulary x
# 1000 topics).  LightLDA's answer is to iterate over *model blocks*: pull a
# fixed-size set of word rows, build alias tables for just those words,
# resample only the tokens whose word falls in the block, push the deltas,
# and prefetch the next block while sampling (the pipelining of section
# 3.4).  Worker memory is O(block x K) instead of O(V x K).
#
# Tokens are pre-grouped by word block by the host pipeline
# (``group_tokens_by_block``), which is the same frequency-ordered layout
# trick as section 3.2: because physical (cyclic) row order interleaves hot
# and cold words, every block carries a balanced share of tokens.
# ---------------------------------------------------------------------------

def block_token_index(w: np.ndarray, valid: np.ndarray, rows_per_block: int,
                      layout, cap_round: int = 256,
                      cap: Optional[int] = None) -> Tuple[np.ndarray,
                                                          np.ndarray]:
    """Host-side: group token indices by their word's *physical* model
    block.

    Returns (block_idx [n_blocks, cap] int32, block_valid [n_blocks, cap]).
    Tokens stay in document order (the doc proposal needs intact doc
    offsets); pad entries point at token 0 with valid=False, which is safe
    because the sweep applies all updates with duplicate-tolerant adds.
    Because physical (cyclic) row order interleaves hot and cold words
    (paper section 3.2), per-block token counts are naturally balanced.

    By default the capacity is sized by this token set's hottest block,
    rounded up to ``cap_round`` -- the stream executor's coarse bucket
    (``make_stream_executor``), so same-bucket shards reuse one jitted
    trace.  ``cap`` instead pins the capacity explicitly (raising if any
    block overflows it) for callers that need identical index shapes
    across every shard.  Fully vectorised: this runs once per shard per
    epoch on the stream path, so an O(N) Python loop here would dominate
    the host side.
    """
    phys = np.asarray(layout.to_physical(np.asarray(w).astype(np.int64)))
    valid = np.asarray(valid)
    block = phys // rows_per_block
    n_blocks = layout.pad_rows // rows_per_block
    counts = np.bincount(block[valid], minlength=n_blocks)
    need = max(int(counts.max()) if counts.size else 0, 1)
    if cap is None:
        cap = -(-need // cap_round) * cap_round
    elif need > cap:
        raise ValueError(f"block capacity {cap} overflows: hottest block "
                         f"holds {need} tokens")
    idx = np.zeros((n_blocks, cap), np.int32)
    bval = np.zeros((n_blocks, cap), bool)
    tok = np.nonzero(valid)[0]                       # token order
    order = np.argsort(block[tok], kind="stable")    # by block, ties in order
    tok = tok[order]
    bs = block[tok]
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    slot = np.arange(tok.shape[0]) - starts[bs]
    idx[bs, slot] = tok
    bval[bs, slot] = True
    return idx, bval


def sweep_blocked(state: SamplerState, key: jax.Array, cfg: LDAConfig,
                  block_idx: jax.Array, block_valid: jax.Array,
                  rows_per_block: int, staleness: int = 0,
                  route: Optional["ps.PushRoute"] = None) -> SamplerState:
    """One sweep processing the model in pulled blocks (paper section 3.4).

    Routed through the asynchronous pipelined executor
    (``train.async_exec.pipelined_sweep``): double-buffered block pulls
    (``PullHandle`` futures), a bounded-staleness merge schedule
    (``staleness`` block deltas may be in flight while a block samples)
    and a declarative push policy (``route``; ``ps.route_for`` maps a
    hot-word count onto the hybrid split).  The defaults reproduce the
    synchronous schedule of ``sweep_blocked_ref`` bitwise (asserted in
    tests/test_async_exec.py).
    """
    from repro.train import async_exec
    return async_exec.pipelined_sweep(state, key, cfg, block_idx,
                                      block_valid, rows_per_block,
                                      staleness=staleness, route=route)


def sweep_blocked_ref(state: SamplerState, key: jax.Array, cfg: LDAConfig,
                      block_idx: jax.Array, block_valid: jax.Array,
                      rows_per_block: int) -> SamplerState:
    """Synchronous blocked sweep, kept verbatim as the executor's oracle.

    This is the pre-executor implementation: every model block does
    pull -> sample -> push on the critical path.  The pipelined executor
    with ``staleness=0`` must match it bitwise -- this function is the
    correctness anchor for the whole asynchronous schedule (DESIGN.md
    section 7), so keep it boring and sequential.

    Per model block b (scanned; on a pod the next block's pull overlaps
    this block's sampling under XLA's async collectives -- the paper's
    pipelining):
      1. "pull" physical rows [b*rpb, (b+1)*rpb) (each pull touches every
         cyclic server equally -- the section 3.2 balance),
      2. build alias tables for those rows only (worker memory is
         O(rpb x K), never O(V x K) -- the Web-scale enabler),
      3. resample this block's tokens (gathered by ``block_token_index``),
      4. aggregate deltas densely [rpb, K] and push.
    Counts/z are updated with duplicate-tolerant adds so the pad entries
    of ``block_idx`` are harmless.
    """
    rpb = rows_per_block
    layout = state.nwk.layout
    n_blocks = block_idx.shape[0]
    cap = block_idx.shape[1]
    assert n_blocks * rpb == layout.pad_rows, (layout.pad_rows, rpb)

    def block_body(carry, inp):
        nwk_phys, nk, ndk, z_flat = carry
        blk, key_b = inp

        # 1. pull this block's rows (physical/cyclic order)
        rows = jax.lax.dynamic_slice_in_dim(nwk_phys, blk * rpb, rpb, axis=0)

        # 2. alias tables for the block only
        weights = (rows.astype(jnp.float32) + cfg.beta) / (
            nk.astype(jnp.float32)[None, :] + cfg.V * cfg.beta)
        table = alias_mod.build_alias_rows(weights)

        # 3. resample the block's tokens
        idx = block_idx[blk]
        vb = block_valid[blk]
        wb = jnp.take(state.w, idx)
        db = jnp.take(state.d, idx)
        z0 = jnp.take(z_flat, idx)
        local = jnp.clip(layout.to_physical(wb) - blk * rpb, 0, rpb - 1)
        nwk_rows = jnp.take(rows, local, axis=0)
        ndk_rows = jnp.take(ndk, db, axis=0)
        aprob = jnp.take(table.prob, local, axis=0)
        aalias = jnp.take(table.alias, local, axis=0)
        doc_draw = make_doc_draw(None, db, z_flat, state.doc_start,
                                 state.doc_len, cfg)
        rng = draw_mh_randoms(key_b, doc_draw, cap, cfg)
        z_new = mh_chain(rng, z0, nwk_rows, ndk_rows, nk, aprob, aalias, cfg)
        z_new = jnp.where(vb, z_new, z0)

        # 4. duplicate-tolerant add updates (pads contribute zero)
        amt = ((z_new != z0) & vb).astype(jnp.int32)
        d_rows = (jnp.zeros((rpb, cfg.K), jnp.int32)
                  .at[local, z0].add(-amt).at[local, z_new].add(amt))
        nwk_phys = jax.lax.dynamic_update_slice_in_dim(
            nwk_phys, rows + d_rows, blk * rpb, axis=0)
        nk = nk + (jnp.zeros((cfg.K,), jnp.int32)
                   .at[z0].add(-amt).at[z_new].add(amt))
        ndk = ndk.at[db, z0].add(-amt).at[db, z_new].add(amt)
        z_flat = z_flat.at[idx].add(jnp.where(vb, z_new - z0, 0))
        return (nwk_phys, nk, ndk, z_flat), ()

    keys = jax.random.split(key, n_blocks)
    carry = (state.nwk.value, state.nk.value, state.ndk, state.z)
    (nwk_phys, nk, ndk, z), _ = jax.lax.scan(
        block_body, carry, (jnp.arange(n_blocks), keys))
    return SamplerState(state.w, state.d, z, state.valid,
                        state.doc_start, state.doc_len,
                        state.nwk.with_value(nwk_phys),
                        state.nk.with_value(nk), ndk)
