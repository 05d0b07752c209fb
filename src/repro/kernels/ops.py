"""Jit'd public wrappers around the Pallas kernels.

These handle the padding/layout contract (token-dim multiples of the tile,
K padded to 128 lanes, per-token vectors promoted to [1, B]) and fall back
to the jnp oracles where a kernel does not exist.

``interpret`` is resolved in ONE place -- ``default_interpret()`` -- so a
TPU run flips a single switch instead of touching every signature: every
wrapper takes ``interpret=None`` meaning "the process default": interpret
on the CPU backend (tests), compiled on an accelerator.  On the CPU the
``REPRO_INTERPRET`` env var may override it (``0``/``false`` compiles);
on an accelerator asking for interpret mode is an error, so a chip run
can never silently time the Pallas interpreter.  Explicit
``True``/``False`` still override per call.
"""
from __future__ import annotations

import os
from functools import partial
from typing import TYPE_CHECKING, Optional

import jax
import jax.numpy as jnp

from repro.core import alias as alias_mod
from repro.kernels import delta_push as _delta
from repro.kernels import mh_sample as _mh

if TYPE_CHECKING:  # avoid import cycle at runtime
    from repro.core.lightlda import LDAConfig, MHRandoms

LANES = 128  # TPU lane width: K is padded to a multiple of this


def default_interpret() -> bool:
    """The process-wide Pallas interpret default (see module docstring)."""
    backend = jax.default_backend()
    env = os.environ.get("REPRO_INTERPRET")
    forced = (None if env is None
              else env.strip().lower() not in ("0", "false", "no", ""))
    if backend != "cpu":
        if forced:
            raise RuntimeError(
                f"REPRO_INTERPRET={env!r} asks for interpreted Pallas "
                f"kernels on the {backend} backend; kernels compile there. "
                f"Unset REPRO_INTERPRET.")
        return False
    return True if forced is None else forced


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    return default_interpret() if interpret is None else bool(interpret)


def _pad_axis(x, mult, axis, value=0):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def mh_sample(rng: "MHRandoms", z0, nwk_rows, ndk_rows, nk,
              aprob_rows, aalias_rows, cfg: "LDAConfig", *,
              tile_tokens: Optional[int] = None,
              interpret: Optional[bool] = None,
              frozen: bool = False) -> jax.Array:
    """Fused MH chain for one block of tokens (kernels/mh_sample.py).

    Accepts the same unpadded [B, K]/[B] arrays as the oracle
    ``lightlda.mh_chain`` and returns [B] int32 new assignments.

    ``frozen=True`` is the inference-mode wrapper used by the serving
    subsystem (repro.infer): same kernel, compiled with the fold-in
    -dw-correction variant (doc counts only), for sampling unseen documents
    against a frozen snapshot.
    """
    interpret = _resolve_interpret(interpret)
    b = z0.shape[0]
    if tile_tokens is None:
        tile_tokens = _mh.token_tile(cfg.K + (-cfg.K) % LANES)

    def prep_rows(x, fill=0.0):
        x = _pad_axis(x.astype(jnp.float32) if x.dtype != jnp.int32 else x,
                      LANES, axis=1, value=fill)
        return _pad_axis(x, tile_tokens, axis=0)

    nwk_p = prep_rows(nwk_rows.astype(jnp.float32))
    ndk_p = prep_rows(ndk_rows.astype(jnp.float32))
    aprob_p = prep_rows(aprob_rows.astype(jnp.float32))
    aalias_p = prep_rows(aalias_rows)
    nk_p = _pad_axis(nk.astype(jnp.float32)[None, :], LANES, axis=1, value=1.0)

    z0_p = _pad_axis(z0[None, :], tile_tokens, axis=1)
    rand = [_pad_axis(r, tile_tokens, axis=1)
            for r in (rng.u_word, rng.u_waccept, rng.z_doc, rng.u_daccept)]
    # padded tokens: force "never accept" coins (ratio can't exceed 1e30)
    out = _mh.mh_sample_call(
        z0_p, nwk_p, ndk_p, nk_p, aprob_p, aalias_p,
        rand[0], rand[1], rand[2].astype(jnp.int32), rand[3],
        num_topics=cfg.K, vocab_size=cfg.V, alpha=cfg.alpha, beta=cfg.beta,
        mh_steps=cfg.mh_steps, tile_tokens=tile_tokens, interpret=interpret,
        frozen=frozen)
    return out[0, :b]


def delta_push(w, z_old, z_new, changed, vocab_size: int, num_topics: int, *,
               tile_tokens: int = 1024, tile_vocab: int = 512,
               interpret: Optional[bool] = None) -> jax.Array:
    """Dense [V, K] reassignment delta via one-hot MXU matmuls
    (kernels/delta_push.py).  Matches ``ref.delta_push_ref`` exactly."""
    interpret = _resolve_interpret(interpret)
    vb = min(tile_vocab, vocab_size + ((-vocab_size) % 8))
    vp = vocab_size + ((-vocab_size) % vb)
    kp = num_topics + ((-num_topics) % LANES)

    def tok(x):
        return _pad_axis(x.astype(jnp.int32)[None, :], tile_tokens, axis=1)

    # padded tokens have changed=0 and thus contribute nothing
    out = _delta.delta_push_call(
        tok(w), tok(z_old), tok(z_new), tok(changed),
        vocab_pad=vp, k_pad=kp, tile_tokens=tile_tokens, tile_vocab=vb,
        interpret=interpret)
    return out[:vocab_size, :num_topics]


def delta_apply_coo(rows, cols, vals, num_rows: int, num_topics: int, *,
                    tile_tokens: int = 1024, tile_vocab: int = 512,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Dense [num_rows, num_topics] delta from compressed ``(row, col, +/-1)``
    coordinate entries (kernels/delta_push.py ``_coo_kernel``) -- the server
    side of the hybrid cold-tail push.  Value-0 entries are padding.
    Matches ``ref.delta_apply_coo_ref`` exactly."""
    interpret = _resolve_interpret(interpret)
    vb = min(tile_vocab, num_rows + ((-num_rows) % 8))
    vp = num_rows + ((-num_rows) % vb)
    kp = num_topics + ((-num_topics) % LANES)

    def tok(x):
        return _pad_axis(x.astype(jnp.int32)[None, :], tile_tokens, axis=1)

    # padded entries have vals=0 and thus contribute nothing
    out = _delta.delta_apply_coo_call(
        tok(rows), tok(cols), tok(vals),
        vocab_pad=vp, k_pad=kp, tile_tokens=tile_tokens, tile_vocab=vb,
        interpret=interpret)
    return out[:num_rows, :num_topics]


def alias_build(weights, *, tile_rows: int = 128,
                interpret: Optional[bool] = None) -> "alias_mod.AliasTable":
    """Alias-table construction via the Pallas kernel
    (kernels/alias_build.py).

    ops-side preprocessing (XLA is better at sorts than kernels): scale
    weights to mean 1, build the initial small/large stack layouts with an
    argsort, pad K to the lane width with exactly-1.0 entries (excluded
    from both stacks -> provably never emitted as alias targets) and rows
    to the tile.  The kernel runs the sequential 2K retirement loop.

    Bitwise ``alias.build_alias_rows``: the same stacks in the same order
    and the same retirement steps, so a kernel sweep stays bit-identical
    to the oracle sweep.
    """
    interpret = _resolve_interpret(interpret)
    v, k = weights.shape
    q = weights.astype(jnp.float32) * (
        k / jnp.maximum(weights.sum(-1, keepdims=True), 1e-30))
    q = _pad_axis(q, LANES, axis=1, value=1.0)
    kp = q.shape[1]
    idx = jnp.arange(kp, dtype=jnp.int32)[None, :]
    is_small = q < 1.0
    # every real column that is not small is large (Vose's split, as in
    # the oracle); only the padded columns stay out of both stacks
    is_large = ~is_small & (idx < k)
    # smalls (then larges) packed to the front, ascending
    small = jnp.argsort(jnp.where(is_small, idx, idx + kp),
                        axis=1).astype(jnp.int32)
    large = jnp.argsort(jnp.where(is_large, idx, idx + kp),
                        axis=1).astype(jnp.int32)
    ns = is_small.sum(-1).astype(jnp.int32)
    nl = is_large.sum(-1).astype(jnp.int32)

    vp = v + ((-v) % tile_rows)
    q = _pad_axis(q, tile_rows, axis=0, value=1.0)
    small = _pad_axis(small, tile_rows, axis=0)
    large = _pad_axis(large, tile_rows, axis=0)
    ns = _pad_axis(ns[:, None], tile_rows, axis=0)
    nl = _pad_axis(nl[:, None], tile_rows, axis=0)

    from repro.kernels import alias_build as _ab
    prob, alias_idx = _ab.alias_build_call(
        q, small, large, ns, nl, num_cols=k, tile_rows=tile_rows,
        interpret=interpret)
    return alias_mod.AliasTable(prob[:v, :k], alias_idx[:v, :k])
