"""Pallas TPU kernel: count-delta aggregation as one-hot MXU matmuls.

The paper buffers topic reassignments and aggregates the hottest 2000 words
into a local *dense* matrix before pushing (section 3.3), because scatter-add
per reassignment is the bottleneck.  The TPU-native generalisation is to
aggregate *everything* densely on the MXU:

    dn_wk = onehot(w)^T @ (onehot(z_new) - onehot(z_old))     over changed tokens

which turns a scatter (no TPU hardware support) into two one-hot
constructions (VPU compares) and one [TB,V]x[TB,K] matmul (MXU).  +/-1
values are exact in f32, so the int32 result is exact.

  grid        : (V / VB, B / TB), token dim innermost so each vocab block
                accumulates over all token tiles before moving on
  VMEM blocks : tokens [1, TB]; output [VB, Kp] accumulator

Oracle: ``ref.delta_push_ref`` (dense scatter-add).

The *hybrid* path (paper section 3.3 verbatim, rather than generalised)
splits words at a hot/cold boundary ``H``: the top-``H`` hottest words --
frequency-ordered ids, so a logical-id prefix -- aggregate through the dense
one-hot kernel above, while the cold tail is emitted as compressed
``(row, col, +/-1)`` coordinate deltas (``cold_coo``) and applied through
``DistributedMatrix.push_sparse``.  ``delta_apply_coo_call`` is the
server-side Pallas kernel that turns such a coordinate buffer back into a
dense delta with the same one-hot-matmul trick (oracle:
``ref.delta_apply_coo_ref``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _delta_kernel(w_ref, zold_ref, znew_ref, chg_ref, out_ref, *,
                  vb: int):
    v_blk = pl.program_id(0)
    t_blk = pl.program_id(1)

    @pl.when(t_blk == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    tb = w_ref.shape[1]
    vb_, kp = out_ref.shape

    w = w_ref[0, :]
    zo = zold_ref[0, :]
    zn = znew_ref[0, :]
    chg = chg_ref[0, :].astype(jnp.float32)

    # one-hot over this vocab block only: local id in [0, VB)
    w_local = w - v_blk * vb
    iota_v = jax.lax.broadcasted_iota(jnp.int32, (tb, vb_), 1)
    onehot_w = jnp.where(iota_v == w_local[:, None], chg[:, None], 0.0)

    iota_k = jax.lax.broadcasted_iota(jnp.int32, (tb, kp), 1)
    dz = ((iota_k == zn[:, None]).astype(jnp.float32)
          - (iota_k == zo[:, None]).astype(jnp.float32))

    acc = jax.lax.dot_general(
        onehot_w, dz, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    out_ref[...] += acc.astype(jnp.int32)


def delta_push_call(w, z_old, z_new, changed, *, vocab_pad: int, k_pad: int,
                    tile_tokens: int = 1024, tile_vocab: int = 512,
                    interpret: bool = True):
    """Aggregate one block of reassignments into a dense [vocab_pad, k_pad]
    int32 delta.  Inputs are [1, B] int32 (``changed`` as int32 mask); B must
    be a multiple of ``tile_tokens``; vocab_pad of ``tile_vocab``; k_pad of
    128 (ops.py maintains this)."""
    b = w.shape[1]
    tb = min(tile_tokens, b)
    vb = min(tile_vocab, vocab_pad)
    assert b % tb == 0 and vocab_pad % vb == 0
    grid = (vocab_pad // vb, b // tb)

    tok = pl.BlockSpec((1, tb), lambda v, t: (0, t))
    out = pl.BlockSpec((vb, k_pad), lambda v, t: (v, 0))

    return pl.pallas_call(
        functools.partial(_delta_kernel, vb=vb),
        grid=grid,
        in_specs=[tok, tok, tok, tok],
        out_specs=out,
        out_shape=jax.ShapeDtypeStruct((vocab_pad, k_pad), jnp.int32),
        interpret=interpret,
        name="delta_push",
    )(w, z_old, z_new, changed)


# ---------------------------------------------------------------------------
# Hybrid hot/cold split (paper section 3.3): host-side helpers.
# ---------------------------------------------------------------------------

def split_hot_cold(w, changed, hot_words: int):
    """Partition changed tokens at the hot/cold word boundary.

    Words are frequency-ordered, so logical ids ``< hot_words`` are the
    paper's hottest words (its top-2000 dense buffer).  Returns boolean
    (hot, cold) masks; both imply ``changed``.
    """
    hot = changed & (w < hot_words)
    cold = changed & (w >= hot_words)
    return hot, cold


def cold_coo(w, z_old, z_new, cold_mask):
    """Compress the cold tail into coordinate deltas.

    Each changed cold token emits two entries: ``-1`` at ``(w, z_old)`` and
    ``+1`` at ``(w, z_new)`` -- the per-reassignment message of the paper's
    100k buffer.  Masked-out tokens emit value-0 entries (harmless under
    additive application), keeping shapes static for jit.
    Returns ``(rows [2B], cols [2B], vals [2B])``, all int32.
    """
    m = cold_mask.astype(jnp.int32)
    rows = jnp.concatenate([w, w]).astype(jnp.int32)
    cols = jnp.concatenate([z_old, z_new]).astype(jnp.int32)
    vals = jnp.concatenate([-m, m])
    return rows, cols, vals


# ---------------------------------------------------------------------------
# Sparse coordinate-delta application kernel.
# ---------------------------------------------------------------------------

def _coo_kernel(rows_ref, cols_ref, vals_ref, out_ref, *, vb: int):
    v_blk = pl.program_id(0)
    t_blk = pl.program_id(1)

    @pl.when(t_blk == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    tb = rows_ref.shape[1]
    vb_, kp = out_ref.shape

    r = rows_ref[0, :]
    c = cols_ref[0, :]
    v = vals_ref[0, :].astype(jnp.float32)

    # one-hot over this vocab block only, weighted by the +/-1 value;
    # out-of-block rows (and value-0 padding) match nothing / contribute 0
    r_local = r - v_blk * vb
    iota_v = jax.lax.broadcasted_iota(jnp.int32, (tb, vb_), 1)
    onehot_r = jnp.where(iota_v == r_local[:, None], v[:, None], 0.0)

    iota_k = jax.lax.broadcasted_iota(jnp.int32, (tb, kp), 1)
    onehot_c = (iota_k == c[:, None]).astype(jnp.float32)

    acc = jax.lax.dot_general(
        onehot_r, onehot_c, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    out_ref[...] += acc.astype(jnp.int32)


def delta_apply_coo_call(rows, cols, vals, *, vocab_pad: int, k_pad: int,
                         tile_tokens: int = 1024, tile_vocab: int = 512,
                         interpret: bool = True):
    """Apply a compressed coordinate-delta buffer as a dense
    [vocab_pad, k_pad] int32 delta.  Inputs are [1, M] int32 with value-0
    entries acting as padding; M must be a multiple of ``tile_tokens``,
    vocab_pad of ``tile_vocab``, k_pad of 128 (ops.py maintains this)."""
    m = rows.shape[1]
    tb = min(tile_tokens, m)
    vb = min(tile_vocab, vocab_pad)
    assert m % tb == 0 and vocab_pad % vb == 0
    grid = (vocab_pad // vb, m // tb)

    tok = pl.BlockSpec((1, tb), lambda v, t: (0, t))
    out = pl.BlockSpec((vb, k_pad), lambda v, t: (v, 0))

    return pl.pallas_call(
        functools.partial(_coo_kernel, vb=vb),
        grid=grid,
        in_specs=[tok, tok, tok],
        out_specs=out,
        out_shape=jax.ShapeDtypeStruct((vocab_pad, k_pad), jnp.int32),
        interpret=interpret,
        name="delta_apply_coo",
    )(rows, cols, vals)
