"""Pallas TPU kernel: Vose alias-table construction for a tile of words.

The sweep rebuilds alias tables for every vocabulary row from the count
snapshot (paper section 3, ref [14]).  Construction is a sequential
two-stack algorithm per row, but it vectorises across the *row* dimension:
this kernel runs the stack loop for a [R, K] tile with all per-row state
(residual weights, stacks, counters) held in VMEM/registers.

Trip count: K-1 steps.  Every real column starts on exactly one stack
(ns + nl == K) and padded columns on neither.  A step is active only while
ns > 0 and nl > 0, so only while ns + nl >= 2, and an active step takes
exactly one off ns + nl: it pops a small, and either that is all
(ns - 1) or the donor drops below 1 and moves from the large stack to the
small one (nl - 1, ns unchanged).  After K-1 steps every row is therefore
at its fixed point; further steps would be inactive in every row.

TPU adaptation: stack pops/pushes become one-hot masked selections over the
K lane dimension (no scatter/gather hardware needed), exactly like the
mh_sample kernel's column selects.  The O(K) cost per step makes the loop
O(K^2) per row (K-1 steps of K lanes) -- acceptable because construction
is amortized over a whole block of token resamples (the LightLDA
argument), and the row tile keeps the MXU-adjacent VPU busy across 8-128
rows at once.

Split of labour (mirrors ops.py's pre-gather pattern): the *initial* stack
layout needs an argsort, which XLA does better than a kernel -- ops.py
precomputes (q, small_stack, large_stack, n_small, n_large) and the kernel
runs only the sequential retirement loop.

Padding contract: padded columns carry q == 1.0 exactly and are excluded
from both stacks, so they finish as self-aliased prob-1 buckets that can
never be emitted as an alias target.

Oracle: ``repro.core.alias.build_alias_rows``, bitwise: both pop the
same stacks in the same order, and a one-hot lane reduction selects one
value exactly.  The oracle runs 2K steps; the K-1 bound above drops only
steps that change nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _alias_kernel(q_ref, small_ref, large_ref, ns_ref, nl_ref,
                  prob_ref, alias_ref, *, num_cols: int):
    r, kp = q_ref.shape
    iota = jax.lax.broadcasted_iota(jnp.int32, (r, kp), 1)

    # Per-row scalars (stack heights, popped indices) stay [R, 1] columns
    # so every select broadcasts along lanes; selections reduce in f32
    # (exact for indices < 2^24).
    def col_f(mat, idx):
        """mat[r, idx_r] per row (one-hot masked lane reduction)."""
        return jnp.sum(jnp.where(iota == idx, mat, 0.0), axis=1,
                       keepdims=True)

    def col_i(mat, idx):
        return col_f(mat.astype(jnp.float32), idx).astype(jnp.int32)

    def set_col(mat, idx, val, active):
        return jnp.where((iota == idx) & active, val, mat)

    def body(_, state):
        q, prob, alias, small, large, ns, nl = state
        active = (ns > 0) & (nl > 0)
        s_idx = col_i(small, jnp.maximum(ns - 1, 0))
        l_idx = col_i(large, jnp.maximum(nl - 1, 0))
        q_s = col_f(q, s_idx)
        q_l = col_f(q, l_idx)

        prob = set_col(prob, s_idx, q_s, active)
        alias = set_col(alias, s_idx, l_idx, active)
        q_l_new = q_l + q_s - 1.0
        q = set_col(q, l_idx, q_l_new, active)

        ns_after = jnp.where(active, ns - 1, ns)
        demote = active & (q_l_new < 1.0)
        nl = jnp.where(demote, nl - 1, nl)
        small = set_col(small, ns_after, l_idx, demote)
        ns = jnp.where(demote, ns_after + 1, ns_after)
        return (q, prob, alias, small, large, ns, nl)

    prob0 = jnp.ones((r, kp), jnp.float32)
    state = (q_ref[...], prob0, iota, small_ref[...], large_ref[...],
             ns_ref[...], nl_ref[...])
    # ns + nl == num_cols falls by one per active step (module docstring)
    state = jax.lax.fori_loop(0, max(num_cols - 1, 0), body, state)
    _, prob, alias, _, _, _, _ = state
    prob_ref[...] = jnp.clip(prob, 0.0, 1.0)
    alias_ref[...] = alias


def alias_build_call(q, small, large, ns, nl, *, num_cols: int,
                     tile_rows: int = 128, interpret: bool = True):
    """q/small/large: [V, Kp]; ns/nl: [V, 1] stack heights.  Returns
    (prob, alias).  ``tile_rows`` is a multiple of 8 (sublanes)."""
    v, kp = q.shape
    tr = min(tile_rows, v)
    assert v % tr == 0, (v, tr)
    grid = (v // tr,)

    rows = pl.BlockSpec((tr, kp), lambda i: (i, 0))
    cnt = pl.BlockSpec((tr, 1), lambda i: (i, 0))

    return pl.pallas_call(
        functools.partial(_alias_kernel, num_cols=num_cols),
        grid=grid,
        in_specs=[rows, rows, rows, cnt, cnt],
        out_specs=(rows, rows),
        out_shape=(jax.ShapeDtypeStruct((v, kp), jnp.float32),
                   jax.ShapeDtypeStruct((v, kp), jnp.int32)),
        interpret=interpret,
        name="alias_build",
    )(q, small, large, ns, nl)
