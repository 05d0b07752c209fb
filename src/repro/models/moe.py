"""Mixture-of-Experts with expert-parallel all-to-all dispatch.

This block is the paper's parameter-server pattern transplanted to MoE
(DESIGN.md section 4): experts are placed on model-axis shards **cyclically**
(expert e lives on shard ``e mod M`` -- paper section 2.2), tokens are
*pushed* to their experts through fixed-capacity buffers (the paper's
bounded message buffers, section 3.3 -- overflow tokens are dropped, the
standard dropped-token MoE), and results are *pulled* back by the symmetric
all-to-all.  Addition-commutativity of the combine (gate-weighted sum) plays
the same role as in the paper's push semantics.

Two paths:
  * ``moe_block_dense``  -- reference: every expert runs on every token with
    gate masking.  Exact (no capacity drops); used by smoke tests and as the
    oracle for the distributed path.
  * ``moe_block_spmd``   -- production: shard_map over (dp..., model) with
    two-level grouping (dst-shard buckets, then local-expert buckets) and a
    pair of all-to-alls.  All buffers are static-shape (capacity-bounded).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import apply_mlp, init_mlp


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def init_moe(key: jax.Array, cfg: ModelConfig) -> dict:
    d = cfg.d_model
    e = cfg.num_experts
    fe = cfg.moe_d_ff or cfg.d_ff
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 5)
    p = {
        "router": (jax.random.normal(ks[0], (d, e)) * d ** -0.5).astype(jnp.float32),
        "experts": {
            "w_gate": (jax.random.normal(ks[1], (e, d, fe)) * d ** -0.5).astype(dt),
            "w_up": (jax.random.normal(ks[2], (e, d, fe)) * d ** -0.5).astype(dt),
            "w_down": (jax.random.normal(ks[3], (e, fe, d)) * fe ** -0.5).astype(dt),
        },
    }
    if cfg.num_shared_experts > 0:
        p["shared"] = init_mlp(ks[4], d, fe * cfg.num_shared_experts, dt)
    return p


def _route(params: dict, x: jax.Array, cfg: ModelConfig):
    """Top-k routing.  x: [T, D] -> (gates [T,k], experts [T,k], aux-loss)."""
    logits = x.astype(jnp.float32) @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    vals, ids = jax.lax.top_k(probs, cfg.top_k)
    gates = vals / jnp.maximum(vals.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss: E * sum_e f_e * p_e
    e = cfg.num_experts
    frac = jnp.zeros((e,), jnp.float32).at[ids.reshape(-1)].add(
        1.0 / (x.shape[0] * cfg.top_k))
    mean_p = probs.mean(0)
    aux = e * jnp.sum(frac * mean_p)
    return gates, ids, aux


# ---------------------------------------------------------------------------
# Reference path: dense (every expert on every token, gate-masked)
# ---------------------------------------------------------------------------

def moe_block_dense(params: dict, x: jax.Array, cfg: ModelConfig
                    ) -> Tuple[jax.Array, jax.Array]:
    """x: [T, D].  Exact MoE (no capacity drops); O(E) compute."""
    gates, ids, aux = _route(params, x, cfg)
    t, d = x.shape
    e = cfg.num_experts
    # [T, E] combined gate per expert
    gate_e = jnp.zeros((t, e), x.dtype).at[
        jnp.arange(t)[:, None], ids].add(gates.astype(x.dtype))
    we = params["experts"]
    h = jnp.einsum("td,edf->tef", x, we["w_gate"])
    u = jnp.einsum("td,edf->tef", x, we["w_up"])
    y = jnp.einsum("tef,efd->ted", jax.nn.silu(h) * u, we["w_down"])
    out = jnp.einsum("ted,te->td", y, gate_e)
    if "shared" in params:
        out = out + apply_mlp(params["shared"], x, cfg.act)
    return out, aux


# ---------------------------------------------------------------------------
# Production path: expert-parallel shard_map with all-to-all routing
# ---------------------------------------------------------------------------

def _group_by(dst: jax.Array, num_groups: int, capacity: int):
    """Assign each row a slot within its destination group.

    Returns (pos [R] slot id, keep [R] bool).  Rows overflowing a group's
    capacity are dropped (pos scatters with mode='drop') -- the bounded
    buffer of paper section 3.3.
    """
    oh = jax.nn.one_hot(dst, num_groups, dtype=jnp.int32)        # [R, G]
    pos = jnp.take_along_axis(jnp.cumsum(oh, axis=0) - 1,
                              dst[:, None], axis=1)[:, 0]
    keep = pos < capacity
    return pos, keep


def _expert_ffn(we: dict, xg: jax.Array) -> jax.Array:
    """xg: [E_local, C, D] -> [E_local, C, D] (per-expert SwiGLU)."""
    h = jnp.einsum("ecd,edf->ecf", xg, we["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", xg, we["w_up"])
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u, we["w_down"])


def _moe_local(x_loc, router, we_local, shared, *, cfg: ModelConfig,
               model_axis: str, num_model_shards: int,
               dp_axes: Tuple[str, ...]):
    """Per-shard body under shard_map.

    x_loc: [t, D] this shard's tokens.  we_local: expert weights with the
    leading E axis already sharded to [E_local, ...] by shard_map.
    """
    m = num_model_shards
    e_local = cfg.num_experts // m
    t, d = x_loc.shape
    k = cfg.top_k

    # ZeRO gather: expert weights arrive dp-sharded on their axis-1 (storage
    # sharding, specs.py); gather them for use.  On a real pod this
    # all-gather overlaps the router compute.
    if dp_axes:
        we_local = jax.tree.map(
            lambda w: jax.lax.all_gather(w, dp_axes, axis=1, tiled=True),
            we_local)

    gates, ids, aux = _route({"router": router}, x_loc, cfg)

    # ---- level 1: bucket (token, k) pairs by destination shard ----
    flat_e = ids.reshape(t * k)                     # global expert ids
    tok_idx = jnp.repeat(jnp.arange(t), k)
    dst = flat_e % m                                # cyclic placement (paper 2.2)
    le = flat_e // m                                # local expert id at dst
    cap1 = _round_up(int(t * k / m * cfg.capacity_factor) + 1, 8)
    pos1, keep1 = _group_by(dst, m, cap1)

    # payload: activations + local-expert id channel (meta rides along)
    send = jnp.zeros((m * cap1, d + 1), x_loc.dtype)
    payload = jnp.concatenate(
        [x_loc[tok_idx], le.astype(x_loc.dtype)[:, None]], axis=-1)
    slot = dst * cap1 + jnp.where(keep1, pos1, m * cap1)   # overflow -> drop
    send = send.at[slot].set(payload, mode="drop")
    # empty slots: mark le channel invalid (-1)
    filled = jnp.zeros((m * cap1,), bool).at[slot].set(True, mode="drop")
    send = send.at[:, d].set(jnp.where(filled, send[:, d], -1.0))

    # ---- push: all-to-all to the expert owners (paper push, sec. 2.4) ----
    recv = jax.lax.all_to_all(send.reshape(m, cap1, d + 1), model_axis,
                              split_axis=0, concat_axis=0, tiled=False)
    recv = recv.reshape(m * cap1, d + 1)
    rx, rle = recv[:, :d], recv[:, d].astype(jnp.int32)
    valid = rle >= 0

    # ---- level 2: bucket received rows by local expert ----
    cap2 = _round_up(int(m * cap1 / max(e_local, 1) * cfg.capacity_factor) + 1, 8)
    le2 = jnp.where(valid, rle, 0)
    pos2, keep2 = _group_by(le2, e_local, cap2)
    keep2 &= valid
    xg = jnp.zeros((e_local * cap2, d), x_loc.dtype)
    slot2 = le2 * cap2 + jnp.where(keep2, pos2, e_local * cap2)
    xg = xg.at[slot2].set(rx, mode="drop").reshape(e_local, cap2, d)

    yg = _expert_ffn(we_local, xg).reshape(e_local * cap2, d)

    # ---- return trip: ungroup, all-to-all back (paper pull, sec. 2.3) ----
    # (slot2 may be the drop sentinel e_local*cap2; clamp the gather and
    # zero dropped rows)
    y_rows = jnp.where(keep2[:, None],
                       jnp.take(yg, jnp.minimum(slot2, e_local * cap2 - 1),
                                axis=0), 0.0)
    back = jax.lax.all_to_all(y_rows.reshape(m, cap1, d), model_axis,
                              split_axis=0, concat_axis=0, tiled=False)
    back = back.reshape(m * cap1, d)

    # ---- combine at source with gates (additive, order-free: sec. 2.5) ----
    y_tok = jnp.take(back, jnp.minimum(slot, m * cap1 - 1), axis=0)
    y_tok = jnp.where(keep1[:, None], y_tok, 0.0)
    out = jnp.zeros_like(x_loc).at[tok_idx].add(
        y_tok * gates.reshape(t * k, 1).astype(x_loc.dtype))

    if shared is not None:
        out = out + apply_mlp(shared, x_loc, cfg.act)

    # aux loss: average over all shards (out_spec P() needs it replicated)
    aux = jax.lax.pmean(aux, (model_axis,) + tuple(dp_axes))
    return out, aux


def moe_block_spmd(params: dict, x: jax.Array, cfg: ModelConfig, mesh,
                   dp_axes: Tuple[str, ...], model_axis: str
                   ) -> Tuple[jax.Array, jax.Array]:
    """x: [T, D] with T divisible by the total mesh size (caller pads).

    Tokens are resharded over (dp..., model); experts live on the model
    axis.  Returns (y [T, D], aux scalar).
    """
    m = dict(zip(mesh.axis_names, mesh.devices.shape))[model_axis]

    body = partial(_moe_local, cfg=cfg, model_axis=model_axis,
                   num_model_shards=m, dp_axes=tuple(dp_axes))
    token_spec = P(tuple(dp_axes) + (model_axis,), None)
    shared = params.get("shared")
    shared_spec = jax.tree.map(lambda _: P(), shared) if shared is not None else None
    expert_spec = jax.tree.map(
        lambda _: P(model_axis, tuple(dp_axes), None), params["experts"])
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(token_spec, P(), expert_spec, shared_spec),
        out_specs=(token_spec, P()),
        check_vma=False)
    return fn(x, params["router"], params["experts"], shared)


def moe_block(params: dict, x: jax.Array, cfg: ModelConfig, mesh_ctx
              ) -> Tuple[jax.Array, jax.Array]:
    """Dispatching wrapper: [B, S, D] in/out.  Chooses the SPMD path when a
    mesh with a model axis is available, else the dense reference."""
    b, s, d = x.shape
    if mesh_ctx is not None and mesh_ctx.mesh is not None and mesh_ctx.model:
        # Stage the reshard explicitly: (1) land the hidden on batch-only
        # sharding (un-shard d_model) so the [B,S,D]->[B*S,D] reshape keeps
        # dim0 dp-sharded, then (2) constrain tokens onto (dp..., model)
        # before shard_map.  Without this GSPMD "involuntarily fully
        # rematerializes" -- an all-gather of the whole global microbatch
        # per MoE layer, measured at 6.3 TB/device/step on llama4-scout.
        # Removing stage (1) and keeping only (2) was tried and REFUTED:
        # the reshape of a d_model-sharded tensor re-triggers the full
        # rematerialization (EXPERIMENTS.md section Perf, iteration 3).
        dp = tuple(mesh_ctx.dp)
        x = mesh_ctx.constrain(x, P(dp, None, None))
        flat = x.reshape(b * s, d)
        # explicit intermediate (dp-only) constraints on BOTH sides of the
        # token resharding: the backward of a merged-dim reshape under
        # (dp, model) token sharding cannot be expressed as a slice and
        # GSPMD falls back to full rematerialization (measured 2x 5 GiB
        # f32 global gathers per layer on llama4).  With the staging
        # points, each reverse reshard is a model-axis gather of the local
        # token slab (~160 MB) instead.
        flat = mesh_ctx.constrain(flat, P(dp, None))
        flat = mesh_ctx.constrain(flat, P(dp + (mesh_ctx.model,), None))
        total = mesh_ctx.num_devices
        tpad = _round_up(b * s, total)
        if tpad != b * s:
            flat = jnp.pad(flat, ((0, tpad - b * s), (0, 0)))
        y, aux = moe_block_spmd(params, flat, cfg, mesh_ctx.mesh,
                                mesh_ctx.dp, mesh_ctx.model)
        y = y[:b * s]
        y = mesh_ctx.constrain(y, P(dp, None))
    else:
        flat = x.reshape(b * s, d)
        y, aux = moe_block_dense(params, flat, cfg)
    return y.reshape(b, s, d), aux
