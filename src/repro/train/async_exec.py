"""Asynchronous pipelined training executor (paper sections 2.5, 3.3, 3.4).

The paper's headline numbers come from its *asynchronous* workload shape:
workers sample against a bounded-stale snapshot while pulls and pushes are
still in flight, and reassignment deltas are buffered -- the hottest words
aggregated densely, the cold tail shipped as per-reassignment messages.
This module is that schedule, made deterministic for SPMD JAX and
expressed entirely through the Glint-style client API (``repro.ps``):
the executor holds ``MatrixHandle``/``VectorHandle``s, prefetches through
``PullHandle`` futures, and merges through the handle's ``PushRoute``.

**Staleness bound ``s``.**  Block ``i`` samples against a view of
``(n_k, n_dk, z)`` that is missing the deltas of the ``s`` most recent
blocks -- those pushes are "in flight".  Because block deltas only commute
(addition, paper section 2.5), any merge order is exactly-once-correct;
the bound makes the paper's unstructured asynchrony testable: ``s = 0`` is
the synchronous schedule and must match ``lightlda.sweep_blocked_ref``
bitwise (asserted in tests/test_async_exec.py).  Blocks whose in-flight
windows overlap are mutually independent, so the executor runs each
*group* of ``s + 1`` consecutive blocks as one fused, vectorised sampling
step and merges all of the group's deltas at the boundary -- fewer, larger
device ops and one cross-worker reduction per group instead of per block.

**Double-buffered pulls, as futures.**  While a group samples, the next
group's ``n_wk`` rows are in flight as a ``PullHandle`` riding the scan
carry: ``issue (pull_block) -> overlap (sample) -> await (result)``.  The
prefetch is *exact*, not just statistically tolerable: a group's
write-back only ever touches its own physical rows, so the next group's
rows cannot change while the pull is in flight.  XLA is free to overlap
the slice-pull with the Metropolis-Hastings chain; on a pod the pull is
the cross-server collective of paper section 3.4.

**Routed delta push (paper section 3.3).**  The group-boundary merge goes
through a declarative ``PushRoute`` -- ``DenseRoute`` (all words through
the dense MXU path), ``CooRoute`` (everything as compressed
``(row, col, +/-1)`` coordinates), or ``HybridRoute(hot_words=H)`` (the
paper's split: hot prefix dense, cold tail as the 100k-reassignment
message).  All routes are integer additions underneath, so the choice
never changes results, only traffic shape.

Every executor runs the same block step -- ``resample_block`` (gather,
MH chain), ``topic_delta`` and ``merge_doc_counts`` -- and the blocked and
tiered executors the same group, ``blocked_group``; each keeps only where
its rows come from and how it pushes n_wk.

Entry points:
  * ``pipelined_sweep``  -- the blocked model-parallel executor (the
    generalisation of ``lightlda.sweep_blocked``; worker memory
    O(group x K), the Web-scale path),
  * ``snapshot_sweep``   -- the full-snapshot executor (the generalisation
    of ``lightlda.sweep``; collectives supplied by the handle's backend),
  * ``make_executor``    -- host-side factory the launchers and
    ``train.loop.fit_lda`` drive.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as _obs
from repro import ps
from repro.core import lightlda as lda
from repro.core.alias import AliasTable
from repro.obs import ObsConfig
from repro.obs import scopes as _scopes
from repro.obs.trace import _block


@dataclasses.dataclass(frozen=True)
class ExecConfig:
    """Executor schedule knobs (orthogonal to the model's ``LDAConfig``).

    ``staleness``: how many block deltas may be in flight while a block
    samples; 0 reproduces the synchronous schedule exactly.  The string
    ``"auto"`` asks ``ps.autotune`` to measure candidate bounds when the
    executor is built (``make_executor`` only).
    ``route``: the declarative push policy (``ps.DenseRoute`` /
    ``ps.CooRoute`` / ``ps.HybridRoute``); ``hot_words`` is the legacy
    scalar knob mapped through ``ps.route_for`` when ``route`` is None.
    The string ``"auto"`` asks ``ps.autotune`` for a cost-model +
    measurement pick (``make_executor`` only).
    ``model_blocks``: >0 selects the blocked executor (``pipelined_sweep``)
    with the model pulled in that many blocks; 0 selects the full-snapshot
    executor (``snapshot_sweep``).
    ``obs``: telemetry tri-state (``repro.obs.ObsConfig``) -- None
    inherits the installed obs session, ``enabled=False`` suppresses the
    executor's spans locally.  Observation only: values are bitwise
    identical either way.
    """

    staleness: Union[int, str] = 0
    hot_words: Optional[int] = None
    model_blocks: int = 0
    route: Optional[Union[ps.PushRoute, str]] = None
    obs: Optional[ObsConfig] = None

    def wants_autotune(self) -> bool:
        return self.route == "auto" or self.staleness == "auto"

    def resolve_route(self, vocab_size: int) -> ps.PushRoute:
        if self.route == "auto" or self.staleness == "auto":
            raise ValueError(
                "route='auto'/staleness='auto' must be resolved by "
                "make_executor (which runs ps.autotune against the actual "
                "state) before the schedule is built; this code path "
                "(streaming / SPMD launchers) needs concrete values -- "
                "pass a ps.PushRoute / int, or run ps.autotune.autotune() "
                "yourself and use its TunedPlan.")
        if self.route is not None:
            return self.route
        return ps.route_for(self.hot_words, vocab_size)


def effective_staleness(n_blocks: int, staleness: int) -> int:
    """Largest usable bound <= ``staleness``.

    The group formulation needs the group size ``s + 1`` to divide the
    block count (scan steps must be uniform); the executor rounds the
    requested bound down to the nearest divisor rather than failing.
    """
    s = max(0, min(int(staleness), n_blocks - 1))
    while s > 0 and n_blocks % (s + 1):
        s -= 1
    return s


# ---------------------------------------------------------------------------
# The block step every executor runs.  Plain functions, traced inline: the
# phase scopes they open land in the caller's name stack unchanged.
# ---------------------------------------------------------------------------

def proposal_tables(rows: jax.Array, nk: jax.Array, cfg: "lda.LDAConfig"
                    ) -> AliasTable:
    """Alias tables of the word proposal q_w(k) ∝ (n_wk + β)/(n_k + Vβ)
    for a set of n_wk rows (the sweep-start snapshot, or one group's
    rows).  The kernel path builds them with the Pallas kernel, bitwise
    the jnp construction (paper section 3, ref [14])."""
    with jax.named_scope("alias.tables"):
        weights = (rows.astype(jnp.float32) + cfg.beta) / (
            nk.astype(jnp.float32)[None, :] + cfg.V * cfg.beta)
        return lda.build_alias_tables(weights, cfg.use_kernels,
                                      cfg.kernel_interpret)


def resample_block(key: jax.Array, cfg: "lda.LDAConfig", rows: jax.Array,
                   table: AliasTable, row_ids: jax.Array, d_b: jax.Array,
                   z0: jax.Array, valid: jax.Array, z_flat: jax.Array,
                   ndk: jax.Array, nk: jax.Array, doc_start: jax.Array,
                   doc_len: jax.Array) -> jax.Array:
    """Resample a batch of tokens against stale counts: gather each
    token's n_wk row (``rows[row_ids]``), n_dk row and alias-table rows,
    then run the MH chain.  Returns the new assignments, ``z0`` where
    ``valid`` is False."""
    with jax.named_scope("ps.pull"):
        nwk_rows = jnp.take(rows, row_ids, axis=0)
        ndk_rows = jnp.take(ndk, d_b, axis=0)
        aprob_rows = jnp.take(table.prob, row_ids, axis=0)
        aalias_rows = jnp.take(table.alias, row_ids, axis=0)
    with jax.named_scope("mh.chain"):
        doc_draw = lda.make_doc_draw(None, d_b, z_flat, doc_start, doc_len,
                                     cfg)
        rng = lda.draw_mh_randoms(key, doc_draw, z0.shape[0], cfg)
        z_new = lda.sample_chain(rng, z0, nwk_rows, ndk_rows, nk, aprob_rows,
                                 aalias_rows, cfg,
                                 use_kernels=cfg.use_kernels,
                                 interpret=cfg.kernel_interpret)
        return jnp.where(valid, z_new, z0)


def topic_delta(z0: jax.Array, z_new: jax.Array, amt: jax.Array,
                num_topics: int) -> jax.Array:
    """The ``[K]`` n_k delta of a batch of reassignments (``amt`` is 1
    where a token moved, else 0)."""
    return (jnp.zeros((num_topics,), jnp.int32)
            .at[z0].add(-amt).at[z_new].add(amt))


def merge_doc_counts(ndk: jax.Array, d_b: jax.Array, z0: jax.Array,
                     z_new: jax.Array, amt: jax.Array) -> jax.Array:
    """Apply a batch of reassignments to the worker-local n_dk in place.
    n_dk never routes: each document is owned by one worker (paper
    section 3), so it merges without a ``[D, K]`` delta."""
    return ndk.at[d_b, z0].add(-amt).at[d_b, z_new].add(amt)


def blocked_group(rows: jax.Array, nk: jax.Array, ndk: jax.Array,
                  z_flat: jax.Array, idx: jax.Array, bval: jax.Array,
                  start, key: jax.Array, *, state: "lda.SamplerState",
                  layout, n_rows: int, cfg: "lda.LDAConfig",
                  route: ps.PushRoute):
    """One group of the blocked schedule: resample the tokens ``idx``
    (valid where ``bval``) whose words own the physical rows ``[start,
    start + n_rows)``, all against the group-start counts, and merge.

    ``rows`` are those n_wk rows; alias tables are built for them alone
    (worker memory O(n_rows x K)).  ``state`` supplies the token arrays.
    Returns ``(rows + delta, nk, ndk, z_flat, local, amt)``: ``local`` is
    each token's row within the group and ``amt`` 1 where it moved.
    """
    table = proposal_tables(rows, nk, cfg)
    with jax.named_scope("ps.pull"):
        wb = jnp.take(state.w, idx)
        db = jnp.take(state.d, idx)
        z0 = jnp.take(z_flat, idx)
        local = jnp.clip(layout.to_physical(wb) - start, 0, n_rows - 1)
    z_new = resample_block(key, cfg, rows, table, local, db, z0, bval, z_flat,
                           ndk, nk, state.doc_start, state.doc_len)

    # the route materialises the group-local delta (hot dense slice, cold
    # COO -- whatever the policy says)
    with jax.named_scope("ps.push"):
        changed = (z_new != z0) & bval
        d_rows = route.block_delta(
            ps.Reassign(rows=local, words=wb, z_old=z0, z_new=z_new,
                        changed=changed),
            n_rows, cfg.K, use_kernels=cfg.use_kernels,
            interpret=cfg.kernel_interpret)
        rows = rows + d_rows
        amt = changed.astype(jnp.int32)
        nk = nk + topic_delta(z0, z_new, amt, cfg.K)

    with jax.named_scope("ndk.merge"):
        ndk = merge_doc_counts(ndk, db, z0, z_new, amt)
        z_flat = z_flat.at[idx].add(jnp.where(bval, z_new - z0, 0))
    return rows, nk, ndk, z_flat, local, amt


# ---------------------------------------------------------------------------
# Blocked executor (generalises lightlda.sweep_blocked_ref; paper sec 3.4).
# ---------------------------------------------------------------------------

def pipelined_sweep(state: "lda.SamplerState", key: jax.Array,
                    cfg: "lda.LDAConfig", block_idx: jax.Array,
                    block_valid: jax.Array, rows_per_block: int,
                    staleness: int = 0,
                    route: Optional[ps.PushRoute] = None
                    ) -> "lda.SamplerState":
    """One staleness-bounded, double-buffered, routed blocked sweep.

    Schedule per group of ``s + 1`` consecutive model blocks (see module
    docstring for why group-mates are independent):

      1. the group's ``n_wk`` rows arrive by awaiting the previous step's
         ``PullHandle``; the *next* group's pull is issued immediately
         (``MatrixHandle.pull_block``), overlapping the sampling below;
      2. ``blocked_group`` builds alias tables for the group's rows only
         (worker memory O(group x K)), resamples all of the group's
         tokens in one fused MH chain against the group-start
         (bounded-stale) counts, and merges the deltas at the group
         boundary: the ``PushRoute`` (``ps.DenseRoute`` when None)
         materialises the group-local delta and ``n_k``/``n_dk``/``z``
         merge through duplicate-tolerant adds;
      3. ``MatrixHandle.store_block`` writes the owned rows back.

    ``staleness=0`` is bitwise-identical to ``lightlda.sweep_blocked_ref``.
    """
    rpb = rows_per_block
    layout = state.nwk.layout
    n_blocks = block_idx.shape[0]
    cap = block_idx.shape[1]
    assert n_blocks * rpb == layout.pad_rows, (layout.pad_rows, rpb)
    s = effective_staleness(n_blocks, staleness)
    group = s + 1
    n_groups = n_blocks // group
    grp_rows = group * rpb
    if route is None:
        route = ps.DenseRoute()

    # Fuse each group of s+1 consecutive blocks into one scan step.  (The
    # host-side ``make_executor`` instead builds the token index directly
    # at group granularity, which amortises per-block padding; this
    # reshape path serves direct callers with a per-block index.)
    with jax.named_scope("ps.pull"):
        gidx = block_idx.reshape(n_groups, group * cap)
        gval = block_valid.reshape(n_groups, group * cap)
        groups = jnp.arange(n_groups)

    def group_body(carry, inp):
        nwk, nk, ndk, z_flat, pulled = carry
        grp, key_g = inp

        # double buffer: await this group's prefetched rows, issue the
        # next group's pull before sampling.  Exact, not approximate: this
        # group's write-back only touches its own physical rows, so the
        # in-flight pull cannot be invalidated.
        with jax.named_scope("ps.pull"):
            rows = pulled.result()
            pulled_next = nwk.pull_block((grp + 1) % n_groups, grp_rows)
            idx, vb = gidx[grp], gval[grp]
        rows, nk, ndk, z_flat, _, _ = blocked_group(
            rows, nk, ndk, z_flat, idx, vb, grp * grp_rows, key_g,
            state=state, layout=layout, n_rows=grp_rows, cfg=cfg,
            route=route)
        with jax.named_scope("ps.push"):
            nwk = nwk.store_block(grp, rows, grp_rows)
        return (nwk, nk, ndk, z_flat, pulled_next), ()

    with jax.named_scope("mh.chain"):
        keys = jax.random.split(key, n_groups)
    with jax.named_scope("ps.pull"):
        pulled0 = state.nwk.pull_block(0, grp_rows)
    carry = (state.nwk, state.nk.value, state.ndk, state.z, pulled0)
    (nwk, nk, ndk, z, _), _ = jax.lax.scan(
        group_body, carry, (groups, keys))
    return lda.SamplerState(state.w, state.d, z, state.valid,
                            state.doc_start, state.doc_len, nwk,
                            state.nk.with_value(nk), ndk)


# ---------------------------------------------------------------------------
# Full-snapshot executor (generalises lightlda.sweep; paper Alg. 1).
# ---------------------------------------------------------------------------

def nwk_carry_layout(route: ps.PushRoute, num_rows: int, num_topics: int,
                     use_kernels: bool) -> str:
    """How ``snapshot_sweep`` carries the n_wk aggregate through its
    scan: ``"flat"`` (``[V * K]``) or ``"rows"`` (``[V, K]``).

    XLA lowers an element scatter into a ``[V, K]`` array as a 1-D
    scatter into a flat copy, so a cold tail scattered into a ``[V, K]``
    carry relays the whole table out and back in every group.  A flat
    carry takes the scatter as it is, and the hybrid's ``[H, K]`` hot
    prefix adds in place to its leading ``H * K`` cells.  Plans with no
    XLA scatter keep rows: a dense-only plan's ``[V, K]`` delta, or the
    ``delta_apply_coo`` kernel's, would be relaid out instead; and a
    flat int32 index cannot address ``2**31`` cells or more.
    """
    if num_rows * num_topics >= 2 ** 31 or route.coo_kernel(use_kernels):
        return "rows"
    if isinstance(route, ps.CooRoute) or (
            isinstance(route, ps.HybridRoute)
            and route.clamped(num_rows) < num_rows):
        return "flat"
    return "rows"


def snapshot_sweep(state: "lda.SamplerState", key: jax.Array,
                   cfg: "lda.LDAConfig", staleness: int = 0,
                   route: Optional[ps.PushRoute] = None
                   ) -> "lda.SamplerState":
    """One full-snapshot sweep with staleness-grouped token blocks.

    Identical to the classic ``lightlda.sweep`` schedule except that
    groups of ``staleness + 1`` consecutive token blocks are resampled as
    one fused step against the group-start counts, and the group's deltas
    (shaped by ``route``, ``ps.DenseRoute`` when None) merge -- including
    the cross-worker "push" reduction -- once per group instead of per
    block.

    The collectives come from ``state.nwk``'s client backend: an
    ``SpmdBackend`` turns the snapshot pull into an all-gather over the
    server axis and the delta merge into one ``psum`` over the worker
    axes; in-process both are the identity.  ``staleness=0`` reproduces
    the per-block schedule exactly.

    The n_wk aggregate rides the scan in ``nwk_carry_layout``'s layout
    and is reshaped to ``[V, K]`` once, after the scan.
    """
    n = state.w.shape[0]
    nblocks = n // cfg.block_tokens
    s = effective_staleness(nblocks, staleness)
    group = s + 1
    n_groups = nblocks // group
    gtok = group * cfg.block_tokens
    if route is None:
        route = ps.DenseRoute()
    flat = nwk_carry_layout(route, cfg.V, cfg.K, cfg.use_kernels) == "flat"
    handle = state.nwk
    backend = handle.client.backend

    # --- snapshot "pull" (paper section 2.3 / 3.4) ---
    # The collectives themselves run under a nested "exchange" scope, in
    # the phase they belong to (in-process they are the identity and
    # compile to nothing).
    with jax.named_scope("ps.pull"):
        with jax.named_scope("exchange"):
            full = backend.pull_full(handle.storage)
        snapshot = full.to_dense()                      # [V, K] stale counts
    nk_snap = state.nk.value                            # [K]
    table = proposal_tables(snapshot, nk_snap, cfg)

    with jax.named_scope("ps.pull"):
        w_groups = state.w.reshape(n_groups, gtok)
        d_groups = state.d.reshape(n_groups, gtok)
        v_groups = state.valid.reshape(n_groups, gtok)
        groups = jnp.arange(n_groups)

    def group_body(carry, inp):
        z_flat, ndk, nwk_dense, nk = carry
        grp, key_g = inp

        # The word rows come from the sweep-start snapshot; the doc rows
        # and n_k are stale by at most ``staleness`` blocks.
        with jax.named_scope("ps.pull"):
            w_b = w_groups[grp]
            d_b = d_groups[grp]
            valid_b = v_groups[grp]
            z0 = jax.lax.dynamic_slice_in_dim(z_flat, grp * gtok, gtok)
        z_new = resample_block(key_g, cfg, snapshot, table, w_b, d_b, z0,
                               valid_b, z_flat, ndk, nk, state.doc_start,
                               state.doc_len)

        # --- routed delta aggregation + group-boundary merge (3.3) ---
        with jax.named_scope("ps.push"):
            changed = (z0 != z_new) & valid_b
            plan = route.plan(
                ps.Reassign(rows=w_b, words=w_b, z_old=z0, z_new=z_new,
                            changed=changed),
                cfg.V, cfg.K, use_kernels=cfg.use_kernels, prefix_rows=True,
                interpret=cfg.kernel_interpret)
            amt = changed.astype(jnp.int32)
            d_nk = topic_delta(z0, z_new, amt, cfg.K)
            # SPMD "push": merge each half of the plan over the workers
            # once per group (identity in-process).  The dense part -- the
            # hybrid's [H, K] hot prefix, never padded to [V, K] -- sums
            # elementwise and lands on the first H rows; the coordinate
            # part stays compressed, the workers' buffers are concatenated
            # and every entry scatter-applied once.  Int adds commute, so
            # the merged counts are bitwise those of the dense formulation.
            if plan.dense is not None:
                with jax.named_scope("exchange"):
                    d = backend.reduce(plan.dense)
                h = d.shape[0]
                if flat:
                    nwk_dense = nwk_dense.at[:h * cfg.K].add(d.reshape(-1))
                elif h < cfg.V:
                    nwk_dense = nwk_dense.at[:h, :].add(d)
                else:
                    nwk_dense = nwk_dense + d
            if plan.coo is not None:
                with jax.named_scope("exchange"):
                    c_rows, c_cols, c_vals = (backend.gather_concat(x)
                                              for x in plan.coo)
                if route.coo_kernel(cfg.use_kernels):
                    from repro.kernels import ops as kops
                    nwk_dense = nwk_dense + kops.delta_apply_coo(
                        c_rows, c_cols, c_vals, cfg.V, cfg.K,
                        interpret=cfg.kernel_interpret)
                else:
                    safe = jnp.clip(c_rows, 0, cfg.V - 1)
                    if flat:
                        nwk_dense = nwk_dense.at[safe * cfg.K + c_cols].add(
                            c_vals)
                    else:
                        nwk_dense = nwk_dense.at[safe, c_cols].add(c_vals)
            with jax.named_scope("exchange"):
                d_nk = backend.reduce(d_nk)
            nk = nk + d_nk

        with jax.named_scope("ndk.merge"):
            ndk = merge_doc_counts(ndk, d_b, z0, z_new, amt)
            z_flat = jax.lax.dynamic_update_slice_in_dim(
                z_flat, z_new, grp * gtok, axis=0)
        return (z_flat, ndk, nwk_dense, nk), ()

    with jax.named_scope("mh.chain"):
        keys = jax.random.split(key, n_groups)
    with jax.named_scope("ps.pull"):
        nwk0 = snapshot.reshape(-1) if flat else snapshot
    carry = (state.z, state.ndk, nwk0, nk_snap)
    (z, ndk, nwk_dense, nk), _ = jax.lax.scan(
        group_body, carry, (groups, keys))

    # --- write back to the server layout (SPMD keeps only own rows) ---
    with jax.named_scope("ps.push"):
        nwk_dense = nwk_dense.reshape(snapshot.shape)
        new_nwk = handle.client.matrix_from_dense(
            nwk_dense, route=handle.route).localize()
    return lda.SamplerState(state.w, state.d, z, state.valid,
                            state.doc_start, state.doc_len, new_nwk,
                            state.nk.with_value(nk), ndk)


# ---------------------------------------------------------------------------
# Host-side factory: what the launchers and train.loop.fit_lda drive.
# ---------------------------------------------------------------------------

def _jit_as(name: str, fn):
    """``jax.jit(fn)`` under a stable name: the compiled program is
    ``jit_<name>`` in HLO, traces and the ``obs.scopes`` registry."""
    fn.__name__ = name
    return jax.jit(fn)


def _obs_step(jit_step, exec_cfg: ExecConfig, info: dict):
    """Wrap a jitted sweep step with host-side sweep spans.

    Per sweep: ``exec.dispatch`` (the host enqueue window -- jit call
    issued, control returned), a profiler annotation whenever a jax
    profiler is recording; and, when an obs session is installed,
    ``exec.sweep`` (dispatch + device completion, closed by an explicit
    ``block_until_ready`` on the new state's ``z``).  The *overlap
    efficiency* is ``1 - dispatch/total``.

    The first call registers the jitted step with ``obs.scopes`` under
    its name, with that call's abstract arguments, so a profiler trace's
    device operations can be read by sweep phase.  With no session
    installed the wrapper costs a flag test, one attribute read, one
    ``is None`` test and an annotation check per sweep -- the <1% bar
    ``bench_obs.py`` asserts.  The unwrapped step stays reachable as
    ``step.raw``.  The sync only ever awaits values the caller would
    consume anyway; the sampled state is bitwise identical with tracing
    on or off.
    """
    registered = []

    def step(st, key, *rest):
        if not registered:
            registered.append(True)
            if hasattr(jit_step, "lower"):
                _scopes.register(jit_step.__name__, jit_step,
                                 (st, key) + rest)
        tr = _obs.tracer_for(exec_cfg.obs)
        if tr is None:
            with _obs.annotation("exec.dispatch"):
                return jit_step(st, key, *rest)
        t0 = time.perf_counter_ns()
        with tr.span("exec.dispatch", cat="exec", mode=info["mode"]):
            out = jit_step(st, key, *rest)
        t1 = time.perf_counter_ns()
        _block(out.z)
        t2 = time.perf_counter_ns()
        overlap = 1.0 - (t1 - t0) / max(t2 - t0, 1)
        tr.complete("exec.sweep", t0, t2, cat="exec", mode=info["mode"],
                    staleness=info["staleness"], group=info.get("group"),
                    route=info["route"],
                    overlap_pct=round(overlap * 100.0, 2))
        reg = _obs.metrics_for(exec_cfg.obs)
        if reg is not None:
            reg.histogram("exec.sweep_ms").record((t2 - t0) / 1e6)
            reg.histogram("exec.overlap_pct", unit="%").record(
                overlap * 100.0)
        return out

    step.raw = jit_step
    return step


def blocked_geometry(layout, model_blocks: int, staleness: int
                     ) -> Tuple[int, int, int]:
    """Resolve the blocked executor's (rows_per_block, n_blocks, effective
    staleness) for a model layout: ``pad_rows`` must split evenly, so the
    requested block count is rounded to the nearest feasible geometry."""
    rpb = -(-layout.pad_rows // model_blocks)
    while layout.pad_rows % rpb:
        rpb += 1
    n_blocks = layout.pad_rows // rpb
    return rpb, n_blocks, effective_staleness(n_blocks, staleness)


def make_stream_executor(cfg: "lda.LDAConfig", exec_cfg: ExecConfig,
                         layout, cap_round: int = 2048):
    """Build the per-shard step for the streaming trainer.

    Unlike ``make_executor`` (which bakes one corpus's token index into
    the jitted step), the stream trainer sees a *sequence* of shards, all
    padded to the same token/doc geometry (data/stream.py).  Returns
    ``(step, build_index, info)``:

      * blocked mode (``model_blocks > 0``): ``step(state, key, idx,
        bval)`` and ``build_index(w, valid, cap=None) -> (idx, bval)`` --
        the host groups each shard's tokens by model block at merge-unit
        granularity, with the capacity rounded to the coarse ``cap_round``
        bucket so same-bucket shards reuse one compiled trace (pass
        ``cap`` to pin one capacity for every shard; overflow raises);
      * snapshot mode: ``step(state, key)`` with ``build_index`` None --
        shard arrays reshape directly, one trace for the whole stream.

    The step function object is created once, so JAX's jit cache keys
    only on argument shapes -- visiting a shard never retraces unless its
    index landed in a new capacity bucket.
    """
    route = exec_cfg.resolve_route(cfg.V)
    if exec_cfg.model_blocks > 0:
        rpb, n_blocks, s = blocked_geometry(layout, exec_cfg.model_blocks,
                                            exec_cfg.staleness)
        rpb_step = rpb * (s + 1)

        step = _jit_as("pipelined_sweep", lambda st, k, idx, bval:
                       pipelined_sweep(st, k, cfg, idx, bval, rpb_step,
                                       staleness=0, route=route))

        def build_index(w, valid, cap=None):
            idx, bval = lda.block_token_index(
                np.asarray(w), np.asarray(valid), rpb_step, layout,
                cap_round=cap_round, cap=cap)
            return jnp.asarray(idx), jnp.asarray(bval)

        info = {"mode": "blocked", "n_blocks": n_blocks,
                "rows_per_block": rpb, "rows_per_step": rpb_step,
                "staleness": s, "group": s + 1,
                "staleness_requested": exec_cfg.staleness,
                "hot_words": exec_cfg.hot_words, "route": repr(route)}
        return _obs_step(step, exec_cfg, info), build_index, info

    jit_step = _jit_as("snapshot_sweep", lambda st, k: snapshot_sweep(
        st, k, cfg, staleness=exec_cfg.staleness, route=route))
    info = {"mode": "snapshot", "n_blocks": None, "rows_per_block": None,
            "staleness": exec_cfg.staleness,
            "staleness_requested": exec_cfg.staleness,
            "hot_words": exec_cfg.hot_words, "route": repr(route),
            "nwk_carry": nwk_carry_layout(route, cfg.V, cfg.K,
                                          cfg.use_kernels)}
    return _obs_step(jit_step, exec_cfg, info), None, info


def make_executor(state: "lda.SamplerState", cfg: "lda.LDAConfig",
                  exec_cfg: ExecConfig):
    """Build the jitted one-sweep step function for an executor config.

    Returns ``(step_fn, info)`` where ``step_fn(state, key) -> state`` and
    ``info`` describes the realised schedule (block geometry, effective
    staleness after divisor rounding, push route).

    ``route="auto"`` / ``staleness="auto"`` on the config run the
    ``ps.autotune`` pass against the *actual* state (word frequencies,
    batch geometry, measured apply costs) here, before anything is
    traced; the winning plan and its report land in ``info["autotune"]``.
    """
    report = None
    if exec_cfg.wants_autotune():
        from repro.ps import autotune as _autotune
        exec_cfg, report = _autotune.resolve_exec(state, cfg, exec_cfg)
    route = exec_cfg.resolve_route(cfg.V)
    if exec_cfg.model_blocks > 0:
        layout = state.nwk.layout
        rpb, n_blocks, s = blocked_geometry(layout, exec_cfg.model_blocks,
                                            exec_cfg.staleness)
        # Build the token index at *merge-unit* granularity (s+1 fused
        # blocks): the per-block cap is sized by the hottest block, so
        # grouping at index-build time lets hot and cold blocks average
        # out and the padding shrink -- a throughput win only the
        # staleness-bounded schedule can take.
        rpb_step = rpb * (s + 1)
        idx, bval = lda.block_token_index(
            np.asarray(state.w), np.asarray(state.valid), rpb_step, layout)
        idx, bval = jnp.asarray(idx), jnp.asarray(bval)
        step = _jit_as("pipelined_sweep", lambda st, k: pipelined_sweep(
            st, k, cfg, idx, bval, rpb_step, staleness=0, route=route))
        info = {"mode": "blocked", "n_blocks": n_blocks,
                "rows_per_block": rpb, "staleness": s,
                "group": s + 1, "token_cap": int(idx.shape[1]),
                "staleness_requested": exec_cfg.staleness,
                "hot_words": exec_cfg.hot_words, "route": repr(route)}
    else:
        n = state.w.shape[0]
        n_blocks = n // cfg.block_tokens
        s = effective_staleness(n_blocks, exec_cfg.staleness)
        step = _jit_as("snapshot_sweep", lambda st, k: snapshot_sweep(
            st, k, cfg, staleness=exec_cfg.staleness, route=route))
        info = {"mode": "snapshot", "n_blocks": n_blocks,
                "rows_per_block": None, "staleness": s, "group": s + 1,
                "token_cap": cfg.block_tokens,
                "staleness_requested": exec_cfg.staleness,
                "hot_words": exec_cfg.hot_words, "route": repr(route),
                "nwk_carry": nwk_carry_layout(route, cfg.V, cfg.K,
                                              cfg.use_kernels)}
    if report is not None:
        info["autotune"] = report
    return _obs_step(step, exec_cfg, info), info


# ---------------------------------------------------------------------------
# Tiered executor: blocked schedule over ps.tiered storage (DESIGN.md s. 13).
# ---------------------------------------------------------------------------

def make_tiered_executor(state: "lda.SamplerState", cfg: "lda.LDAConfig",
                         exec_cfg: ExecConfig, *, refresh_every: int = 1,
                         hot_budget_bytes: Optional[int] = None,
                         auto_resize: bool = False):
    """Build the one-sweep step for a state whose ``nwk`` is a
    ``ps.TieredMatrixHandle`` (device hot-row cache over a host memmap).

    Same blocked schedule as ``pipelined_sweep`` at staleness 0 -- pull a
    model block, resample its tokens against block-start counts, write
    the owned rows back -- but driven from a *host* loop: the tier's
    residency maps and cold memmap are host state, so the handle cannot
    ride a jitted scan carry.  The per-block math is one jitted inner
    step around ``blocked_group``, the group step ``pipelined_sweep``
    runs; the host loop supplies the asynchrony the paper's PS promises --
    block ``b+1``'s
    tier pull (including any cold-tier H2D misses) is issued *before*
    block ``b`` samples, so the miss path hides behind the MH chain.
    Exact, not approximate: blocks own disjoint rows, so the in-flight
    pull cannot be invalidated by the write-back racing it.

    Token index: per-block id lists padded to power-of-two capacities
    (the jit retraces once per distinct capacity).  Under the Zipf +
    frequency-ordering workload the block sizes span orders of magnitude,
    so per-block capacities cost a handful of traces where a uniform cap
    (sized by the hottest block) would pad ~40x the real token count.

    After each sweep the observed per-row push traffic drives the tier's
    ``refresh()`` every ``refresh_every`` sweeps (0: never), and -- when
    ``auto_resize`` -- ``ps.autotune.retune_hot_rows`` grows the hot tier
    while the measured hit rate is below target (bounded by
    ``hot_budget_bytes``).  Returns ``(step_fn, info)`` like
    ``make_executor``.
    """
    from repro.ps.tiered import TieredMatrixHandle

    nwk = state.nwk
    assert isinstance(nwk, TieredMatrixHandle), (
        "make_tiered_executor needs a ps.TieredMatrixHandle state "
        "(build one via PSClient.tiered_matrix_from_dense)")
    if exec_cfg.wants_autotune():
        raise ValueError(
            "route='auto'/staleness='auto' are not supported with tiered "
            "storage: the autotuner measures against dense in-memory "
            "handles; pass concrete values (api.job validates this).")
    if exec_cfg.model_blocks <= 0:
        raise ValueError(
            "tiered storage requires the blocked executor (the whole "
            "point is never materialising [V, K] on device): set "
            "ExecConfig.model_blocks > 0.")
    route = exec_cfg.resolve_route(cfg.V)
    layout = nwk.layout
    rpb, n_blocks, _ = blocked_geometry(layout, exec_cfg.model_blocks, 0)

    # --- host-side token index: per-block ids, power-of-two caps ---
    w_np = np.asarray(state.w)
    tok = np.nonzero(np.asarray(state.valid))[0]
    blk = w_np[tok] // rpb            # one shard: physical == logical
    order = np.argsort(blk, kind="stable")
    tok, blk = tok[order], blk[order]
    starts = np.searchsorted(blk, np.arange(n_blocks + 1))
    index = []
    for b in range(n_blocks):
        ids = tok[starts[b]: starts[b + 1]]
        if ids.size == 0:
            index.append(None)
            continue
        cap = max(128, 1 << (int(ids.size) - 1).bit_length())
        idx = np.zeros(cap, np.int32)
        idx[: ids.size] = ids
        bval = np.zeros(cap, bool)
        bval[: ids.size] = True
        index.append((jnp.asarray(idx), jnp.asarray(bval)))

    @jax.jit
    def inner(rows, nk, ndk, z_flat, idx, bval, start, key_b):
        # the blocked schedule's group step at staleness 0, with the block
        # offset a traced scalar so every block of one capacity shares a
        # single compiled trace
        rows, nk, ndk, z_flat, local, amt = blocked_group(
            rows, nk, ndk, z_flat, idx, bval, start, key_b, state=state,
            layout=layout, n_rows=rpb, cfg=cfg, route=route)
        with jax.named_scope("ps.push"):
            rtraf = jnp.zeros((rpb,), jnp.int32).at[local].add(amt)
        return rows, nk, ndk, z_flat, rtraf

    sweep_count = [0]

    def step(st: "lda.SamplerState", key: jax.Array) -> "lda.SamplerState":
        tier_h = st.nwk
        nk, ndk, z = st.nk.value, st.ndk, st.z
        keys = jax.random.split(key, n_blocks)
        pulled = tier_h.pull_block(0, rpb)
        for b in range(n_blocks):
            rows = pulled.result()
            if b + 1 < n_blocks:
                pulled = tier_h.pull_block(b + 1, rpb)   # issue -> overlap
            if index[b] is None:
                continue
            idx, bval = index[b]
            rows, nk, ndk, z, rtraf = inner(
                rows, nk, ndk, z, idx, bval,
                jnp.asarray(b * rpb, jnp.int32), keys[b])
            rtraf_np = np.asarray(rtraf)
            tier_h.store_block(b, rows, rpb, row_changed=rtraf_np > 0)
            tier_h.note_traffic(b, rpb, rtraf_np)
        sweep_count[0] += 1
        if refresh_every > 0 and sweep_count[0] % refresh_every == 0:
            tier_h.refresh()
            if auto_resize:
                from repro.ps import autotune as _autotune
                new_h = _autotune.retune_hot_rows(
                    tier_h.tier.hot_rows, tier_h.tier_stats().hit_rate(),
                    vocab_size=cfg.V, budget_bytes=hot_budget_bytes,
                    num_topics=cfg.K)
                if new_h != tier_h.tier.hot_rows:
                    tier_h.resize_hot(new_h)
        reg = _obs.metrics_for(exec_cfg.obs)
        if reg is not None:
            # device-resident table footprint: hot tier + the two block
            # buffers in flight (pulled + being-sampled) -- the quantity
            # the bench_tiered device-memory gate bounds
            reg.gauge("exec.tiered.device_table_bytes").set(
                float(tier_h.tier.device_bytes() + 2 * rpb * cfg.K * 4))
        return lda.SamplerState(st.w, st.d, z, st.valid, st.doc_start,
                                st.doc_len, tier_h, st.nk.with_value(nk),
                                ndk)

    caps = sorted({int(ix.shape[0]) for ix, _ in filter(None, index)})
    info = {"mode": "tiered", "n_blocks": n_blocks, "rows_per_block": rpb,
            "staleness": 0, "group": 1, "token_caps": caps,
            "hot_rows": nwk.tier.hot_rows,
            "refresh_every": refresh_every, "route": repr(route)}
    return _obs_step(step, exec_cfg, info), info
