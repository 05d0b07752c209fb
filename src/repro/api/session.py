"""Unified training session: one loop, four data/backend planes.

This module merges the previously divergent host loops (``fit_lda``,
``fit_lda_stream``, the launcher's ``run_distributed``) behind one
``Session`` driving a single visit loop:

    plane.setup()
    for visit in plane.schedule():
        plane.step(visit)                 # the only state transition
        callbacks.on_sweep_end(view)      # observation, never perturbation
    callbacks.on_fit_end(final_view)

A *plane* binds a data source (in-memory corpus or on-disk shard stream)
to an execution backend (in-process or SPMD mesh).  The in-memory corpus
is treated as a one-shard stream that happens to stay resident: every
plane exposes the same visit protocol, so checkpointing, evaluation and
logging are plane-agnostic callbacks instead of copy-pasted loop bodies.

Equivalence contract (tests/test_api.py): each plane is bitwise-identical
to the pre-redesign path it replaces --

  * memory x in-process  == the old ``train.loop.fit_lda`` chain
    (``key, sub = split(key)`` per sweep through ``make_executor``);
  * stream x in-process  == the old ``fit_lda_stream`` (all randomness
    from ``(seed, schedule position)`` via ``stream_sweep_key``);
  * memory x SPMD        == the old launcher ``run_distributed`` loop;
  * stream x SPMD        is new (stream shards feed SPMD workers in
    groups); its anchor is the exactly-once conservation law.

RNG discipline is therefore *per plane*, deliberately: unifying the loop
does not get to re-derive anybody's random stream.
"""
from __future__ import annotations

import time
from typing import List, NamedTuple, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro import obs as _obs
from repro import ps
from repro.api.callbacks import (Callback, CheckpointCallback, EvalCallback,
                                 SweepView)
from repro.api.job import NET, SPMD, JobValidationError, LDAJob
from repro.core import lightlda as lda
from repro.core import perplexity as ppl
from repro.data import stream as stream_mod
from repro.sharding.mesh import make_mesh
from repro.train import async_exec
from repro.train import checkpoint as ckpt


class SessionResult(NamedTuple):
    """What a finished run hands back.

    ``nwk``/``nk`` are the final PS handles (always present); ``state``
    is the full ``SamplerState`` for in-memory runs (for the SPMD plane,
    the global view: every worker's tokens flattened, doc ids offset per
    worker); ``reader``
    the stream reader for streamed runs (its z files hold the
    assignments).  ``history`` is the eval callback's rows, ``info`` the
    executor's realised-schedule description.
    """

    nwk: "ps.MatrixHandle"
    nk: "ps.VectorHandle"
    history: list
    info: dict
    state: Optional["lda.SamplerState"]
    reader: Optional["stream_mod.ShardedCorpusReader"]


# ---------------------------------------------------------------------------
# Stream RNG discipline (moved here from train/loop.py; re-exported there).
#
# Every random draw derives from one base seed through ``fold_in`` chains
# keyed by *schedule position*, never by host iteration state -- that is
# what makes resume bitwise (DESIGN.md section 9).
# ---------------------------------------------------------------------------

def stream_init_key(seed: int, shard_id: int) -> jax.Array:
    """Key for shard ``shard_id``'s initial topic assignment draw."""
    base = jax.random.fold_in(jax.random.PRNGKey(seed), 0)
    return jax.random.fold_in(base, shard_id)


def stream_sweep_key(seed: int, epoch: int, pos: int) -> jax.Array:
    """Key for the sweep at schedule position (epoch, pos)."""
    base = jax.random.fold_in(jax.random.PRNGKey(seed), 1)
    return jax.random.fold_in(jax.random.fold_in(base, epoch), pos)


def init_stream(reader, cfg, seed: int = 0, client=None):
    """Pass 0 of stream training: draw every shard's initial assignments
    (persisted as the shard's ``z`` file) and histogram the global count
    tables.  One streaming pass; host memory is O(V x K) + one shard --
    the same recovery shape as ``data.stream.rebuild_counts_from_stream``.

    Returns ``(nwk, nk)`` PS handles holding the initial counts.
    """
    meta = reader.meta
    k = cfg.K
    nwk = np.zeros((meta.vocab_size, k), np.int32)
    nk = np.zeros(k, np.int64)
    for sid in range(meta.num_shards):
        shard = reader.shard(sid, load_z=False)
        z = np.array(jax.random.randint(
            stream_init_key(seed, sid), (meta.tokens_per_shard,), 0, k,
            dtype=jnp.int32))                   # np.array: writable copy
        z[shard.n_tokens:] = 0
        reader.write_z(sid, z)
        wv = np.asarray(shard.w[:shard.n_tokens])
        zv = z[:shard.n_tokens]
        np.add.at(nwk, (wv, zv), 1)
        nk += np.bincount(zv, minlength=k)
    client = client or ps.client_for(cfg)
    return (client.matrix_from_dense(jnp.asarray(nwk)),
            client.wrap_vector(jnp.asarray(nk, dtype=jnp.int32)))


# ---------------------------------------------------------------------------
# SPMD wiring (moved here from launch/lda.py; the launcher re-exports).
# ---------------------------------------------------------------------------

class SpmdState(NamedTuple):
    """The SPMD plane's training state, every array placed with the
    sweep's own sharding (``spmd_specs``).

    The per-worker arrays carry a leading worker dim whose row ``j`` lives
    on the mesh's ``j``-th device in ``(data, model)`` order: worker
    ``j``'s padded tokens (``w``, ``d``, ``z``, ``valid``), its documents
    (``doc_start``, ``doc_len``) and their counts (``ndk`` [workers, dmax,
    K]).  ``nwk`` is the physical (cyclic-ordered) ``[pad_rows, K]`` count
    table, rows sharded over the model (server) axis; ``nk`` [K] is
    replicated.  The field order is the sweep's argument order.
    """

    w: jax.Array
    d: jax.Array
    z: jax.Array
    valid: jax.Array
    doc_start: jax.Array
    doc_len: jax.Array
    ndk: jax.Array
    nwk: jax.Array
    nk: jax.Array


def spmd_specs() -> SpmdState:
    """The ``PartitionSpec`` of each ``SpmdState`` field."""
    from jax.sharding import PartitionSpec as P
    wspec = P(("data", "model"), None)
    return SpmdState(wspec, wspec, wspec, wspec, wspec, wspec,
                     P(("data", "model"), None, None), P("model", None), P())


def make_spmd_sweep(mesh, cfg: "lda.LDAConfig", staleness: int = 0,
                    hot_words=None, route: Optional["ps.PushRoute"] = None):
    """shard_map'd sweep: tokens split over (data, model); n_wk rows cyclic
    over model (the servers); deltas psum'd over all workers.  The count
    tables enter through an SPMD-backed ``PSClient`` -- the sweep gets its
    collectives (all-gather pull, one psum push per group) from the
    handle's backend, not from axis kwargs.  The executor schedule knobs
    thread through: with ``staleness`` s, each worker merges (and psums)
    deltas once per group of s+1 token blocks -- fewer, larger
    collectives -- and ``route`` (or the legacy ``hot_words``) selects the
    push policy (dense / coordinate / hybrid).  Arguments are the
    ``SpmdState`` fields, then the ``[workers, 2]`` per-worker keys."""
    from jax.sharding import PartitionSpec as P

    client = ps.client_for(cfg, axis_name=("data", "model"),
                           model_axis="model")

    def local(w, d, z, valid, doc_start, doc_len, ndk, nwk_local, nk, keys):
        state = lda.SamplerState(
            w[0], d[0], z[0], valid[0], doc_start[0], doc_len[0],
            client.wrap_matrix(nwk_local, cfg.V),
            client.wrap_vector(nk), ndk[0])
        out = lda.sweep(state, keys[0], cfg,
                        staleness=staleness, hot_words=hot_words,
                        route=route)
        return (out.z[None], out.ndk[None], out.nwk.value, out.nk.value)

    specs = spmd_specs()
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=tuple(specs) + (P(("data", "model"), None),),
        out_specs=(specs.z, specs.ndk, specs.nwk, specs.nk),
        check_vma=False)


def make_spmd_step(mesh, cfg: "lda.LDAConfig", workers: int,
                   staleness: int = 0,
                   route: Optional["ps.PushRoute"] = None):
    """The SPMD plane's jitted sweep, ``step(state, key) -> state`` over
    an ``SpmdState``: worker ``j`` samples with ``split(key, workers)[j]``.
    Compiled as ``jit_spmd_sweep``; inputs placed with ``spmd_specs``
    come back with the same shardings, so consecutive sweeps share one
    executable."""
    sweep = make_spmd_sweep(mesh, cfg, staleness=staleness, route=route)

    def spmd_sweep(state: SpmdState, key: jax.Array) -> SpmdState:
        z, ndk, nwk, nk = sweep(*state, jax.random.split(key, workers))
        return state._replace(z=z, ndk=ndk, nwk=nwk, nk=nk)

    return async_exec._jit_as("spmd_sweep", spmd_sweep)


def spmd_exchange_bytes(cfg: "lda.LDAConfig", route: "ps.PushRoute",
                        data: int, model: int, tokens_per_worker: int,
                        staleness: int = 0) -> dict:
    """Bytes each device puts into the sweep's collectives per sweep, by
    collective, from the static shapes: the snapshot all-gather of its
    server shard, and per group the hot dense delta's psum, the cold COO
    buffers' all-gather (one per worker axis, the second carrying what the
    first gathered) and the n_k delta's psum."""
    n_blocks = tokens_per_worker // cfg.block_tokens
    group = async_exec.effective_staleness(n_blocks, staleness) + 1
    n_groups = n_blocks // group
    gtok = group * cfg.block_tokens
    tok = jax.ShapeDtypeStruct((gtok,), jnp.int32)
    plan = jax.eval_shape(
        lambda w, z0, z1: route.plan(
            ps.Reassign(rows=w, words=w, z_old=z0, z_new=z1,
                        changed=z0 != z1),
            cfg.V, cfg.K, use_kernels=cfg.use_kernels, prefix_rows=True,
            interpret=cfg.kernel_interpret), tok, tok, tok)
    nbytes = lambda a: int(np.prod(a.shape)) * a.dtype.itemsize
    rows_per_shard = -(-cfg.V // model)
    coo = sum(nbytes(a) for a in plan.coo) if plan.coo is not None else 0
    return {"pull_all_gather": rows_per_shard * cfg.K * 4,
            "hot_psum": n_groups * (nbytes(plan.dense)
                                    if plan.dense is not None else 0),
            "cold_all_gather": n_groups * coo * (1 + data),
            "nk_psum": n_groups * cfg.K * 4}


def init_distributed_state(corp, cfg: "lda.LDAConfig", workers: int,
                           key: jax.Array, mesh=None):
    """Shard the corpus over ``workers`` and build the count tables on
    the devices that own them (the checkpoint recovery's rebuild, paper
    section 3.5).

    Returns an ``SpmdState``, each array placed with its ``spmd_specs``
    sharding on ``mesh`` (default: ``(workers / cfg.num_shards,
    cfg.num_shards)`` over every device): the per-worker arrays go
    straight from the host to their worker's device; each worker
    histograms its own ``ndk``, each server its own cyclic rows of
    ``nwk`` from every worker's tokens; ``nk`` is psum'd.  No whole table
    is built on one device.  Shared by the SPMD planes and the SPMD tests.
    """
    from jax.sharding import NamedSharding

    from repro.data import corpus as corpus_mod

    model = cfg.num_shards
    if mesh is None:
        mesh = make_mesh((workers // model, model), ("data", "model"))
    specs = spmd_specs()
    put = lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec))

    shards = corpus_mod.shard_tokens(corp, workers, cfg.block_tokens)
    npad = max(s[0].shape[0] for s in shards)
    dmax = max(s[3].shape[0] for s in shards)

    def stack(i, pad_to):
        return np.stack([np.pad(s[i], (0, pad_to - len(s[i])))
                         for s in shards])

    w, d, valid = (put(stack(i, npad), specs.w) for i in range(3))
    doc_start, doc_len = (put(stack(i, dmax), specs.doc_start)
                          for i in (3, 4))
    z = jax.jit(lambda k: jax.random.randint(k, (workers, npad), 0, cfg.K,
                                             dtype=jnp.int32),
                out_shardings=NamedSharding(mesh, specs.z))(key)

    rows = -(-cfg.V // model)
    workers_ax = ("data", "model")

    def local(w, d, z, valid):
        one = valid[0].astype(jnp.int32)
        ndk = jnp.zeros((1, dmax, cfg.K), jnp.int32).at[0, d[0], z[0]].add(
            one)
        nk = jax.lax.psum(jnp.zeros((cfg.K,), jnp.int32).at[z[0]].add(one),
                          workers_ax)
        # this server's cyclic rows (word % model == server), counted
        # from every worker's tokens; other rows fall out of range
        wa, za, va = (jax.lax.all_gather(x[0], workers_ax, tiled=True)
                      for x in (w, z, valid))
        mine = va & (wa % model == jax.lax.axis_index("model"))
        row = jnp.where(mine, wa // model, rows)
        nwk = jnp.zeros((rows, cfg.K), jnp.int32).at[row, za].add(
            1, mode="drop")
        return ndk, nwk, nk

    ndk, nwk, nk = jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(specs.w,) * 4,
        out_specs=(specs.ndk, specs.nwk, specs.nk), check_vma=False))(
        w, d, z, valid)
    return SpmdState(w, d, z, valid, doc_start, doc_len, ndk, nwk, nk)


# ---------------------------------------------------------------------------
# The generic visit loop: the only trainer body left in the codebase.
# ---------------------------------------------------------------------------

def _run_loop(plane, callbacks: Sequence[Callback]) -> SessionResult:
    # The obs spans here cover the host side of each visit -- dispatching
    # the executor step (``session.step``) and running the observers
    # (``session.callbacks``).  Spans read clocks only; with no obs
    # session installed each is a no-op object (NULL_SPAN), so the loop
    # body is unchanged for untraced runs.
    with _obs.span("session.setup", cat="session", kind=plane.kind):
        plane.setup()
    info = dict(plane.info)
    for cb in callbacks:
        cb.on_fit_start(info)
    view = None
    stopped = False
    for visit in plane.schedule():
        with _obs.span("session.step", cat="session"):
            plane.step(visit)
        view = plane.view(visit)
        with _obs.span("session.callbacks", cat="session",
                       n=len(callbacks)):
            for cb in callbacks:
                cb.on_sweep_end(view)
        if plane.should_stop():
            stopped = True
            break
    final = plane.final_view(view)
    for cb in callbacks:
        cb.on_fit_end(final)
    plane.finish(stopped)
    return plane.result()


# ---------------------------------------------------------------------------
# Plane 1: in-memory corpus, in-process backend (the old fit_lda).
# ---------------------------------------------------------------------------

class _MemoryPlane:
    """Resident ``SamplerState`` driven through ``make_executor``.

    RNG: the old ``fit_lda`` chain -- ``key, sub = split(key)`` before
    every sweep -- so results are bitwise-identical to the pre-redesign
    host loop.
    """

    kind = "memory"

    def __init__(self, cfg, exec_cfg, state, key, sweeps, log_fn=print):
        self.cfg = cfg
        self.exec_cfg = exec_cfg
        self.state = state
        self.key = key
        self.sweeps = int(sweeps)
        self.log_fn = log_fn
        self.info: dict = {}
        self.t0 = time.time()
        self._ready = False

    def setup(self):
        if self._ready:
            return
        self._ready = True
        cfg, state = self.cfg, self.state
        self.step_fn, info = async_exec.make_executor(state, cfg,
                                                      self.exec_cfg)
        self.info = dict(info)
        tuned = info.get("autotune")
        if tuned is not None:
            self.log_fn(f"[lda] autotune: chose {tuned['chosen']} "
                        f"(route='auto'/staleness='auto' measured against "
                        f"the materialised state)")
        if info["mode"] == "blocked":
            rpb = info["rows_per_block"]
            self.log_fn(
                f"[lda] blocked executor: {info['n_blocks']} model blocks "
                f"x {rpb} rows, group {info['group']} (staleness "
                f"{info['staleness']}), route {info['route']}, "
                f"worker block mem "
                f"{info['group'] * rpb * cfg.K * 4 / 2**20:.1f} MiB (vs "
                f"{state.nwk.layout.pad_rows * cfg.K * 4 / 2**20:.1f} MiB "
                f"snapshot)")
        else:
            self.log_fn(
                f"[lda] snapshot executor: {info['n_blocks']} token "
                f"blocks, group {info['group']} (staleness "
                f"{info['staleness']}), route {info['route']}, "
                f"nwk_carry {info['nwk_carry']}")
        self.num_tokens = int(jnp.sum(state.valid))
        self.t0 = time.time()

    def schedule(self):
        return range(self.sweeps)

    def step(self, i: int):
        self.key, sub = jax.random.split(self.key)
        self.state = self.step_fn(self.state, sub)

    def view(self, i: int) -> SweepView:
        st = self.state
        return SweepView(self, step=i + 1, epoch=0, pos=i, shard_id=None,
                         is_last=(i == self.sweeps - 1), state=st,
                         nwk=st.nwk, nk=st.nk,
                         tokens_seen=self.num_tokens * (i + 1))

    # -- observation hooks ------------------------------------------------
    def sync(self, view):
        jax.block_until_ready(view.state.z)

    def perplexity(self, view) -> float:
        st, cfg = view.state, self.cfg
        return float(ppl.training_perplexity(
            st.w, st.d, st.valid, st.ndk, st.nwk.to_dense(), st.nk.value,
            cfg.alpha, cfg.beta))

    def history_row(self, view, p: float) -> dict:
        el = view.elapsed_s
        return {"sweep": view.step, "perplexity": p, "elapsed_s": el,
                "tokens_per_s": self.num_tokens * view.step / el}

    def log_line(self, view, p: float) -> str:
        el = view.elapsed_s
        return (f"[lda] sweep {view.step:4d}  perplexity {p:9.2f}  "
                f"({el:.1f}s, {self.num_tokens * view.step / el:,.0f} "
                f"tok/s)")

    def checkpoint(self, view, path: str):
        ckpt.save_lda(path, view.state if view.state is not None
                      else self.state)

    # -- loop plumbing ----------------------------------------------------
    def should_stop(self) -> bool:
        return False

    def final_view(self, last: Optional[SweepView]) -> Optional[SweepView]:
        if last is not None:
            return last
        st = self.state
        return SweepView(self, step=0, epoch=0, pos=0, shard_id=None,
                         is_last=True, state=st, nwk=st.nwk, nk=st.nk,
                         tokens_seen=0)

    def finish(self, stopped: bool):
        pass

    def result(self) -> SessionResult:
        st = self.state
        return SessionResult(st.nwk, st.nk, [], self.info, st, None)


# ---------------------------------------------------------------------------
# Plane 1b: in-memory corpus over tiered parameter storage (ps.tiered).
# ---------------------------------------------------------------------------

class _TieredPlane(_MemoryPlane):
    """The memory plane with the count table in tiered storage: the
    ``hot_rows`` hottest rows device-resident, the full ``[V, K]`` table
    in a host memmap cold store (``repro.ps.tiered``, DESIGN.md s. 13).

    Differences from ``_MemoryPlane``, all confined to setup/teardown:
    the initial ``n_wk`` is histogrammed *host-side* straight into the
    cold store (the full table never lands on device -- the point of the
    plane), the executor is ``make_tiered_executor``'s host-driven
    blocked loop, and ``finish`` flushes the cold store and reports the
    tier's hit rate.  The visit protocol, eval and RNG discipline are
    inherited -- a sweep key chain of ``key, sub = split(key)`` exactly
    like the dense memory plane.
    """

    kind = "tiered"

    def __init__(self, corp, cfg, exec_cfg, sweeps, job, log_fn=print):
        super().__init__(cfg, exec_cfg, None, None, sweeps, log_fn)
        self.corp = corp
        self.job = job
        self.tier_dir: Optional[str] = None

    def setup(self):
        if self._ready:
            return
        self._ready = True
        import tempfile

        from repro.ps import autotune as _autotune
        from repro.ps import tiered as tiered_mod

        cfg, corp, job = self.cfg, self.corp, self.job
        key = jax.random.PRNGKey(job.seed)

        # token arrays + z init, padded exactly like lda.init_state
        w = jnp.asarray(corp.w)
        d = jnp.asarray(corp.d)
        n = int(w.shape[0])
        pad = (-n) % cfg.block_tokens
        z = jax.random.randint(key, (n,), 0, cfg.K, dtype=jnp.int32)
        w = jnp.concatenate([w.astype(jnp.int32),
                             jnp.zeros((pad,), jnp.int32)])
        d = jnp.concatenate([d.astype(jnp.int32),
                             jnp.zeros((pad,), jnp.int32)])
        z = jnp.concatenate([z, jnp.zeros((pad,), jnp.int32)])
        valid = jnp.concatenate([jnp.ones((n,), bool),
                                 jnp.zeros((pad,), bool)])
        doc_len = jnp.zeros((corp.num_docs,), jnp.int32).at[d[:n]].add(1)
        doc_start = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                     jnp.cumsum(doc_len)[:-1]])

        # counts: n_wk histogrammed host-side straight into the cold
        # store (the full [V, K] never materialises on device); n_k and
        # n_dk are small and build on device like rebuild_counts
        w_np, z_np = np.asarray(w[:n]), np.asarray(z[:n])
        nwk_np = np.zeros((cfg.V, cfg.K), np.int32)
        np.add.at(nwk_np, (w_np, z_np), 1)
        one = valid.astype(jnp.int32)
        nk = jnp.zeros((cfg.K,), jnp.int32).at[z].add(one)
        ndk = jnp.zeros((corp.num_docs, cfg.K), jnp.int32).at[d, z].add(one)

        hot_rows = job.hot_rows
        if hot_rows is None:
            freq = _autotune.word_frequencies(w_np, None, cfg.V)
            hot_rows = _autotune.size_hot_rows(freq, cfg.K)
        self.tier_dir = job.tier_dir or tempfile.mkdtemp(
            prefix="repro-tier-")
        client = ps.PSClient(backend=tiered_mod.TieredBackend(),
                             interpret=cfg.kernel_interpret)
        nwk = tiered_mod.tiered_matrix_from_dense(
            nwk_np, hot_rows, self.tier_dir,
            route=self.exec_cfg.resolve_route(cfg.V), client=client)
        self.state = lda.SamplerState(w, d, z, valid, doc_start, doc_len,
                                      nwk, client.wrap_vector(nk), ndk)
        _, self.key = jax.random.split(key)

        self.step_fn, info = async_exec.make_tiered_executor(
            self.state, cfg, self.exec_cfg,
            refresh_every=job.tier_refresh,
            auto_resize=(job.hot_rows is None))
        self.info = dict(info, storage="tiered", tier_dir=self.tier_dir)
        tier = nwk.tier
        self.log_fn(
            f"[lda] tiered storage: hot {tier.hot_rows} / {cfg.V} rows "
            f"({tier.device_bytes() / 2**20:.2f} MiB device) over cold "
            f"memmap {tier.cold.nbytes / 2**20:.1f} MiB at "
            f"{self.tier_dir}; {info['n_blocks']} blocks x "
            f"{info['rows_per_block']} rows, route {info['route']}")
        self.num_tokens = int(jnp.sum(valid))
        self.t0 = time.time()

    def checkpoint(self, view, path: str):
        raise ValueError("checkpointing tiered storage is not supported "
                         "yet; the cold store under tier_dir persists the "
                         "count table itself (and TopicModel.save the "
                         "frozen model)")

    def finish(self, stopped: bool):
        st = self.state
        st.nwk.flush()
        s = st.nwk.tier_stats()
        self.log_fn(
            f"[lda] tier: hit rate {s.hit_rate():.3f} "
            f"({s.hits}/{s.hits + s.misses} changed assignments "
            f"device-local), {s.promotions} promotions, {s.evictions} "
            f"evictions, H2D {s.h2d_bytes / 2**20:.1f} MiB, D2H "
            f"{s.d2h_bytes / 2**20:.1f} MiB")


# ---------------------------------------------------------------------------
# Plane 2: on-disk shard stream, in-process backend (the old
# fit_lda_stream).
# ---------------------------------------------------------------------------

class _StreamPlane:
    """Multi-epoch out-of-core training over a sharded stream.

    The model (the PS count tables) is the only global state; token data
    streams through shard by shard via the double-buffered
    ``StreamingLoader``.  Each shard visit rebuilds its worker-local
    ``n_dk`` from the persisted assignments, runs one executor sweep
    against the *global* handles, and writes the updated ``z`` back --
    the paper's section-3.5 discipline (assignments are data; counts are
    derived).  All randomness derives from (seed, schedule position), so
    resume is bitwise.
    """

    kind = "stream"

    def __init__(self, reader, cfg, exec_cfg, epochs, *, seed=0,
                 checkpoint_path=None, resume=False, max_shards=None,
                 prefetch=True, log_fn=print):
        if isinstance(reader, str):
            reader = stream_mod.ShardedCorpusReader(reader)
        self.reader = reader
        self.cfg = cfg
        self.exec_cfg = exec_cfg
        self.epochs = int(epochs)
        self.seed = int(seed)
        self.checkpoint_path = checkpoint_path
        self.resume = resume
        self.max_shards = max_shards
        self.prefetch = prefetch
        self.log_fn = log_fn
        self.info: dict = {}
        self.t0 = time.time()
        self._ready = False

    def setup(self):
        if self._ready:
            return
        self._ready = True
        import os

        cfg, reader = self.cfg, self.reader
        meta = reader.meta
        if (self.exec_cfg.model_blocks == 0
                and meta.tokens_per_shard % cfg.block_tokens):
            raise ValueError(
                f"tokens_per_shard={meta.tokens_per_shard} must be a "
                f"multiple of block_tokens={cfg.block_tokens} for the "
                f"snapshot executor")
        self.ckpt_meta = {"vocab_size": cfg.V, "num_topics": cfg.K,
                          "ps_shards": cfg.num_shards,
                          "tokens_per_shard": meta.tokens_per_shard,
                          "stream_shards": meta.num_shards}
        client = ps.client_for(cfg)
        if self.resume:
            path = self.checkpoint_path
            if not (path and os.path.exists(path)):
                raise FileNotFoundError(
                    f"resume requested but no checkpoint at {path}")
            saved = ckpt.restore_stream(path)
            mismatch = {k: (saved.meta.get(k), v)
                        for k, v in self.ckpt_meta.items()
                        if saved.meta.get(k) != v}
            if mismatch:
                raise ValueError(f"checkpoint/config mismatch: {mismatch}")
            self.seed = saved.seed
            self.nwk = client.wrap_matrix(jnp.asarray(saved.nwk_phys),
                                          cfg.V)
            self.nk = client.wrap_vector(jnp.asarray(saved.nk))
            cursor = saved.cursor
            self.log_fn(f"[stream] resumed at epoch {cursor.epoch} pos "
                        f"{cursor.pos} (seed {self.seed}) from {path}")
        else:
            self.nwk, self.nk = init_stream(reader, cfg, self.seed,
                                            client=client)
            cursor = stream_mod.Cursor(0, 0)
        self.cursor0 = cursor
        self.final_cursor = cursor

        self.step_fn, self.build_index, info = \
            async_exec.make_stream_executor(cfg, self.exec_cfg,
                                            self.nwk.layout)
        self.info = dict(info, stream_shards=meta.num_shards,
                         tokens_per_shard=meta.tokens_per_shard,
                         num_tokens=meta.num_tokens)
        self.loader = stream_mod.StreamingLoader(reader, seed=self.seed,
                                                 prefetch=self.prefetch)
        self.total_visits = len(self.loader.schedule(cursor, self.epochs))
        if self.max_shards is not None:
            self.total_visits = min(self.total_visits, self.max_shards)
        self.valid_np = np.arange(meta.tokens_per_shard)
        self.shards_done = 0
        self.tokens_seen = 0
        self.state: Optional[lda.SamplerState] = None
        self.t0 = time.time()

    def schedule(self):
        return self.loader.iterate(self.cursor0, self.epochs)

    def step(self, visit):
        cur, sid, shard = visit
        cfg, meta = self.cfg, self.reader.meta
        if shard.z is None:
            raise FileNotFoundError(
                f"shard {sid} has no z file; stream was never initialised")
        w = jnp.asarray(shard.w)
        d = jnp.asarray(shard.d)
        z = jnp.asarray(shard.z)
        valid = jnp.asarray(self.valid_np < shard.n_tokens)
        ndk = jnp.zeros((meta.doc_cap, cfg.K), jnp.int32).at[d, z].add(
            valid.astype(jnp.int32))
        state = lda.SamplerState(w, d, z, valid,
                                 jnp.asarray(shard.doc_start),
                                 jnp.asarray(shard.doc_len),
                                 self.nwk, self.nk, ndk)
        key = stream_sweep_key(self.seed, cur.epoch, cur.pos)
        if self.build_index is not None:
            idx, bval = self.build_index(shard.w, np.asarray(valid))
            state = self.step_fn(state, key, idx, bval)
        else:
            state = self.step_fn(state, key)
        self.reader.write_z(sid, np.asarray(state.z))
        self.state = state
        self.nwk, self.nk = state.nwk, state.nk
        self.shards_done += 1
        self.tokens_seen += shard.n_tokens
        self.final_cursor = cur.next(meta.num_shards)

    def view(self, visit) -> SweepView:
        cur, sid, shard = visit
        return SweepView(self, step=self.shards_done, epoch=cur.epoch,
                         pos=cur.pos, shard_id=sid,
                         is_last=(self.shards_done >= self.total_visits),
                         state=self.state, nwk=self.nwk, nk=self.nk,
                         tokens_seen=self.tokens_seen,
                         cursor_next=self.final_cursor)

    # -- observation hooks ------------------------------------------------
    def sync(self, view):
        if view.state is not None:
            jax.block_until_ready(view.state.z)

    def perplexity(self, view) -> float:
        st, cfg = view.state, self.cfg
        return float(ppl.training_perplexity(
            st.w, st.d, st.valid, st.ndk, st.nwk.to_dense(), st.nk.value,
            cfg.alpha, cfg.beta))

    def history_row(self, view, p: float) -> dict:
        el = view.elapsed_s
        return {"epoch": view.epoch, "pos": view.pos,
                "shard": view.shard_id, "perplexity": p, "elapsed_s": el,
                "tokens_per_s": self.tokens_seen / el}

    def log_line(self, view, p: float) -> str:
        el = view.elapsed_s
        return (f"[stream] epoch {view.epoch} shard {view.pos:3d} "
                f"(#{view.shard_id})  perplexity {p:9.2f}  "
                f"({self.tokens_seen / el:,.0f} tok/s)")

    def checkpoint(self, view, path: str):
        ckpt.save_stream(path, np.asarray(self.nwk.value),
                         np.asarray(self.nk.value), view.cursor_next,
                         self.seed, self.ckpt_meta)

    # -- loop plumbing ----------------------------------------------------
    def should_stop(self) -> bool:
        return (self.max_shards is not None
                and self.shards_done >= self.max_shards)

    def final_view(self, last: Optional[SweepView]) -> Optional[SweepView]:
        if last is not None:
            return last
        return SweepView(self, step=0, epoch=self.cursor0.epoch,
                         pos=self.cursor0.pos, shard_id=None, is_last=True,
                         state=None, nwk=self.nwk, nk=self.nk,
                         tokens_seen=0, cursor_next=self.final_cursor)

    def finish(self, stopped: bool):
        if stopped:
            self.log_fn(f"[stream] stopping after {self.shards_done} "
                        f"shards (max_shards), cursor -> epoch "
                        f"{self.final_cursor.epoch} pos "
                        f"{self.final_cursor.pos}")
        elif self.shards_done:
            el = time.time() - self.t0
            self.log_fn(f"[stream] done: {self.shards_done} shard visits, "
                        f"{self.tokens_seen} tokens in {el:.1f}s "
                        f"({self.tokens_seen / el:,.0f} tok/s)")

    def result(self) -> SessionResult:
        return SessionResult(self.nwk, self.nk, [], self.info, None,
                             self.reader)


# ---------------------------------------------------------------------------
# Plane: stream (or materialised memory) source, network backend --
# a standalone PS process + an elastic pool of worker subprocesses
# (repro.ps.net, DESIGN.md section 15).
# ---------------------------------------------------------------------------

class _NetPlane:
    """Training through the network parameter server.

    The session process never samples: it seeds the stream
    (``init_stream``), loads the initial counts into the server, installs
    the visit schedule as a lease plan, spawns the worker pool and then
    *supervises* -- each ``step`` waits for one more lease to commit,
    reaping dead workers (their leases re-queue) along the way.  The
    conservation law (server counts == histogram of the on-disk z) holds
    at every commit boundary; a 1-worker run is bitwise identical to
    ``_StreamPlane`` (same ``stream_sweep_key``, same executor).
    """

    kind = "net"

    def __init__(self, source, cfg, exec_cfg, epochs, job, *, log_fn=print):
        # source: a ShardedCorpusReader (stream job) or a Corpus
        # (memory job -- materialised into a temp stream dir in setup)
        self.source = source
        self.cfg = cfg
        self.exec_cfg = exec_cfg
        self.epochs = int(epochs)
        self.job = job
        self.seed = int(job.seed)
        self.log_fn = log_fn
        self.info: dict = {}
        self.t0 = time.time()
        self.visit_timeout = 600.0
        self._ready = False
        self._tmp = None
        self._server = None
        self._final = None

    # -- lifecycle ---------------------------------------------------------
    def setup(self):
        if self._ready:
            return
        self._ready = True
        import tempfile

        from repro.ps.net import (NetClient, PSServer, WorkerConfig,
                                  WorkerPool, wire)
        self._wire = wire
        job, cfg = self.job, self.cfg
        if isinstance(self.source, stream_mod.ShardedCorpusReader):
            self.reader = self.source
            self.stream_dir = job.stream_dir
        else:
            # materialise the in-memory corpus as a stream the worker
            # processes can read; shard size targets ~2 visits per worker
            # per epoch, rounded to the executor's block granularity
            self._tmp = tempfile.mkdtemp(prefix="repro-net-")
            corp = self.source
            target = max(2 * job.workers, 4)
            blocks = max(1, -(-corp.w.shape[0] //
                              (cfg.block_tokens * target)))
            stream_mod.write_sharded(self._tmp, corp,
                                     tokens_per_shard=blocks
                                     * cfg.block_tokens)
            self.reader = stream_mod.ShardedCorpusReader(self._tmp)
            self.stream_dir = self._tmp
        meta = self.reader.meta
        if (self.exec_cfg.model_blocks == 0
                and meta.tokens_per_shard % cfg.block_tokens):
            raise ValueError(
                f"tokens_per_shard={meta.tokens_per_shard} must be a "
                f"multiple of block_tokens={cfg.block_tokens} for the "
                f"snapshot executor")

        self._client = ps.PSClient.create(num_shards=1,
                                          interpret=cfg.kernel_interpret)
        nwk0, nk0 = init_stream(self.reader, cfg, self.seed,
                                client=self._client)
        if job.server:
            self.address = job.server
        else:
            self._server = PSServer(cfg.V, cfg.K,
                                    stream_dir=self.stream_dir,
                                    log_fn=self.log_fn).start()
            self.address = self._server.address
        self.ctl = NetClient.connect(self.address, name="session-ctl",
                                     role="ctl")
        if self.ctl.meta["vocab"] != cfg.V or self.ctl.meta["topics"] != cfg.K:
            raise ValueError(
                f"server at {self.address} hosts a "
                f"[{self.ctl.meta['vocab']}, {self.ctl.meta['topics']}] "
                f"table; this job needs [{cfg.V}, {cfg.K}]")
        self.ctl.push_dense_prefix(wire.MAT_NWK, np.asarray(nwk0.to_dense()))
        self.ctl.push_dense_prefix(wire.MAT_NK, np.asarray(nk0.value))

        loader = stream_mod.StreamingLoader(self.reader, seed=self.seed,
                                            prefetch=False)
        sched = [(c.epoch, c.pos, s) for c, s in
                 loader.schedule(stream_mod.Cursor(0, 0), self.epochs)]
        if job.max_shards is not None:
            sched = sched[:job.max_shards]
        self.sched = sched
        self.total_visits = len(sched)
        mode = job.net_assign
        self.ctl.plan(sched, mode=mode,
                      slots=job.workers if mode != "dynamic" else 0,
                      expected_workers=job.workers)

        base = WorkerConfig(
            server=self.address, stream_dir=self.stream_dir,
            num_topics=cfg.K, alpha=cfg.alpha, beta=cfg.beta,
            mh_steps=cfg.mh_steps, block_tokens=cfg.block_tokens,
            model_blocks=self.exec_cfg.model_blocks,
            staleness=int(self.exec_cfg.staleness),
            hot_words=self.exec_cfg.hot_words,
            use_kernels=cfg.use_kernels, seed=self.seed,
            commit_hot_rows=self.exec_cfg.hot_words or 0)
        self.pool = WorkerPool(self.address, base, log_fn=self.log_fn)
        if not self.pool.cpu_workers and jax.default_backend() != "cpu":
            raise ValueError(
                f"backend='net' spawns {job.workers} worker process(es), "
                f"but this session process already holds the "
                f"{jax.default_backend()} chip(s), and a chip belongs to "
                f"one process.  Ask for CPU workers by name "
                f"(JAX_PLATFORMS=cpu), or train on the chip with "
                f"backend='in_process' or 'spmd'.")
        self.pool.start(job.workers)
        self._shard_tokens = [self.reader.shard(s, load_z=False).n_tokens
                              for s in range(meta.num_shards)]
        self.info = {"mode": "net", "workers": job.workers,
                     "net_assign": mode, "server": self.address,
                     "stream_shards": meta.num_shards,
                     "tokens_per_shard": meta.tokens_per_shard,
                     "num_tokens": meta.num_tokens,
                     "total_visits": self.total_visits}
        self.shards_done = 0
        self.tokens_seen = 0
        self.t0 = time.time()

    def schedule(self):
        return range(self.total_visits)

    def step(self, i: int):
        """Wait for the (i+1)-th lease commit, supervising the pool."""
        deadline = time.time() + self.visit_timeout
        while True:
            self.pool.reap()
            st = self.ctl.status()
            leases = st.get("leases") or {}
            if leases.get("done", 0) > i:
                break
            if self.pool.alive() == 0:
                raise RuntimeError(
                    f"all workers exited with "
                    f"{self.total_visits - leases.get('done', 0)} visits "
                    f"unfinished: {leases}")
            if time.time() > deadline:
                raise TimeoutError(
                    f"no lease commit within {self.visit_timeout}s "
                    f"(done={leases.get('done', 0)}/{self.total_visits})")
            time.sleep(0.05)
        self.shards_done = i + 1
        self.tokens_seen += self._shard_tokens[self.sched[i][2]]

    def view(self, i: int) -> SweepView:
        e, p, s = self.sched[i]
        return SweepView(self, step=self.shards_done, epoch=e, pos=p,
                         shard_id=s,
                         is_last=(self.shards_done >= self.total_visits),
                         state=None, nwk=None, nk=None,
                         tokens_seen=self.tokens_seen,
                         cursor_next=stream_mod.Cursor(e, p).next(
                             self.reader.meta.num_shards))

    # -- observation hooks -------------------------------------------------
    def sync(self, view):
        pass

    def perplexity(self, view) -> float:
        """Live stream-wide eval: current server counts + persisted z.
        Mid-training this reads *moving* state (atomic per shard); the
        final call sees the quiesced model."""
        nwk = self.ctl.pull_full(self._wire.MAT_NWK)
        nk = self.ctl.pull_full(self._wire.MAT_NK)
        return ppl.stream_training_perplexity(self.reader, nwk, nk,
                                              self.cfg.alpha, self.cfg.beta)

    def history_row(self, view, p: float) -> dict:
        el = view.elapsed_s
        return {"epoch": view.epoch, "pos": view.pos,
                "shard": view.shard_id, "perplexity": p, "elapsed_s": el,
                "tokens_per_s": self.tokens_seen / el}

    def log_line(self, view, p: float) -> str:
        el = view.elapsed_s
        return (f"[net] visit {view.step}/{self.total_visits} "
                f"(epoch {view.epoch})  perplexity {p:9.2f}  "
                f"({self.tokens_seen / el:,.0f} tok/s)")

    def checkpoint(self, view, path: str):
        raise NotImplementedError(
            "checkpointing the net plane is not supported (LDAJob "
            "validation rejects it)")

    # -- loop plumbing -----------------------------------------------------
    def should_stop(self) -> bool:
        return False

    def final_view(self, last: Optional[SweepView]) -> Optional[SweepView]:
        if last is not None:
            return last
        return SweepView(self, step=0, epoch=0, pos=0, shard_id=None,
                         is_last=True, state=None, nwk=None, nk=None,
                         tokens_seen=0,
                         cursor_next=stream_mod.Cursor(0, 0))

    def finish(self, stopped: bool):
        status = self.pool.join(timeout=self.visit_timeout)
        self._final = (self.ctl.pull_full(self._wire.MAT_NWK),
                       self.ctl.pull_full(self._wire.MAT_NK))
        self.info["server_status"] = status
        self.pool.close()
        if self._server is not None:
            self.ctl.shutdown()      # embedded server dies with the run
            self._server = None
        self.ctl.close()
        el = time.time() - self.t0
        if self.shards_done:
            self.log_fn(f"[net] done: {self.shards_done} shard visits over "
                        f"{self.job.workers} workers in {el:.1f}s "
                        f"({self.tokens_seen / el:,.0f} tok/s)")

    def result(self) -> SessionResult:
        nwk_np, nk_np = self._final
        nwk = self._client.matrix_from_dense(jnp.asarray(nwk_np))
        nk = self._client.wrap_vector(jnp.asarray(nk_np))
        return SessionResult(nwk, nk, [], self.info, None, self.reader)


# ---------------------------------------------------------------------------
# SPMD planes share the mesh resolution (and its failure modes).
# ---------------------------------------------------------------------------

def _resolve_mesh(cfg: "lda.LDAConfig", mesh_model: int):
    """Build the (data, model) mesh for ``mesh_model`` servers and pin the
    PS shard count to the model axis (paper section 2.2).  Returns
    ``(mesh, data, model, workers, cfg)``; raises with the actionable
    device-count message shared by both SPMD planes."""
    n_dev = jax.device_count()
    model = int(mesh_model)
    if model < 1 or n_dev % model:
        raise ValueError(
            f"device count {n_dev} ({jax.default_backend()}) is not "
            f"divisible by mesh_model={model}; on a TPU host pick a "
            f"mesh_model that divides its chip count, or under "
            f"JAX_PLATFORMS=cpu force host devices "
            f"(XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    data = n_dev // model
    mesh = make_mesh((data, model), ("data", "model"))
    cfg = lda.LDAConfig(**{**cfg.__dict__, "num_shards": model})
    return mesh, data, model, data * model, cfg


# ---------------------------------------------------------------------------
# Plane 3: in-memory corpus, SPMD backend (the old run_distributed loop).
# ---------------------------------------------------------------------------

class _SpmdPlane:
    """shard_map'd training over a ``(data, model)`` mesh.

    Workers (all mesh shards) sample their document partitions; servers
    (the model axis) hold cyclic rows of ``n_wk``.  RNG matches the old
    launcher loop bitwise: ``key = PRNGKey(seed)`` seeds the shared z
    init, then ``key, sub = split(key)`` per sweep, and the step splits
    ``sub`` over the workers (``make_spmd_step``).  ``step_fn`` is the
    one compiled sweep that ``Session.make_step()`` hands out.
    """

    kind = "spmd"

    def __init__(self, corp, cfg, exec_cfg, sweeps, *, seed=0,
                 mesh_model=2, log_fn=print):
        self.corp = corp
        self.cfg = cfg
        self.exec_cfg = exec_cfg
        self.sweeps = int(sweeps)
        self.seed = int(seed)
        self.mesh_model = int(mesh_model)
        self.log_fn = log_fn
        self.info: dict = {}
        self.t0 = time.time()
        self._ready = False

    def setup(self):
        if self._ready:
            return
        self._ready = True
        mesh, data, model, workers, cfg = _resolve_mesh(self.cfg,
                                                        self.mesh_model)
        self.workers = workers
        self.cfg = cfg
        self.log_fn(f"[lda] mesh data={data} x model={model} "
                    f"({workers} workers, {model} servers)")
        self.key = jax.random.PRNGKey(self.seed)
        self.state = st = init_distributed_state(self.corp, cfg, workers,
                                                 self.key, mesh=mesh)
        staleness = self.exec_cfg.staleness
        route = self.exec_cfg.resolve_route(cfg.V)
        self.dmax = st.doc_start.shape[1]
        self.num_tokens = int(jnp.sum(st.valid))
        n_blocks = st.w.shape[1] // cfg.block_tokens
        group = async_exec.effective_staleness(n_blocks, staleness) + 1
        self.info = {"mode": "spmd", "mesh_data": data, "mesh_model": model,
                     "workers": workers, "n_blocks": n_blocks,
                     "staleness": group - 1, "group": group,
                     "route": repr(route),
                     "nwk_carry": async_exec.nwk_carry_layout(
                         route, cfg.V, cfg.K, cfg.use_kernels),
                     "exchange_bytes_per_sweep": spmd_exchange_bytes(
                         cfg, route, data, model, st.w.shape[1],
                         staleness)}
        self.step_fn = async_exec._obs_step(
            make_spmd_step(mesh, cfg, workers, staleness, route),
            self.exec_cfg, self.info)
        self.log_fn(f"[lda] spmd executor: {n_blocks} token blocks per "
                    f"worker, group {group}, route {self.info['route']}, "
                    f"nwk_carry {self.info['nwk_carry']}, exchange bytes "
                    f"per device per sweep "
                    f"{self.info['exchange_bytes_per_sweep']}")
        self.t0 = time.time()

    def schedule(self):
        return range(self.sweeps)

    def step(self, i: int):
        self.key, sub = jax.random.split(self.key)
        self.state = self.step_fn(self.state, sub)

    def _handles(self):
        client = ps.client_for(self.cfg)
        return (client.wrap_matrix(self.state.nwk, self.cfg.V),
                client.wrap_vector(self.state.nk))

    def _global_state(self) -> "lda.SamplerState":
        """Every worker's partition as one flat ``SamplerState``: worker
        ``j``'s doc ids (and token offsets) shift by ``j`` times the
        per-worker capacity, so ``ndk`` rows stay distinct."""
        st, k = self.state, self.cfg.K
        n = st.w.shape[1]
        wk = jnp.arange(self.workers)[:, None]
        nwk, nk = self._handles()
        return lda.SamplerState(
            st.w.reshape(-1), (st.d + wk * self.dmax).reshape(-1),
            st.z.reshape(-1), st.valid.reshape(-1),
            (st.doc_start + wk * n).reshape(-1), st.doc_len.reshape(-1),
            nwk, nk, st.ndk.reshape(self.workers * self.dmax, k))

    def view(self, i: int) -> SweepView:
        nwk, nk = self._handles()
        return SweepView(self, step=i + 1, epoch=0, pos=i, shard_id=None,
                         is_last=(i == self.sweeps - 1), state=None,
                         nwk=nwk, nk=nk,
                         tokens_seen=self.num_tokens * (i + 1))

    # -- observation hooks ------------------------------------------------
    def sync(self, view):
        jax.block_until_ready(self.state.z)

    def perplexity(self, view) -> float:
        cfg, st = self.cfg, self._global_state()
        return float(ppl.training_perplexity(
            st.w, st.d, st.valid, st.ndk, view.nwk.to_dense(), st.nk.value,
            cfg.alpha, cfg.beta))

    def history_row(self, view, p: float) -> dict:
        return {"sweep": view.step, "perplexity": p,
                "elapsed_s": view.elapsed_s}

    def log_line(self, view, p: float) -> str:
        return (f"[lda] sweep {view.step:4d}  perplexity {p:9.2f}  "
                f"({view.elapsed_s:.1f}s)")

    def checkpoint(self, view, path: str):
        raise ValueError("checkpointing the SPMD plane is not supported; "
                         "train in-process to checkpoint, or persist the "
                         "final model via TopicModel.save")

    # -- loop plumbing ----------------------------------------------------
    def should_stop(self) -> bool:
        return False

    def final_view(self, last):
        return last

    def finish(self, stopped: bool):
        pass

    def result(self) -> SessionResult:
        st = self._global_state()
        return SessionResult(st.nwk, st.nk, [], self.info, st, None)


# ---------------------------------------------------------------------------
# Plane 4: shard stream x SPMD backend (new: stream shards feed SPMD
# workers in groups -- the scenario TestStreamSpmd wired by hand).
# ---------------------------------------------------------------------------

class _StreamSpmdPlane:
    """Each visit feeds ``workers`` consecutive scheduled stream shards to
    the SPMD sweep as its worker partitions (the uniform padded shard
    geometry is exactly what shard_map wants), then writes every shard's
    updated ``z`` back.  Correctness anchor: the exactly-once conservation
    law -- after any number of epochs the global PS counts equal the
    histogram of the persisted assignments (tests/test_api.py).
    """

    kind = "stream_spmd"

    def __init__(self, reader, cfg, exec_cfg, epochs, *, seed=0,
                 mesh_model=2, max_shards=None, log_fn=print):
        if isinstance(reader, str):
            reader = stream_mod.ShardedCorpusReader(reader)
        self.reader = reader
        self.cfg = cfg
        self.exec_cfg = exec_cfg
        self.epochs = int(epochs)
        self.seed = int(seed)
        self.mesh_model = int(mesh_model)
        self.max_shards = max_shards
        self.log_fn = log_fn
        self.info: dict = {}
        self.t0 = time.time()
        self._ready = False

    def setup(self):
        if self._ready:
            return
        self._ready = True
        mesh, data, model, workers, cfg = _resolve_mesh(self.cfg,
                                                        self.mesh_model)
        self.workers = workers
        meta = self.reader.meta
        if meta.num_shards % workers:
            raise ValueError(
                f"stream has {meta.num_shards} shards but the SPMD "
                f"backend consumes groups of {workers} (= mesh "
                f"data x model) per sweep; re-shard the stream so the "
                f"shard count is a multiple of {workers}, or adjust "
                f"mesh_model/--devices")
        if meta.tokens_per_shard % cfg.block_tokens:
            raise ValueError(
                f"tokens_per_shard={meta.tokens_per_shard} must be a "
                f"multiple of block_tokens={cfg.block_tokens} for "
                f"the snapshot executor")
        self.cfg = cfg
        self.log_fn(f"[lda] mesh data={data} x model={model} "
                    f"({workers} workers, {model} servers); stream of "
                    f"{meta.num_shards} shards in groups of {workers}")
        nwk, nk = init_stream(self.reader, cfg, self.seed)
        self.nwk_val, self.nk_val = nwk.value, nk.value
        route = self.exec_cfg.resolve_route(cfg.V)
        self.step_fn = make_spmd_step(mesh, cfg, workers,
                                      self.exec_cfg.staleness, route)
        self.loader = stream_mod.StreamingLoader(self.reader,
                                                 seed=self.seed,
                                                 prefetch=False,
                                                 load_z=True)
        self._sched = self.loader.schedule(stream_mod.Cursor(0, 0),
                                           self.epochs)
        self.total_visits = len(self._sched)
        if self.max_shards is not None:
            self.total_visits = min(self.total_visits, self.max_shards)
        self.valid_np = np.arange(meta.tokens_per_shard)
        self.shards_done = 0
        self.tokens_seen = 0
        self._last_group = None
        self.info = {"mode": "stream_spmd", "mesh_data": data,
                     "mesh_model": model, "workers": workers,
                     "stream_shards": meta.num_shards,
                     "tokens_per_shard": meta.tokens_per_shard,
                     "num_tokens": meta.num_tokens,
                     "staleness": self.exec_cfg.staleness,
                     "route": repr(route),
                     "nwk_carry": async_exec.nwk_carry_layout(
                         route, cfg.V, cfg.K, cfg.use_kernels)}
        self.t0 = time.time()

    def schedule(self):
        for g in range(0, len(self._sched), self.workers):
            yield self._sched[g:g + self.workers]

    def step(self, group):
        cfg, meta, reader = self.cfg, self.reader.meta, self.reader
        shards = [reader.shard(sid, mmap=False) for _, sid in group]
        for (_, sid), sh in zip(group, shards):
            if sh.z is None:
                raise FileNotFoundError(
                    f"shard {sid} has no z file; stream was never "
                    f"initialised")
        w = jnp.asarray(np.stack([np.asarray(s.w) for s in shards]))
        d = jnp.asarray(np.stack([np.asarray(s.d) for s in shards]))
        z = jnp.asarray(np.stack([np.asarray(s.z) for s in shards]))
        ds = jnp.asarray(np.stack([np.asarray(s.doc_start)
                                   for s in shards]))
        dl = jnp.asarray(np.stack([np.asarray(s.doc_len) for s in shards]))
        valid = jnp.asarray(np.stack([self.valid_np < s.n_tokens
                                      for s in shards]))
        one = valid.astype(jnp.int32)
        widx = jnp.arange(self.workers)[:, None].repeat(w.shape[1], 1)
        ndk = jnp.zeros((self.workers, meta.doc_cap, cfg.K), jnp.int32).at[
            widx.reshape(-1), d.reshape(-1), z.reshape(-1)].add(
            one.reshape(-1))
        cur0 = group[0][0]
        key = stream_sweep_key(self.seed, cur0.epoch, cur0.pos)
        out = self.step_fn(SpmdState(w, d, z, valid, ds, dl, ndk,
                                     self.nwk_val, self.nk_val), key)
        z2, ndk2, self.nwk_val, self.nk_val = out.z, out.ndk, out.nwk, out.nk
        z2_np = np.asarray(z2)
        for j, (_, sid) in enumerate(group):
            reader.write_z(sid, z2_np[j])
        self._last_group = (w, d, valid, ndk2, z2)
        self.shards_done += len(group)
        self.tokens_seen += int(sum(s.n_tokens for s in shards))

    def _handles(self):
        client = ps.client_for(self.cfg)
        return (client.wrap_matrix(self.nwk_val, self.cfg.V),
                client.wrap_vector(self.nk_val))

    def view(self, group) -> SweepView:
        # step counts *shard visits* (not groups), so eval/checkpoint
        # cadences mean the same thing as on the in-process stream plane;
        # callbacks fire on crossing a multiple, since steps advance by
        # ``workers`` per sweep.
        cur0 = group[0][0]
        nwk, nk = self._handles()
        return SweepView(self, step=self.shards_done,
                         epoch=cur0.epoch, pos=cur0.pos, shard_id=None,
                         is_last=(self.shards_done >= self.total_visits),
                         state=None, nwk=nwk, nk=nk,
                         tokens_seen=self.tokens_seen)

    # -- observation hooks ------------------------------------------------
    def sync(self, view):
        jax.block_until_ready(self.nk_val)

    def perplexity(self, view) -> float:
        cfg = self.cfg
        w, d, valid, ndk, _ = self._last_group
        dmax = ndk.shape[1]
        full = view.nwk.to_dense()
        return float(ppl.training_perplexity(
            w.reshape(-1),
            (d + jnp.arange(self.workers)[:, None] * dmax).reshape(-1),
            valid.reshape(-1), ndk.reshape(self.workers * dmax, cfg.K),
            full, self.nk_val, cfg.alpha, cfg.beta))

    def history_row(self, view, p: float) -> dict:
        el = view.elapsed_s
        return {"epoch": view.epoch, "pos": view.pos, "perplexity": p,
                "elapsed_s": el, "tokens_per_s": self.tokens_seen / el}

    def log_line(self, view, p: float) -> str:
        el = view.elapsed_s
        return (f"[stream] epoch {view.epoch} group at pos {view.pos:3d}  "
                f"perplexity {p:9.2f}  "
                f"({self.tokens_seen / el:,.0f} tok/s)")

    def checkpoint(self, view, path: str):
        raise ValueError("checkpointing the streamed SPMD plane is not "
                         "supported yet; train in-process to checkpoint")

    # -- loop plumbing ----------------------------------------------------
    def should_stop(self) -> bool:
        return (self.max_shards is not None
                and self.shards_done >= self.max_shards)

    def final_view(self, last):
        return last

    def finish(self, stopped: bool):
        if self.shards_done:
            el = time.time() - self.t0
            self.log_fn(f"[stream] done: {self.shards_done} shard visits "
                        f"({self.workers} per sweep), {self.tokens_seen} "
                        f"tokens in {el:.1f}s "
                        f"({self.tokens_seen / el:,.0f} tok/s)")

    def result(self) -> SessionResult:
        nwk, nk = self._handles()
        return SessionResult(nwk, nk, [], self.info, None, self.reader)


# ---------------------------------------------------------------------------
# Shim entry points (what the deprecated train.loop wrappers call).
# ---------------------------------------------------------------------------

def memory_fit(state, key, cfg, exec_cfg, sweeps, *, eval_every=10,
               log_fn=print, callbacks: Sequence[Callback] = ()):
    """The old ``fit_lda`` contract on the unified loop: returns
    ``(state, history, info)``."""
    plane = _MemoryPlane(cfg, exec_cfg, state, key, sweeps, log_fn)
    ev = EvalCallback(every=eval_every, include_last=True, log_fn=log_fn)
    _run_loop(plane, [ev, *callbacks])
    return plane.state, ev.history, plane.info


def stream_fit(reader, cfg, exec_cfg, epochs, *, seed=0,
               checkpoint_path=None, checkpoint_every=0, resume=False,
               max_shards=None, eval_every=0, prefetch=True, log_fn=print,
               callbacks: Sequence[Callback] = ()):
    """The old ``fit_lda_stream`` contract on the unified loop: returns
    ``(nwk, nk, history, info)``."""
    plane = _StreamPlane(reader, cfg, exec_cfg, epochs, seed=seed,
                         checkpoint_path=checkpoint_path, resume=resume,
                         max_shards=max_shards, prefetch=prefetch,
                         log_fn=log_fn)
    ev = EvalCallback(every=eval_every, include_last=False, log_fn=log_fn)
    cbs: List[Callback] = [ev, *callbacks]
    if checkpoint_path:
        cbs.append(CheckpointCallback(checkpoint_path,
                                      every=checkpoint_every))
    _run_loop(plane, cbs)
    return plane.nwk, plane.nk, ev.history, plane.info


# ---------------------------------------------------------------------------
# Session: LDAJob -> plane -> result.
# ---------------------------------------------------------------------------

class Session:
    """Resolve a validated ``LDAJob`` into a data/backend plane and run it.

    ``run(callbacks)`` executes the full schedule and returns a
    ``SessionResult``; the session wires the job's eval cadence and
    checkpoint policy in as callbacks (before the caller's, matching the
    pre-redesign eval-then-checkpoint ordering).  ``make_step()`` exposes
    the compiled executor of an in-memory job (in-process, tiered or
    SPMD) for benchmark-grade timing loops.
    """

    def __init__(self, job: LDAJob, log_fn=print):
        self.job = job.validate()
        self.log_fn = log_fn
        self._plane = None
        self.cfg: Optional[lda.LDAConfig] = None

    # -- resolution --------------------------------------------------------
    def _ensure_plane(self):
        if self._plane is not None:
            return self._plane
        job = self.job
        exec_cfg = job.exec_config()
        if job.source_kind == "memory":
            corp = job.materialize_corpus()
            vocab = (corp.vocab_size if job.vocab_size is None
                     else job.vocab_size)
            if vocab < corp.vocab_size:
                raise JobValidationError(
                    [f"vocab_size={vocab} is smaller than the corpus "
                     f"vocabulary ({corp.vocab_size}); drop vocab_size= "
                     f"to infer it from the corpus"])
            cfg = job.lda_config(vocab)
            if job.backend == SPMD:
                self._plane = _SpmdPlane(corp, cfg, exec_cfg, job.sweeps,
                                         seed=job.seed,
                                         mesh_model=job.mesh_model,
                                         log_fn=self.log_fn)
            elif job.backend == NET:
                # a sweep over the materialised corpus == one stream epoch
                self._plane = _NetPlane(corp, cfg, exec_cfg, job.sweeps,
                                        job, log_fn=self.log_fn)
            elif job.storage == "tiered":
                self._plane = _TieredPlane(corp, cfg, exec_cfg, job.sweeps,
                                           job, log_fn=self.log_fn)
            else:
                key = jax.random.PRNGKey(job.seed)
                state = lda.init_state(key, jnp.asarray(corp.w),
                                       jnp.asarray(corp.d), corp.num_docs,
                                       cfg)
                key, sub = jax.random.split(key)
                self._plane = _MemoryPlane(cfg, exec_cfg, state, sub,
                                           job.sweeps, log_fn=self.log_fn)
        else:
            reader = stream_mod.ShardedCorpusReader(job.stream_dir)
            vocab = reader.meta.vocab_size
            if job.vocab_size is not None and job.vocab_size != vocab:
                self.log_fn(f"[api] stream vocab {vocab} overrides "
                            f"vocab_size={job.vocab_size}")
            cfg = job.lda_config(vocab)
            if job.backend == SPMD:
                self._plane = _StreamSpmdPlane(
                    reader, cfg, exec_cfg, job.epochs, seed=job.seed,
                    mesh_model=job.mesh_model, max_shards=job.max_shards,
                    log_fn=self.log_fn)
            elif job.backend == NET:
                self._plane = _NetPlane(reader, cfg, exec_cfg, job.epochs,
                                        job, log_fn=self.log_fn)
            else:
                self._plane = _StreamPlane(
                    reader, cfg, exec_cfg, job.epochs, seed=job.seed,
                    checkpoint_path=job.checkpoint.path or None,
                    resume=job.checkpoint.resume,
                    max_shards=job.max_shards, prefetch=job.prefetch,
                    log_fn=self.log_fn)
        self.cfg = self._plane.cfg
        return self._plane

    # -- execution ---------------------------------------------------------
    def run(self, callbacks: Sequence[Callback] = ()) -> SessionResult:
        plane = self._ensure_plane()
        cbs: List[Callback] = []
        ev = None
        if self.job.eval_every:
            ev = EvalCallback(every=self.job.eval_every,
                              include_last=plane.kind in ("memory", "tiered",
                                                          "spmd"),
                              log_fn=self.log_fn)
            cbs.append(ev)
        cbs.extend(callbacks)
        if self.job.checkpoint.path:
            cbs.append(CheckpointCallback(self.job.checkpoint.path,
                                          every=self.job.checkpoint.every))
        # job.obs enabled: install the telemetry session for the fit and
        # save trace/metrics under obs.out_dir on exit (no-op otherwise)
        with _obs.session(self.job.obs if self.job.obs.enabled else None):
            res = _run_loop(plane, cbs)
        # cfg may have been refined during setup (SPMD shard count)
        self.cfg = plane.cfg
        return res._replace(history=ev.history if ev is not None else [])

    def make_step(self):
        """Benchmark access for in-memory jobs: returns ``(state,
        step_fn, info)`` with ``step_fn(state, key) -> state`` the
        compiled executor the plane's ``step`` runs, so timing loops
        drive it directly.  In-process the state is a ``SamplerState``;
        on the SPMD backend an ``SpmdState`` placed with the sweep's
        shardings, and ``step_fn`` splits ``key`` over the workers."""
        plane = self._ensure_plane()
        if plane.kind not in ("memory", "tiered", "spmd"):
            raise ValueError(
                "make_step() exposes the in-memory executors only; drive "
                "streamed and networked planes through run()")
        plane.setup()
        return plane.state, plane.step_fn, plane.info
