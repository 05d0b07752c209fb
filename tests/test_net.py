"""Network parameter server (repro/ps/net, DESIGN.md section 15).

Laws pinned here:

  * **Wire codec**: encode/decode round-trips are bitwise; raw int32
    buffers survive framing unchanged.
  * **Exactly-once**: a replayed mutating op (same worker, same seq) is
    answered from the dedup cache (``ST_DUP``) without re-applying --
    counts match the single-application oracle after any injected
    drop/close fault, for every op type.
  * **Hello idempotency**: a retried registration (same nonce) returns
    the existing worker id -- no ghost workers, no polluted start gate.
  * **Lease book**: shard exclusivity, epoch order, eviction re-queue,
    static-mode orphaning and work stealing.
  * **Determinism**: a 1-worker net run is bitwise identical to the
    single-process ``_StreamPlane`` (counts AND on-disk assignments);
    any worker count conserves counts exactly.
  * **Backend selection** (satellite): ``PSClient.create(backend=...)``
    accepts the four canonical names and raises a typed error listing
    them for anything else.
"""
from __future__ import annotations

import json
import shutil
import threading

import numpy as np
import pytest

import repro.ps as ps
from repro.data import stream as stream_mod
from repro.data.leases import ShardLeaseBook
from repro.ps.net import (FaultInjector, NetClient, PSServer, TableStore,
                          Transport, TransportConfig, TransportError,
                          WorkerConfig, run_worker, wire)

V, K = 40, 6


@pytest.fixture
def server():
    srv = PSServer(V, K).start()
    yield srv
    srv.stop()


@pytest.fixture
def client(server):
    c = NetClient.connect(server.address, name="t")
    yield c
    c.close()


# ---------------------------------------------------------------------------
# wire codec
# ---------------------------------------------------------------------------

class TestWire:
    def test_request_roundtrip(self):
        payload = wire.RANGE.pack(3, 5) + b"xyz"
        frame = wire.encode_request(wire.OP_PULL_BLOCK, wire.MAT_NWK,
                                    7, 99, payload)
        (n,) = wire._LEN.unpack_from(frame)
        body = frame[wire._LEN.size:]
        assert len(body) == n
        op, mat, worker, seq = wire.REQ.unpack_from(body)
        assert (op, mat, worker, seq) == (wire.OP_PULL_BLOCK,
                                          wire.MAT_NWK, 7, 99)
        assert body[wire.REQ.size:] == payload

    def test_response_roundtrip(self):
        frame = wire.encode_response(wire.ST_DUP, 42, b"cached")
        body = frame[wire._LEN.size:]
        st, seq = wire.RESP.unpack_from(body)
        assert (st, seq) == (wire.ST_DUP, 42)
        assert body[wire.RESP.size:] == b"cached"

    def test_array_bytes_bitwise(self):
        rng = np.random.default_rng(0)
        a = rng.integers(-(2 ** 31), 2 ** 31 - 1, size=(17, 5),
                         dtype=np.int32)
        b = wire.b2a(wire.a2b(a), a.shape)
        np.testing.assert_array_equal(a, b)
        assert b.flags.writeable

    def test_mutating_set_includes_acquire(self):
        # a lost lease grant must never be granted twice on retry
        assert wire.OP_ACQUIRE in wire.MUTATING
        assert wire.OP_COMMIT in wire.MUTATING
        assert wire.OP_PULL_FULL not in wire.MUTATING


# ---------------------------------------------------------------------------
# TableStore vs the numpy oracle
# ---------------------------------------------------------------------------

class TestTableStore:
    def test_dense_and_coo_match_oracle(self):
        store = TableStore(V, K)
        oracle = np.zeros((V, K), np.int32)
        rng = np.random.default_rng(1)
        dense = rng.integers(-3, 4, size=(8, K)).astype(np.int32)
        store.apply_dense(wire.MAT_NWK, 0, dense)
        oracle[:8] += dense
        rows = rng.integers(0, V, size=50).astype(np.int32)
        cols = rng.integers(0, K, size=50).astype(np.int32)
        vals = rng.choice([-1, 1], size=50).astype(np.int32)
        store.apply_coo(wire.MAT_NWK, rows, cols, vals)
        np.add.at(oracle, (rows, cols), vals)
        np.testing.assert_array_equal(store.nwk, oracle)

    def test_coo_out_of_range_rows_masked(self):
        store = TableStore(V, K)
        rows = np.array([0, -1, V, 2], np.int32)
        cols = np.array([0, 0, 0, 3], np.int32)
        vals = np.array([5, 7, 7, 1], np.int32)
        store.apply_coo(wire.MAT_NWK, rows, cols, vals)
        assert store.nwk[0, 0] == 5 and store.nwk[2, 3] == 1
        assert store.nwk.sum() == 6      # the out-of-range 7s vanished

    def test_pull_bounds_checked(self):
        store = TableStore(V, K)
        with pytest.raises(ValueError, match="out of bounds"):
            store.pull(wire.MAT_NWK, V - 1, 2)
        with pytest.raises(ValueError, match="unknown matrix"):
            store.mat(9)


# ---------------------------------------------------------------------------
# loopback server: ops + exactly-once dedup
# ---------------------------------------------------------------------------

class TestLoopbackOps:
    def test_push_pull_roundtrip(self, client):
        dense = np.arange(V * K, dtype=np.int32).reshape(V, K)
        assert client.push_dense_prefix(wire.MAT_NWK, dense)
        np.testing.assert_array_equal(client.pull_full(wire.MAT_NWK),
                                      dense)
        np.testing.assert_array_equal(
            client.pull_block(wire.MAT_NWK, 3, 4), dense[3:7])
        nk = np.arange(K, dtype=np.int32)
        assert client.push_dense_prefix(wire.MAT_NK, nk)
        np.testing.assert_array_equal(client.pull_full(wire.MAT_NK), nk)

    def test_replayed_push_not_reapplied(self, server, client):
        """Same (worker, seq) sent twice: applied once, second answer is
        ST_DUP from the cache -- the exactly-once contract."""
        delta = np.ones((V, K), np.int32)
        seq = client.t.next_seq()
        payload = wire.DENSE.pack(0, K) + wire.a2b(delta)
        st1, _ = client.t.request(wire.OP_PUSH_DENSE, wire.MAT_NWK,
                                  payload, seq=seq)
        st2, _ = client.t.request(wire.OP_PUSH_DENSE, wire.MAT_NWK,
                                  payload, seq=seq)
        assert (st1, st2) == (wire.ST_OK, wire.ST_DUP)
        assert int(client.pull_full(wire.MAT_NWK).sum()) == V * K
        assert server.dup_acks == 1

    def test_hello_nonce_idempotent(self, server, client):
        """A retried hello (same nonce) must not register a ghost."""
        nonce_payload = json.dumps({"name": "x", "role": "worker",
                                    "nonce": "deadbeef"}).encode()
        _, r1 = client.t.request(wire.OP_HELLO, payload=nonce_payload)
        _, r2 = client.t.request(wire.OP_HELLO, payload=nonce_payload)
        w1 = json.loads(r1.decode())["worker"]
        w2 = json.loads(r2.decode())["worker"]
        assert w1 == w2
        # distinct nonce -> distinct registration
        _, r3 = client.t.request(wire.OP_HELLO, payload=json.dumps(
            {"name": "y", "role": "worker", "nonce": "beefdead"}).encode())
        assert json.loads(r3.decode())["worker"] != w1

    def test_server_error_reported_not_fatal(self, client):
        with pytest.raises(ps.net.ServerError, match="out of bounds"):
            client.pull_block(wire.MAT_NWK, V - 1, 5)
        # the connection survives a logical error
        assert client.pull_full(wire.MAT_NK).shape == (K,)

    def test_barrier_releases_all(self, server):
        a = NetClient.connect(server.address, name="a")
        b = NetClient.connect(server.address, name="b")
        done = []
        t = threading.Thread(
            target=lambda: (a.barrier("e0", 2), done.append("a")))
        t.start()
        assert not done
        b.barrier("e0", 2)
        t.join(timeout=10)
        assert done == ["a"]
        a.close()
        b.close()


class TestFaultInjection:
    """Every op type retried at least once under injected faults; state
    still matches the apply-once oracle."""

    @pytest.mark.parametrize("action", [FaultInjector.DROP,
                                        FaultInjector.CLOSE_BEFORE,
                                        FaultInjector.CLOSE_AFTER])
    def test_once_per_op_conserves_counts(self, server, action):
        fault = FaultInjector.once_per_op(action)
        c = NetClient.connect(server.address, name="faulty", fault=fault)
        dense = np.full((V, K), 2, np.int32)
        c.push_dense_prefix(wire.MAT_NWK, dense)
        rows = np.array([0, 1, 2], np.int32)
        cols = np.array([0, 1, 2], np.int32)
        vals = np.array([1, -1, 1], np.int32)
        c.push_coo(wire.MAT_NWK, rows, cols, vals)
        c.barrier("fault-e0", 1)
        got = c.pull_full(wire.MAT_NWK)
        oracle = dense.copy()
        np.add.at(oracle, (rows, cols), vals)
        np.testing.assert_array_equal(got, oracle)
        # hello + both pushes + barrier + pull all faulted exactly once
        for op in ("hello", "push_dense_prefix", "push_coo", "barrier",
                   "pull_full"):
            assert fault.fired.get(op) == 1, fault.fired
        assert c.t.retries >= 5
        # mutating replays were deduplicated, not re-applied
        if action == FaultInjector.CLOSE_AFTER:
            assert server.dup_acks >= 3
        c.close()

    def test_retries_exhausted_raises(self, server):
        fault = FaultInjector(lambda op, attempt: FaultInjector.DROP)
        c = NetClient(Transport(server.address,
                                TransportConfig(retries=2,
                                                backoff_base=0.001),
                                fault=fault))
        with pytest.raises(TransportError, match="after 3 attempts"):
            c.t.request(wire.OP_STATUS)

    def test_duplicate_acquire_returns_same_lease(self, server, client):
        client.plan([(0, 0, 0), (0, 1, 1)], expected_workers=0)
        seq = client.t.next_seq()
        _, r1 = client.t.request(wire.OP_ACQUIRE, seq=seq)
        st2, r2 = client.t.request(wire.OP_ACQUIRE, seq=seq)
        assert json.loads(r1.decode()) == json.loads(r2.decode())
        assert st2 == wire.ST_DUP
        # only ONE visit went active despite two grant responses
        assert client.status()["leases"]["active"] == 1


# ---------------------------------------------------------------------------
# lease book
# ---------------------------------------------------------------------------

class TestShardLeaseBook:
    SCHED = [(0, 0, 0), (0, 1, 1), (1, 2, 0), (1, 3, 1)]

    def test_shard_exclusive_and_epoch_ordered(self):
        book = ShardLeaseBook(self.SCHED)
        st, l0 = book.acquire(0)
        st, l1 = book.acquire(1)
        assert {l0.shard_id, l1.shard_id} == {0, 1}
        assert l0.epoch == l1.epoch == 0       # epoch 1 visits are locked
        st, none = book.acquire(2)
        assert st == "wait" and none is None
        book.complete(l0.lease_id)
        st, l2 = book.acquire(2)               # shard 0's epoch-1 visit opens
        assert (l2.shard_id, l2.epoch) == (0, 1)

    def test_complete_is_exactly_once(self):
        book = ShardLeaseBook(self.SCHED)
        _, lease = book.acquire(0)
        assert book.complete(lease.lease_id)
        assert not book.complete(lease.lease_id)   # superseded signal

    def test_eviction_requeues_active(self):
        book = ShardLeaseBook(self.SCHED)
        _, lease = book.acquire(0)
        assert book.release_worker(0) == 1
        assert book.stats()["reassigned"] == 1
        _, again = book.acquire(1)             # someone else picks it up
        assert again.lease_id == lease.lease_id

    def test_static_orphan_prevents_deadlock(self):
        book = ShardLeaseBook(self.SCHED, mode="static", slots=2)
        # worker 1's slot dies before starting; orphan its visits
        assert book.orphan_slot(1) == 2
        served = []
        while True:
            st, lease = book.acquire(0, slot=0)
            if st == "done":
                break
            assert st == "lease"
            book.complete(lease.lease_id)
            served.append(lease.lease_id)
        assert len(served) == 4                # one worker drained it all

    def test_static_steal_takes_from_backlog(self):
        sched = [(0, i, i) for i in range(6)]
        book = ShardLeaseBook(sched, mode="static_steal", slots=2)
        # slot 0 never shows up; slot 1 steals everything
        done = 0
        while True:
            st, lease = book.acquire(1, slot=1)
            if st == "done":
                break
            book.complete(lease.lease_id)
            done += 1
        assert done == 6
        assert book.stolen >= 1

    def test_modes_validated(self):
        with pytest.raises(ValueError, match="mode must be one of"):
            ShardLeaseBook([(0, 0, 0)], mode="nope")
        with pytest.raises(ValueError, match="slots >= 1"):
            ShardLeaseBook([(0, 0, 0)], mode="static", slots=0)


# ---------------------------------------------------------------------------
# end-to-end: bitwise vs the single-process stream plane
# ---------------------------------------------------------------------------

def _lda_cfg(vocab):
    from repro.core import lightlda as lda

    return lda.LDAConfig(num_topics=K, vocab_size=vocab, block_tokens=512,
                         num_shards=1)


def _init_and_plan(srv, reader, cfg, epochs, expected_workers):
    """Seed the server from the stream (the session's setup path)."""
    import jax.numpy as jnp

    from repro.api.session import init_stream
    from repro.ps.client import PSClient

    nwk0, nk0 = init_stream(reader, cfg, 0,
                            client=PSClient.create(num_shards=1))
    ctl = NetClient.connect(srv.address, name="ctl", role="ctl")
    ctl.push_dense_prefix(wire.MAT_NWK, np.asarray(nwk0.to_dense()))
    ctl.push_dense_prefix(wire.MAT_NK, np.asarray(nk0.value))
    loader = stream_mod.StreamingLoader(reader, seed=0, prefetch=False)
    sched = loader.schedule(stream_mod.Cursor(0, 0), epochs)
    ctl.plan(sched, expected_workers=expected_workers)
    return ctl


def test_one_worker_bitwise_equals_stream_plane(stream_dir, tmp_path):
    """The tentpole law: the same schedule run through the network plane
    lands bit-identically -- counts AND every persisted z file."""
    from repro.api.session import _StreamPlane
    from repro.train import async_exec

    path, _, corp = stream_dir
    epochs = 2

    ref_dir = str(tmp_path / "ref")
    shutil.copytree(path, ref_dir)
    cfg = _lda_cfg(corp.vocab_size)
    plane = _StreamPlane(ref_dir, cfg, async_exec.ExecConfig(), epochs,
                         seed=0, prefetch=False, log_fn=lambda *a: None)
    plane.setup()
    for visit in plane.schedule():
        plane.step(visit)

    reader = stream_mod.ShardedCorpusReader(path)
    srv = PSServer(corp.vocab_size, K, stream_dir=path).start()
    try:
        ctl = _init_and_plan(srv, reader, cfg, epochs, expected_workers=1)
        stats = run_worker(WorkerConfig(
            server=srv.address, stream_dir=path, num_topics=K,
            block_tokens=512, seed=0, warmup=False))
        assert stats["superseded"] == 0
        np.testing.assert_array_equal(ctl.pull_full(wire.MAT_NWK),
                                      np.asarray(plane.nwk.to_dense()))
        np.testing.assert_array_equal(ctl.pull_full(wire.MAT_NK),
                                      np.asarray(plane.nk.value))
        ref_reader = stream_mod.ShardedCorpusReader(ref_dir)
        for s in range(reader.meta.num_shards):
            np.testing.assert_array_equal(reader.shard(s).z,
                                          ref_reader.shard(s).z,
                                          err_msg=f"shard {s} z diverged")
        ctl.close()
    finally:
        srv.stop()


def test_two_threaded_workers_conserve_counts(stream_dir):
    """Any interleaving of workers conserves counts: server tables ==
    histogram of the on-disk assignments, token mass unchanged."""
    path, reader, corp = stream_dir
    srv = PSServer(corp.vocab_size, K, stream_dir=path).start()
    try:
        cfg = _lda_cfg(corp.vocab_size)
        ctl = _init_and_plan(srv, reader, cfg, epochs=2, expected_workers=2)
        results = [None, None]

        def go(i):
            results[i] = run_worker(WorkerConfig(
                server=srv.address, stream_dir=path, num_topics=K,
                block_tokens=512, seed=0, name=f"t{i}",
                commit_hot_rows=16, warmup=False))

        ts = [threading.Thread(target=go, args=(i,)) for i in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=300)
        assert all(r is not None for r in results), results
        nwk = ctl.pull_full(wire.MAT_NWK)
        nk = ctl.pull_full(wire.MAT_NK)
        rw, rk = stream_mod.rebuild_counts_from_stream(reader, K)
        np.testing.assert_array_equal(nwk, rw)
        np.testing.assert_array_equal(nk, rk)
        assert int(nk.sum()) == corp.w.shape[0]
        st = ctl.status()
        assert st["leases"]["done"] == st["leases"]["total"]
        ctl.close()
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# satellite: PSClient.create(backend=...) selection
# ---------------------------------------------------------------------------

class TestBackendSelection:
    def test_names_exported(self):
        assert ps.BACKEND_NAMES == ("in_process", "spmd", "tiered", "net")

    def test_unknown_name_lists_valid_choices(self):
        with pytest.raises(ps.BackendConfigError) as ei:
            ps.PSClient.create(backend="carrier_pigeon")
        msg = str(ei.value)
        for name in ps.BACKEND_NAMES:
            assert name in msg
        assert ei.value.valid == ps.BACKEND_NAMES
        assert isinstance(ei.value, ValueError)     # typed but catchable

    def test_in_process_by_name(self):
        c = ps.PSClient.create(backend="in_process")
        assert isinstance(c.backend, ps.InProcessBackend)

    def test_net_by_name_detached(self):
        c = ps.PSClient.create(backend="net")
        assert isinstance(c.backend, ps.NetBackend)
        assert c.backend.net is None

    def test_net_by_name_connected(self, server):
        c = ps.PSClient.create(backend="net", server=server.address)
        assert isinstance(c.backend, ps.NetBackend)
        assert c.backend.net is not None
        assert c.backend.net.meta["vocab"] == V
        c.backend.net.close()

    def test_spmd_by_name_requires_mesh_or_axes(self):
        with pytest.raises(ps.BackendConfigError, match="axis_name"):
            ps.PSClient.create(backend="spmd")
        c = ps.PSClient.create(backend="spmd", axis_name="data")
        assert isinstance(c.backend, ps.SpmdBackend)

    def test_instances_still_accepted(self):
        c = ps.PSClient.create(backend=ps.InProcessBackend())
        assert isinstance(c.backend, ps.InProcessBackend)
        with pytest.raises(ps.BackendConfigError, match="valid backends"):
            ps.PSClient.create(backend=object())


# ---------------------------------------------------------------------------
# satellite: job-level validation
# ---------------------------------------------------------------------------

class TestNetJobValidation:
    def test_net_rejects_unsupported_combos(self, tiny_corpus):
        from repro import api

        with pytest.raises(api.JobValidationError, match="workers"):
            api.LDAJob(corpus=tiny_corpus, num_topics=K, backend=api.NET,
                       workers=0).validate()
        with pytest.raises(api.JobValidationError, match="net_assign"):
            api.LDAJob(corpus=tiny_corpus, num_topics=K, backend=api.NET,
                       net_assign="telepathy").validate()
        with pytest.raises(api.JobValidationError, match="num_shards"):
            api.LDAJob(corpus=tiny_corpus, num_topics=K, backend=api.NET,
                       num_shards=2).validate()

    def test_net_defaults_validate(self, tiny_corpus):
        from repro import api

        job = api.LDAJob(corpus=tiny_corpus, num_topics=K,
                         backend=api.NET).validate()
        assert job.workers == 2 and job.net_assign == "dynamic"

    def test_server_requires_net_backend(self, tiny_corpus):
        from repro import api

        with pytest.raises(api.JobValidationError, match="backend"):
            api.LDAJob(corpus=tiny_corpus, num_topics=K,
                       server="127.0.0.1:1").validate()


class TestWorkerPlatform:
    """Workers run where ``JAX_PLATFORMS`` says; nothing forces the CPU."""

    def _pool(self, platforms, lines):
        from repro.ps.net import WorkerPool
        base = WorkerConfig(server="localhost:1", stream_dir="unused",
                            num_topics=K)
        return WorkerPool("localhost:1", base,
                          env={"JAX_PLATFORMS": platforms},
                          log_fn=lines.append)

    def test_cpu_workers_by_name(self):
        pool = self._pool("cpu", [])
        assert pool.cpu_workers
        assert pool.env["JAX_PLATFORMS"] == "cpu"
        assert pool.env["OMP_NUM_THREADS"] == "1"

    def test_accelerator_workers_hold_one_chip(self):
        lines = []
        pool = self._pool("tpu", lines)
        assert not pool.cpu_workers
        assert pool.env["JAX_PLATFORMS"] == "tpu"
        pool.procs.append(None)          # one worker already holds the chip
        with pytest.raises(ValueError, match="JAX_PLATFORMS=cpu"):
            pool.add_worker()
        assert lines == []
