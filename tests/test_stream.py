"""Streaming corpus pipeline + out-of-core trainer tests (ISSUE 4).

Correctness anchors:

  * the sharded writer/reader round-trip conserves every token and keeps
    document structure intact (uniform padded geometry);
  * the loader's per-epoch shard order is a pure function of (seed,
    epoch) and cursor-resumable mid-epoch; prefetch changes nothing;
  * the stream trainer at staleness 0 on a single-shard stream is
    **bitwise identical** to the in-memory ``sweep_blocked_ref`` path
    (the acceptance criterion), and at any staleness/sharding the
    epoch-level conservation law holds: PS counts == histogram of the
    persisted assignments (Petterson & Caetano's distributed-LDA
    invariant).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import lightlda as lda
from repro.data import stream as stream_mod
from repro.sharding.mesh import make_mesh
from repro.train import async_exec
from repro.train import loop as train_loop


class TestWriterReader:
    def test_roundtrip_conserves_tokens_and_docs(self, stream_dir):
        path, reader, corp = stream_dir
        meta = reader.meta
        assert meta.num_tokens == corp.num_tokens
        assert meta.num_docs == corp.num_docs
        freq = np.zeros(corp.vocab_size, np.int64)
        docs_seen = []
        for sid in range(reader.num_shards):
            sh = reader.shard(sid)
            # uniform padded geometry
            assert sh.w.shape == (meta.tokens_per_shard,)
            assert sh.doc_len.shape == (meta.doc_cap,)
            n = sh.n_tokens
            freq += np.bincount(np.asarray(sh.w[:n]),
                                minlength=corp.vocab_size)
            # per-doc structure: offsets tile the valid region exactly
            dl = np.asarray(sh.doc_len[:sh.n_docs])
            ds = np.asarray(sh.doc_start[:sh.n_docs])
            assert int(dl.sum()) == n
            assert np.array_equal(ds, np.concatenate([[0],
                                                      np.cumsum(dl)[:-1]]))
            for i in range(sh.n_docs):
                docs_seen.append(np.asarray(sh.w[ds[i]:ds[i] + dl[i]]))
            # padding is inert
            assert (np.asarray(sh.w[n:]) == 0).all()
        assert np.array_equal(freq, corp.word_freq)
        assert np.array_equal(freq, reader.word_freq)
        # docs arrive in corpus order, bit-exact
        assert len(docs_seen) == corp.num_docs
        for i, doc in enumerate(docs_seen):
            s, l = corp.doc_start[i], corp.doc_len[i]
            assert np.array_equal(doc, corp.w[s:s + l])

    def test_oversized_document_raises(self, tmp_path):
        w = stream_mod.ShardedCorpusWriter(str(tmp_path / "s"), 10, 8)
        with pytest.raises(ValueError):
            w.add_document(np.zeros(9, np.int32))

    def test_out_of_range_word_raises(self, tmp_path):
        w = stream_mod.ShardedCorpusWriter(str(tmp_path / "s"), 10, 8)
        with pytest.raises(ValueError):
            w.add_document(np.array([11], np.int32))
            w.close()

    def test_bulk_add_tokens_matches_per_doc(self, tmp_path, tiny_corpus):
        a = stream_mod.ShardedCorpusWriter(str(tmp_path / "a"),
                                           tiny_corpus.vocab_size, 1024)
        for i in range(tiny_corpus.num_docs):
            s, l = tiny_corpus.doc_start[i], tiny_corpus.doc_len[i]
            a.add_document(tiny_corpus.w[s:s + l])
        ma = a.close()
        mb = stream_mod.write_sharded(str(tmp_path / "b"), tiny_corpus,
                                      1024)
        assert ma.shard_tokens == mb.shard_tokens
        assert ma.shard_docs == mb.shard_docs
        ra = stream_mod.ShardedCorpusReader(str(tmp_path / "a"))
        rb = stream_mod.ShardedCorpusReader(str(tmp_path / "b"))
        for sid in range(ra.num_shards):
            assert np.array_equal(np.asarray(ra.shard(sid).w),
                                  np.asarray(rb.shard(sid).w))

    def test_z_roundtrip_atomic(self, stream_dir):
        _, reader, _ = stream_dir
        assert not reader.has_z(0)
        z = np.arange(reader.meta.tokens_per_shard, dtype=np.int32)
        reader.write_z(0, z)
        assert reader.has_z(0)
        assert np.array_equal(reader.read_z(0), z)


class TestLoader:
    def test_epoch_orders_deterministic_and_shuffled(self, stream_dir):
        _, reader, _ = stream_dir
        loader = stream_mod.StreamingLoader(reader, seed=3)
        o0 = loader.order_for_epoch(0)
        assert np.array_equal(o0, loader.order_for_epoch(0))
        assert sorted(o0.tolist()) == list(range(reader.num_shards))
        orders = [tuple(loader.order_for_epoch(e)) for e in range(6)]
        assert len(set(orders)) > 1, "epoch orders never shuffle"
        other = stream_mod.StreamingLoader(reader, seed=4)
        assert [tuple(other.order_for_epoch(e)) for e in range(6)] != orders

    def test_cursor_resume_midepoch(self, stream_dir):
        _, reader, _ = stream_dir
        loader = stream_mod.StreamingLoader(reader, seed=1, load_z=False)
        full = [(c, sid) for c, sid, _ in
                loader.iterate(stream_mod.Cursor(0, 0), 2)]
        assert len(full) == 2 * reader.num_shards
        cut = 3
        resumed = [(c, sid) for c, sid, _ in
                   loader.iterate(full[cut][0], 2)]
        assert resumed == full[cut:]
        # Cursor.next walks the same schedule
        cur = stream_mod.Cursor(0, 0)
        for c, _ in full:
            assert c == cur
            cur = cur.next(reader.num_shards)

    def test_prefetch_matches_sync(self, stream_dir):
        _, reader, _ = stream_dir
        sync = stream_mod.StreamingLoader(reader, seed=2, prefetch=False,
                                          load_z=False)
        pre = stream_mod.StreamingLoader(reader, seed=2, prefetch=True,
                                         load_z=False)
        a = list(sync.iterate(stream_mod.Cursor(0, 1), 3))
        b = list(pre.iterate(stream_mod.Cursor(0, 1), 3))
        assert [(c, sid) for c, sid, _ in a] == [(c, sid) for c, sid, _ in b]
        for (_, _, sa), (_, _, sb) in zip(a, b):
            assert np.array_equal(np.asarray(sa.w), np.asarray(sb.w))

    def test_memory_budget_enforced(self, stream_dir):
        _, reader, _ = stream_dir
        need = 2 * reader.shard_nbytes(with_z=True)
        stream_mod.StreamingLoader(reader, memory_budget=need)  # exact fit
        with pytest.raises(ValueError):
            stream_mod.StreamingLoader(reader, memory_budget=need - 1)


class TestStreamTrainer:
    def test_bitwise_vs_sweep_blocked_ref(self, tiny_corpus, tmp_path):
        """The acceptance anchor: single-shard stream, blocked executor,
        staleness 0 -> bitwise-identical counts/assignments to the
        in-memory synchronous reference over multiple epochs."""
        corp = tiny_corpus
        cfg = lda.LDAConfig(num_topics=8, vocab_size=corp.vocab_size,
                            block_tokens=256, num_shards=2)
        cap = -(-corp.num_tokens // 256) * 256
        path = str(tmp_path / "one")
        stream_mod.write_sharded(path, corp, tokens_per_shard=cap,
                                 doc_cap=corp.num_docs)
        reader = stream_mod.ShardedCorpusReader(path)
        assert reader.num_shards == 1
        seed, epochs = 7, 2
        ec = async_exec.ExecConfig(staleness=0, model_blocks=4)
        nwk, nk, _, _ = train_loop.fit_lda_stream(
            reader, cfg, ec, epochs=epochs, seed=seed,
            log_fn=lambda *a: None)

        # in-memory reference: same z0 draw, same keys, same token index
        sh = reader.shard(0, load_z=False)
        z0 = np.array(jax.random.randint(
            train_loop.stream_init_key(seed, 0), (cap,), 0, cfg.K,
            dtype=jnp.int32))
        z0[sh.n_tokens:] = 0
        w, d = jnp.asarray(sh.w), jnp.asarray(sh.d)
        valid = jnp.asarray(np.arange(cap) < sh.n_tokens)
        nwk0, nk0, ndk0 = lda.rebuild_counts(w, d, jnp.asarray(z0), valid,
                                             reader.meta.doc_cap, cfg)
        state = lda.SamplerState(w, d, jnp.asarray(z0), valid,
                                 jnp.asarray(sh.doc_start),
                                 jnp.asarray(sh.doc_len), nwk0, nk0, ndk0)
        _, build_index, info = async_exec.make_stream_executor(
            cfg, ec, nwk0.layout)
        idx, bval = build_index(sh.w, np.asarray(valid))
        for epoch in range(epochs):
            key = train_loop.stream_sweep_key(seed, epoch, 0)
            state = lda.sweep_blocked_ref(state, key, cfg, idx, bval,
                                          info["rows_per_step"])
        assert bool((state.nwk.value == nwk.value).all())
        assert bool((state.nk.value == nk.value).all())
        assert np.array_equal(np.asarray(state.z), reader.read_z(0))

    @pytest.mark.parametrize("exec_kw", [
        {"staleness": 1},                        # snapshot executor
        {"staleness": 1, "model_blocks": 4},     # blocked executor
    ])
    def test_epoch_conservation_multi_shard(self, stream_dir, exec_kw):
        """After any number of epochs, the global PS counts equal the
        histogram of the persisted per-shard assignments exactly."""
        path, reader, corp = stream_dir
        cfg = lda.LDAConfig(num_topics=8, vocab_size=corp.vocab_size,
                            block_tokens=256, num_shards=2)
        nwk, nk, _, _ = train_loop.fit_lda_stream(
            reader, cfg, async_exec.ExecConfig(**exec_kw), epochs=2,
            seed=11, log_fn=lambda *a: None)
        nwk_ref, nk_ref = stream_mod.rebuild_counts_from_stream(reader,
                                                                cfg.K)
        assert int(nk_ref.sum()) == corp.num_tokens
        assert np.array_equal(np.asarray(nwk.to_dense()), nwk_ref)
        assert np.array_equal(np.asarray(nk.value), nk_ref)

    def test_history_and_info(self, stream_dir):
        path, reader, corp = stream_dir
        cfg = lda.LDAConfig(num_topics=8, vocab_size=corp.vocab_size,
                            block_tokens=256, num_shards=2)
        nwk, nk, history, info = train_loop.fit_lda_stream(
            reader, cfg, async_exec.ExecConfig(staleness=1), epochs=1,
            seed=0, eval_every=2, log_fn=lambda *a: None)
        assert info["stream_shards"] == reader.num_shards
        assert len(history) == reader.num_shards // 2
        assert all(h["tokens_per_s"] > 0 for h in history)

    def test_build_index_pinned_cap_and_overflow(self, stream_dir):
        """``build_index(..., cap=...)`` pins one index shape for every
        shard (identical traces by construction); an impossible cap
        raises instead of silently dropping tokens."""
        path, reader, corp = stream_dir
        cfg = lda.LDAConfig(num_topics=8, vocab_size=corp.vocab_size,
                            block_tokens=256, num_shards=2)
        from repro import ps
        layout = ps.client_for(cfg).matrix(cfg.V, cfg.K).layout
        _, build_index, _ = async_exec.make_stream_executor(
            cfg, async_exec.ExecConfig(model_blocks=4), layout)
        sh = reader.shard(0, load_z=False)
        valid = np.arange(reader.meta.tokens_per_shard) < sh.n_tokens
        idx_a, _ = build_index(sh.w, valid, cap=reader.meta.tokens_per_shard)
        for sid in range(1, reader.num_shards):
            s2 = reader.shard(sid, load_z=False)
            v2 = np.arange(reader.meta.tokens_per_shard) < s2.n_tokens
            idx_b, bval_b = build_index(s2.w, v2,
                                        cap=reader.meta.tokens_per_shard)
            assert idx_b.shape == idx_a.shape
            assert int(bval_b.sum()) == s2.n_tokens
        with pytest.raises(ValueError, match="overflow"):
            build_index(sh.w, valid, cap=1)

    def test_snapshot_mode_rejects_misaligned_shards(self, stream_dir):
        path, reader, corp = stream_dir
        cfg = lda.LDAConfig(num_topics=8, vocab_size=corp.vocab_size,
                            block_tokens=768, num_shards=2)
        with pytest.raises(ValueError):
            train_loop.fit_lda_stream(reader, cfg,
                                      async_exec.ExecConfig(), epochs=1)


@pytest.mark.multidevice(4)
class TestStreamSpmd:
    """Stream shards as SPMD worker partitions: each mesh worker takes one
    on-disk shard (the uniform padded geometry is exactly what shard_map
    wants), and the sweep's collectives merge their deltas exactly once.
    Exercised by the forced-4-device CI matrix entry."""

    def test_stream_shards_feed_spmd_workers(self, stream_dir):
        from repro import ps
        from repro.launch import lda as launch_lda

        path, reader, corp = stream_dir
        model = 2
        data = jax.device_count() // model
        workers = data * model
        assert reader.num_shards >= workers
        cfg = lda.LDAConfig(num_topics=8, vocab_size=corp.vocab_size,
                            block_tokens=256, num_shards=model)
        mesh = make_mesh((data, model), ("data", "model"))
        sweep_fn = jax.jit(launch_lda.make_spmd_sweep(mesh, cfg,
                                                      staleness=1))
        meta = reader.meta
        shards = [reader.shard(s, load_z=False) for s in range(workers)]
        w = jnp.asarray(np.stack([np.asarray(s.w) for s in shards]))
        d = jnp.asarray(np.stack([np.asarray(s.d) for s in shards]))
        ds = jnp.asarray(np.stack([np.asarray(s.doc_start)
                                   for s in shards]))
        dl = jnp.asarray(np.stack([np.asarray(s.doc_len) for s in shards]))
        valid = jnp.asarray(np.stack(
            [np.arange(meta.tokens_per_shard) < s.n_tokens
             for s in shards]))
        z = jax.random.randint(jax.random.PRNGKey(0), w.shape, 0, cfg.K,
                               dtype=jnp.int32)
        one = valid.reshape(-1).astype(jnp.int32)
        nwk_dense = jnp.zeros((cfg.V, cfg.K), jnp.int32).at[
            w.reshape(-1), z.reshape(-1)].add(one)
        nk = jnp.zeros((cfg.K,), jnp.int32).at[z.reshape(-1)].add(one)
        widx = jnp.arange(workers)[:, None].repeat(w.shape[1], 1)
        ndk = jnp.zeros((workers, meta.doc_cap, cfg.K), jnp.int32).at[
            widx.reshape(-1), d.reshape(-1), z.reshape(-1)].add(one)
        nwk = ps.client_for(cfg).matrix_from_dense(nwk_dense)

        z2, ndk2, nwk_val2, nk2 = sweep_fn(
            w, d, z, valid, ds, dl, ndk, nwk.value, nk,
            jax.random.split(jax.random.PRNGKey(1), workers))
        n = int(valid.sum())
        full = ps.client_for(cfg).wrap_matrix(nwk_val2, cfg.V).to_dense()
        assert int(nk2.sum()) == n
        assert int(full.sum()) == n
        rebuilt = jnp.zeros((cfg.V, cfg.K), jnp.int32).at[
            w.reshape(-1), z2.reshape(-1)].add(one)
        assert bool((rebuilt == full).all())
