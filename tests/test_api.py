"""Unified estimator/session API tests (ISSUE 5).

Correctness anchors:

  * **Job validation** is up-front and actionable: every malformed spec
    fails before device work, with fix-it messages.
  * **Equivalence**: one ``LDAJob`` reaches every pre-redesign scenario
    bitwise -- the in-memory plane equals the old ``fit_lda`` chain
    (``make_executor`` + ``key, sub = split(key)``), the stream plane
    equals the old ``fit_lda_stream`` (same (seed, schedule-position) RNG
    and z discipline), the SPMD plane equals the old launcher loop, for
    dense/COO/hybrid push routes alike.
  * **Callback non-interference** (extends the PR 4 resume-equivalence
    suites): ``fit`` with ``EvalCallback`` + ``CheckpointCallback``
    attached is bitwise identical to a callback-free run, for both
    in-memory and streamed sources.
  * **TopicModel**: transform/score/save/load/publisher round-trips.
"""
import json
import shutil

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import api
from repro import ps
from repro.core import lightlda as lda
from repro.data import corpus as corpus_mod
from repro.data import stream as stream_mod
from repro.sharding.mesh import make_mesh
from repro.train import async_exec


def _quiet(*a, **k):
    pass


def _mem_job(corp, **kw):
    base = dict(corpus=corp, num_topics=8, block_tokens=256, num_shards=2,
                sweeps=3, seed=3, eval_every=0)
    base.update(kw)
    return api.LDAJob(**base)


# ---------------------------------------------------------------------------
# Validation
# ---------------------------------------------------------------------------

class TestJobValidation:
    def test_no_source(self):
        with pytest.raises(api.JobValidationError, match="exactly one"):
            api.LDAJob().validate()

    def test_two_sources(self, tiny_corpus):
        with pytest.raises(api.JobValidationError, match="exactly one"):
            api.LDAJob(corpus=tiny_corpus, stream_dir="/tmp/x").validate()

    def test_route_and_hot_words_conflict(self, tiny_corpus):
        with pytest.raises(api.JobValidationError, match="not both"):
            api.LDAJob(corpus=tiny_corpus, route=ps.CooRoute(),
                       hot_words=10).validate()

    def test_spmd_rejects_model_blocks(self, tiny_corpus):
        with pytest.raises(api.JobValidationError, match="full-snapshot"):
            api.LDAJob(corpus=tiny_corpus, backend=api.SPMD,
                       model_blocks=4).validate()

    def test_spmd_rejects_checkpoint_up_front(self, tiny_corpus):
        """Regression: an SPMD job with a checkpoint path must fail at
        validate(), not after the whole run at on_fit_end."""
        with pytest.raises(api.JobValidationError, match="SPMD"):
            api.LDAJob(corpus=tiny_corpus, backend=api.SPMD,
                       checkpoint=api.CheckpointPolicy(
                           path="/tmp/c.npz")).validate()

    def test_resume_needs_stream(self, tiny_corpus):
        with pytest.raises(api.JobValidationError, match="streamed"):
            api.LDAJob(corpus=tiny_corpus,
                       checkpoint=api.CheckpointPolicy(
                           path="/tmp/c.npz", resume=True)).validate()

    def test_checkpoint_every_needs_path(self, tiny_corpus):
        with pytest.raises(api.JobValidationError, match="path"):
            api.LDAJob(corpus=tiny_corpus,
                       checkpoint=api.CheckpointPolicy(every=2)).validate()

    def test_max_shards_memory_source(self, tiny_corpus):
        with pytest.raises(api.JobValidationError, match="max_shards"):
            api.LDAJob(corpus=tiny_corpus, max_shards=3).validate()

    def test_all_problems_reported_at_once(self):
        with pytest.raises(api.JobValidationError) as ei:
            api.LDAJob(num_topics=0, staleness=-1, sweeps=0,
                       backend="cluster").validate()
        assert len(ei.value.problems) >= 4

    def test_missing_stream_dir(self, tmp_path):
        with pytest.raises(api.JobValidationError, match="does not exist"):
            api.LDAJob(stream_dir=str(tmp_path / "nope")).validate()

    def test_vocab_smaller_than_corpus(self, tiny_corpus):
        job = api.LDAJob(corpus=tiny_corpus, vocab_size=10, num_topics=4)
        with pytest.raises(api.JobValidationError, match="smaller"):
            api.Session(job, log_fn=_quiet).run()

    def test_docs_source_materialises(self):
        docs = [np.array([0, 1, 1, 2]), np.array([2, 2, 3])]
        job = api.LDAJob(docs=docs, num_topics=2, block_tokens=64,
                         sweeps=1, eval_every=0)
        res = api.Session(job, log_fn=_quiet).run()
        assert int(res.nk.value.sum()) == 7


# ---------------------------------------------------------------------------
# Bitwise equivalence with the pre-redesign paths
# ---------------------------------------------------------------------------

def _reference_fit(corp, cfg, exec_cfg, sweeps, seed):
    """The pre-redesign run_single/fit_lda recipe, inlined verbatim."""
    key = jax.random.PRNGKey(seed)
    state = lda.init_state(key, jnp.asarray(corp.w), jnp.asarray(corp.d),
                           corp.num_docs, cfg)
    key, sub = jax.random.split(key)
    step, _ = async_exec.make_executor(state, cfg, exec_cfg)
    for _ in range(sweeps):
        sub, k = jax.random.split(sub)
        state = step(state, k)
    return state


class TestMemoryEquivalence:
    @pytest.mark.parametrize("exec_kw", [
        {},                                      # synchronous snapshot
        {"staleness": 1},                        # stale snapshot
        {"staleness": 1, "model_blocks": 4},     # stale blocked/pipelined
    ])
    def test_bitwise_vs_pre_redesign(self, tiny_corpus, exec_kw):
        corp = tiny_corpus
        cfg = lda.LDAConfig(num_topics=8, vocab_size=corp.vocab_size,
                            block_tokens=256, num_shards=2)
        ref = _reference_fit(corp, cfg, async_exec.ExecConfig(**exec_kw),
                             sweeps=3, seed=3)
        res = api.Session(_mem_job(corp, **exec_kw), log_fn=_quiet).run()
        assert bool((res.state.z == ref.z).all())
        assert bool((res.state.nwk.value == ref.nwk.value).all())
        assert bool((res.state.nk.value == ref.nk.value).all())
        assert bool((res.state.ndk == ref.ndk).all())

    def test_routes_reach_identical_counts(self, tiny_corpus):
        """Dense / COO / hybrid routes are traffic shapes, not semantics:
        the same job under each lands on the bitwise-identical model."""
        outs = []
        for route in (api.DenseRoute(), api.CooRoute(),
                      api.HybridRoute(hot_words=32)):
            res = api.Session(_mem_job(tiny_corpus, route=route),
                              log_fn=_quiet).run()
            outs.append(res)
        for other in outs[1:]:
            assert bool((outs[0].state.z == other.state.z).all())
            assert bool((outs[0].nwk.to_dense()
                         == other.nwk.to_dense()).all())

    def test_estimator_returns_model_with_history(self, tiny_corpus):
        job = _mem_job(tiny_corpus, eval_every=2)
        est = api.APSLDA(job, log_fn=_quiet)
        model = est.fit()
        assert model.nwk.shape == (tiny_corpus.vocab_size, 8)
        assert len(model.history) >= 2          # sweep 2 + final sweep 3
        assert model.history[-1]["sweep"] == 3
        assert est.model_ is model

    def test_make_step_exposes_executor(self, tiny_corpus):
        sess = api.Session(_mem_job(tiny_corpus), log_fn=_quiet)
        state, step, info = sess.make_step()
        out = step(state, jax.random.PRNGKey(0))
        assert int(out.nk.value.sum()) == int(state.nk.value.sum())
        assert info["mode"] in ("snapshot", "blocked")

    @pytest.mark.parametrize("route,layout", [
        (api.DenseRoute(), "rows"), (api.HybridRoute(hot_words=32), "flat"),
    ])
    def test_session_reports_nwk_carry(self, tiny_corpus, route, layout):
        lines = []
        sess = api.Session(_mem_job(tiny_corpus, route=route),
                           log_fn=lines.append)
        _, _, info = sess.make_step()
        assert info["nwk_carry"] == layout
        assert any(ln.startswith("[lda] snapshot executor")
                   and ln.endswith(f"nwk_carry {layout}") for ln in lines)


class TestStreamEquivalence:
    def test_bitwise_vs_fit_lda_stream(self, tiny_corpus, tmp_path):
        """LDAJob(stream_dir=...) == the deprecated fit_lda_stream shim
        (itself anchored bitwise to sweep_blocked_ref in test_stream.py),
        including persisted z files."""
        from repro.train import loop as train_loop

        corp = tiny_corpus
        pa, pb = str(tmp_path / "a"), str(tmp_path / "b")
        stream_mod.write_sharded(pa, corp, tokens_per_shard=1024)
        shutil.copytree(pa, pb)
        cfg = lda.LDAConfig(num_topics=8, vocab_size=corp.vocab_size,
                            block_tokens=256, num_shards=2)
        with pytest.deprecated_call():
            nwa, nka, _, _ = train_loop.fit_lda_stream(
                pa, cfg, async_exec.ExecConfig(staleness=1), epochs=2,
                seed=5, log_fn=_quiet)

        job = api.LDAJob(stream_dir=pb, num_topics=8, block_tokens=256,
                         num_shards=2, staleness=1, epochs=2, seed=5,
                         eval_every=0)
        res = api.Session(job, log_fn=_quiet).run()
        assert bool((res.nwk.value == nwa.value).all())
        assert bool((res.nk.value == nka.value).all())
        ra = stream_mod.ShardedCorpusReader(pa)
        for sid in range(ra.num_shards):
            assert np.array_equal(ra.read_z(sid),
                                  res.reader.read_z(sid))

    def test_checkpoint_resume_through_job(self, tiny_corpus, tmp_path):
        """The CheckpointPolicy path: preempt via max_shards, resume via
        the policy, land bitwise on the straight-through run."""
        corp = tiny_corpus
        pa, pb = str(tmp_path / "a"), str(tmp_path / "b")
        stream_mod.write_sharded(pa, corp, tokens_per_shard=1024)
        shutil.copytree(pa, pb)
        base = dict(num_topics=8, block_tokens=256, num_shards=2,
                    staleness=1, model_blocks=4, epochs=2, seed=5,
                    eval_every=0)
        res_a = api.Session(api.LDAJob(stream_dir=pa, **base),
                            log_fn=_quiet).run()

        ck = str(tmp_path / "ck.npz")
        api.Session(api.LDAJob(
            stream_dir=pb, max_shards=7,
            checkpoint=api.CheckpointPolicy(path=ck, every=1), **base),
            log_fn=_quiet).run()
        res_b = api.Session(api.LDAJob(
            stream_dir=pb,
            checkpoint=api.CheckpointPolicy(path=ck, resume=True), **base),
            log_fn=_quiet).run()

        assert bool((res_a.nwk.value == res_b.nwk.value).all())
        assert bool((res_a.nk.value == res_b.nk.value).all())
        ra = stream_mod.ShardedCorpusReader(pa)
        rb = stream_mod.ShardedCorpusReader(pb)
        for sid in range(ra.num_shards):
            assert np.array_equal(ra.read_z(sid), rb.read_z(sid))


# ---------------------------------------------------------------------------
# Callback non-interference (ISSUE 5 satellite; extends PR 4's suites)
# ---------------------------------------------------------------------------

class TestCallbackNonInterference:
    def test_memory_fit_bitwise_with_and_without_callbacks(
            self, tiny_corpus, tmp_path):
        job = _mem_job(tiny_corpus, staleness=1, model_blocks=4)
        bare = api.Session(job, log_fn=_quiet).run()

        seen = []

        class Spy(api.Callback):
            def on_sweep_end(self, view):
                seen.append(view.step)

        cbs = [api.EvalCallback(every=1, log_fn=_quiet),
               api.CheckpointCallback(str(tmp_path / "m.npz"), every=1),
               api.LogCallback(str(tmp_path / "log.jsonl")),
               Spy()]
        with_cbs = api.Session(job, log_fn=_quiet).run(cbs)

        assert seen == [1, 2, 3]
        assert (tmp_path / "m.npz").exists()
        assert bool((bare.state.z == with_cbs.state.z).all())
        assert bool((bare.state.nwk.value
                     == with_cbs.state.nwk.value).all())
        assert bool((bare.state.nk.value == with_cbs.state.nk.value).all())
        assert bool((bare.state.ndk == with_cbs.state.ndk).all())

    def test_stream_fit_bitwise_with_and_without_callbacks(
            self, tiny_corpus, tmp_path):
        corp = tiny_corpus
        pa, pb = str(tmp_path / "a"), str(tmp_path / "b")
        stream_mod.write_sharded(pa, corp, tokens_per_shard=1024)
        shutil.copytree(pa, pb)
        base = dict(num_topics=8, block_tokens=256, num_shards=2,
                    staleness=1, epochs=2, seed=5, eval_every=0)
        bare = api.Session(api.LDAJob(stream_dir=pa, **base),
                           log_fn=_quiet).run()
        cbs = [api.EvalCallback(every=2, include_last=False,
                                log_fn=_quiet),
               api.CheckpointCallback(str(tmp_path / "s.npz"), every=3)]
        with_cbs = api.Session(api.LDAJob(stream_dir=pb, **base),
                               log_fn=_quiet).run(cbs)

        assert (tmp_path / "s.npz").exists()
        assert bool((bare.nwk.value == with_cbs.nwk.value).all())
        assert bool((bare.nk.value == with_cbs.nk.value).all())
        ra = stream_mod.ShardedCorpusReader(pa)
        rb = stream_mod.ShardedCorpusReader(pb)
        for sid in range(ra.num_shards):
            assert np.array_equal(ra.read_z(sid), rb.read_z(sid))

    def test_eval_callback_heldout_and_coherence_rows(self, tiny_corpus):
        train_corp, held = corpus_mod.train_heldout_split(tiny_corpus, 0.2,
                                                          seed=2)
        ev = api.EvalCallback(every=2, heldout=held, coherence=True,
                              log_fn=_quiet)
        api.Session(_mem_job(train_corp, sweeps=2),
                    log_fn=_quiet).run([ev])
        assert len(ev.history) == 1
        row = ev.history[0]
        assert np.isfinite(row["heldout_perplexity"])
        assert "coherence" in row

    def test_log_callback_jsonl(self, tiny_corpus, tmp_path):
        path = tmp_path / "events.jsonl"
        api.Session(_mem_job(tiny_corpus), log_fn=_quiet).run(
            [api.LogCallback(str(path))])
        events = [json.loads(l) for l in path.read_text().splitlines()]
        kinds = [e["event"] for e in events]
        assert kinds[0] == "fit_start" and kinds[-1] == "fit_end"
        assert kinds.count("sweep") == 3


# ---------------------------------------------------------------------------
# TopicModel
# ---------------------------------------------------------------------------

class TestTopicModel:
    @pytest.fixture(scope="class")
    def fitted(self, tiny_corpus):
        model = api.APSLDA(_mem_job(tiny_corpus, sweeps=4),
                           log_fn=_quiet).fit()
        docs = [tiny_corpus.w[s:s + n] for s, n in
                zip(tiny_corpus.doc_start[:6], tiny_corpus.doc_len[:6])]
        return model, docs

    def test_transform_shape_and_determinism(self, fitted):
        model, docs = fitted
        a = model.transform(docs, seeds=list(range(len(docs))))
        b = model.transform(docs, seeds=list(range(len(docs))))
        assert a.shape == (len(docs), model.num_topics)
        np.testing.assert_allclose(a.sum(axis=1), 1.0, atol=1e-3)
        np.testing.assert_array_equal(a, b)

    def test_score_shape_finite(self, fitted):
        model, docs = fitted
        queries = [d[:3] for d in docs[:2]]
        s = model.score(queries, docs)
        assert s.shape == (2, len(docs))
        assert np.isfinite(s).all()

    def test_save_load_roundtrip(self, fitted, tmp_path):
        model, docs = fitted
        path = str(tmp_path / "model.npz")
        model.save(path)
        back = api.TopicModel.load(path)
        np.testing.assert_array_equal(back.nwk, model.nwk)
        np.testing.assert_array_equal(back.nk, model.nk)
        assert back.cfg == model.cfg
        np.testing.assert_array_equal(
            back.transform(docs[:2], seeds=[0, 1]),
            model.transform(docs[:2], seeds=[0, 1]))

    def test_publisher_handoff_to_service(self, fitted):
        from repro.serve.topic_service import TopicService

        model, docs = fitted
        pub = model.publisher()
        assert pub.version == 1
        svc = TopicService(model.cfg, publisher=pub)
        results = svc.fold_in(docs[:3], seeds=[0, 1, 2])
        assert len(results) == 3
        assert all(r.version == 1 for r in results)

    def test_top_words_shape(self, fitted):
        model, _ = fitted
        top = model.top_words(num_words=5)
        assert top.shape == (model.num_topics, 5)


# ---------------------------------------------------------------------------
# SPMD planes (forced-4-device CI matrix entry)
# ---------------------------------------------------------------------------

@pytest.mark.multidevice(4)
class TestSpmdPlanes:
    def test_memory_spmd_bitwise_vs_pre_redesign(self, tiny_corpus):
        """The SPMD plane == the old launcher run_distributed loop."""
        from repro.api.session import (init_distributed_state,
                                       make_spmd_sweep)

        corp = tiny_corpus
        mesh_model, sweeps, seed = 2, 3, 0
        cfg = lda.LDAConfig(num_topics=8, vocab_size=corp.vocab_size,
                            block_tokens=256, num_shards=mesh_model)
        n_dev = jax.device_count()
        data = n_dev // mesh_model
        mesh = make_mesh((data, mesh_model), ("data", "model"))
        workers = data * mesh_model
        key = jax.random.PRNGKey(seed)
        st = init_distributed_state(corp, cfg, workers, key)
        sweep_fn = jax.jit(make_spmd_sweep(mesh, cfg, staleness=1))
        z, ndk, nwk_val, nk_val = st.z, st.ndk, st.nwk, st.nk
        for _ in range(sweeps):
            key, sub = jax.random.split(key)
            keys = jax.random.split(sub, workers)
            z, ndk, nwk_val, nk_val = sweep_fn(
                st.w, st.d, z, st.valid, st.doc_start, st.doc_len, ndk,
                nwk_val, nk_val, keys)

        job = api.LDAJob(corpus=corp, num_topics=8, block_tokens=256,
                         backend=api.SPMD, mesh_model=mesh_model,
                         staleness=1, sweeps=sweeps, seed=seed,
                         eval_every=0)
        res = api.Session(job, log_fn=_quiet).run()
        assert bool((res.nwk.value == nwk_val).all())
        assert bool((res.nk.value == nk_val).all())

    @staticmethod
    def _write_stream(corp, tmp_path, workers, want_divisible,
                      block_tokens=256):
        """Write ``corp`` as a stream whose shard count is (or is not)
        a multiple of ``workers``; shard packing is greedy, so probe a
        few shard sizes."""
        for i, tps in enumerate((512, 768, 1024, 1280, 1536, 1792)):
            if tps % block_tokens:
                continue
            path = str(tmp_path / f"s{i}")
            stream_mod.write_sharded(path, corp, tokens_per_shard=tps)
            reader = stream_mod.ShardedCorpusReader(path)
            if (reader.num_shards % workers == 0) == want_divisible:
                return path, reader
        pytest.skip("no probed shard geometry matched")

    @pytest.mark.parametrize("route_kw", [
        {},                                      # dense
        {"hot_words": 64},                       # hybrid
    ])
    def test_stream_spmd_conservation(self, tiny_corpus, tmp_path,
                                      route_kw):
        """Stream shards feed SPMD workers in groups; after any number of
        epochs the global PS counts equal the histogram of the persisted
        assignments exactly (exactly-once pushes through the mesh)."""
        corp = tiny_corpus
        path, reader = self._write_stream(corp, tmp_path,
                                          jax.device_count(), True)
        job = api.LDAJob(stream_dir=path, num_topics=8, block_tokens=256,
                         backend=api.SPMD, mesh_model=2, staleness=1,
                         epochs=2, seed=7, eval_every=1, **route_kw)
        res = api.Session(job, log_fn=_quiet).run()
        nwk_ref, nk_ref = stream_mod.rebuild_counts_from_stream(reader, 8)
        assert int(nk_ref.sum()) == corp.num_tokens
        assert np.array_equal(np.asarray(res.nwk.to_dense()), nwk_ref)
        assert np.array_equal(np.asarray(res.nk.value), nk_ref)
        assert len(res.history) >= 1

    def test_stream_spmd_shard_mismatch_actionable(self, tiny_corpus,
                                                   tmp_path):
        path, _ = self._write_stream(tiny_corpus, tmp_path,
                                     jax.device_count(), False)
        job = api.LDAJob(stream_dir=path, num_topics=8, block_tokens=256,
                         backend=api.SPMD, mesh_model=2, epochs=1,
                         eval_every=0)
        with pytest.raises(ValueError, match="re-shard"):
            api.Session(job, log_fn=_quiet).run()
