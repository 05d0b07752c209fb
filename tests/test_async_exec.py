"""Asynchronous pipelined executor tests (train/async_exec.py).

The correctness anchor is twofold (DESIGN.md section 7):

  * staleness-0 executor output is **bitwise identical** to the
    synchronous reference ``lightlda.sweep_blocked_ref`` -- the executor
    *is* the old schedule when nothing is in flight;
  * for any staleness bound / hot-word boundary / block geometry (any
    interleaving of pull and push events the schedule can produce), the
    conservation law holds: every count table equals the histogram of the
    assignments, and total token mass is preserved.

The hypothesis suite randomises corpora and schedules when hypothesis is
installed; fixed-seed parametrised tests cover the same invariants
everywhere else.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_lda_state
from repro import ps
from repro.core import lightlda as lda
from repro.data import corpus as corpus_mod
from repro.sharding.mesh import make_mesh
from repro.train import async_exec
from repro.train import loop as train_loop

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def _block_index(state, cfg, n_blocks):
    layout = state.nwk.layout
    rpb = layout.pad_rows // n_blocks
    assert rpb * n_blocks == layout.pad_rows
    idx, bval = lda.block_token_index(
        np.asarray(state.w), np.asarray(state.valid), rpb, layout)
    return jnp.asarray(idx), jnp.asarray(bval), rpb


def _assert_conserved(state, cfg, n_tokens):
    """sum(nwk) == sum(ndk) == sum(nk) == num_tokens, counts == histogram
    of z -- the paper's exactly-once push, observable."""
    assert int(state.nk.value.sum()) == n_tokens
    assert int(state.nwk.to_dense().sum()) == n_tokens
    assert int(state.ndk.sum()) == n_tokens
    nwk2, nk2, ndk2 = lda.rebuild_counts(
        state.w, state.d, state.z, state.valid, state.ndk.shape[0], cfg)
    assert bool((nwk2.value == state.nwk.value).all())
    assert bool((nk2.value == state.nk.value).all())
    assert bool((ndk2 == state.ndk).all())
    z = np.asarray(state.z)[np.asarray(state.valid)]
    assert z.min() >= 0 and z.max() < cfg.K


class TestEffectiveStaleness:
    def test_zero_is_zero(self):
        assert async_exec.effective_staleness(8, 0) == 0

    def test_rounds_down_to_divisor(self):
        # group s+1 must divide the block count
        assert async_exec.effective_staleness(8, 2) == 1   # 3 !| 8 -> 2 | 8
        assert async_exec.effective_staleness(8, 3) == 3
        assert async_exec.effective_staleness(12, 4) == 3  # 5 !| 12 -> 4 | 12
        assert async_exec.effective_staleness(6, 99) == 5  # capped at n-1


class TestStalenessZeroBitwise:
    """The acceptance anchor: s=0 executor == synchronous path, bitwise."""

    @pytest.mark.parametrize("hot_words", [None, 0, 37])
    def test_matches_sweep_blocked_ref(self, lda_state, hot_words):
        corp, cfg, state = lda_state()
        idx, bval, rpb = _block_index(state, cfg, n_blocks=6)
        key = jax.random.PRNGKey(7)
        ref = jax.jit(lambda s_, k: lda.sweep_blocked_ref(
            s_, k, cfg, idx, bval, rpb))(state, key)
        got = jax.jit(lambda s_, k: async_exec.pipelined_sweep(
            s_, k, cfg, idx, bval, rpb, staleness=0,
            route=ps.route_for(hot_words, cfg.V)))(state, key)
        assert bool((ref.z == got.z).all())
        assert bool((ref.nwk.value == got.nwk.value).all())
        assert bool((ref.nk.value == got.nk.value).all())
        assert bool((ref.ndk == got.ndk).all())

    def test_public_sweep_blocked_routes_through_executor(self, lda_state):
        """lightlda.sweep_blocked is the executor now; defaults unchanged."""
        corp, cfg, state = lda_state(seed=3)
        idx, bval, rpb = _block_index(state, cfg, n_blocks=4)
        key = jax.random.PRNGKey(11)
        ref = lda.sweep_blocked_ref(state, key, cfg, idx, bval, rpb)
        got = lda.sweep_blocked(state, key, cfg, idx, bval, rpb)
        assert bool((ref.z == got.z).all())
        assert bool((ref.nwk.value == got.nwk.value).all())

    def test_hybrid_split_never_changes_values(self, lda_state):
        """Dense-hot + sparse-cold is a traffic split, not a semantic one:
        identical results at any boundary (integer adds are exact)."""
        corp, cfg, state = lda_state(seed=5)
        idx, bval, rpb = _block_index(state, cfg, n_blocks=6)
        key = jax.random.PRNGKey(13)
        outs = [async_exec.pipelined_sweep(state, key, cfg, idx, bval, rpb,
                                           staleness=2,
                                           route=ps.route_for(h, cfg.V))
                for h in (None, 0, 1, 150, cfg.V)]
        for other in outs[1:]:
            assert bool((outs[0].z == other.z).all())
            assert bool((outs[0].nwk.value == other.nwk.value).all())
            assert bool((outs[0].ndk == other.ndk).all())

    def test_tiered_step_is_blocked_step(self, lda_state, tmp_path):
        """The tiered executor's per-block step is the blocked executor's
        group step: one model block, the pipelined index pinned to the
        tiered capacity, and one sweep of each agrees bitwise."""
        corp, cfg, state = lda_state(seed=8, num_shards=1)
        route = ps.route_for(37, cfg.V)
        tiered = state._replace(nwk=ps.tiered_matrix_from_dense(
            state.nwk.to_dense(), 16, str(tmp_path / "tier"), route=route))
        step, info = async_exec.make_tiered_executor(
            tiered, cfg, async_exec.ExecConfig(model_blocks=1, route=route),
            refresh_every=0)
        (cap,) = info["token_caps"]
        layout = state.nwk.layout
        idx, bval = lda.block_token_index(
            np.asarray(state.w), np.asarray(state.valid), layout.pad_rows,
            layout, cap=cap)
        key = jax.random.PRNGKey(19)
        ref = async_exec.pipelined_sweep(
            state, key, cfg, jnp.asarray(idx), jnp.asarray(bval),
            layout.pad_rows, staleness=0, route=route)
        got = step(tiered, key)
        assert bool((ref.z == got.z).all())
        assert bool((ref.nwk.to_dense() == got.nwk.to_dense()).all())
        assert bool((ref.nk.value == got.nk.value).all())
        assert bool((ref.ndk == got.ndk).all())


class TestConservation:
    @pytest.mark.parametrize("staleness,hot_words", [
        (0, None), (1, None), (2, 50), (5, 0), (3, 300),
    ])
    def test_blocked_executor(self, lda_state, staleness, hot_words):
        corp, cfg, state = lda_state()
        idx, bval, rpb = _block_index(state, cfg, n_blocks=6)
        key = jax.random.PRNGKey(1)
        for i in range(2):
            key, sub = jax.random.split(key)
            state = jax.jit(lambda s_, k: async_exec.pipelined_sweep(
                s_, k, cfg, idx, bval, rpb, staleness=staleness,
                route=ps.route_for(hot_words, cfg.V)))(state, sub)
            _assert_conserved(state, cfg, corp.num_tokens)

    @pytest.mark.parametrize("staleness,hot_words", [
        (1, None), (3, 64), (7, 0),
    ])
    def test_snapshot_executor(self, lda_state, staleness, hot_words):
        corp, cfg, state = lda_state(seed=2)
        key = jax.random.PRNGKey(2)
        for i in range(2):
            key, sub = jax.random.split(key)
            state = jax.jit(lambda s_, k: lda.sweep(
                s_, k, cfg, staleness=staleness,
                route=ps.route_for(hot_words, cfg.V)))(state, sub)
            _assert_conserved(state, cfg, corp.num_tokens)

    def test_staleness_converges_like_sync(self, lda_state):
        """The MH correction tolerates the stale proposals: perplexity
        after a stale-executor run lands near the synchronous run's."""
        from repro.core import perplexity as ppl

        corp, cfg, state = lda_state(seed=4, num_docs=200, vocab=400,
                                     k=10, num_shards=4)
        idx, bval, rpb = _block_index(state, cfg, n_blocks=4)

        def run(staleness):
            st, key = state, jax.random.PRNGKey(21)
            step = jax.jit(lambda s_, k: async_exec.pipelined_sweep(
                s_, k, cfg, idx, bval, rpb, staleness=staleness,
                route=ps.HybridRoute(hot_words=64)))
            for _ in range(20):
                key, sub = jax.random.split(key)
                st = step(st, sub)
            return float(ppl.training_perplexity(
                st.w, st.d, st.valid, st.ndk, st.nwk.to_dense(),
                st.nk.value, cfg.alpha, cfg.beta))

        p_sync, p_async = run(0), run(3)
        assert p_async < p_sync * 1.06, (p_sync, p_async)


class TestKernelPathEquality:
    def test_kernel_executor_matches_oracle_executor(self, lda_state):
        """The Pallas path (MH kernel + hot delta_push kernel + COO cold
        tail) through the pipelined executor is bit-identical to the jnp
        oracle path, staleness and hybrid split included."""
        corp, _, _ = lda_state(seed=6)
        outs = {}
        for uk in (False, True):
            cfg = lda.LDAConfig(num_topics=8, vocab_size=300,
                                block_tokens=512, num_shards=2,
                                use_kernels=uk)
            state = lda.init_state(jax.random.PRNGKey(0),
                                   jnp.asarray(corp.w), jnp.asarray(corp.d),
                                   corp.num_docs, cfg)
            idx, bval, rpb = _block_index(state, cfg, n_blocks=4)
            outs[uk] = async_exec.pipelined_sweep(
                state, jax.random.PRNGKey(17), cfg, idx, bval, rpb,
                staleness=1, route=ps.HybridRoute(hot_words=80))
        assert bool((outs[False].z == outs[True].z).all())
        assert bool((outs[False].nwk.value == outs[True].nwk.value).all())
        assert bool((outs[False].ndk == outs[True].ndk).all())


class TestMakeExecutor:
    def test_blocked_info_and_group_cap(self, lda_state):
        corp, cfg, state = lda_state(num_shards=4)
        step, info = async_exec.make_executor(
            state, cfg, async_exec.ExecConfig(staleness=1, model_blocks=4))
        assert info["mode"] == "blocked"
        assert info["staleness"] == 1 and info["group"] == 2
        st = step(state, jax.random.PRNGKey(0))
        _assert_conserved(st, cfg, corp.num_tokens)

    def test_snapshot_mode(self, lda_state):
        corp, cfg, state = lda_state()
        step, info = async_exec.make_executor(
            state, cfg, async_exec.ExecConfig(staleness=2))
        assert info["mode"] == "snapshot"
        st = step(state, jax.random.PRNGKey(0))
        _assert_conserved(st, cfg, corp.num_tokens)

    def test_fit_lda_host_loop(self, lda_state):
        corp, cfg, state = lda_state()
        state, history, info = train_loop.fit_lda(
            state, jax.random.PRNGKey(5), cfg,
            async_exec.ExecConfig(staleness=1, hot_words=64,
                                  model_blocks=6),
            sweeps=2, eval_every=1, log_fn=lambda *_: None)
        assert len(history) == 2
        assert all(h["tokens_per_s"] > 0 for h in history)
        _assert_conserved(state, cfg, corp.num_tokens)


@pytest.mark.multidevice(2)
class TestDistributedExecutor:
    """In-process SPMD executor: exercised by the forced-4-device CI
    matrix entry; skipped on plain single-device hosts."""

    def test_spmd_sweep_with_staleness_conserves(self):
        from repro.launch import lda as launch_lda

        model = 2
        data = jax.device_count() // model
        mesh = make_mesh((data, model), ("data", "model"))
        workers = data * model
        corp = corpus_mod.generate_lda_corpus(
            seed=0, num_docs=80, mean_doc_len=30, vocab_size=200,
            num_topics=6)
        cfg = lda.LDAConfig(num_topics=8, vocab_size=200, block_tokens=256,
                            num_shards=model)
        st = launch_lda.init_distributed_state(
            corp, cfg, workers, jax.random.PRNGKey(0))
        w, valid = st.w, st.valid

        sweep_fn = jax.jit(launch_lda.make_spmd_sweep(
            mesh, cfg, staleness=1, route=ps.HybridRoute(hot_words=32)))
        keys = jax.random.split(jax.random.PRNGKey(1), workers)
        z2, ndk2, nwk_val2, nk2 = sweep_fn(*st, keys)
        n_tokens = int(valid.sum())
        one = valid.reshape(-1).astype(jnp.int32)
        assert int(nk2.sum()) == n_tokens
        full = ps.PSClient.create(num_shards=model) \
            .wrap_matrix(nwk_val2, cfg.V).to_dense()
        assert int(full.sum()) == n_tokens
        assert int(ndk2.sum()) == n_tokens
        # counts == histogram of the new assignments, globally
        rebuilt = jnp.zeros((cfg.V, cfg.K), jnp.int32).at[
            w.reshape(-1), z2.reshape(-1)].add(one)
        assert bool((rebuilt == full).all())


if HAVE_HYPOTHESIS:
    @given(seed=st.integers(0, 10_000),
           num_docs=st.integers(20, 60),
           vocab=st.integers(40, 200),
           k=st.integers(3, 12),
           num_shards=st.integers(1, 4),
           n_blocks_pick=st.integers(0, 3),
           staleness=st.integers(0, 9),
           hot_frac=st.floats(0.0, 1.0))
    @settings(max_examples=12, deadline=None)
    def test_mass_conserved_any_interleaving(seed, num_docs, vocab, k,
                                             num_shards, n_blocks_pick,
                                             staleness, hot_frac):
        """Random corpora x random schedules: whatever interleaving of
        pull/push events the (staleness, hot-word, geometry) draw induces,
        token mass is conserved and counts match the z histogram."""
        corp, cfg, state = make_lda_state(
            seed=seed, num_docs=num_docs, vocab=vocab, k=k,
            num_shards=num_shards, block_tokens=256)
        layout = state.nwk.layout
        divisors = [b for b in (2, 3, 4, 6, 8) if layout.pad_rows % b == 0]
        if not divisors:
            divisors = [1]
        n_blocks = divisors[n_blocks_pick % len(divisors)]
        idx, bval, rpb = _block_index(state, cfg, n_blocks)
        hot_words = int(hot_frac * cfg.V)
        state = async_exec.pipelined_sweep(
            state, jax.random.PRNGKey(seed + 1), cfg, idx, bval, rpb,
            staleness=staleness, route=ps.route_for(hot_words, cfg.V))
        _assert_conserved(state, cfg, corp.num_tokens)

    @given(seed=st.integers(0, 10_000), staleness=st.integers(0, 6))
    @settings(max_examples=6, deadline=None)
    def test_staleness_zero_bitwise_hypothesis(seed, staleness):
        """s=0 must stay bitwise-identical for any corpus draw; s>0 must
        at least preserve the conservation law on the same draw."""
        corp, cfg, state = make_lda_state(seed=seed, num_docs=50,
                                          vocab=120, k=6, num_shards=3,
                                          block_tokens=256)
        idx, bval, rpb = _block_index(state, cfg, n_blocks=4)
        key = jax.random.PRNGKey(seed)
        ref = lda.sweep_blocked_ref(state, key, cfg, idx, bval, rpb)
        got = async_exec.pipelined_sweep(state, key, cfg, idx, bval, rpb,
                                         staleness=0)
        assert bool((ref.z == got.z).all())
        assert bool((ref.nwk.value == got.nwk.value).all())
        stale = async_exec.pipelined_sweep(state, key, cfg, idx, bval,
                                           rpb, staleness=staleness)
        _assert_conserved(stale, cfg, corp.num_tokens)
else:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_mass_conserved_any_interleaving():
        pass

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_staleness_zero_bitwise_hypothesis():
        pass
