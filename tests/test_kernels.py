"""Per-kernel shape/dtype sweeps asserting allclose against ref.py oracles
(interpret=True executes the Pallas kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lightlda as lda
from repro.kernels import ops as kops
from repro.kernels import ref as kref


def _mh_inputs(key, b, k, v, mh_steps):
    ks = jax.random.split(key, 11)
    return dict(
        z0=jax.random.randint(ks[0], (b,), 0, k, dtype=jnp.int32),
        nwk_rows=jax.random.randint(ks[1], (b, k), 0, 100).astype(jnp.int32),
        ndk_rows=jax.random.randint(ks[2], (b, k), 0, 30).astype(jnp.int32),
        nk=jax.random.randint(ks[3], (k,), 50, 10_000).astype(jnp.int32),
        aprob_rows=jax.random.uniform(ks[4], (b, k)),
        aalias_rows=jax.random.randint(ks[5], (b, k), 0, k, dtype=jnp.int32),
        rng=lda.MHRandoms(
            u_word=jax.random.uniform(ks[6], (mh_steps, b)),
            u_waccept=jax.random.uniform(ks[7], (mh_steps, b)),
            z_doc=jax.random.randint(ks[8], (mh_steps, b), 0, k,
                                     dtype=jnp.int32),
            u_daccept=jax.random.uniform(ks[9], (mh_steps, b))))


class TestMHSampleKernel:
    @pytest.mark.parametrize("b,k,v,mh", [
        (64, 8, 50, 1),
        (300, 17, 211, 2),
        (1000, 64, 997, 3),
        (257, 128, 64, 2),     # K already lane-aligned
        (1024, 130, 301, 2),   # K just over one lane group
    ])
    def test_matches_oracle(self, b, k, v, mh):
        cfg = lda.LDAConfig(num_topics=k, vocab_size=v, mh_steps=mh)
        inp = _mh_inputs(jax.random.PRNGKey(b * k + mh), b, k, v, mh)
        rng = inp.pop("rng")
        ref = kref.mh_sample_ref(rng, cfg=cfg, **inp)
        got = kops.mh_sample(rng, cfg=cfg, tile_tokens=256, **inp)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))

    def test_float_count_inputs(self):
        """Counts may arrive as f32 (from dense deltas); identical result."""
        cfg = lda.LDAConfig(num_topics=12, vocab_size=99, mh_steps=2)
        inp = _mh_inputs(jax.random.PRNGKey(0), 128, 12, 99, 2)
        rng = inp.pop("rng")
        ref = kref.mh_sample_ref(rng, cfg=cfg, **inp)
        inp_f = dict(inp, nwk_rows=inp["nwk_rows"].astype(jnp.float32),
                     ndk_rows=inp["ndk_rows"].astype(jnp.float32),
                     nk=inp["nk"].astype(jnp.float32))
        got = kops.mh_sample(rng, cfg=cfg, tile_tokens=64, **inp_f)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


class TestDeltaPushKernel:
    @pytest.mark.parametrize("b,v,k", [
        (100, 50, 8),
        (1000, 513, 40),
        (4096, 2048, 100),
        (77, 128, 128),
    ])
    def test_matches_scatter(self, b, v, k):
        key = jax.random.PRNGKey(b + v + k)
        ks = jax.random.split(key, 3)
        w = jax.random.randint(ks[0], (b,), 0, v, dtype=jnp.int32)
        zo = jax.random.randint(ks[1], (b,), 0, k, dtype=jnp.int32)
        zn = jax.random.randint(ks[2], (b,), 0, k, dtype=jnp.int32)
        chg = zo != zn
        ref = kref.delta_push_ref(w, zo, zn, chg, v, k)
        got = kops.delta_push(w, zo, zn, chg, v, k,
                              tile_tokens=256, tile_vocab=128)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
        # conservation: every changed token moves exactly one count
        assert int(np.asarray(got).sum()) == 0

    def test_no_changes_is_zero(self):
        w = jnp.arange(64, dtype=jnp.int32) % 10
        z = jnp.zeros(64, jnp.int32)
        out = kops.delta_push(w, z, z, z != z, 10, 5)
        assert int(jnp.abs(out).sum()) == 0


class TestDeltaApplyCooKernel:
    """Sparse cold-tail application kernel vs the scatter-add oracle."""

    @pytest.mark.parametrize("m,v,k", [
        (64, 50, 8),
        (700, 513, 40),
        (2048, 1024, 100),
        (130, 128, 128),
    ])
    def test_matches_scatter(self, m, v, k):
        key = jax.random.PRNGKey(m + v + k)
        ks = jax.random.split(key, 4)
        rows = jax.random.randint(ks[0], (m,), 0, v, dtype=jnp.int32)
        cols = jax.random.randint(ks[1], (m,), 0, k, dtype=jnp.int32)
        vals = jax.random.randint(ks[2], (m,), -1, 2, dtype=jnp.int32)
        ref = kref.delta_apply_coo_ref(rows, cols, vals, v, k)
        got = kops.delta_apply_coo(rows, cols, vals, v, k,
                                   tile_tokens=256, tile_vocab=128)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))

    def test_zero_vals_are_padding(self):
        rows = jnp.zeros((32,), jnp.int32)
        cols = jnp.zeros((32,), jnp.int32)
        vals = jnp.zeros((32,), jnp.int32)
        out = kops.delta_apply_coo(rows, cols, vals, 10, 6)
        assert int(jnp.abs(out).sum()) == 0


class TestHybridDeltaParity:
    """Hybrid hot-dense + cold-sparse path == the dense scatter oracle
    (ref.delta_push_ref) at every hot/cold boundary, including the
    boundary row itself and the all-cold / all-hot edge cases."""

    def _batch(self, b, v, k, seed, include_boundary=None):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        w = jax.random.randint(ks[0], (b,), 0, v, dtype=jnp.int32)
        if include_boundary is not None:
            # force tokens exactly on both sides of the hot/cold boundary
            w = (w.at[0].set(max(include_boundary - 1, 0))
                 .at[1].set(min(include_boundary, v - 1)))
        zo = jax.random.randint(ks[1], (b,), 0, k, dtype=jnp.int32)
        zn = jax.random.randint(ks[2], (b,), 0, k, dtype=jnp.int32)
        return w, zo, zn, zo != zn

    @pytest.mark.parametrize("use_kernel", [False, True])
    @pytest.mark.parametrize("hot", [0, 1, 64, 199, 200])  # 0=all-cold, V=all-hot
    def test_matches_dense_oracle(self, hot, use_kernel):
        from repro import ps
        from repro.train.async_exec import topic_delta

        v, k, b = 200, 12, 512
        w, zo, zn, chg = self._batch(b, v, k, seed=hot + 1,
                                     include_boundary=max(hot, 1))
        ref = kref.delta_push_ref(w, zo, zn, chg, v, k)
        d_nwk = ps.route_for(hot, v).block_delta(
            ps.Reassign(w, w, zo, zn, chg), v, k, use_kernels=use_kernel,
            prefix_rows=True)
        d_nk = topic_delta(zo, zn, chg.astype(jnp.int32), k)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(d_nwk))
        # the split must also conserve: every changed token moves one count
        assert int(np.asarray(d_nwk).sum()) == 0
        assert int(np.asarray(d_nk).sum()) == 0

    def test_cold_coo_through_push_coo(self):
        """The executor's actual cold path: COO emitted by cold_coo and
        applied via the client's ``MatrixHandle.push_coo`` equals the
        dense push of the same delta, on both the scatter and the kernel
        route."""
        from repro import ps
        from repro.kernels.delta_push import cold_coo, split_hot_cold

        v, k, b, hot = 150, 10, 256, 40
        w, zo, zn, chg = self._batch(b, v, k, seed=9, include_boundary=hot)
        m = ps.PSClient.create(num_shards=3).matrix_from_dense(
            jax.random.randint(jax.random.PRNGKey(1), (v, k), 5, 50))
        _, cold = split_hot_cold(w, chg, hot)
        rows, cols, vals = cold_coo(w, zo, zn, cold)
        amt = cold.astype(jnp.int32)
        dense_delta = (jnp.zeros((v, k), jnp.int32)
                       .at[w, zo].add(-amt).at[w, zn].add(amt))
        want = m.push_dense(dense_delta).to_dense()
        got_scatter = m.push_coo(rows, cols, vals).to_dense()
        got_kernel = m.push_coo(rows, cols, vals, use_kernel=True).to_dense()
        np.testing.assert_array_equal(np.asarray(want),
                                      np.asarray(got_scatter))
        np.testing.assert_array_equal(np.asarray(want),
                                      np.asarray(got_kernel))


def _alias_weights(kind, v, k, key):
    """[v, k] weight rows of one shape of distribution."""
    ks = jax.random.split(key, 3)
    if kind == "counts":
        # Poisson counts, plus rows with weights exactly at the mean
        # (q == 1 joins the large stack in the kernel and the oracle)
        cnt = jax.random.poisson(ks[0], 0.5, (v, k)).astype(jnp.float32)
        at_mean = jnp.ones((3, k)).at[:, 0].set(2.0).at[:, 1:3].set(0.5)
        return jnp.concatenate([cnt + 0.01, at_mean[:, :k]])
    if kind == "one_heavy":
        light = jax.random.uniform(ks[0], (v, k), minval=0.5, maxval=1.0)
        heavy = 10.0 * k * jax.random.uniform(ks[1], (v,), minval=1.0,
                                              maxval=100.0)
        col = jnp.arange(v) % k
        return light.at[jnp.arange(v), col].set(heavy)
    if kind == "gamma":
        return jax.random.gamma(ks[0], 0.05, (v, k))
    if kind == "at_mean":
        return jnp.ones((v, k))
    if kind == "one_nonzero":
        col = jax.random.randint(ks[0], (v,), 0, k)
        val = jax.random.uniform(ks[1], (v,), minval=0.1, maxval=10.0)
        return jnp.zeros((v, k)).at[jnp.arange(v), col].set(val)
    if kind == "mixed":
        kinds = ("counts", "one_heavy", "gamma", "at_mean", "one_nonzero")
        parts = [_alias_weights(kd, v, k, kk)
                 for kd, kk in zip(kinds, jax.random.split(ks[2], len(kinds)))]
        return jnp.concatenate(parts)
    raise ValueError(kind)


class TestAliasBuildKernel:
    @pytest.mark.parametrize("v,k", [
        (16, 8),
        (64, 33),
        (100, 64),
        (64, 128),     # K already a lane multiple
        (37, 130),     # K just over a lane group, ragged V
    ])
    def test_pmf_matches_oracle(self, v, k):
        """The kernel's alias table induces the same pmf as Vose (alias
        assignments are permutation-dependent; the distribution is not)."""
        from repro.core import alias as alias_mod
        key = jax.random.PRNGKey(v * k)
        w = jax.random.uniform(key, (v, k)) ** 2 + 1e-5
        got = kops.alias_build(w, tile_rows=32)
        ref = kref.alias_build_ref(w)
        pmf_got = np.asarray(alias_mod.alias_pmf(got))
        pmf_ref = np.asarray(alias_mod.alias_pmf(ref))
        np.testing.assert_allclose(pmf_got, pmf_ref, rtol=3e-5, atol=3e-6)
        # alias targets must never point at padded columns
        assert int(np.asarray(got.alias).max()) < k

    @pytest.mark.parametrize("v,k,kind", [
        pytest.param(64, 33, "counts", id="64-33"),
        pytest.param(37, 130, "counts", id="37-130"),
        # one donor feeds every other column: all K-1 steps are active
        pytest.param(16, 33, "one_heavy", id="one_heavy-16-33"),
        pytest.param(16, 200, "one_heavy", id="one_heavy-16-200"),
        pytest.param(16, 200, "gamma", id="gamma-16-200"),
        pytest.param(16, 200, "at_mean", id="at_mean-16-200"),
        pytest.param(16, 200, "one_nonzero", id="one_nonzero-16-200"),
        pytest.param(9, 1, "counts", id="counts-9-1"),
        pytest.param(9, 1, "one_nonzero", id="one_nonzero-9-1"),
        # the benchmark cells' K, every kind of row in one build
        pytest.param(10, 1024, "mixed", id="mixed-10-1024"),
    ])
    def test_bitwise_matches_oracle(self, v, k, kind):
        """Same prob and alias arrays as the jnp Vose build, so a kernel
        sweep can use it and stay bit-identical.  The kernel stops its
        retirement loop after K-1 steps, the oracle runs 2K: rows whose
        last active step is the (K-1)-th (one heavy donor, one nonzero
        weight) hold it to that bound."""
        w = _alias_weights(kind, v, k, jax.random.PRNGKey(v + k))
        got = kops.alias_build(w, tile_rows=32)
        ref = kref.alias_build_ref(w)
        np.testing.assert_array_equal(np.asarray(got.prob),
                                      np.asarray(ref.prob))
        np.testing.assert_array_equal(np.asarray(got.alias),
                                      np.asarray(ref.alias))

    def test_uniform_row(self):
        from repro.core import alias as alias_mod
        w = jnp.ones((4, 10))
        got = kops.alias_build(w)
        pmf = np.asarray(alias_mod.alias_pmf(got))
        np.testing.assert_allclose(pmf, 0.1, rtol=1e-6)


class TestKernelSweepEquality:
    def test_full_sweep_kernel_vs_oracle(self):
        """The kernel path must be bit-identical through a whole Gibbs
        sweep, not just per-call (integration of mh_sample + delta_push)."""
        from repro.data import corpus as corpus_mod
        corp = corpus_mod.generate_lda_corpus(
            seed=3, num_docs=50, mean_doc_len=30, vocab_size=150,
            num_topics=6)
        outs = {}
        for uk in (False, True):
            cfg = lda.LDAConfig(num_topics=6, vocab_size=150,
                                block_tokens=512, use_kernels=uk)
            st = lda.init_state(jax.random.PRNGKey(0), jnp.asarray(corp.w),
                                jnp.asarray(corp.d), corp.num_docs, cfg)
            st = jax.jit(lambda s, k: lda.sweep(s, k, cfg))(
                st, jax.random.PRNGKey(11))
            outs[uk] = st
        assert bool((outs[False].z == outs[True].z).all())
        assert bool((outs[False].nwk.value == outs[True].nwk.value).all())
        assert bool((outs[False].ndk == outs[True].ndk).all())
