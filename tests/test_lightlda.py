"""LightLDA sampler: invariants, convergence, recovery (paper section 3)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lightlda as lda
from repro.core import perplexity as ppl
from repro.data import corpus as corpus_mod
from repro.train import checkpoint


@pytest.fixture(scope="module")
def small_setup():
    corp = corpus_mod.generate_lda_corpus(
        seed=0, num_docs=200, mean_doc_len=50, vocab_size=400, num_topics=8)
    cfg = lda.LDAConfig(num_topics=10, vocab_size=400, block_tokens=1024)
    key = jax.random.PRNGKey(0)
    state = lda.init_state(key, jnp.asarray(corp.w), jnp.asarray(corp.d),
                           corp.num_docs, cfg)
    return corp, cfg, state


def _train_ppl(state, cfg):
    return float(ppl.training_perplexity(
        state.w, state.d, state.valid, state.ndk, state.nwk.to_dense(),
        state.nk.value, cfg.alpha, cfg.beta))


def _check_invariants(state, cfg, n_tokens):
    """Counts always equal the histogram of assignments (the sampler's
    conservation law)."""
    assert int(state.nk.value.sum()) == n_tokens
    assert int(state.nwk.to_dense().sum()) == n_tokens
    assert int(state.ndk.sum()) == n_tokens
    assert bool((state.nwk.to_dense() >= 0).all())
    assert bool((state.ndk >= 0).all())
    assert bool((state.nk.value >= 0).all())
    # counts rebuilt from z match the incremental counts exactly
    nwk2, nk2, ndk2 = lda.rebuild_counts(
        state.w, state.d, state.z, state.valid, state.ndk.shape[0], cfg)
    assert bool((nwk2.value == state.nwk.value).all())
    assert bool((nk2.value == state.nk.value).all())
    assert bool((ndk2 == state.ndk).all())


class TestSweep:
    def test_invariants_over_sweeps(self, small_setup):
        corp, cfg, state = small_setup
        key = jax.random.PRNGKey(1)
        for i in range(3):
            key, sub = jax.random.split(key)
            state = jax.jit(lambda s, k: lda.sweep(s, k, cfg))(state, sub)
            _check_invariants(state, cfg, corp.num_tokens)

    def test_perplexity_decreases(self, small_setup):
        corp, cfg, state = small_setup
        p0 = _train_ppl(state, cfg)
        state = lda.train(state, jax.random.PRNGKey(2), cfg, 30)
        p1 = _train_ppl(state, cfg)
        assert p1 < p0 * 0.98, (p0, p1)

    def test_z_stays_in_range(self, small_setup):
        corp, cfg, state = small_setup
        state = lda.train(state, jax.random.PRNGKey(3), cfg, 2)
        z = np.asarray(state.z)
        assert z.min() >= 0 and z.max() < cfg.K

    def test_block_size_invariance_statistical(self):
        """Different staleness windows (block sizes) converge to comparable
        perplexity -- the paper's asynchrony-tolerance claim."""
        corp = corpus_mod.generate_lda_corpus(
            seed=1, num_docs=150, mean_doc_len=40, vocab_size=300,
            num_topics=6)
        outs = []
        for bt in (512, 4096):
            cfg = lda.LDAConfig(num_topics=8, vocab_size=300, block_tokens=bt)
            st = lda.init_state(jax.random.PRNGKey(0), jnp.asarray(corp.w),
                                jnp.asarray(corp.d), corp.num_docs, cfg)
            st = lda.train(st, jax.random.PRNGKey(5), cfg, 25)
            outs.append(_train_ppl(st, cfg))
        assert abs(outs[0] - outs[1]) / min(outs) < 0.05, outs


class TestRecovery:
    def test_checkpoint_rebuild(self, small_setup, tmp_path):
        """Paper section 3.5: checkpoint z, rebuild counts, continue."""
        corp, cfg, state = small_setup
        state = lda.train(state, jax.random.PRNGKey(4), cfg, 3)
        path = str(tmp_path / "lda.npz")
        checkpoint.save_lda(path, state)
        restored = checkpoint.restore_lda(path, cfg, state.ndk.shape[0])
        assert bool((restored.z == state.z).all())
        assert bool((restored.nwk.value == state.nwk.value).all())
        assert bool((restored.nk.value == state.nk.value).all())
        # and it can continue training
        cont = lda.train(restored, jax.random.PRNGKey(6), cfg, 2)
        _check_invariants(cont, cfg, corp.num_tokens)


class TestHeldout:
    def test_heldout_perplexity_beats_uniform(self, small_setup):
        corp, cfg, state = small_setup
        state = lda.train(state, jax.random.PRNGKey(7), cfg, 30)
        phi = ppl.phi_from_counts(state.nwk.to_dense().astype(jnp.float32),
                                  state.nk.value.astype(jnp.float32),
                                  cfg.beta)
        held = corpus_mod.generate_lda_corpus(
            seed=9, num_docs=40, mean_doc_len=50, vocab_size=400,
            num_topics=8)
        w, d = jnp.asarray(held.w), jnp.asarray(held.d)
        coin = np.random.default_rng(0).random(held.num_tokens) < 0.5
        p = float(ppl.heldout_perplexity(
            w, d, jnp.asarray(coin), w, d, jnp.asarray(~coin),
            phi, held.num_docs, cfg.alpha))
        assert p < 400  # uniform model would give exactly V = 400

    def test_packed_heldout_matches_flat(self, small_setup):
        """The [D, L] document-major evaluation computes the flat
        gather/scatter-add estimator, to f32 summation-order tolerance."""
        corp, cfg, state = small_setup
        state = lda.train(state, jax.random.PRNGKey(7), cfg, 5)
        phi = ppl.phi_from_counts(state.nwk.to_dense().astype(jnp.float32),
                                  state.nk.value.astype(jnp.float32),
                                  cfg.beta)
        held = corpus_mod.generate_lda_corpus(
            seed=9, num_docs=40, mean_doc_len=50, vocab_size=400,
            num_topics=8)
        w, d, fold, ev = corpus_mod.fold_eval_split(held)
        w, d = jnp.asarray(w), jnp.asarray(d)
        flat = float(ppl.heldout_perplexity(
            w, d, jnp.asarray(fold), w, d, jnp.asarray(ev), phi,
            held.num_docs, cfg.alpha))
        packed = corpus_mod.packed_fold_eval_split(held)
        assert packed[0].shape == (held.num_docs, held.doc_len.max())
        assert packed[1].sum() == fold.sum() and packed[2].sum() == ev.sum()
        got = float(ppl.heldout_perplexity_packed(
            *map(jnp.asarray, packed), phi, cfg.alpha))
        np.testing.assert_allclose(got, flat, rtol=1e-5)
