"""EM and Online-VB baselines (paper section 4 comparison set)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lda_em as em
from repro.core import lda_online as ov
from repro.core import lightlda as lda
from repro.core import perplexity as ppl
from repro.data import corpus as corpus_mod


@pytest.fixture(scope="module")
def corp():
    return corpus_mod.generate_lda_corpus(
        seed=0, num_docs=200, mean_doc_len=50, vocab_size=300, num_topics=8)


class TestEM:
    def test_responsibilities_normalised(self, corp):
        cfg = em.EMConfig(num_topics=10, vocab_size=300)
        w, d = jnp.asarray(corp.w), jnp.asarray(corp.d)
        valid = jnp.ones(corp.num_tokens, bool)
        st = em.init_state(jax.random.PRNGKey(0), w, d, valid,
                           corp.num_docs, cfg)
        st = em.em_iteration(st, w, d, valid, corp.num_docs, cfg)
        sums = np.asarray(st.gamma.sum(-1))
        np.testing.assert_allclose(sums, 1.0, atol=1e-4)
        # expected counts conserve token mass
        assert abs(float(st.nk.sum()) - corp.num_tokens) < 1.0

    def test_perplexity_decreases(self, corp):
        cfg = em.EMConfig(num_topics=10, vocab_size=300)
        w, d = jnp.asarray(corp.w), jnp.asarray(corp.d)
        valid = jnp.ones(corp.num_tokens, bool)
        st = em.init_state(jax.random.PRNGKey(0), w, d, valid,
                           corp.num_docs, cfg)

        def p(st):
            return float(ppl.training_perplexity(
                w, d, valid, st.ndk, st.nwk, st.nk, cfg.alpha, cfg.beta))

        p0 = p(st)
        st = em.train(st, w, d, valid, corp.num_docs, cfg, 20)
        assert p(st) < p0 * 0.9

    def test_shuffle_bytes_model(self, corp):
        cfg = em.EMConfig(num_topics=20, vocab_size=300)
        b = em.shuffle_bytes_per_iter(corp.num_tokens, cfg)
        assert b == 2 * corp.num_tokens * 20 * 4


class TestOnline:
    def test_perplexity_decreases(self, corp):
        cfg = ov.OnlineConfig(num_topics=10, vocab_size=300, batch_docs=32)
        st = ov.init_state(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(0)
        w, d = jnp.asarray(corp.w), jnp.asarray(corp.d)
        valid = jnp.ones(corp.num_tokens, bool)

        def p(st):
            phi = ov.phi_from_state(st)
            theta = ppl.fold_in_theta(w, d, valid, phi, corp.num_docs,
                                      cfg.alpha)
            ll = ppl.log_likelihood(w, d, valid, theta, phi, corp.num_docs)
            return float(jnp.exp(-ll / corp.num_tokens))

        p0 = p(st)
        for _ in range(30):
            docs = rng.choice(corp.num_docs, cfg.batch_docs, replace=False)
            dw = jnp.asarray(corpus_mod.doc_term_matrix(corp, docs))
            st = ov.online_step(st, dw, jnp.ones(cfg.batch_docs),
                                corp.num_docs, cfg)
        p1 = p(st)
        assert p1 < p0 * 0.9, (p0, p1)


class TestThreeWayComparison:
    def test_comparable_quality(self, corp):
        """Paper Table 1's central claim: the three algorithms reach
        *roughly equal* perplexity on the same corpus."""
        k = 10
        w, d = jnp.asarray(corp.w), jnp.asarray(corp.d)
        valid = jnp.ones(corp.num_tokens, bool)

        lcfg = lda.LDAConfig(num_topics=k, vocab_size=300, block_tokens=2048)
        ls = lda.init_state(jax.random.PRNGKey(0), w, d, corp.num_docs, lcfg)
        ls = lda.train(ls, jax.random.PRNGKey(1), lcfg, 40)
        p_light = float(ppl.training_perplexity(
            ls.w, ls.d, ls.valid, ls.ndk, ls.nwk.to_dense(), ls.nk.value,
            lcfg.alpha, lcfg.beta))

        ecfg = em.EMConfig(num_topics=k, vocab_size=300)
        es = em.init_state(jax.random.PRNGKey(0), w, d, valid,
                           corp.num_docs, ecfg)
        es = em.train(es, w, d, valid, corp.num_docs, ecfg, 40)
        p_em = float(ppl.training_perplexity(
            w, d, valid, es.ndk, es.nwk, es.nk, ecfg.alpha, ecfg.beta))

        # same ballpark (paper: within ~10% of each other across Table 1)
        assert abs(p_light - p_em) / min(p_light, p_em) < 0.15, \
            (p_light, p_em)


@pytest.mark.parametrize("chunk", [128, 1000])
def test_log_likelihood_chunked_matches_whole(corp, chunk):
    """Scoring tokens a chunk at a time (what keeps a 10^8-token corpus
    off [N, K] temporaries) sums the same log-likelihood, to f32
    summation-order tolerance."""
    k = 8
    theta = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(0),
                                             (corp.num_docs, k)))
    phi = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(1),
                                           (corp.vocab_size, k)), axis=0)
    w, d = jnp.asarray(corp.w), jnp.asarray(corp.d)
    valid = jnp.arange(corp.num_tokens) % 7 != 0
    whole = ppl.log_likelihood(w, d, valid, theta, phi, corp.num_docs,
                               chunk=corp.num_tokens)
    chunked = ppl.log_likelihood(w, d, valid, theta, phi, corp.num_docs,
                                 chunk=chunk)
    assert corp.num_tokens > chunk
    np.testing.assert_allclose(float(chunked), float(whole), rtol=1e-5)
