"""Corpus pipeline: Zipf statistics (paper fig. 4), frequency ordering
(section 3.2), shard balance -- plus edge-case and hypothesis property
tests for ``reindex`` / ``shard_tokens`` / ``train_heldout_split``
(ISSUE 4 satellite: these caught the empty-shard offsets bug where
``doc_start`` had a phantom entry while ``doc_len`` was empty, and empty
shards skipped block padding entirely)."""
import numpy as np
import pytest

from repro.data import corpus as corpus_mod
from repro.data.lm_data import LMDataConfig, MarkovZipfSource, token_frequencies

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


@pytest.fixture(scope="module")
def corp():
    return corpus_mod.generate_lda_corpus(
        seed=0, num_docs=500, mean_doc_len=80, vocab_size=2000, num_topics=10)


def _assert_corpus_consistent(c):
    """Structural invariants every Corpus must satisfy."""
    assert c.doc_start.shape == c.doc_len.shape
    assert int(c.doc_len.sum()) == c.num_tokens
    if c.num_docs:
        assert c.doc_start[0] == 0
        assert (c.doc_start[1:] == c.doc_start[:-1] + c.doc_len[:-1]).all()
    # frequency-ordered vocabulary (paper section 3.2)
    assert (c.word_freq[:-1] >= c.word_freq[1:]).all()
    assert np.array_equal(np.bincount(c.w, minlength=c.vocab_size),
                          c.word_freq)


class TestZipf:
    def test_frequency_ordered(self, corp):
        f = corp.word_freq
        assert (f[:-1] >= f[1:]).all()
        counts = np.bincount(corp.w, minlength=corp.vocab_size)
        assert np.array_equal(counts, f)

    def test_zipf_slope(self, corp):
        """log-freq vs log-rank is near-linear with slope ~ -1 (fig. 4)."""
        f = corp.word_freq[:200].astype(float)
        ranks = np.arange(1, 201)
        mask = f > 0
        slope = np.polyfit(np.log(ranks[mask]), np.log(f[mask]), 1)[0]
        assert -1.6 < slope < -0.6, slope

    def test_doc_offsets(self, corp):
        assert corp.doc_start[0] == 0
        assert (corp.doc_start[1:] ==
                corp.doc_start[:-1] + corp.doc_len[:-1]).all()
        assert corp.doc_start[-1] + corp.doc_len[-1] == corp.num_tokens
        # tokens grouped by doc
        assert (np.diff(corp.d) >= 0).all()

    def test_subset_fraction(self, corp):
        sub = corp.subset(0.1)
        assert 0.05 < sub.num_tokens / corp.num_tokens < 0.2


class TestSharding:
    def test_shard_token_balance(self, corp):
        shards = corpus_mod.shard_tokens(corp, 8, block_tokens=256)
        loads = [int(s[2].sum()) for s in shards]  # valid counts
        assert sum(loads) == corp.num_tokens
        assert max(loads) / (sum(loads) / 8) < 1.1  # greedy LPT balance
        for w, d, valid, ds, dl in shards:
            assert len(w) % 256 == 0
            n = int(valid.sum())
            assert (w[:n] < corp.vocab_size).all()
            assert int(dl.sum()) == n

    def test_heldout_split_shares_vocab(self, corp):
        train, held = corpus_mod.train_heldout_split(corp, 0.2)
        assert train.vocab_size == held.vocab_size == corp.vocab_size
        assert train.num_tokens + held.num_tokens == corp.num_tokens


def _loop_lda_corpus(seed, num_docs, mean_doc_len, vocab_size, num_topics,
                     doc_block, zipf_exponent=1.05, doc_topic_alpha=0.08,
                     topic_concentration=2000.0):
    """Token-by-token reference for ``generate_lda_corpus``: the same
    draws from the same stream, each inverted against its own row's CDF."""
    rng = np.random.default_rng(seed)
    base = 1.0 / np.arange(1, vocab_size + 1) ** zipf_exponent
    base /= base.sum()
    phi = rng.dirichlet(base * topic_concentration, size=num_topics)
    doc_lens = np.maximum(rng.poisson(mean_doc_len, size=num_docs), 4)
    d = np.repeat(np.arange(num_docs), doc_lens)
    z = []
    for b0 in range(0, num_docs, doc_block):
        b1 = min(b0 + doc_block, num_docs)
        theta = rng.dirichlet(np.full(num_topics, doc_topic_alpha),
                              size=b1 - b0)
        u = rng.random(int(doc_lens[b0:b1].sum()))
        for doc, ui in zip(d[d >= b0][:len(u)], u):
            cdf = np.cumsum(theta[doc - b0])
            cdf[-1] = 1.0
            z.append(min(int(np.searchsorted(cdf, ui, side="right")),
                         num_topics - 1))
    z = np.asarray(z)
    w = np.empty(len(z), np.int64)
    for k in range(num_topics):
        tok = np.nonzero(z == k)[0]
        u = rng.random(tok.size)
        cdf = np.cumsum(phi[k])
        cdf[-1] = 1.0
        for t, ui in zip(tok, u):
            w[t] = min(int(np.searchsorted(cdf, ui, side="right")),
                       vocab_size - 1)
    return corpus_mod.reindex(w, d, vocab_size)


@pytest.mark.parametrize("doc_block", [7, 64])
def test_generator_matches_token_loop(doc_block):
    """The vectorised generator draws exactly what the token-by-token
    generative process draws from the same stream (one doc block and
    several)."""
    args = dict(seed=3, num_docs=40, mean_doc_len=12, vocab_size=150,
                num_topics=6)
    got = corpus_mod.generate_lda_corpus(doc_block=doc_block, **args)
    want = _loop_lda_corpus(doc_block=doc_block, **args)
    np.testing.assert_array_equal(got.w, want.w)
    np.testing.assert_array_equal(got.d, want.d)
    np.testing.assert_array_equal(got.word_freq, want.word_freq)
    _assert_corpus_consistent(got)


class TestShardEdgeCases:
    """The cases that exposed the padding/offsets bug: shards with no
    documents, and blocks bigger than a shard's token count."""

    def _tiny(self):
        w = np.array([0, 1, 0, 2, 1, 0, 3, 0], np.int64)
        d = np.array([0, 0, 0, 1, 1, 2, 2, 2], np.int64)
        return corpus_mod.reindex(w, d, vocab_size=5)

    def test_more_shards_than_docs(self):
        c = self._tiny()
        shards = corpus_mod.shard_tokens(c, num_shards=6, block_tokens=4)
        assert len(shards) == 6
        total = 0
        for w, d, valid, ds, dl in shards:
            # the fix: doc_start/doc_len lengths agree even when empty,
            # and empty shards still pad to a full (all-invalid) block
            assert ds.shape == dl.shape
            assert len(w) > 0 and len(w) % 4 == 0
            assert len(w) == len(d) == len(valid)
            n = int(valid.sum())
            assert int(dl.sum()) == n
            assert not valid[n:].any()
            total += n
        assert total == c.num_tokens
        assert sum(1 for s in shards if int(s[2].sum()) == 0) == 3

    def test_block_tokens_larger_than_shard(self):
        c = self._tiny()
        shards = corpus_mod.shard_tokens(c, num_shards=2, block_tokens=64)
        for w, d, valid, ds, dl in shards:
            assert len(w) == 64          # padded up to one full block
            assert int(valid.sum()) == int(dl.sum())

    def test_reindex_empty(self):
        c = corpus_mod.reindex(np.zeros(0, np.int64), np.zeros(0, np.int64),
                               vocab_size=4)
        assert c.num_tokens == 0 and c.num_docs == 0
        assert c.doc_start.shape == c.doc_len.shape == (0,)
        _assert_corpus_consistent(c)

    def test_heldout_split_extreme_fractions(self):
        c = self._tiny()
        train, held = corpus_mod.train_heldout_split(c, heldout_frac=0.0)
        assert held.num_tokens == 0
        assert held.doc_start.shape == held.doc_len.shape == (0,)
        assert train.num_tokens == c.num_tokens


if HAVE_HYPOTHESIS:
    @st.composite
    def _token_lists(draw):
        n = draw(st.integers(1, 120))
        vocab = draw(st.integers(1, 30))
        ndocs = draw(st.integers(1, 12))
        w = draw(st.lists(st.integers(0, vocab - 1), min_size=n,
                          max_size=n))
        d = draw(st.lists(st.integers(0, ndocs - 1), min_size=n,
                          max_size=n))
        return (np.asarray(w, np.int64), np.asarray(d, np.int64), vocab)

    @given(_token_lists())
    @settings(max_examples=40, deadline=None)
    def test_reindex_roundtrip(tokens):
        """reindex conserves the token multiset per document and is
        idempotent (already frequency-ordered + compact input is a fixed
        point)."""
        w, d, vocab = tokens
        c = corpus_mod.reindex(w, d, vocab)
        _assert_corpus_consistent(c)
        assert c.num_tokens == len(w)
        assert c.num_docs == len(np.unique(d))
        # per-document token *counts* survive (ids are renamed by rank)
        want = sorted(np.bincount(d)[np.bincount(d) > 0].tolist())
        assert sorted(c.doc_len.tolist()) == want
        # idempotence
        c2 = corpus_mod.reindex(c.w, c.d, vocab)
        assert np.array_equal(c2.w, c.w)
        assert np.array_equal(c2.d, c.d)
        assert np.array_equal(c2.doc_start, c.doc_start)
        assert np.array_equal(c2.word_freq, c.word_freq)

    @given(_token_lists(), st.integers(1, 7), st.sampled_from([2, 4, 16]))
    @settings(max_examples=40, deadline=None)
    def test_shard_tokens_conservation(tokens, num_shards, block_tokens):
        """Token mass is conserved across any shard count, every shard is
        block-padded, and each document lands on exactly one shard."""
        w, d, vocab = tokens
        c = corpus_mod.reindex(w, d, vocab)
        shards = corpus_mod.shard_tokens(c, num_shards, block_tokens)
        assert len(shards) == num_shards
        total, docs = 0, 0
        freq = np.zeros(vocab, np.int64)
        for sw, sd, valid, ds, dl in shards:
            assert ds.shape == dl.shape
            assert len(sw) % block_tokens == 0 and len(sw) > 0
            n = int(valid.sum())
            assert int(dl.sum()) == n
            total += n
            docs += len(dl)
            freq += np.bincount(sw[valid], minlength=vocab)
        assert total == c.num_tokens
        assert docs == c.num_docs
        assert np.array_equal(freq, c.word_freq)

    @given(_token_lists(), st.floats(0.0, 1.0), st.integers(0, 5))
    @settings(max_examples=40, deadline=None)
    def test_train_heldout_disjoint(tokens, frac, seed):
        """The split partitions tokens: counts sum to the parent's, word
        ids keep the parent ordering, offsets stay consistent."""
        w, d, vocab = tokens
        c = corpus_mod.reindex(w, d, vocab)
        train, held = corpus_mod.train_heldout_split(c, frac, seed=seed)
        assert train.num_tokens + held.num_tokens == c.num_tokens
        assert train.num_docs + held.num_docs == c.num_docs
        for part in (train, held):
            assert part.doc_start.shape == part.doc_len.shape
            assert int(part.doc_len.sum()) == part.num_tokens
        # both halves keep the parent's word ids: frequency histograms
        # add back up exactly (disjointness + completeness of the split)
        fsum = (np.bincount(train.w, minlength=vocab)
                + np.bincount(held.w, minlength=vocab))
        assert np.array_equal(fsum, c.word_freq)
else:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_reindex_roundtrip():
        pass

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_shard_tokens_conservation():
        pass

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_train_heldout_disjoint():
        pass


class TestLMData:
    def test_markov_batches(self):
        src = MarkovZipfSource(LMDataConfig(vocab_size=512, seq_len=64,
                                            batch_size=4))
        b = src.batch()
        assert b["tokens"].shape == (4, 64)
        assert (b["targets"][:, :-1] == b["tokens"][:, 1:]).all()
        assert b["tokens"].max() < 512

    def test_zipfian_token_marginal(self):
        src = MarkovZipfSource(LMDataConfig(vocab_size=1024, seq_len=256,
                                            batch_size=8))
        f = token_frequencies(src, 4)
        # head should dominate: the top 10% of ranks carry most of the mass
        order = np.argsort(-f)
        top = f[order[:102]].sum() / max(f.sum(), 1)
        assert top > 0.5, top
