"""Data pipeline tests: native backend vs Python fallback parity, corpus
semantics, Huffman validity, pair generation, LDA CSR reading."""

import os

import numpy as np
import pytest

from multiverso_tpu.data import (Corpus, PyData, load_native,
                                 synthetic_docs, synthetic_text)

native = load_native()
BACKENDS = [pytest.param(PyData(), id="python")]
if native is not None:
    BACKENDS.append(pytest.param(native, id="native"))


@pytest.fixture(scope="module")
def text_file(tmp_path_factory):
    p = tmp_path_factory.mktemp("data") / "corpus.txt"
    p.write_text("the quick brown fox jumps over the lazy dog\n"
                 "the quick brown fox\nthe dog sleeps\n")
    return str(p)


@pytest.mark.parametrize("be", BACKENDS)
class TestCorpusBuild:
    def test_vocab_and_encoding(self, be, text_file):
        c = be.build_corpus(text_file, min_count=1)
        assert c.words[0] == "the"                      # most frequent first
        assert c.counts[0] == 4
        assert c.total_raw_tokens == 16
        assert len(c.ids) == 16
        # encoding round-trips: id of first token is id of 'the' = 0
        assert c.ids[0] == 0
        # counts sorted descending
        assert (np.diff(c.counts) <= 0).all()

    def test_min_count_filters(self, be, text_file):
        c = be.build_corpus(text_file, min_count=2)
        assert set(c.words) <= {"the", "quick", "brown", "fox", "dog"}
        assert all(cnt >= 2 for cnt in c.counts)
        # dropped words removed from the id stream
        assert len(c.ids) < 16

    def test_missing_file_raises(self, be, tmp_path):
        with pytest.raises(FileNotFoundError):
            be.build_corpus(str(tmp_path / "nope.txt"), 1)

    def test_deterministic_word_order(self, be, text_file):
        c1 = be.build_corpus(text_file, min_count=1)
        c2 = be.build_corpus(text_file, min_count=1)
        assert c1.words == c2.words


@pytest.mark.parametrize("be", BACKENDS)
class TestHuffman:
    def test_codes_are_prefix_free_and_complete(self, be):
        counts = np.asarray([50, 30, 10, 5, 3, 2], np.int64)
        codes, points, lengths = be.huffman(counts)
        assert (lengths > 0).all()
        # more frequent words get codes no longer than rarer ones
        assert lengths[0] <= lengths[-1]
        # prefix-free: no code is a prefix of another
        strs = ["".join(str(int(codes[w, i])) for i in range(lengths[w]))
                for w in range(len(counts))]
        for a in range(len(strs)):
            for b in range(len(strs)):
                if a != b:
                    assert not strs[b].startswith(strs[a])
        # expected code length ~ entropy bound
        p = counts / counts.sum()
        entropy = -(p * np.log2(p)).sum()
        avg_len = (p * lengths).sum()
        assert entropy <= avg_len <= entropy + 1
        # points index inner nodes [0, V-2]; root = V-2 is first point
        V = len(counts)
        for w in range(V):
            assert points[w, 0] == V - 2
            for i in range(lengths[w]):
                assert 0 <= points[w, i] <= V - 2

    def test_single_word_vocab(self, be):
        codes, points, lengths = be.huffman(np.asarray([7], np.int64))
        assert lengths[0] == 0
        # regression: padding must be -1-filled, not uninitialized memory
        assert (codes[0] == -1).all()
        assert (points[0] == -1).all()

class TestHuffmanParity:
    def test_python_native_parity(self):
        if native is None:
            pytest.skip("native backend unavailable")
        counts = np.sort(np.random.default_rng(3).integers(
            1, 1000, size=50))[::-1].astype(np.int64)
        c1, p1, l1 = PyData().huffman(counts)
        c2, p2, l2 = native.huffman(counts)
        np.testing.assert_array_equal(l1, l2)
        np.testing.assert_array_equal(c1, c2)
        np.testing.assert_array_equal(p1, p2)


@pytest.mark.parametrize("be", BACKENDS)
class TestPairs:
    def test_skipgram_pairs_valid(self, be):
        ids = np.arange(100, dtype=np.int32) % 10
        c, x = be.skipgram_pairs(ids, window=3, keep_prob=None, seed=7)
        assert len(c) == len(x) > 0
        assert c.max() < 10 and x.max() < 10
        assert (c >= 0).all() and (x >= 0).all()

    def test_skipgram_deterministic_per_seed(self, be):
        ids = np.arange(50, dtype=np.int32) % 5
        a = be.skipgram_pairs(ids, 2, None, seed=1)
        b = be.skipgram_pairs(ids, 2, None, seed=1)
        np.testing.assert_array_equal(a[0], b[0])
        np.testing.assert_array_equal(a[1], b[1])

    def test_subsampling_reduces_pairs(self, be):
        ids = np.zeros(200, np.int32)  # one hyper-frequent word
        keep = np.asarray([0.1], np.float32)
        c_all, _ = be.skipgram_pairs(ids, 2, None, seed=3)
        c_sub, _ = be.skipgram_pairs(ids, 2, keep, seed=3)
        assert len(c_sub) < len(c_all)

    def test_cbow_examples(self, be):
        ids = np.arange(60, dtype=np.int32) % 6
        ctx, tgt = be.cbow_examples(ids, window=2, keep_prob=None, seed=5)
        assert ctx.shape == (len(tgt), 4)
        assert tgt.max() < 6
        # padding marker -1 only at row tails
        for row in ctx:
            seen_pad = False
            for v in row:
                if v == -1:
                    seen_pad = True
                else:
                    assert not seen_pad


class TestMultiThreadedPairs:
    """The native mt fill (n worker threads per block, the reference
    word2vec's corpus-partitioned generator shape). Oracle: chunk t of a
    threads=T call is bit-identical to the single-thread call on that
    chunk with seed + t*CHUNK_SEED_STEP (native.py documents the
    contract; chunk_seed() in mvtpu_data.cpp implements it)."""

    def setup_method(self):
        if native is None:
            pytest.skip("native backend unavailable")

    def test_threads_1_matches_single_thread_exactly(self):
        ids = (np.arange(5000, dtype=np.int32) * 7) % 50
        a = native.skipgram_pairs(ids, 3, None, seed=11)
        b = native.skipgram_pairs(ids, 3, None, seed=11, threads=1)
        np.testing.assert_array_equal(a[0], b[0])
        np.testing.assert_array_equal(a[1], b[1])

    def test_mt_equals_chunked_single_thread_oracle(self):
        from multiverso_tpu.data.native import CHUNK_SEED_STEP
        rng = np.random.default_rng(0)
        ids = rng.integers(0, 40, 10_001).astype(np.int32)
        kp = np.linspace(0.3, 1.0, 40).astype(np.float32)
        seed, T = 123, 3
        got_c, got_x = native.skipgram_pairs(ids, 4, kp, seed=seed,
                                             threads=T)
        want_c, want_x = [], []
        n = len(ids)
        for t in range(T):
            chunk = ids[n * t // T:n * (t + 1) // T]
            c, x = native.skipgram_pairs(
                chunk, 4, kp, seed=(seed + t * CHUNK_SEED_STEP) % 2**64)
            want_c.append(c)
            want_x.append(x)
        np.testing.assert_array_equal(got_c, np.concatenate(want_c))
        np.testing.assert_array_equal(got_x, np.concatenate(want_x))

    def test_mt_cbow_equals_chunked_oracle(self):
        from multiverso_tpu.data.native import CHUNK_SEED_STEP
        rng = np.random.default_rng(1)
        ids = rng.integers(0, 25, 4_003).astype(np.int32)
        seed, T = 77, 4
        got_ctx, got_tgt = native.cbow_examples(ids, 2, None, seed=seed,
                                                threads=T)
        want_ctx, want_tgt = [], []
        n = len(ids)
        for t in range(T):
            chunk = ids[n * t // T:n * (t + 1) // T]
            ctx, tgt = native.cbow_examples(
                chunk, 2, None, seed=(seed + t * CHUNK_SEED_STEP) % 2**64)
            want_ctx.append(ctx)
            want_tgt.append(tgt)
        np.testing.assert_array_equal(got_ctx, np.concatenate(want_ctx))
        np.testing.assert_array_equal(got_tgt, np.concatenate(want_tgt))

    def test_mt_deterministic_and_near_lossless(self):
        """Chunking loses only O(T*window) boundary pairs, and repeat
        calls are bit-identical."""
        rng = np.random.default_rng(2)
        ids = rng.integers(0, 100, 50_000).astype(np.int32)
        c1, x1 = native.skipgram_pairs(ids, 5, None, seed=9, threads=4)
        c2, _ = native.skipgram_pairs(ids, 5, None, seed=9, threads=4)
        np.testing.assert_array_equal(c1, c2)
        c_st, _ = native.skipgram_pairs(ids, 5, None, seed=9)
        # same-expectation pair volume (seeds differ so counts wiggle via
        # the dynamic windows; boundary loss itself is <= 2*window^2*T)
        assert abs(len(c1) - len(c_st)) / len(c_st) < 0.02
        assert c1.max() < 100 and x1.max() < 100 and c1.min() >= 0

    def test_mt_small_cap_falls_back_exactly(self):
        """cap too small for the chunked worst case -> the single-thread
        fill with the caller's cap (the exact-cap contract holds)."""
        ids = (np.arange(300, dtype=np.int32)) % 10
        cap = 50
        a = native.skipgram_pairs(ids, 3, None, seed=5, cap=cap,
                                  threads=4)
        b = native.skipgram_pairs(ids, 3, None, seed=5, cap=cap)
        assert len(a[0]) == len(b[0]) == cap
        np.testing.assert_array_equal(a[0], b[0])
        np.testing.assert_array_equal(a[1], b[1])

    def test_batches_iterator_with_threads(self, tmp_path):
        """The block pipeline runs end-to-end with gen_threads>1 and
        yields the same fixed shapes and in-range ids."""
        from multiverso_tpu.data import Corpus, synthetic_text
        p = tmp_path / "corpus.txt"
        synthetic_text(str(p), num_tokens=20_000, vocab_size=200, seed=3)
        corpus = Corpus.from_file(str(p), min_count=1, subsample=0)
        total = 0
        for src, tgt in corpus.skipgram_batches(256, window=3, seed=1,
                                                epochs=1, gen_threads=3):
            assert src.shape == tgt.shape == (256,)
            assert src.max() < corpus.vocab_size and src.min() >= 0
            total += len(src)
        assert total > 0


@pytest.mark.parametrize("be", BACKENDS)
class TestLdaDocs:
    def test_csr_roundtrip(self, be, tmp_path):
        # includes an empty line AND a whitespace-only line: neither is a doc
        p = tmp_path / "docs.txt"
        p.write_text("0:2 3:1\n5:4\n\n \t \n1:1 2:1 3:1\n")
        offsets, wids, wcnts = be.lda_read_docs(str(p))
        assert len(offsets) == 4  # 3 non-empty docs
        np.testing.assert_array_equal(offsets, [0, 2, 3, 6])
        np.testing.assert_array_equal(wids, [0, 3, 5, 1, 2, 3])
        np.testing.assert_array_equal(wcnts, [2, 1, 4, 1, 1, 1])

    def test_malformed_tokens_skipped(self, be, tmp_path):
        p = tmp_path / "docs.txt"
        p.write_text("0:2 garbage 3:x 4:1\n")
        offsets, wids, wcnts = be.lda_read_docs(str(p))
        np.testing.assert_array_equal(wids, [0, 4])

    def test_missing_file(self, be, tmp_path):
        with pytest.raises(FileNotFoundError):
            be.lda_read_docs(str(tmp_path / "nope"))


class TestCorpusClass:
    def test_from_file_and_distributions(self, text_file):
        c = Corpus.from_file(text_file, min_count=1, subsample=1e-3)
        assert c.vocab_size > 0
        kp = c.keep_prob()
        assert kp.shape == (c.vocab_size,)
        assert (kp > 0).all() and (kp <= 1).all()
        # rarer words kept with probability >= more frequent words
        assert kp[-1] >= kp[0]
        u = c.unigram_probs()
        assert abs(u.sum() - 1.0) < 1e-5
        # ^0.75 flattens: max prob below raw frequency share
        raw = c.counts / c.counts.sum()
        assert u.max() < raw.max()

    def test_skipgram_batches_fixed_shape(self, text_file):
        c = Corpus.from_file(text_file, min_count=1, subsample=0)
        batches = list(c.skipgram_batches(batch_size=8, window=2, epochs=2))
        assert len(batches) > 0
        for ctr, ctx in batches:
            assert ctr.shape == (8,) and ctx.shape == (8,)


class TestSynthetic:
    def test_synthetic_text(self, tmp_path):
        p = tmp_path / "syn.txt"
        synthetic_text(str(p), num_tokens=5000, vocab_size=100, seed=1)
        c = Corpus.from_file(str(p), min_count=1)
        assert c.num_tokens == 5000
        assert c.vocab_size <= 100
        # zipf: most frequent word much more common than median
        assert c.counts[0] > 5 * np.median(c.counts)

    def test_synthetic_docs(self, tmp_path):
        p = tmp_path / "docs.txt"
        synthetic_docs(str(p), num_docs=20, vocab_size=50, avg_doc_len=10,
                       seed=1)
        from multiverso_tpu.data import backend
        offsets, wids, wcnts = backend().lda_read_docs(str(p))
        assert len(offsets) == 21
        assert wids.max() < 50
        assert (wcnts > 0).all()


class TestNativeLoadIsNotOptionalWhereItCanBeBuilt:
    """With a Makefile and a compiler present, a failed build or an ABI
    mismatch that survives a rebuild RAISES: the word2vec pair generator
    must not quietly become the Python one."""

    @pytest.fixture()
    def fresh(self, monkeypatch, tmp_path):
        from multiverso_tpu.data import native as nat
        self.real_so = nat._SO_PATH if native is not None else None
        monkeypatch.setattr(nat, "_CACHED", None)
        monkeypatch.setattr(nat, "_TRIED", False)
        monkeypatch.setattr(nat, "_SO_PATH", str(tmp_path / "missing.so"))
        return nat

    def test_failed_build_raises(self, fresh, monkeypatch):
        monkeypatch.setattr(fresh, "_toolchain", lambda: True)

        def boom(rebuild):
            raise RuntimeError("native data lib build failed (rc=2)")
        monkeypatch.setattr(fresh, "_build", boom)
        with pytest.raises(RuntimeError, match="build failed"):
            fresh.load_native()

    def test_abi_mismatch_after_rebuild_raises(self, fresh, monkeypatch):
        import shutil
        if self.real_so is None:
            pytest.skip("no native toolchain here")
        monkeypatch.setattr(fresh, "_toolchain", lambda: True)
        builds = []

        def fake_build(rebuild):
            builds.append(rebuild)
            # like the linker: a NEW file, never a write into one that
            # is already mapped into this process
            if os.path.exists(fresh._SO_PATH):
                os.unlink(fresh._SO_PATH)
            shutil.copy(self.real_so, fresh._SO_PATH)
        monkeypatch.setattr(fresh, "_build", fake_build)
        monkeypatch.setattr(fresh, "ABI_VERSION", 10 ** 6)
        with pytest.raises(RuntimeError, match="ABI"):
            fresh.load_native()
        assert builds == [False, True]     # one rebuild, then it raises

    def test_none_only_where_it_cannot_be_built(self, fresh, monkeypatch):
        monkeypatch.setattr(fresh, "_toolchain", lambda: False)
        assert fresh.load_native() is None
