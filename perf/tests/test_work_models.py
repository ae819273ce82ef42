"""Work models against bytes and operations computed by hand."""

import pytest

from perf import peaks, work_models as wm


def test_lda_sampler_one_block():
    # 512 tokens, K=1024, block of 512: per token 2*1024 (word row, 2 B a
    # count) + 8 (z in and out) + 8 (stream) + 64*1024/512 (doc counts)
    got = wm.lda_sampler({"num_topics": 1024, "block_tokens": 512},
                         {"tokens": 512})
    assert got["bytes"] == 512 * (2048 + 8 + 8 + 128)
    assert got["flops"] == 512 * 6 * 1024


def test_lda_sweep_adds_the_rebuild():
    sizes = {"num_topics": 1024, "block_tokens": 512, "vocab_size": 1000}
    one = wm.lda_sampler(sizes, {"tokens": 512})
    got = wm.lda_sweep(sizes, {"tokens": 512, "sweeps": 2})
    assert got["bytes"] == one["bytes"] + 2 * 6 * 1000 * 1024


def test_w2v_pairs():
    # dim 300, 5 negatives: 7 rows of 1,200 B, 3 passes; 6*6*300 ops
    got = wm.w2v_pairs({"embedding_dim": 300, "negative": 5},
                       {"pairs": 10})
    assert got["bytes"] == 10 * 3 * 7 * 1200
    assert got["flops"] == 10 * 6 * 6 * 300


def test_kv_probe_commit():
    # scalar values (4 B), 8 B of FTRL state: get 12 B, add 8+8+16 = 32 B
    got = wm.kv_probe_commit({"value_dim": 0, "state_bytes": 8},
                             {"get_keys": 100, "add_keys": 10})
    assert got["bytes"] == 100 * 12 + 10 * 32


def test_least_seconds_names_its_bound():
    got = peaks.least_seconds({"bytes": 819e9, "flops": 1.0},
                              "TPU v5 lite")
    assert got == {"seconds": pytest.approx(1.0), "bound": "bytes"}
    got = peaks.least_seconds({"bytes": 1.0, "flops": 4 * 197e12},
                              "TPU v5 lite", chips=4)
    assert got == {"seconds": pytest.approx(1.0), "bound": "flops"}
    with pytest.raises(KeyError):
        peaks.least_seconds({"bytes": 1, "flops": 1}, "cpu")
