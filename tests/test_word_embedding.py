"""apps/word_embedding: alias sampling, convergence, semantic structure.

Convergence tests mirror the reference's examples-as-system-tests
(SURVEY.md §5): loss decreases, co-occurring words embed closer.
"""

import numpy as np
import pytest

from multiverso_tpu.apps.word_embedding import (W2VConfig, WordEmbedding,
                                                build_alias)
from multiverso_tpu.data.corpus import Corpus
from multiverso_tpu.tables import base as table_base


@pytest.fixture(autouse=True)
def _clean_tables():
    yield
    table_base.reset_tables()


def _clustered_corpus(tmp_path, n_clusters=8, words_per_cluster=4,
                      n_sents=600, sent_len=20, seed=0):
    """Text whose words co-occur only within their cluster — gives the
    embeddings a recoverable structure to test against."""
    rng = np.random.default_rng(seed)
    path = tmp_path / "corpus.txt"
    with open(path, "w") as f:
        for _ in range(n_sents):
            c = rng.integers(n_clusters)
            ws = rng.integers(0, words_per_cluster, sent_len)
            f.write(" ".join(f"c{c}w{w}" for w in ws) + "\n")
    corpus = Corpus.from_file(str(path), min_count=1, subsample=0)
    cluster_ids = {}
    for wid, w in enumerate(corpus.words):
        cluster_ids.setdefault(int(w[1:w.index("w")]), []).append(wid)
    return corpus, cluster_ids


def test_build_alias_distribution():
    rng = np.random.default_rng(0)
    probs = rng.random(50)
    probs /= probs.sum()
    prob, alias = build_alias(probs)
    # emulate sampling exactly as the device does, in numpy
    n = 200_000
    j = rng.integers(0, 50, n)
    u = rng.random(n)
    out = np.where(u < prob[j], j, alias[j])
    emp = np.bincount(out, minlength=50) / n
    np.testing.assert_allclose(emp, probs, atol=0.005)


def test_build_unigram_table_distribution():
    from multiverso_tpu.apps.word_embedding import build_unigram_table
    probs = np.array([0.5, 0.25, 0.125, 0.125])
    table = build_unigram_table(probs, 1 << 16)
    counts = np.bincount(table, minlength=4) / (1 << 16)
    np.testing.assert_allclose(counts, probs, atol=1e-4)


def test_build_alias_degenerate():
    prob, alias = build_alias(np.array([1.0]))
    assert prob[0] == 1.0


@pytest.mark.parametrize("model,objective", [
    ("skipgram", "ns"), ("skipgram", "hs"),
    ("cbow", "ns"), ("cbow", "hs"),
])
def test_variants_loss_decreases(mesh_dp8, tmp_path, model, objective):
    # cbow yields ~1 example/token vs skip-gram's ~6 pairs; size the
    # corpus so both produce >= 6 full superstep calls
    corpus, _ = _clustered_corpus(
        tmp_path, n_sents=300 if model == "skipgram" else 600)
    cfg = W2VConfig(embedding_dim=16, window=3, negative=4, model=model,
                    objective=objective, batch_size=256, steps_per_call=4,
                    learning_rate=0.05, epochs=1, subsample=0, seed=1)
    app = WordEmbedding(corpus, cfg, mesh=mesh_dp8,
                        name=f"w2v_{model}_{objective}")
    app.train()
    hist = app.loss_history
    assert len(hist) >= 6 and np.all(np.isfinite(hist))
    early = np.mean(hist[:3])
    late = np.mean(hist[-3:])
    assert late < early, f"loss did not decrease: {early:.3f} -> {late:.3f}"


def test_local_batches_empty_shard_raises(mesh_dp8, tmp_path):
    """A shard too small to yield one local batch must raise, not return
    — a silent return would deadlock the other processes' collective
    schedule (the 2-process happy path runs in test_multihost)."""
    corpus, _ = _clustered_corpus(tmp_path, n_sents=5)
    cfg = W2VConfig(embedding_dim=8, window=2, negative=2, batch_size=64,
                    steps_per_call=2, epochs=1, subsample=0, seed=0)
    app = WordEmbedding(corpus, cfg, mesh=mesh_dp8, name="w2v_empty")
    assert app._local_chunks is None      # single-process: mode inert
    app._local_chunks = [(0, 64)]
    app._local_batch = 1 << 20            # no shard can fill this
    with pytest.raises(ValueError, match="yields no"):
        next(app._local_batches())


def test_save_text_format(mesh_dp8, tmp_path):
    """The reference word2vec's text dump: header + word-per-line."""
    corpus, _ = _clustered_corpus(tmp_path, n_sents=100)
    cfg = W2VConfig(embedding_dim=8, window=2, negative=2, batch_size=128,
                    steps_per_call=2, epochs=1, subsample=0, seed=0)
    app = WordEmbedding(corpus, cfg, mesh=mesh_dp8, name="w2v_txt")
    app.train(total_steps=2)
    out = tmp_path / "vec.txt"
    app.save_text(str(out))
    lines = out.read_text().splitlines()
    v, d = map(int, lines[0].split())
    assert v == corpus.vocab_size and d == 8
    assert len(lines) == v + 1
    first = lines[1].split()
    assert first[0] == corpus.words[0] and len(first) == 1 + d


def test_alias_sampler_config(mesh_dp8, tmp_path):
    """ns_sampler='alias' (the exact Vose draw) keeps training — the
    default moved to the reference's unigram-table draw."""
    corpus, _ = _clustered_corpus(tmp_path, n_sents=300)
    cfg = W2VConfig(embedding_dim=16, window=3, negative=4,
                    batch_size=256, steps_per_call=4, learning_rate=0.05,
                    epochs=1, subsample=0, seed=1, ns_sampler="alias")
    app = WordEmbedding(corpus, cfg, mesh=mesh_dp8, name="w2v_alias")
    app.train()
    hist = app.loss_history
    assert len(hist) >= 6 and np.all(np.isfinite(hist))
    assert np.mean(hist[-3:]) < np.mean(hist[:3])


def test_large_vocab_int32_pairs(mesh_dp8):
    """Vocab past the int16 range must ship pairs as int32 (the _place
    dtype switch) and still train."""
    from multiverso_tpu.data.native import CorpusData
    from multiverso_tpu.data.corpus import Corpus
    v = 40_000
    rng = np.random.default_rng(0)
    ids = rng.integers(0, v, 20_000).astype(np.int32)
    counts = np.maximum(np.bincount(ids, minlength=v), 1).astype(np.int64)
    corpus = Corpus(CorpusData(words=[f"w{i}" for i in range(v)],
                               counts=counts, ids=ids,
                               total_raw_tokens=len(ids)), subsample=0)
    cfg = W2VConfig(embedding_dim=8, window=2, negative=2, batch_size=256,
                    steps_per_call=2, epochs=1, subsample=0, seed=0)
    app = WordEmbedding(corpus, cfg, mesh=mesh_dp8, name="w2v_bigv")
    assert app._scratch >= np.iinfo(np.int16).max  # int32 path active
    app.train(total_steps=4)
    assert np.all(np.isfinite(app.loss_history))


def test_skipgram_recovers_clusters(mesh_dp8, tmp_path):
    corpus, clusters = _clustered_corpus(tmp_path, n_sents=800, seed=3)
    cfg = W2VConfig(embedding_dim=24, window=3, negative=5,
                    batch_size=256, steps_per_call=4,
                    learning_rate=0.03, epochs=3, subsample=0, seed=2)
    app = WordEmbedding(corpus, cfg, mesh=mesh_dp8, name="w2v_clusters")
    app.train()
    emb = app.embeddings()
    norm = emb / np.maximum(np.linalg.norm(emb, axis=1, keepdims=True),
                            1e-12)
    sims = norm @ norm.T
    intra, inter = [], []
    ids = list(clusters.values())
    for ci, members in enumerate(ids):
        for i in members:
            for j in members:
                if i < j:
                    intra.append(sims[i, j])
            for other in ids[ci + 1:]:
                for j in other:
                    inter.append(sims[i, j])
    assert np.mean(intra) > np.mean(inter) + 0.2, \
        f"intra {np.mean(intra):.3f} vs inter {np.mean(inter):.3f}"


def test_nearest_is_same_cluster(mesh_dp8, tmp_path):
    corpus, clusters = _clustered_corpus(tmp_path, n_sents=800, seed=4)
    cfg = W2VConfig(embedding_dim=24, window=3, negative=5,
                    batch_size=256, steps_per_call=4,
                    learning_rate=0.03, epochs=3, subsample=0, seed=5)
    app = WordEmbedding(corpus, cfg, mesh=mesh_dp8, name="w2v_nn")
    app.train()
    hits = 0
    total = 0
    for members in clusters.values():
        for wid in members:
            nn = app.nearest(wid, k=len(members) - 1)
            hits += len(set(nn) & set(members))
            total += len(members) - 1
    assert hits / total > 0.5, f"nearest-neighbor cluster hit rate " \
                               f"{hits}/{total}"


def test_store_load_roundtrip(mesh_dp8, tmp_path):
    corpus, _ = _clustered_corpus(tmp_path, n_sents=200, seed=6)
    cfg = W2VConfig(embedding_dim=8, window=2, negative=2, batch_size=256,
                    steps_per_call=2, epochs=1, subsample=0)
    app = WordEmbedding(corpus, cfg, mesh=mesh_dp8, name="w2v_ckpt")
    app.train()
    emb = app.embeddings()
    app.store(f"file://{tmp_path}/w2v")
    app2 = WordEmbedding(corpus, cfg, mesh=mesh_dp8, name="w2v_ckpt2")
    app2.load(f"file://{tmp_path}/w2v")
    np.testing.assert_allclose(app2.embeddings(), emb, rtol=1e-6)


def test_analogy_rule():
    """The compute-accuracy rule on a planted geometry (pure host math,
    no app needed): with a row on the b - a + c direction, the helpers
    nearest()/analogy() share return it, excluding the query words."""
    from multiverso_tpu.apps.word_embedding import (_normalized_rows,
                                                    _topk_excluding)
    emb = np.zeros((40, 4), np.float32)
    rng = np.random.default_rng(0)
    emb[4:] = rng.normal(0, 0.1, (36, 4))
    emb[0] = [1, 0, 0, 0]
    emb[1] = [0, 1, 0, 0]
    emb[2] = [0, 0, 1, 0]
    emb[3] = [-0.6, 0.6, 0.6, 0]     # normalized b - a + c direction
    norm = _normalized_rows(emb)
    q = norm[1] - norm[0] + norm[2]
    q = q / np.linalg.norm(q)
    got = _topk_excluding(norm, q, (0, 1, 2), 1)
    assert got[0] == 3, got
    # exclusion really excludes: the raw best IS a query word
    raw = _topk_excluding(norm, norm[1], (), 1)
    assert raw[0] == 1


def test_periodic_checkpoint_and_resume(mesh_dp8, tmp_path):
    """SURVEY §6.4's flag-driven periodic dump + true resume: training
    with checkpoint_interval stores mid-train; a fresh app loads the
    dump, restores the step counter, and CONTINUES the LR decay and the
    fold_in key sequence instead of restarting/replaying them."""
    corpus, _ = _clustered_corpus(tmp_path, n_sents=300, seed=9)
    prefix = f"file://{tmp_path}/w2v_per"
    cfg = W2VConfig(embedding_dim=8, window=2, negative=2, batch_size=256,
                    steps_per_call=2, epochs=1, subsample=0,
                    checkpoint_prefix=prefix, checkpoint_interval=2)
    app = WordEmbedding(corpus, cfg, mesh=mesh_dp8, name="w2v_per")
    app.train(total_steps=8)             # 4 calls -> stores at 2 and 4
    assert (tmp_path / "w2v_per.in.npz").exists()
    assert (tmp_path / "w2v_per.meta.npz").exists()
    steps_at_ck = app._step_no

    # resume WITHOUT the periodic trigger (so the stored meta stays put
    # for the torn-set scenario below)
    cfg_r = W2VConfig(embedding_dim=8, window=2, negative=2,
                      batch_size=256, steps_per_call=2, epochs=1,
                      subsample=0)
    app2 = WordEmbedding(corpus, cfg_r, mesh=mesh_dp8, name="w2v_per2")
    app2.load(prefix)
    assert app2._step_no == steps_at_ck          # counter restored
    assert app2._sched_offset == steps_at_ck // cfg.steps_per_call
    # resumed continuation trains and the embeddings move
    before = app2.embeddings().copy()
    app2.train(total_steps=4)
    assert np.isfinite(app2.loss_history).all()
    assert not np.allclose(app2.embeddings(), before)

    # a TORN set (crash between the three per-file writes: table moved
    # on, meta stale) is detected, not silently resumed
    app2.train(total_steps=4)
    app2.w_in.store(f"{prefix}.in.npz")     # newer table, stale meta
    app_t = WordEmbedding(corpus, cfg, mesh=mesh_dp8, name="w2v_torn")
    with pytest.raises(ValueError, match="torn"):
        app_t.load(prefix)

    # refresh a complete set, then: resuming under a DIFFERENT
    # steps_per_call is rejected (call-indexed RNG would replay)
    app2.store(prefix)
    cfg4 = W2VConfig(embedding_dim=8, window=2, negative=2,
                     batch_size=256, steps_per_call=4, epochs=1,
                     subsample=0)
    app_s = WordEmbedding(corpus, cfg4, mesh=mesh_dp8, name="w2v_spc")
    with pytest.raises(ValueError, match="steps_per_call"):
        app_s.load(prefix)

    # a corrupt meta RAISES (a silent skip would desync lockstep peers)
    (tmp_path / "w2v_per.meta.npz").write_bytes(b"garbage not an npz")
    app_c = WordEmbedding(corpus, cfg, mesh=mesh_dp8, name="w2v_corr")
    with pytest.raises(ValueError):
        app_c.load(prefix)

    # a pre-meta checkpoint (tables only) still loads, without resume
    import os
    os.remove(tmp_path / "w2v_per.meta.npz")
    app3 = WordEmbedding(corpus, cfg, mesh=mesh_dp8, name="w2v_per3")
    app3.load(prefix)
    assert app3._sched_offset == 0


def test_lda_periodic_checkpoint(mesh_dp8):
    """LightLDA's periodic trigger stores full sampler state mid-train;
    the dump loads into a fresh app with z preserved."""
    from multiverso_tpu.apps.lightlda import LDAConfig, LightLDA
    from multiverso_tpu.io.stream import mem_store_clear
    rng = np.random.default_rng(3)
    tw = rng.integers(0, 30, 640).astype(np.int32)
    td = np.sort(rng.integers(0, 20, 640)).astype(np.int32)
    cfg = LDAConfig(num_topics=8, batch_tokens=320, steps_per_call=2,
                    seed=2, num_iterations=3, eval_every=10,
                    checkpoint_prefix="mem://lda_per",
                    checkpoint_interval=2)
    app = LightLDA(tw, td, 30, cfg, mesh=mesh_dp8, name="lda_per")
    app.train()                          # 3 sweeps -> store after sweep 2
    app2 = LightLDA(tw, td, 30, cfg, mesh=mesh_dp8, name="lda_per2")
    app2.load("mem://lda_per")
    z = np.asarray(app2._z)
    assert z.min() >= 0 and z.max() < cfg.num_topics
    assert int(app2.word_topics().sum()) == len(tw)
    mem_store_clear()


def test_batch_size_must_divide_mesh(mesh_dp8, tmp_path):
    corpus, _ = _clustered_corpus(tmp_path, n_sents=100, seed=7)
    cfg = W2VConfig(embedding_dim=8, batch_size=100)  # 100 % 8 != 0
    app = WordEmbedding(corpus, cfg, mesh=mesh_dp8, name="w2v_bad")
    with pytest.raises(ValueError, match="divisible"):
        app.train()


# -- the distinct-row writer (ops/distinct_rows.py) --------------------------


@pytest.fixture()
def mesh_one(devices):
    """ONE device: where both tables lie whole and the writer runs."""
    from multiverso_tpu import core
    m = core.init(devices=devices[:1], data_parallel=1, model_parallel=1)
    yield m
    core.shutdown()


def _writer_case(name, rows, lanes, rng):
    """Row ids of one step, by the cases the writer has to get right."""
    scratch = rows - 1
    if name == "distinct":          # no duplicates at all: bit-equal
        return rng.permutation(rows)[:lanes]
    if name == "one_row":           # every lane the same row
        return np.full(lanes, 37)
    if name == "scratch_heavy":     # masked lanes parked on the scratch row
        return np.where(rng.random(lanes) < 0.8, scratch,
                        rng.integers(0, rows, lanes))
    if name == "first_and_last":    # ids 0 and V - 1 only
        return np.where(rng.random(lanes) < 0.5, 0, scratch)
    if name == "runs_cross_blocks":
        # runs of 100 over 256-lane blocks, and one 8-row group (rows
        # 64..71) that spans more than two whole blocks
        return np.concatenate([np.repeat(np.arange(5, 5 + lanes // 200),
                                         100)[:lanes - 600],
                               rng.integers(64, 72, 600)])
    if name == "one_centre":        # 4,096 lanes of one centre word
        return np.full(4096, 11)
    if name == "zipf":              # a step as the trainer meets it
        return np.minimum(rng.zipf(1.3, lanes) - 1, scratch)
    if name == "two_calls":         # more lanes than one kernel call takes
        return np.minimum(rng.zipf(1.3, 8192 + 700) - 1, scratch)
    raise AssertionError(name)


@pytest.mark.parametrize("case", ["distinct", "one_row", "scratch_heavy",
                                  "first_and_last", "runs_cross_blocks",
                                  "one_centre", "zipf", "two_calls"])
def test_distinct_row_writer_adds_what_the_scatter_adds(case):
    """``add_rows`` against ``.at[].add`` in float32, bit for bit: the
    sort is stable and a row's duplicates are added one by one in lane
    order, the order of XLA's scatter — word2vec's training amplifies a
    last-digit difference of a hot row past the benchmark's limits
    within three calls (PERF.md §6, PR 33). The count of rows written
    is numpy's count of distinct rows, kernel call by kernel call."""
    import jax
    import jax.numpy as jnp
    from multiverso_tpu.ops import distinct_rows

    rng = np.random.default_rng(5)
    rows, cols = distinct_rows.aligned_shape(2001, 300)
    ids = _writer_case(case, rows, 1500, rng).astype(np.int32)
    table = rng.normal(size=(rows, cols)).astype(np.float32)
    upd = rng.normal(size=(len(ids), cols)).astype(np.float32)

    @jax.jit
    def both(table, ids, upd):
        new, distinct = distinct_rows.add_rows(
            table, ids, lambda lanes: jnp.take(upd, lanes, axis=0),
            interpret=True)
        return new, distinct, table.at[ids].add(upd)
    new, distinct, want = (np.asarray(x) for x in both(table, ids, upd))
    step = distinct_rows.MAX_LANES
    assert int(distinct) == sum(len(np.unique(ids[lo:lo + step]))
                                for lo in range(0, len(ids), step))
    assert np.array_equal(new, want)
    untouched = np.setdiff1d(np.arange(rows), ids)
    assert np.array_equal(new[untouched], table[untouched])


def test_distinct_row_writer_reads_ids_as_the_scatter_reads_them():
    """As ``.at[].add`` does: a negative id counts from the table's end,
    and a lane whose id is still no row adds nothing."""
    import jax.numpy as jnp
    from multiverso_tpu.ops import distinct_rows

    rows, cols = distinct_rows.aligned_shape(64, 128)
    table = jnp.ones((rows, cols), jnp.float32)
    ids = jnp.asarray([3, rows, 3, rows + 9, -1, 5, -rows, -rows - 1, -1],
                      jnp.int32)
    upd = jnp.asarray(np.random.default_rng(2).normal(size=(9, cols)),
                      jnp.float32)
    new, written = distinct_rows.add_rows(
        table, ids, lambda lanes: jnp.take(upd, lanes, axis=0),
        interpret=True)
    want = np.asarray(table.at[ids].add(upd))
    assert want[rows - 1, 0] != 1 and want[0, 0] != 1    # -1 and -rows
    assert np.array_equal(np.asarray(new), want)
    assert int(written) == 4                     # rows 0, 3, 5, rows - 1
    with pytest.raises(ValueError, match="whole"):
        distinct_rows.add_rows(jnp.ones((60, 100)), ids, lambda lanes: upd,
                               interpret=True)


def _zipf_corpus(v=400, tokens=12_000, seed=0):
    from multiverso_tpu.data.native import CorpusData
    rng = np.random.default_rng(seed)
    ids = np.minimum(rng.zipf(1.3, tokens) - 1, v - 1).astype(np.int32)
    counts = np.maximum(np.bincount(ids, minlength=v), 1).astype(np.int64)
    return Corpus(CorpusData(words=[f"w{i}" for i in range(v)],
                             counts=counts, ids=ids,
                             total_raw_tokens=len(ids)), subsample=0)


def _small_config(model, objective):
    return W2VConfig(embedding_dim=20, window=2, negative=3, batch_size=64,
                     steps_per_call=4, learning_rate=0.1, epochs=1,
                     subsample=0, seed=3, model=model, objective=objective,
                     max_code_len=24)


@pytest.mark.parametrize("model,objective", [
    ("skipgram", "ns"), ("cbow", "ns"), ("skipgram", "hs"), ("cbow", "hs")])
def test_writer_path_reaches_the_scatter_paths_tables(devices, model,
                                                      objective):
    """Three fused calls on ONE device (rows through the writer) against
    the same calls on eight (XLA's scatter, the parent's path): losses
    to 1e-6, both tables to 1e-6 of their norm. Both hold their tables
    in whole tiles, and the padding rows and columns are still 0."""
    from multiverso_tpu import core
    got = {}
    for n in (1, 8):
        mesh = core.init(devices=devices[:n], data_parallel=n,
                         model_parallel=1)
        try:
            app = WordEmbedding(_zipf_corpus(), _small_config(
                model, objective), mesh=mesh, name=f"w2v_{n}")
            assert app._whole == (n == 1)
            app.train(total_steps=12)
            for table in (app.w_in, app.w_out):
                held = np.asarray(table.raw())
                assert held.shape == (408, 128)
                assert not held[400:407].any() and not held[:, 20:].any()
            got[n] = (np.asarray(app.loss_history), app.embeddings(),
                      app.w_out.get())
        finally:
            table_base.reset_tables()
            core.shutdown()
    (l1, in1, out1), (l8, in8, out8) = got[1], got[8]
    assert in1.shape == in8.shape == out1.shape == (400, 20)
    np.testing.assert_allclose(l1, l8, rtol=1e-6)
    for a, b in ((in1, in8), (out1, out8)):
        assert np.linalg.norm(a - b) <= 1e-6 * np.linalg.norm(b)


def _scatter_counters():
    from multiverso_tpu import telemetry
    snap = telemetry.snapshot()["counters"]
    return {(name, table): snap.get(
        f"w2v.scatter.{name}{{table={table}}}", 0.0)
        for name in ("rows", "rows_written") for table in ("in", "out")}


def test_sharded_tables_keep_the_scatter_and_still_train(mesh8):
    """Tables sharded over the model axis (4 x 2): the writer does not
    run (GSPMD cannot split its call), the loss falls, and the counters
    say so: XLA's scatter writes a row a lane."""
    before = _scatter_counters()
    cfg = _small_config("skipgram", "ns")
    cfg.epochs = 4
    app = WordEmbedding(_zipf_corpus(), cfg, mesh=mesh8, name="w2v_4x2")
    assert not app._whole and app.w_in.get().shape == (400, 20)
    app.train()
    hist = app.loss_history
    assert np.all(np.isfinite(hist)) and len(hist) >= 6
    assert np.mean(hist[-3:]) < np.mean(hist[:3])
    grew = {k: v - before[k] for k, v in _scatter_counters().items()}
    lanes = app._step_no * cfg.batch_size
    assert grew[("rows", "in")] == grew[("rows_written", "in")] == lanes
    assert grew[("rows", "out")] == grew[("rows_written", "out")] \
        == lanes * (cfg.negative + 1)


def test_scatter_counters_read_what_numpy_unique_reads(mesh_one):
    """``w2v.scatter.rows`` / ``.rows_written`` of two calls against the
    calls' own ids: the centres from the host's batches, the targets and
    negatives drawn again with the calls' keys. (A step here is one
    kernel call of the writer, so rows written are the step's distinct
    rows.)"""
    import itertools
    import jax
    from multiverso_tpu.apps.word_embedding import table_sample

    cfg = _small_config("skipgram", "ns")
    app = WordEmbedding(_zipf_corpus(), cfg, mesh=mesh_one, name="w2v_cnt")
    S, B, K = cfg.steps_per_call, cfg.batch_size, cfg.negative
    before = _scatter_counters()
    app.train(total_steps=2 * S)
    grew = {k: v - before[k] for k, v in _scatter_counters().items()}
    batches = list(itertools.islice(app._batches(), 2 * S))
    want_in = want_out = 0
    for call in range(2):
        keys = jax.random.split(jax.random.fold_in(app._key, call), S)
        for step in range(S):
            src, tgt = batches[call * S + step]
            negs = np.asarray(table_sample(keys[step], app._ns_table,
                                           (B, K)))
            want_in += len(np.unique(src))
            want_out += len(np.unique(np.concatenate([tgt, negs.ravel()])))
    assert grew[("rows", "in")] == 2 * S * B
    assert grew[("rows", "out")] == 2 * S * B * (K + 1)
    assert grew[("rows_written", "in")] == want_in
    assert grew[("rows_written", "out")] == want_out
    assert want_in < 2 * S * B and want_out < 2 * S * B * (K + 1)


@pytest.mark.parametrize("stored_on,loaded_on", [(1, 8), (8, 1), (0, 1),
                                                 (0, 8)])
def test_checkpoints_load_across_meshes_and_table_layouts(
        devices, tmp_path, stored_on, loaded_on):
    """A trainer's tables are [V, D] whatever holds them: a checkpoint
    stored on one device loads on a 4 x 2 mesh and the other way round,
    and one of plain [V + 1, D] tables (``stored_on`` 0: what a trainer
    wrote before its tables were held in whole tiles) loads on both."""
    from multiverso_tpu import core
    from multiverso_tpu.tables import MatrixTable

    def mesh_of(n):
        return core.init(devices=devices[:max(n, 1)],
                         data_parallel=4 if n == 8 else 1,
                         model_parallel=2 if n == 8 else 1)
    cfg = _small_config("skipgram", "ns")
    prefix = f"file://{tmp_path}/w2v"
    mesh = mesh_of(stored_on)
    try:
        if stored_on:
            app = WordEmbedding(_zipf_corpus(), cfg, mesh=mesh, name="w2v_s")
            app.train(total_steps=8)
            want = app.w_in.get(), app.w_out.get()
            app.store(prefix)
        else:
            rng = np.random.default_rng(4)
            want = tuple(rng.normal(size=(400, 20)).astype(np.float32)
                         for _ in range(2))
            for side, value in zip(("in", "out"), want):
                plain = MatrixTable(400, 20, init_value=value, mesh=mesh,
                                    updater="default", name=f"plain_{side}")
                assert plain.padded_shape == (401, 20)
                plain.store(f"{prefix}.{side}.npz")
    finally:
        table_base.reset_tables()
        core.shutdown()
    mesh = mesh_of(loaded_on)
    try:
        app = WordEmbedding(_zipf_corpus(), cfg, mesh=mesh, name="w2v_l")
        app.load(prefix)
        assert app.w_in.padded_shape == (
            (408, 128) if loaded_on == 1 else (416, 128))
        for table, value in zip((app.w_in, app.w_out), want):
            assert np.array_equal(table.get(), value)
            held = np.asarray(table.raw())
            assert not held[400:].any() and not held[:, 20:].any()
        app.train(total_steps=4)            # and trains on from there
        assert np.all(np.isfinite(app.loss_history))
    finally:
        table_base.reset_tables()
        core.shutdown()
