"""apps/lightlda: parsing, count invariants, convergence vs a sequential
numpy collapsed-Gibbs oracle (the strongest correctness check: the
batch-parallel TPU sampler must mix like sequential Gibbs)."""

import numpy as np
import pytest

from multiverso_tpu import core, telemetry
from multiverso_tpu.apps.lightlda import LDAConfig, LightLDA, load_docs
from multiverso_tpu.data.corpus import synthetic_docs
from multiverso_tpu.tables import base as table_base


@pytest.fixture(autouse=True)
def _clean_tables():
    yield
    table_base.reset_tables()


# the doc-blocked sampler at this file's corpus: 8 blocks a step
_TILED = dict(num_topics=128, batch_tokens=2048, steps_per_call=2, seed=1,
              sampler="tiled", block_tokens=256, block_docs=8)


@pytest.fixture(scope="module")
def docs(tmp_path_factory):
    path = tmp_path_factory.mktemp("lda") / "docs.txt"
    synthetic_docs(str(path), num_docs=150, vocab_size=300,
                   avg_doc_len=40, num_topics=8, seed=0)
    return load_docs(str(path))


def test_load_docs(tmp_path):
    p = tmp_path / "d.txt"
    p.write_text("0:2 3:1\n1:1\n")
    tw, td, vocab = load_docs(str(p))
    assert vocab == 4
    assert list(tw) == [0, 0, 3, 1]   # count 2 expands to two tokens
    assert list(td) == [0, 0, 0, 1]


def test_invariants_after_training(mesh_dp8, docs):
    tw, td, V = docs
    app = LightLDA(tw, td, V,
                   LDAConfig(num_topics=8, batch_tokens=512,
                             steps_per_call=4, seed=1), mesh=mesh_dp8,
                   name="lda_inv")
    app.train(num_iterations=3)
    nwk = app.word_topics()
    nk = np.asarray(app.summary.get())
    ndk = app.doc_topics()
    assert nwk.sum() == app.num_tokens
    assert np.array_equal(nk[: app.K], nwk.sum(0))
    assert np.array_equal(ndk.sum(1),
                          np.bincount(td, minlength=app.num_docs))
    assert (nwk >= 0).all() and (ndk >= 0).all() and (nk >= 0).all()


def test_loglik_rises(mesh_dp8, docs):
    tw, td, V = docs
    app = LightLDA(tw, td, V,
                   LDAConfig(num_topics=8, batch_tokens=512,
                             steps_per_call=4, seed=2), mesh=mesh_dp8,
                   name="lda_ll")
    app.train(num_iterations=8)
    assert app.ll_history[-1] > app.ll_history[0]
    assert np.all(np.isfinite(app.ll_history))


def test_matches_sequential_gibbs_oracle(mesh_dp8, docs):
    """After the same number of sweeps, the batch-parallel sampler must
    reach the same likelihood as sequential collapsed Gibbs."""
    tw, td, V = docs
    K = 8
    alpha, beta = 50.0 / K, 0.01
    sweeps = 12

    # -- numpy sequential oracle
    D, T = td.max() + 1, len(tw)
    rng = np.random.default_rng(1)
    z = rng.integers(0, K, T)
    nwk = np.zeros((V, K), np.int64)
    ndk = np.zeros((D, K), np.int64)
    nk = np.zeros(K, np.int64)
    np.add.at(nwk, (tw, z), 1)
    np.add.at(ndk, (td, z), 1)
    np.add.at(nk, z, 1)
    for _ in range(sweeps):
        for i in range(T):
            w, d = tw[i], td[i]
            k = z[i]
            nwk[w, k] -= 1
            ndk[d, k] -= 1
            nk[k] -= 1
            p = (ndk[d] + alpha) * (nwk[w] + beta) / (nk + V * beta)
            k = rng.choice(K, p=p / p.sum())
            z[i] = k
            nwk[w, k] += 1
            ndk[d, k] += 1
            nk[k] += 1
    theta = (ndk + alpha) / (ndk.sum(1, keepdims=True) + K * alpha)
    phi = (nwk + beta) / (nk + V * beta)
    oracle_ll = float(np.mean(np.log((theta[td] * phi[tw]).sum(1))))

    # -- ours
    app = LightLDA(tw, td, V,
                   LDAConfig(num_topics=K, batch_tokens=512,
                             steps_per_call=4, seed=1), mesh=mesh_dp8,
                   name="lda_oracle")
    app.train(num_iterations=sweeps)
    ours = app.ll_history[-1]
    assert ours > oracle_ll - 0.1, \
        f"batch sampler ll {ours:.4f} vs oracle {oracle_ll:.4f}"


def test_docblock_sampler_invariants_and_quality(mesh_dp8, docs):
    """doc_blocked: whole-doc kernel blocks own exclusive doc-count
    slices; all invariants must hold at sweep boundaries and mixing must
    stay near the exact-Gibbs level."""
    tw, td, V = docs
    app = LightLDA(tw, td, V,
                   LDAConfig(num_topics=128, batch_tokens=2048,
                             steps_per_call=2, seed=1, sampler="tiled",
                             doc_blocked=True, block_tokens=256,
                             block_docs=8),
                   mesh=mesh_dp8, name="lda_db")
    app.train(num_iterations=8)
    nwk = app.word_topics()
    nk = np.asarray(app.summary.get())
    ndk = app.doc_topics()
    assert nwk.sum() == app.num_tokens
    assert np.array_equal(nk[: app.K], nwk.sum(0))
    assert np.array_equal(ndk.sum(1),
                          np.bincount(td, minlength=app.num_docs))
    assert (nwk >= 0).all() and (ndk >= 0).all() and (nk >= 0).all()
    assert app.ll_history[-1] > app.ll_history[0] + 0.1
    assert app.ll_history[-1] > -4.9, app.ll_history


def test_docblock_checkpoint_roundtrip(mesh_dp8, docs, tmp_path):
    tw, td, V = docs
    cfg = LDAConfig(num_topics=128, batch_tokens=2048, steps_per_call=2,
                    seed=3, sampler="tiled", doc_blocked=True,
                    block_tokens=256, block_docs=8)
    app = LightLDA(tw, td, V, cfg, mesh=mesh_dp8, name="lda_dbc1")
    app.train(num_iterations=2)
    prefix = str(tmp_path / "db_ckpt")
    app.store(prefix)
    app2 = LightLDA(tw, td, V, cfg, mesh=mesh_dp8, name="lda_dbc2")
    app2.load(prefix)
    np.testing.assert_array_equal(app2.word_topics(), app.word_topics())
    np.testing.assert_array_equal(app2.doc_topics(), app.doc_topics())
    # resumed in mid-run: the device call counter is seeded from the
    # stored calls_done, so the next sweep draws what the run that never
    # stopped draws
    assert int(app2._calls_dev) == app2._calls_done == app._calls_done
    app.train(num_iterations=1)
    app2.train(num_iterations=1)
    assert app2.word_topics().sum() == app2.num_tokens
    np.testing.assert_array_equal(app2.assignments(), app.assignments())
    # layout mismatch rejected: a gibbs app's z is indexed in its own
    # shuffled stream and can't take this one
    app3 = LightLDA(tw, td, V,
                    LDAConfig(num_topics=128, batch_tokens=512,
                              steps_per_call=4, seed=3),
                    mesh=mesh_dp8, name="lda_dbc3")
    with pytest.raises(ValueError, match="layout"):
        app3.load(prefix)


def test_docblock_rejects_oversized_docs(mesh_dp8):
    tw = np.zeros(600, np.int32)
    td = np.zeros(600, np.int32)  # one 600-token doc > block_tokens
    with pytest.raises(ValueError, match="block_tokens"):
        LightLDA(tw, td, 1,
                 LDAConfig(num_topics=128, batch_tokens=2048,
                           sampler="tiled", doc_blocked=True,
                           block_tokens=256),
                 mesh=mesh_dp8, name="lda_dbbig")


def test_stale_words_rejects_giant_docs(mesh_dp8):
    """Doc counts are int16: a document the block geometry would admit
    (block_tokens above 32,767) is still refused."""
    tw = np.zeros(40000, np.int32)
    td = np.zeros(40000, np.int32)  # one 40k-token document
    with pytest.raises(ValueError, match="32767"):
        LightLDA(tw, td, 1,
                 LDAConfig(num_topics=128, sampler="tiled",
                           block_tokens=40960, batch_tokens=8 * 40960),
                 mesh=mesh_dp8, name="lda_giant")


def test_tiled_requires_lane_aligned_topics(mesh_dp8, docs):
    tw, td, V = docs
    with pytest.raises(ValueError, match="128"):
        LightLDA(tw, td, V, LDAConfig(num_topics=100, sampler="tiled"),
                 mesh=mesh_dp8, name="lda_tiled_bad")


def test_dump_model_sparse_format(mesh_dp8, docs, tmp_path):
    """The reference-style sparse model dump must reconstruct the dense
    word-topic counts exactly (it rides the sparse Get: only nonzero
    entries leave the device)."""
    tw, td, V = docs
    app = LightLDA(tw, td, V,
                   LDAConfig(num_topics=8, batch_tokens=512,
                             steps_per_call=4, seed=6),
                   mesh=mesh_dp8, name="lda_dump")
    app.train(num_iterations=2)
    uri = str(tmp_path / "model.txt")
    app.dump_model(uri, rows_per_fetch=64)
    dense = app.word_topics()
    got = np.zeros_like(dense)
    with open(uri) as f:
        lines = f.read().splitlines()
    assert len(lines) == V
    for ln in lines:
        parts = ln.split()
        w = int(parts[0])
        for tok in parts[1:]:
            k, v = tok.split(":")
            got[w, int(k)] = int(v)
    np.testing.assert_array_equal(got, dense)


def test_eval_every_cadence(mesh_dp8, docs):
    tw, td, V = docs
    app = LightLDA(tw, td, V,
                   LDAConfig(num_topics=8, batch_tokens=512,
                             steps_per_call=4, seed=5, eval_every=3),
                   mesh=mesh_dp8, name="lda_cadence")
    app.train(num_iterations=7)
    # evals at sweeps 3, 6 and the final 7th
    assert len(app.ll_history) == 3
    assert np.all(np.isfinite(app.ll_history))


def test_bad_precision_rejected(mesh_dp8, docs):
    tw, td, V = docs
    with pytest.raises(ValueError, match="precision"):
        LightLDA(tw, td, V, LDAConfig(num_topics=8, batch_tokens=512,
                                      precision="bf16"), mesh=mesh_dp8,
                 name="lda_badprec")


def test_checkpoint_roundtrip(mesh_dp8, docs, tmp_path):
    tw, td, V = docs
    app = LightLDA(tw, td, V,
                   LDAConfig(num_topics=8, batch_tokens=512,
                             steps_per_call=4, seed=3), mesh=mesh_dp8,
                   name="lda_ckpt")
    app.train(num_iterations=2)
    app.store(f"file://{tmp_path}/lda")
    nwk = app.word_topics()
    app2 = LightLDA(tw, td, V,
                    LDAConfig(num_topics=8, batch_tokens=512,
                              steps_per_call=4, seed=3), mesh=mesh_dp8,
                    name="lda_ckpt2")
    app2.load(f"file://{tmp_path}/lda")
    np.testing.assert_array_equal(app2.word_topics(), nwk)
    np.testing.assert_array_equal(app2.doc_topics(), app.doc_topics())
    # resumed sweeps must keep counts consistent (no negative counts)
    app2.train(num_iterations=1)
    assert (app2.word_topics() >= 0).all()
    # mismatched seed must be rejected (z permutation would not line up)
    app3 = LightLDA(tw, td, V,
                    LDAConfig(num_topics=8, batch_tokens=512,
                              steps_per_call=4, seed=9), mesh=mesh_dp8,
                    name="lda_ckpt3")
    with pytest.raises(ValueError, match="seed"):
        app3.load(f"file://{tmp_path}/lda")


def test_batch_divisibility_error(mesh_dp8, docs):
    tw, td, V = docs
    with pytest.raises(ValueError, match="divisible"):
        LightLDA(tw, td, V,
                 LDAConfig(num_topics=8, batch_tokens=100,
                           steps_per_call=2), mesh=mesh_dp8,
                 name="lda_bad")


def test_top_words_shape(mesh_dp8, docs):
    tw, td, V = docs
    app = LightLDA(tw, td, V,
                   LDAConfig(num_topics=8, batch_tokens=512,
                             steps_per_call=4), mesh=mesh_dp8,
                   name="lda_top")
    app.train(num_iterations=1)
    top = app.top_words(0, k=5)
    assert top.shape == (5,)
    assert (top < V).all()


def test_docblock_zero_token_corpus(mesh_dp8):
    # regression: doc_ends broadcast ValueError on an empty stream
    tw = np.zeros(0, np.int32)
    td = np.zeros(0, np.int32)
    lda = LightLDA(tw, td, 4,
                   LDAConfig(num_topics=128, batch_tokens=2048,
                             sampler="tiled", doc_blocked=True,
                             block_tokens=256),
                   mesh=mesh_dp8, name="lda_empty")
    lda.sweep()


def _sub_mesh(devices, dp, mp):
    return core.init(devices=devices[:dp * mp], data_parallel=dp,
                     model_parallel=mp)


def _docblock_state(app):
    """What a sweep leaves, in the caller's terms: every token's topic,
    the word-topic table, the doc-topic counts, the topic summary."""
    return (app.assignments(), app.word_topics(), app.doc_topics(),
            np.asarray(app.summary.get()))


@pytest.fixture(scope="module")
def docblock_two_sweeps(devices, docs):
    """Two sweeps of the STREAMED doc-blocked sampler on one device: the
    other residency (host z, the count-building kernel, no shard_map) of
    the same draws — the state every resident mesh must land on."""
    tw, td, V = docs
    mesh = _sub_mesh(devices, 1, 1)
    app = LightLDA(tw, td, V, LDAConfig(**_TILED, stream_blocks=True),
                   mesh=mesh, name="lda_mesh_ref")
    app.train(num_iterations=2)
    state = _docblock_state(app) + (app.ll_history[-1],)
    table_base.reset_tables()
    core.shutdown()
    return state


@pytest.mark.parametrize("dp,mp", [(8, 1), (4, 2), (2, 4), (1, 8), (2, 2),
                                   (1, 2), (1, 1)])
def test_docblock_model_parallel_matches_dp(devices, docs,
                                            docblock_two_sweeps, dp, mp):
    """A step's blocks are split over every chip of the mesh, data x
    model, and which chip samples a block must not change what it
    samples (the uniforms and the start are made for the whole step and
    sliced; the summary delta's psum and the rebuild's are integer; the
    replicated bf16 mirror holds the same rows): z and all counts are
    BIT-IDENTICAL on every mesh shape the eight devices allow, and equal
    to the streamed path's on one device."""
    tw, td, V = docs
    ref_z, ref_w, ref_d, ref_nk, ref_ll = docblock_two_sweeps
    mesh = _sub_mesh(devices, dp, mp)
    app = LightLDA(tw, td, V, LDAConfig(**_TILED), mesh=mesh,
                   name=f"lda_mesh_{dp}x{mp}")
    # the counter of the mechanism: every chip samples its own blocks
    assert telemetry.gauge("lda.sample.chips").value == dp * mp
    (shard,) = {s.data.shape for s in app._ndk.addressable_shards}
    assert shard[1] * dp * mp == app._ndk.shape[1] == 2048 // 256
    app.train(num_iterations=2)
    z, w, d, nk = _docblock_state(app)
    np.testing.assert_array_equal(z, ref_z)
    np.testing.assert_array_equal(w, ref_w)
    np.testing.assert_array_equal(d, ref_d)
    np.testing.assert_array_equal(nk, ref_nk)
    np.testing.assert_allclose(app.ll_history[-1], ref_ll, rtol=1e-5)
    table_base.reset_tables()
    core.shutdown()


def test_blocks_a_step_must_divide_by_the_chips_that_split_them(devices,
                                                                docs):
    """4 blocks a step on a 4x2 mesh: the resident sweep needs them to
    divide by data x model and refuses; the streamed one splits over
    data alone and takes them."""
    tw, td, V = docs
    small = dict(_TILED, batch_tokens=1024)         # 4 blocks a step
    mesh = _sub_mesh(devices, 4, 2)
    with pytest.raises(ValueError, match="4 not divisible by the 8 chips"):
        LightLDA(tw, td, V, LDAConfig(**small), mesh=mesh,
                 name="lda_split_bad")
    app = LightLDA(tw, td, V, LDAConfig(**small, stream_blocks=True),
                   mesh=mesh, name="lda_split_stream")
    assert telemetry.gauge("lda.sample.chips").value == 4
    assert app._block_axes == (core.DATA_AXIS,)
    table_base.reset_tables()
    core.shutdown()


@pytest.mark.parametrize("dp,mp", [(4, 2), (2, 2)])
def test_docblock_checkpoint_roundtrip_across_meshes(devices, docs,
                                                     tmp_path, dp, mp):
    """The stored sampler state knows nothing of how the blocks lie over
    the chips (z flat in packed block order, doc counts dense [D, K]): a
    state stored on a dp x mp mesh resumes on it, and on another shape,
    to the same bits as the run that never stopped."""
    tw, td, V = docs
    cfg = LDAConfig(**dict(_TILED, seed=3))
    mesh = _sub_mesh(devices, dp, mp)
    app = LightLDA(tw, td, V, cfg, mesh=mesh, name="lda_ckm1")
    app.train(num_iterations=2)
    prefix = str(tmp_path / "ckm")
    app.store(prefix)
    app.train(num_iterations=1)
    want = _docblock_state(app)
    table_base.reset_tables()
    core.shutdown()
    for shape in ((dp, mp), (8, 1)):
        mesh = _sub_mesh(devices, *shape)
        app2 = LightLDA(tw, td, V, cfg, mesh=mesh, name="lda_ckm2")
        app2.load(prefix)
        assert app2._z.sharding.spec[1] == (core.DATA_AXIS,
                                            core.MODEL_AXIS)
        app2.train(num_iterations=1)
        for got, ref in zip(_docblock_state(app2), want):
            np.testing.assert_array_equal(got, ref)
        table_base.reset_tables()
        core.shutdown()


def test_a_state_stored_by_the_parent_layout_resumes(devices, docs):
    """``tests/data/lda_pr30/`` was written by PR 30's tree — z
    ``[nb_pad, TB]`` and the doc counts ``[nb_pad, MAXD, C, 128]`` whole
    on every chip of a dp8 mesh — after two sweeps at this file's corpus
    (``LDAConfig(**_TILED, seed=3)``), with every token's topic after
    that tree's third sweep beside it. What is stored is z flat in
    packed block order and the counts dense [D, K]: the block-major,
    data x model layout loads it unchanged and sweeps on to the same
    bits."""
    import os
    tw, td, V = docs
    prefix = os.path.join(os.path.dirname(__file__), "data", "lda_pr30",
                          "pr30_dp8")
    mesh = _sub_mesh(devices, 4, 2)
    app = LightLDA(tw, td, V, LDAConfig(**dict(_TILED, seed=3)),
                   mesh=mesh, name="lda_from_pr30")
    app.load(prefix)
    assert app._z.ndim == 3 and app._ndk.ndim == 5
    assert app.word_topics().sum() == app.num_tokens
    np.testing.assert_array_equal(
        app.doc_topics().sum(1), np.bincount(td, minlength=app.num_docs))
    app.train(num_iterations=1)
    np.testing.assert_array_equal(
        app.assignments(), np.load(prefix + ".next_sweep_z.npy"))
    table_base.reset_tables()
    core.shutdown()


def test_docblock_streamed_matches_inmemory(mesh_dp8, docs):
    """Out-of-core mode (host-resident stream/z/doc-counts, per-call
    staging, on-device count rebuild, incremental master updates) must be
    BIT-IDENTICAL to the in-memory mode: same kernel sequence, same RNG,
    and the doc counts are a pure function of z at call boundaries."""
    tw, td, V = docs
    kw = dict(num_topics=128, batch_tokens=2048, steps_per_call=2,
              seed=1, sampler="tiled", doc_blocked=True,
              block_tokens=256, block_docs=8)
    ref = LightLDA(tw, td, V, LDAConfig(**kw), mesh=mesh_dp8,
                   name="db_ref")
    ref.train(num_iterations=3)
    ref_w, ref_d = ref.word_topics(), ref.doc_topics()
    ref_nk = np.asarray(ref.summary.get())
    ref_z = np.asarray(ref._z)      # [steps, blocks a step, TB]
    table_base.reset_tables()

    app = LightLDA(tw, td, V, LDAConfig(**kw, stream_blocks=True),
                   mesh=mesh_dp8, name="db_stream")
    app.train(num_iterations=3)
    # the same packed block order, row-major, under either residency
    np.testing.assert_array_equal(app._z_host,
                                  ref_z.reshape(app._z_host.shape))
    np.testing.assert_array_equal(app.word_topics(), ref_w)
    np.testing.assert_array_equal(app.doc_topics(), ref_d)
    np.testing.assert_array_equal(np.asarray(app.summary.get()), ref_nk)
    np.testing.assert_allclose(app.ll_history, ref.ll_history, rtol=1e-6)


def test_docblock_streamed_model_parallel(devices, docs):
    """Streamed mode on a dp x mp mesh equals the streamed pure-DP run
    (sharded master-delta scatters are integer-exact; the sweep reads
    the replicated mirror, eval the sharded master)."""
    from multiverso_tpu import core
    tw, td, V = docs
    kw = dict(num_topics=128, batch_tokens=2048, steps_per_call=2,
              seed=1, sampler="tiled", doc_blocked=True,
              block_tokens=256, block_docs=8, stream_blocks=True)
    mesh_dp = core.init(devices=devices, data_parallel=8,
                        model_parallel=1)
    ref = LightLDA(tw, td, V, LDAConfig(**kw), mesh=mesh_dp,
                   name="dbs_ref")
    ref.train(num_iterations=2)
    ref_w, ref_z = ref.word_topics(), ref._z_host.copy()
    table_base.reset_tables()
    core.shutdown()

    mesh_mp = core.init(devices=devices, data_parallel=4,
                        model_parallel=2)
    app = LightLDA(tw, td, V, LDAConfig(**kw), mesh=mesh_mp,
                   name="dbs_mp")
    app.train(num_iterations=2)
    np.testing.assert_array_equal(app._z_host, ref_z)
    np.testing.assert_array_equal(app.word_topics(), ref_w)
    table_base.reset_tables()
    core.shutdown()


def test_local_corpus_single_process(mesh_dp8, docs):
    """local_corpus on one process owns every lane — count invariants
    hold, training improves, and the run is deterministic."""
    tw, td, V = docs
    kw = dict(num_topics=128, batch_tokens=2048, steps_per_call=2,
              seed=1, sampler="tiled", doc_blocked=True,
              block_tokens=256, block_docs=8, stream_blocks=True,
              local_corpus=True)
    app = LightLDA(tw, td, V, LDAConfig(**kw), mesh=mesh_dp8,
                   name="lc_a")
    app.train(num_iterations=3)
    nwk = app.word_topics()
    assert nwk.sum() == app.num_tokens
    # host recount of (tw, z) must equal the device-side master
    recount = np.zeros((V, app.K), np.int64)
    valid = app._tw_host < V
    np.add.at(recount, (app._tw_host[valid], app._z_host[valid]), 1)
    np.testing.assert_array_equal(recount, nwk.astype(np.int64))
    assert app.ll_history[-1] > app.ll_history[0]
    dt = app.doc_topics()
    lens = np.bincount(td, minlength=app.num_docs)
    np.testing.assert_array_equal(dt.sum(1), lens)
    table_base.reset_tables()

    app2 = LightLDA(tw, td, V, LDAConfig(**kw), mesh=mesh_dp8,
                    name="lc_b")
    app2.train(num_iterations=3)
    np.testing.assert_array_equal(app2.word_topics(), nwk)


def test_local_corpus_checkpoint_roundtrip(mesh_dp8, docs, tmp_path):
    """local_corpus store/load: per-rank z shard (no global dense ndk);
    resumed training continues deterministically."""
    tw, td, V = docs
    kw = dict(num_topics=128, batch_tokens=2048, steps_per_call=2,
              seed=1, sampler="tiled", doc_blocked=True,
              block_tokens=256, block_docs=8, stream_blocks=True,
              local_corpus=True)
    app = LightLDA(tw, td, V, LDAConfig(**kw), mesh=mesh_dp8,
                   name="lcc_a")
    app.train(num_iterations=2)
    app.store(str(tmp_path / "ck"))
    app.train(num_iterations=1)
    want = app.word_topics()
    table_base.reset_tables()

    app2 = LightLDA(tw, td, V, LDAConfig(**kw), mesh=mesh_dp8,
                    name="lcc_b")
    app2.load(str(tmp_path / "ck"))
    app2.train(num_iterations=1)
    np.testing.assert_array_equal(app2.word_topics(), want)


def test_local_corpus_requires_stream(mesh_dp8, docs):
    tw, td, V = docs
    with pytest.raises(ValueError, match="local_corpus requires"):
        LightLDA(tw, td, V,
                 LDAConfig(num_topics=128, batch_tokens=2048,
                           steps_per_call=2, sampler="tiled",
                           doc_blocked=True, local_corpus=True),
                 mesh=mesh_dp8, name="lc_bad")


def test_docblock_streamed_checkpoint_crossmode(mesh_dp8, docs, tmp_path):
    """A streamed checkpoint resumes in an in-memory app (same packed z
    layout) and vice versa."""
    tw, td, V = docs
    kw = dict(num_topics=128, batch_tokens=2048, steps_per_call=2,
              seed=3, sampler="tiled", doc_blocked=True,
              block_tokens=256, block_docs=8)
    app = LightLDA(tw, td, V, LDAConfig(**kw, stream_blocks=True),
                   mesh=mesh_dp8, name="dbs_ck1")
    app.train(num_iterations=2)
    prefix = str(tmp_path / "dbs_ckpt")
    app.store(prefix)
    z_after = app._z_host.copy()
    # the run that never stops: its next sweep is what either resume
    # must draw (the device call counter re-seeded from calls_done)
    app.train(num_iterations=1)
    z_next = app._z_host.copy()
    table_base.reset_tables()

    mem = LightLDA(tw, td, V, LDAConfig(**kw), mesh=mesh_dp8,
                   name="dbs_ck2")
    mem.load(prefix)
    np.testing.assert_array_equal(
        np.asarray(mem._z).reshape(z_after.shape), z_after)
    mem.train(num_iterations=1)
    ref_w = mem.word_topics()
    np.testing.assert_array_equal(
        np.asarray(mem._z).reshape(z_next.shape), z_next)
    table_base.reset_tables()

    # and back into a streamed app: one more sweep must match in-memory
    st = LightLDA(tw, td, V, LDAConfig(**kw, stream_blocks=True),
                  mesh=mesh_dp8, name="dbs_ck3")
    st.load(prefix)
    st.train(num_iterations=1)
    np.testing.assert_array_equal(st.word_topics(), ref_w)
    np.testing.assert_array_equal(st._z_host, z_next)
    assert int(st._calls_dev) == st._calls_done


# -- the call's key, folded in on the device --------------------------------

@pytest.mark.parametrize("streamed", [False, True])
def test_device_call_counter_draws_the_host_folded_keys(
        mesh_dp8, docs, monkeypatch, streamed):
    """Call i's key is ``fold_in(key, i)`` folded INSIDE the superstep
    from the int32 counter it carries: two sweeps give z, the doc
    counts, the word table and the summary bit for bit as the same run
    whose keys are folded on the host from a Python int (the parent's
    way, here by handing the program the folded key and making its own
    fold the identity)."""
    import jax
    tw, td, V = docs
    cfg = LDAConfig(**_TILED, stream_blocks=streamed)
    app = LightLDA(tw, td, V, cfg, mesh=mesh_dp8, name="lda_dev_keys")
    app.train(num_iterations=2)
    want = _docblock_state(app)
    table_base.reset_tables()

    fold_in = jax.random.fold_in
    monkeypatch.setattr(jax.random, "fold_in", lambda key, data: key)
    ref = LightLDA(tw, td, V, cfg, mesh=mesh_dp8, name="lda_host_keys")
    attr = "_fused_stream" if streamed else "_fused"
    fused = getattr(ref, attr)

    def host_folded(locals_, *inputs):
        key = fold_in(inputs[-1], ref._calls_done)
        return fused(locals_, *inputs[:-1], key)

    setattr(ref, attr, host_folded)
    ref.train(num_iterations=2)
    for got, exp in zip(_docblock_state(ref), want):
        np.testing.assert_array_equal(got, exp)


@pytest.mark.parametrize("sampler", ["tiled", "tiled_streamed", "gibbs"])
def test_a_sweep_folds_no_key_on_the_host(mesh_dp8, docs, monkeypatch,
                                          sampler):
    """No ``fold_in`` runs outside a trace during ``sweep()`` (each such
    call was two programs launched per superstep call), and the device
    counter the supersteps carry equals the host's ``_calls_done``
    after every sweep."""
    import jax
    tw, td, V = docs
    if sampler == "gibbs":
        cfg = LDAConfig(num_topics=8, batch_tokens=512, steps_per_call=4,
                        seed=1)
    else:
        cfg = LDAConfig(**_TILED,
                        stream_blocks=sampler == "tiled_streamed")
    app = LightLDA(tw, td, V, cfg, mesh=mesh_dp8, name="lda_no_host_key")
    fold_in = jax.random.fold_in
    on_host = []

    def counted(key, data):
        if not isinstance(data, jax.core.Tracer):
            on_host.append(data)
        return fold_in(key, data)

    monkeypatch.setattr(jax.random, "fold_in", counted)
    for k in range(1, 4):
        app.sweep()
        assert app._calls_done == k * app.calls_per_sweep
        assert int(app._calls_dev) == app._calls_done
    assert on_host == []


def test_stream_blocks_requires_docblock(mesh_dp8):
    """Only the doc-blocked sampler streams: refused under gibbs."""
    with pytest.raises(ValueError, match="stream_blocks requires"):
        LightLDA(np.zeros(8, np.int32), np.zeros(8, np.int32), 4,
                 LDAConfig(num_topics=128, sampler="gibbs",
                           stream_blocks=True),
                 mesh=mesh_dp8, name="lda_sb_bad")


@pytest.mark.parametrize("sampler", ["mh", "alias"])
def test_removed_and_unknown_samplers_are_refused(mesh_dp8, docs, sampler):
    tw, td, V = docs
    with pytest.raises(ValueError, match=r"gibbs \| tiled"):
        LightLDA(tw, td, V, LDAConfig(num_topics=8, batch_tokens=512,
                                      sampler=sampler),
                 mesh=mesh_dp8, name="lda_no_such_sampler")


@pytest.fixture(scope="module")
def benchmark_keywords_z(devices, docs):
    """assignments() after one sweep of the sampler the benchmark's
    driver builds: sampler="tiled", stale_words=True, doc_blocked=True."""
    tw, td, V = docs
    mesh = core.init(devices=devices, data_parallel=8, model_parallel=1)
    app = LightLDA(tw, td, V, LDAConfig(**_TILED, stale_words=True,
                                        doc_blocked=True),
                   mesh=mesh, name="lda_kw_ref")
    app.sweep()
    z = app.assignments()
    table_base.reset_tables()
    core.shutdown()
    return z


@pytest.mark.parametrize("stale_words", [False, True])
@pytest.mark.parametrize("doc_blocked", [False, True])
def test_tiled_is_one_sampler_whatever_the_old_keywords(
        benchmark_keywords_z, mesh_dp8, docs, stale_words, doc_blocked):
    """``tiled`` MEANS the doc-blocked stale sampler: the two keywords it
    used to be spelled with change nothing it builds or draws."""
    tw, td, V = docs
    app = LightLDA(tw, td, V,
                   LDAConfig(**_TILED, stale_words=stale_words,
                             doc_blocked=doc_blocked),
                   mesh=mesh_dp8, name="lda_kw")
    assert app._docblock and app._fused.name == "lda_docblock"
    # blocked int16 doc counts [steps, blocks a step, MAXD, C, 128]
    assert app._ndk.dtype == np.int16 and app._ndk.ndim == 5
    app.sweep()
    np.testing.assert_array_equal(app.assignments(), benchmark_keywords_z)


def test_old_keywords_are_refused_under_gibbs(mesh_dp8, docs):
    tw, td, V = docs
    for kw in (dict(stale_words=True), dict(doc_blocked=True)):
        with pytest.raises(ValueError, match="stale_words/doc_blocked"):
            LightLDA(tw, td, V, LDAConfig(num_topics=8, batch_tokens=512,
                                          **kw),
                     mesh=mesh_dp8, name="lda_kw_gibbs")


def test_docblock_model_parallel_invariants_and_quality(mesh8, docs):
    """The doc-blocked sampler on a 4x2 mesh (int32 word table
    vocab-sliced over the model axis, its bf16 mirror whole on every
    chip) against the exact ``gibbs`` oracle on the same mesh: count
    invariants at sweep boundaries, and the oracle's likelihood level
    (word rows a sweep stale mix about half as fast a sweep: 20 sweeps
    for the oracle's 12)."""
    tw, td, V = docs
    oracle = LightLDA(tw, td, V,
                      LDAConfig(num_topics=128, batch_tokens=512,
                                steps_per_call=4, seed=1),
                      mesh=mesh8, name="lda_mp_oracle")
    oracle.train(num_iterations=12)
    table_base.reset_tables()
    app = LightLDA(tw, td, V, LDAConfig(**_TILED, eval_every=20),
                   mesh=mesh8, name="lda_mp_db")
    start = app.loglik()
    app.train(num_iterations=20)
    nwk = app.word_topics()
    nk = np.asarray(app.summary.get())
    ndk = app.doc_topics()
    assert nwk.sum() == app.num_tokens
    assert np.array_equal(nk[: app.K], nwk.sum(0))
    assert np.array_equal(ndk.sum(1),
                          np.bincount(td, minlength=app.num_docs))
    assert (nwk >= 0).all() and (ndk >= 0).all() and (nk >= 0).all()
    assert app.ll_history[-1] > start + 0.1
    assert app.ll_history[-1] > oracle.ll_history[-1] - 0.1, \
        (start, app.ll_history, oracle.ll_history)


@pytest.fixture(scope="module")
def mesh_v5e_2x2():
    """data=2 x model=2 over a DESCRIBED v5e 2x2: programs compile for
    the chip here, nothing runs (no chip needed)."""
    from jax.sharding import Mesh
    try:
        from jax.experimental import topologies
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception:       # noqa: BLE001
        pytest.skip("libtpu cannot describe a v5e topology here")
    return Mesh(np.asarray(topo.devices).reshape(2, 2),
                (core.DATA_AXIS, core.MODEL_AXIS))


def test_dp_mp_eval_compiles_for_a_v5e_2x2(mesh_v5e_2x2):
    """The doc-blocked eval on a data=2 x model=2 mesh (the sharded
    gather from the int32 master: partial gather + psum over the model
    axis), compiled ahead of time for a described v5e 2x2. On the first
    four-chip run XLA:TPU refused it ("Reshape should have supported
    layout before reaching the emitter") until the scanned chunks'
    sharding was stated before the loop (_chunked_ll); dp-only and
    mp-only meshes never showed it."""
    import types

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = mesh_v5e_2x2
    K, V, tiles, B = 1024, 50_000, 8, 307_200      # chip_smoke's widths
    vpad, nbs, steps = V + 2, 600, 5
    app = types.SimpleNamespace(
        mesh=mesh, K=K, V=V, alpha=50.0 / K, beta=0.01,
        word_topic=types.SimpleNamespace(storage_shape=(vpad, tiles, 128)))
    for method in ("_eval_chunk", "_chunked_ll", "_build_word_gather"):
        setattr(app, method,
                types.MethodType(getattr(LightLDA, method), app))
    LightLDA._build_blocked_loglik(app)

    def sds(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    # the operands as _setup_docblock places them: lanes and the doc
    # counts' blocks split over data x model, the counts [steps, blocks
    # a step, ..] of which the eval reads one call's steps
    axes = (core.DATA_AXIS, core.MODEL_AXIS)
    lanes = sds((1, B), jnp.int32, P(None, axes))
    text = app._loglik.trace(
        sds((vpad, tiles, 128), jnp.int32, P(core.MODEL_AXIS, None, None)),
        sds((steps, nbs, 16, tiles, 128), jnp.int16,
            P(None, axes, None, None, None)),
        sds((K,), jnp.int32, P(core.MODEL_AXIS)),
        lanes, lanes, lanes, sds((1,), jnp.int32)).lower().compile() \
        .as_text()
    # nothing larger than one call's window of doc counts is moved
    assert f"s16[{steps}," not in "".join(
        ln for ln in text.splitlines() if " all-gather" in ln)


@pytest.fixture(scope="module")
def superstep_v5e_2x2(mesh_v5e_2x2):
    """(the app's stand-in, ``lda.sample.chips`` as building it left the
    gauge, compiled text of the resident doc-blocked superstep) at
    chip_smoke's widths, compiled ONCE ahead of time for the described
    v5e 2x2, the operands sharded as ``_setup_docblock`` places them."""
    import types

    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh, steps = mesh_v5e_2x2, 4
    K, V, tiles, B, TB, MAXD = 1024, 50_000, 8, 307_200, 512, 16
    vpad, nbs = V + 2, B // TB
    app = types.SimpleNamespace(
        mesh=mesh, K=K, V=V, alpha=50.0 / K, beta=0.01, _tb=TB,
        _interpret=False, _account_mirror=lambda: None,  # no chip to ask
        config=LDAConfig(num_topics=K, batch_tokens=B, block_tokens=TB,
                         block_docs=MAXD, sampler="tiled",
                         doc_blocked=True),
        word_topic=types.SimpleNamespace(storage_shape=(vpad, tiles, 128)))
    for method in ("_wrap_docblock_dp", "_build_stale_helpers",
                   "_build_vocab_slice_scatter", "_split_blocks"):
        setattr(app, method,
                types.MethodType(getattr(LightLDA, method), app))
    app._split_blocks(nbs)
    chips = telemetry.gauge("lda.sample.chips").value
    LightLDA._build_docblock_kernel(app)
    axes = app._block_axes

    def sds(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    def run(nk, ndk, z, calls, wstale, ws, drels, msks, ts, base_key):
        keys = jax.random.split(jax.random.fold_in(base_key, calls),
                                ws.shape[0])
        (nk, ndk, z), _ = lax.scan(
            lambda cy, inp: app._db_scan_body(wstale, cy, inp),
            (nk, ndk, z), (ws, drels, msks, ts, keys))
        return nk, ndk, z, calls + 1

    lanes = sds((1, B), jnp.int32, P(None, axes))
    state = (sds((K,), jnp.int32),
             sds((steps, nbs, MAXD, tiles, 128), jnp.int16,
                 P(None, axes, None, None, None)),
             sds((steps, nbs, TB), jnp.int32, P(None, axes, None)),
             sds((), jnp.int32))
    text = jax.jit(
        run, donate_argnums=(0, 1, 2, 3),
        out_shardings=tuple(x.sharding for x in state)).trace(
        *state, sds((vpad, tiles, 128), jnp.bfloat16),
        lanes, lanes, lanes, sds((1,), jnp.int32),
        sds((2,), jnp.uint32)).lower().compile().as_text()
    return app, chips, text


def test_docblock_superstep_for_a_v5e_2x2_reduces_no_word_rows(
        mesh_v5e_2x2, superstep_v5e_2x2):
    """The doc-blocked sweep compiled ahead of time for a described v5e
    2x2 at chip_smoke's widths: ``to_stale`` casts and all-gathers the
    mirror over the model axis (bf16, once), and the superstep gathers
    from it locally — no all-reduce over gathered ``bf16[.., 8, 128]``
    rows, no select under ``lda.gather_words``. Before, every step
    psum'd its masked partial rows over the model axis (2 KB a lane)."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = mesh_v5e_2x2
    app, _, text = superstep_v5e_2x2
    vpad = 50_002
    stale = app._to_stale.trace(jax.ShapeDtypeStruct(
        (vpad, 8, 128), jnp.int32, sharding=NamedSharding(
            mesh, P(core.MODEL_AXIS, None, None)))).lower().compile()
    gathers = [ln for ln in stale.as_text().splitlines()
               if " all-gather(" in ln or " all-gather-start(" in ln]
    assert len(gathers) == 1 and f"bf16[{vpad},8,128]" in gathers[0], gathers
    (mirror_sharding,) = jax.tree.leaves(stale.output_shardings)
    assert mirror_sharding.is_fully_replicated
    assert "tpu_custom_call" in text        # the Mosaic kernel is in it
    assert not re.search(r"= bf16\[[0-9,]*8,128\]\S* all-reduce", text)
    scoped = [ln for ln in text.splitlines()
              if "jit(lda.gather_words)" in ln]
    assert [ln for ln in scoped if " gather(" in ln]
    assert not [ln for ln in scoped
                if " select(" in ln or " all-reduce" in ln
                or " all-gather" in ln]


def test_docblock_superstep_for_a_v5e_2x2_gives_each_chip_a_quarter(
        superstep_v5e_2x2):
    """The same compiled superstep, read for the partition: the sampler
    kernel of one chip sees ``nbs / (dp * mp)`` = 150 of the step's 600
    blocks (300 while the blocks were split over data alone), gathers
    150 x 512 word rows for them, and no window of z or of the doc
    counts crosses chips — the only collective left in the step is the
    all-reduce of the 1,024 topic-summary deltas."""
    import re
    app, chips, text = superstep_v5e_2x2
    assert app._block_axes == (core.DATA_AXIS, core.MODEL_AXIS)
    assert chips == 4
    kernel = [ln for ln in text.splitlines() if " custom-call(" in ln
              and "tpu_custom_call" in ln]
    assert kernel and all("s16[150,16,8,128]" in ln for ln in kernel), kernel
    gather = [ln for ln in text.splitlines()
              if "jit(lda.gather_words)" in ln and " gather(" in ln]
    assert gather and all("bf16[76800,8,128]" in ln for ln in gather), gather
    assert not re.search(r" all-gather(-start)?\(", text), \
        [ln for ln in text.splitlines() if " all-gather" in ln]
    reduces = [ln for ln in text.splitlines()
               if re.search(r" all-reduce(-start)?\(", ln)]
    assert reduces and all("s32[8,128]" in ln for ln in reduces), reduces


@pytest.mark.parametrize("bad", [-1, "V"])
def test_word_ids_outside_the_vocabulary_are_refused(mesh_dp8, docs, bad):
    """Out-of-range indices raise nothing on the device (reads clamp,
    updates drop), so the constructor owns the check every word-row
    gather and scatter leans on."""
    tw, td, V = docs
    tw = tw.copy()
    tw[7] = V if bad == "V" else bad
    with pytest.raises(ValueError, match=r"token_words must lie in \[0, "):
        LightLDA(tw, td, V, LDAConfig(num_topics=8, batch_tokens=512,
                                      steps_per_call=4),
                 mesh=mesh_dp8, name="lda_bad_word")


def test_a_mirror_that_cannot_fit_is_refused_with_both_sizes():
    from multiverso_tpu.apps.lightlda import _require_mirror_fits
    _require_mirror_fits(1 << 30, 1 << 29, None)     # backend says nothing
    _require_mirror_fits(1 << 30, 1 << 29,
                         {"bytes_limit": 3 << 30, "bytes_in_use": 1 << 30})
    with pytest.raises(ValueError) as e:
        _require_mirror_fits(1 << 30, 1 << 29,
                             {"bytes_limit": 3 << 30,
                              "bytes_in_use": (2 << 30) + 1})
    assert str(1 << 30) in str(e.value) and str(1 << 29) in str(e.value)
