"""Child process for the P-process multi-host test (SURVEY.md §5: the
mpirun-np-N analog extended to REAL multi-process — P local processes
with a CPU coordinator exercising init/barrier/table ops/logreg).

Run by tests/test_multihost.py:
    python _multihost_child.py <port> <pid> [<nprocs>=2]
(env: JAX_PLATFORMS=cpu, XLA_FLAGS=--xla_force_host_platform_device_count=2
 — 2 devices per process, so the global mesh has 2*P devices)

All the P-generic arithmetic (owned_axis_slices, allgather_i64, z-sync
slab exchange, local_data/local_corpus chunk ownership) runs here at
WHATEVER P the parent passes: several off-by-one/ordering bug classes
are invisible at P=2 (VERDICT r3 weak #5), so the parent runs P=2 and
P=4 with the same child.
"""

import sys

import numpy as np


def main() -> None:
    port, pid = int(sys.argv[1]), int(sys.argv[2])
    P = int(sys.argv[3]) if len(sys.argv) > 3 else 2
    n_dev = 2 * P                       # 2 virtual CPU devices per process

    import jax
    # pure CPU, whatever the environment says: a chip belongs to one
    # process at a time, and this test starts P of them
    jax.config.update("jax_platforms", "cpu")
    from multiverso_tpu import core
    from multiverso_tpu.tables import ArrayTable, KVTable, reset_tables

    mesh = core.init([f"-machine_file=127.0.0.1:{port}",
                      f"-num_processes={P}", f"-process_id={pid}",
                      f"-data_parallel={P}", "-model_parallel=2"])
    assert jax.process_count() == P, jax.process_count()
    assert len(jax.devices()) == n_dev, jax.devices()
    assert core.size() == P and core.rank() == pid
    assert core.num_workers() == n_dev and core.num_servers() == n_dev

    core.barrier()

    # P-agnostic app phases (logreg, sparse LR, dense w2v) run at P=2
    # only: P=4 exists to exercise the P-GENERIC arithmetic (owned lane
    # offsets, z-sync slabs, local_data/local_corpus ownership), and the
    # single-core CI host pays ~P x compile for every extra phase
    full = P <= 2

    # ArrayTable sharded over ALL hosts' devices: add + replicated get
    t = ArrayTable(10, "float32", updater="sgd")
    from multiverso_tpu.updaters import AddOption
    t.add(np.arange(10, dtype=np.float32),
          option=AddOption(learning_rate=0.5), sync=True)
    np.testing.assert_allclose(t.get(), -0.5 * np.arange(10), rtol=1e-6)

    # weight-update sharding with the data axis REALLY cross-process:
    # state leaves span processes, so adds, the collective store's
    # data-axis state gather, and load must all run multi-host
    import os as _os
    import tempfile as _tf
    from multiverso_tpu.updaters import AddOption as _AO
    wus = ArrayTable(24, "float32", updater="adagrad", shard_update=True,
                     default_option=_AO(learning_rate=0.5, lam=1e-8),
                     name="mh_wus")
    assert wus.shard_update, "data axis should enable shard_update"
    wus.add(np.ones(24, np.float32), sync=True)
    wus.add(np.ones(24, np.float32), sync=True)
    h = np.full(24, 2.0)        # adagrad oracle after two unit adds
    want = -0.5 * (1 / (np.sqrt(1.0) + 1e-8) + 1 / (np.sqrt(2.0) + 1e-8))
    np.testing.assert_allclose(wus.get(), np.full(24, want), rtol=1e-5)
    ck = _os.path.join(_tf.gettempdir(), f"mh_wus_{port}.npz")
    wus.store(ck)               # the data-axis state gather, for real
    wus2 = ArrayTable(24, "float32", updater="adagrad", shard_update=True,
                      default_option=_AO(learning_rate=0.5, lam=1e-8),
                      name="mh_wus2")
    wus2.load(ck)
    np.testing.assert_allclose(wus2.get(), wus.get(), rtol=1e-6)

    # a second update through the fused-superstep path
    from multiverso_tpu.tables import make_superstep

    def body(params, states, locals_, options):
        (p,) = params
        return (p + 1.0,), states, locals_, p.sum()

    fused = make_superstep((t,), body)
    _, aux = fused(())
    assert np.isfinite(float(aux))
    np.testing.assert_allclose(t.get(), 1.0 - 0.5 * np.arange(10),
                               rtol=1e-6)

    if full:
        # logreg: one real data-parallel epoch across the P processes
        from multiverso_tpu.apps.logreg import (LogisticRegression,
                                                LogRegConfig,
                                                synthetic_blobs)
        X, y = synthetic_blobs(64, 8, 3, seed=0)
        app = LogisticRegression(LogRegConfig(
            input_dim=8, num_classes=3, minibatch_size=32, epochs=2,
            learning_rate=0.1))
        loss = app.train(X, y)
        assert np.isfinite(loss), loss

    # KVTable across all processes: slot assignment is a device-side
    # probe (pure function of table state + batch), so collective adds
    # keep every process in lockstep with no host mirror
    kv = KVTable(128, value_dim=2)
    ks = np.array([3, 9, 1 << 40, 7], np.uint64)
    kv.add(ks, np.arange(8, dtype=np.float32).reshape(4, 2), sync=True)
    vals, found = kv.get(ks)
    assert found.all(), found
    np.testing.assert_allclose(vals,
                               np.arange(8, dtype=np.float32).reshape(4, 2))
    kv.add(ks[:2], np.ones((2, 2), np.float32), sync=True)
    vals2, _ = kv.get(ks)
    np.testing.assert_allclose(vals2[:2], vals[:2] + 1.0)
    _, missing = kv.get(np.array([12345], np.uint64))
    assert not missing.any()
    assert len(kv) == 4

    if full:
        # sparse logreg (KVTable consumer) trains across the P-process
        # mesh
        from multiverso_tpu.apps.sparse_logreg import (
            SparseLogisticRegression, SparseLRConfig, synthetic_sparse)
        rows, y = synthetic_sparse(n=200, dim=30_000, num_classes=2,
                                   nnz=8, seed=0)
        slr = SparseLogisticRegression(SparseLRConfig(
            num_classes=2, max_features=10, capacity=1 << 13,
            minibatch_size=50, learning_rate=0.5, epochs=3))
        slr.train(rows, y)
        acc = slr.accuracy(rows, y)
        assert acc > 0.75, acc

    from multiverso_tpu.apps.word_embedding import W2VConfig, WordEmbedding
    from multiverso_tpu.data.corpus import Corpus
    from multiverso_tpu.data.native import CorpusData
    rng = np.random.default_rng(1)
    ids = rng.integers(0, 50, 4000).astype(np.int32)
    counts = np.maximum(np.bincount(ids, minlength=50), 1).astype(np.int64)
    if full:
        # word2vec across all processes: pair stream device_put sharded
        # over the data axis spanning hosts, embeddings on the P x 2 mesh
        corpus = Corpus(CorpusData(words=[f"w{i}" for i in range(50)],
                                   counts=counts, ids=ids,
                                   total_raw_tokens=len(ids)), subsample=0)
        w2v = WordEmbedding(corpus,
                            W2VConfig(embedding_dim=16, window=2,
                                      negative=3, batch_size=64,
                                      steps_per_call=2, epochs=1,
                                      subsample=0, seed=0),
                            name="mh_w2v")
        w2v.train(total_steps=4)
        assert np.all(np.isfinite(w2v.loss_history))

    # local_data: shared dictionary, PER-RANK token stream — each
    # process generates only its devices' share of every batch from its
    # own shard (the reference's workers-each-stream-their-own-corpus)
    rng_r = np.random.default_rng(100 + pid)
    ids_r = rng_r.integers(0, 50, 3000).astype(np.int32)
    corpus_r = Corpus(CorpusData(words=[f"w{i}" for i in range(50)],
                                 counts=counts, ids=ids_r,
                                 total_raw_tokens=len(ids_r)),
                      subsample=0)
    w2v_l = WordEmbedding(corpus_r,
                          W2VConfig(embedding_dim=16, window=2,
                                    negative=3, batch_size=64,
                                    steps_per_call=2, epochs=1,
                                    subsample=0, seed=0,
                                    local_data=True),
                          name="mh_w2v_local")
    assert w2v_l._local_batch == 64 // P   # 1/P of the global batch
    w2v_l.train(total_steps=4)
    assert np.all(np.isfinite(w2v_l.loss_history))

    # the flagship doc-blocked LDA sampler across ALL processes: a
    # shard_map'd pallas kernel (interpret mode on CPU) with per-chip
    # block ownership and psum'd summary deltas over the P-host mesh.
    # Every LightLDA instance re-TRACES the interpret-mode kernel
    # (~10s of uncacheable python work PER instance PER process on the
    # 1-core CI host), so the P=4 pass keeps only the variants whose
    # arithmetic actually varies with P (streamed z-slab sync,
    # local_corpus ownership) and leans on the P=2 pass for the
    # in-memory reference and the dp x mp replica-dedup variants
    # (their logic does not depend on the data-axis process count).
    from jax.sharding import Mesh
    from multiverso_tpu.apps.lightlda import LDAConfig, LightLDA
    core.shutdown()
    core.set_mesh(Mesh(np.array(jax.devices()).reshape(n_dev, 1),
                       ("data", "model")))
    rng = np.random.default_rng(0)
    tb = 64
    n_tok = tb * n_dev * 2
    td_l = np.sort(rng.integers(0, 32, n_tok)).astype(np.int32)
    tw_l = rng.integers(0, 16, n_tok).astype(np.int32)
    if full:
        lda = LightLDA(tw_l, td_l, 16,
                       LDAConfig(num_topics=128, batch_tokens=tb * n_dev,
                                 steps_per_call=2, seed=0,
                                 sampler="tiled", doc_blocked=True,
                                 block_tokens=tb, block_docs=16),
                       name="mh_lda_db")
        lda.sweep()
        ll = lda.loglik()
        assert np.isfinite(ll), ll
        nwk = lda.word_topics()
        assert nwk.sum() == lda.num_tokens, (nwk.sum(), lda.num_tokens)
        # the resident z is split over every process's chips: read it
        # whole through the app's own (collective) host read
        from multiverso_tpu.apps.lightlda import _to_host
        z_ref = _to_host(lda._z)

    # OUT-OF-CORE streamed mode across all processes: process-local
    # staging (each host device_puts only its addressable lanes) and
    # shard-local z readback must reproduce the in-memory run
    # bit-identically — same kernels, same RNG, counts are a pure
    # function of z at call boundaries
    lda_s = LightLDA(tw_l, td_l, 16,
                     LDAConfig(num_topics=128, batch_tokens=tb * n_dev,
                               steps_per_call=2, seed=0, sampler="tiled",
                               doc_blocked=True, block_tokens=tb,
                               block_docs=16, stream_blocks=True),
                     name="mh_lda_dbs")
    lda_s.sweep()
    lda_s._sync_z_host()   # full-z consumers trigger this lazily
    nwk_s = lda_s.word_topics()
    assert nwk_s.sum() == lda_s.num_tokens
    assert np.isfinite(lda_s.loglik())
    if full:
        np.testing.assert_array_equal(
            lda_s._z_host, z_ref.reshape(lda_s._z_host.shape))
        np.testing.assert_array_equal(nwk_s, nwk)
        np.testing.assert_array_equal(lda_s.doc_topics(),
                                      lda.doc_topics())
        ref_dt = lda.doc_topics()

    # multi-process streamed store/load: store is collective (z sync +
    # chunked allgather); every rank writes the shared state path via
    # the stream layer's atomic temp+rename (identical payloads — z is
    # globally complete after the sync), so loads are safe immediately
    # — the round-trip must preserve z exactly
    import os
    import tempfile
    ck_s = os.path.join(tempfile.gettempdir(), f"mh_ck_{port}_s")
    lda_s.store(ck_s)
    z_before = lda_s._z_host.copy()
    lda_s.load(ck_s)
    np.testing.assert_array_equal(lda_s._z_host, z_before)

    if full:
        # and on a dp x mp mesh (P x 2): model-axis replica dedup in
        # the z drain, per-replica staging, and the sync's
        # uniform-ownership allgather all run with REAL replicas;
        # still bit-identical
        from multiverso_tpu.tables import base as table_base
        table_base.reset_tables()
        core.shutdown()
        core.set_mesh(Mesh(np.array(jax.devices()).reshape(P, 2),
                           ("data", "model")))
        lda_m = LightLDA(tw_l, td_l, 16,
                         LDAConfig(num_topics=128,
                                   batch_tokens=tb * n_dev,
                                   steps_per_call=2, seed=0,
                                   sampler="tiled", doc_blocked=True,
                                   block_tokens=tb, block_docs=16,
                                   stream_blocks=True),
                         name="mh_lda_dbs_mp")
        lda_m.sweep()
        np.testing.assert_array_equal(lda_m.word_topics(), nwk)
        np.testing.assert_array_equal(lda_m.doc_topics(), ref_dt)

    # PER-PROCESS corpus shards (local_corpus): each rank passes ONLY
    # its own docs (disjoint by doc-id mod P, global doc ids);
    # device-side counts must equal the host recount allgathered across
    # ranks, and the run must be deterministic
    from jax.experimental import multihost_utils
    reset_tables()
    core.set_mesh(Mesh(np.array(jax.devices()).reshape(n_dev, 1),
                       ("data", "model")))
    mine = (td_l % P) == pid
    lda_lc = LightLDA(tw_l[mine], td_l[mine], 16,
                      LDAConfig(num_topics=128, batch_tokens=tb * n_dev,
                                steps_per_call=2, seed=0,
                                sampler="tiled", doc_blocked=True,
                                block_tokens=tb, block_docs=16,
                                stream_blocks=True, local_corpus=True),
                      name="mh_lda_lc")
    assert lda_lc.num_tokens == len(tw_l)       # global, agreed
    lda_lc.sweep()
    nwk_lc = lda_lc.word_topics()
    assert nwk_lc.sum() == len(tw_l)
    local_count = np.zeros((16, 128), np.int64)
    valid = lda_lc._tw_host < 16
    np.add.at(local_count, (lda_lc._tw_host[valid],
                            lda_lc._z_host[valid]), 1)
    total = np.asarray(multihost_utils.process_allgather(
        local_count)).sum(axis=0)
    np.testing.assert_array_equal(total, nwk_lc.astype(np.int64))
    assert np.isfinite(lda_lc.loglik())

    # local_corpus store/load: per-rank shard files; the manifest's
    # shard digest must accept the SAME shard and reject a DIFFERENT
    # doc-to-process split of equal process count and global tokens
    ck_lc = os.path.join(tempfile.gettempdir(), f"mh_ck_{port}_lc")
    lda_lc.store(ck_lc)
    z_lc = lda_lc._z_host.copy()
    lda_lc.load(ck_lc)
    np.testing.assert_array_equal(lda_lc._z_host, z_lc)
    reset_tables()
    theirs = (td_l % P) == ((pid + 1) % P)      # the complement split
    lda_wrong = LightLDA(tw_l[theirs], td_l[theirs], 16,
                         LDAConfig(num_topics=128,
                                   batch_tokens=tb * n_dev,
                                   steps_per_call=2, seed=0,
                                   sampler="tiled", doc_blocked=True,
                                   block_tokens=tb, block_docs=16,
                                   stream_blocks=True, local_corpus=True),
                         name="mh_lda_lc_w")
    assert lda_wrong.num_tokens == len(tw_l)    # global totals agree...
    try:
        lda_wrong.load(ck_lc)                   # ...but the shard differs
    except ValueError as e:
        assert "shard mismatch" in str(e), e
    else:
        raise AssertionError("wrong-shard load was not rejected")

    core.barrier()
    reset_tables()
    print(f"MULTIHOST_OK rank={pid}")


if __name__ == "__main__":
    main()
