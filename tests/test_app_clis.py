"""App CLI smoke tests: every reference-style ``-name=value`` main()
runs end-to-end on tiny synthetic data (the reference's binding tests
exercise the public surface the same way; these are the TPU build's
app binaries)."""

import numpy as np
import pytest

from multiverso_tpu import core
from multiverso_tpu.tables import base as table_base


@pytest.fixture(autouse=True)
def _clean_runtime():
    """Mains own the runtime (core.init(argv)) AND the process-wide
    flag store (-updater_type=... etc. persist after parse): give each
    test a clean runtime and restore flag defaults afterwards so later
    tests don't inherit CLI flag values (a leaked -updater_type=adagrad
    makes unrelated SparseMatrixTable constructions raise)."""
    from multiverso_tpu.utils import configure
    table_base.reset_tables()
    core.shutdown()
    yield
    table_base.reset_tables()
    core.shutdown()
    configure.reset_flags()


def _write_libsvm(path, n, dim, classes, nnz, seed, one_based=False):
    rng = np.random.default_rng(seed)
    # planted linear structure so training has signal
    w = rng.normal(size=(dim, classes))
    with open(path, "w") as f:
        for _ in range(n):
            idx = np.sort(rng.choice(dim, nnz, replace=False))
            val = rng.normal(size=nnz).astype(np.float32)
            x = np.zeros(dim, np.float32)
            x[idx] = val
            y = int(np.argmax(x @ w))
            base = 1 if one_based else 0
            f.write(f"{y} " + " ".join(
                f"{i + base}:{v:.4f}" for i, v in zip(idx, val)) + "\n")


def test_logreg_cli(tmp_path):
    from multiverso_tpu.apps import logreg
    train = tmp_path / "train.svm"
    _write_libsvm(train, 128, 20, 3, nnz=6, seed=0)
    out = tmp_path / "lr.ckpt"
    logreg.main([f"-train_file={train}", f"-test_file={train}",
                 "-input_dimension=20", "-output_dimension=3",
                 "-minibatch_size=32", "-train_epoch=2",
                 "-learning_rate=0.2", "-updater_type=adagrad",
                 "-shard_update=true",
                 f"-output_model_file={out}"])
    assert out.exists() or any(
        p.name.startswith("lr.ckpt") for p in tmp_path.iterdir())


def test_sparse_logreg_cli(tmp_path):
    from multiverso_tpu.apps import sparse_logreg
    train = tmp_path / "train.svm"
    _write_libsvm(train, 128, 5000, 2, nnz=5, seed=1, one_based=True)
    out = tmp_path / "slr.ckpt"
    sparse_logreg.main([f"-train_file={train}", f"-test_file={train}",
                        "-num_classes=2", "-max_features=8",
                        "-capacity=8192", "-minibatch_size=32",
                        "-learning_rate=0.3", "-epoch=2",
                        f"-output_file={out}"])
    assert any(p.name.startswith("slr.ckpt") for p in tmp_path.iterdir())


def test_word_embedding_cli(tmp_path):
    from multiverso_tpu.apps import word_embedding
    from multiverso_tpu.data.corpus import synthetic_text
    corpus = tmp_path / "c.txt"
    synthetic_text(str(corpus), num_tokens=12_000, vocab_size=200, seed=2)
    out = tmp_path / "w2v"
    txt = tmp_path / "w2v.txt"
    word_embedding.main([f"-train_file={corpus}", "-size=16", "-window=2",
                         "-negative=3", "-batch_size=128",
                         "-min_count=1", f"-output_file={out}",
                         "-checkpoint_interval=2",
                         f"-output_text={txt}"])
    assert (tmp_path / "w2v.meta.npz").exists()
    header = txt.read_text().splitlines()[0].split()
    assert header[1] == "16"          # reference text dump format


def test_lightlda_cli(tmp_path):
    from multiverso_tpu.apps import lightlda
    from multiverso_tpu.data.corpus import synthetic_docs
    docs = tmp_path / "d.txt"
    synthetic_docs(str(docs), num_docs=120, vocab_size=150,
                   avg_doc_len=30, seed=3)
    out = tmp_path / "lda"
    dump = tmp_path / "lda_model.txt"
    lightlda.main([f"-input_file={docs}", "-num_topics=8",
                   "-num_iterations=2", "-batch_tokens=512",
                   "-eval_every=10", f"-output_file={out}",
                   f"-dump_file={dump}"])
    assert (tmp_path / "lda.state.npz").exists()
    assert dump.exists() and dump.stat().st_size > 0


def test_lightlda_cli_tiled_is_the_docblocked_sampler(tmp_path):
    """``-sampler tiled`` reaches the sampler the benchmark's cells
    measure (it built an intermediate rung before): the state it stores
    is laid out in whole-document kernel blocks."""
    from multiverso_tpu.apps import lightlda
    from multiverso_tpu.data.corpus import synthetic_docs
    from multiverso_tpu.tables.base import loadz_stream
    docs = tmp_path / "d.txt"
    synthetic_docs(str(docs), num_docs=120, vocab_size=150,
                   avg_doc_len=30, seed=3)
    out = tmp_path / "lda"
    lightlda.main([f"-input_file={docs}", "-num_topics=128",
                   "-num_iterations=2", "-eval_every=10",
                   "-sampler=tiled", f"-output_file={out}"])
    manifest, data = loadz_stream(f"{out}.state.npz",
                                  "multiverso_tpu.lda_state.v1")
    assert manifest["layout"] == "docblock"
    assert (manifest["block_tokens"], manifest["block_docs"]) == (512, 16)
    assert data["ndk"].dtype == np.int16


def test_cli_flag_validation():
    """-sync=banana raises; unknown flags pass through as remainder."""
    from multiverso_tpu.utils import configure
    with pytest.raises(ValueError):
        configure.parse_flags(["-sync=banana"])
    rest = configure.parse_flags(["-no_such_flag_xyz=1"])
    assert any("no_such_flag_xyz" in r for r in rest)
