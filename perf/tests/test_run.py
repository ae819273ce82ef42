"""``perf/run.py`` end to end on the CPU: the rehearsal at the tiny size
says ``"platform": "cpu"`` and carries no metric; without the rehearsal
path and without a TPU it exits non-zero and prints no line; and with
the timed path broken underneath, ``correct`` comes out false."""

import json
import os
import subprocess
import sys

import pytest

import perf.run as run
from conftest import ROOT, benchmark

CELLS = [w["name"] for w in benchmark()["workloads"]]
LDA = [w["name"] for w in benchmark()["workloads"]
       if w["config"].startswith("lda_") and w["chips"] == 1]


@pytest.fixture
def kv_cell(tmp_path, monkeypatch):
    """The served key-value cell is kept out of ``BENCHMARK.json`` (the
    program cannot hold its table at an admissible size: PERF.md, Open
    questions); its files stay, and these tests drive them through a
    copy of the benchmark that has its entries."""
    bench = benchmark()
    with open(os.path.join(ROOT, "perf", "tests", "data",
                           "kv_cell_entries.json")) as f:
        extra = json.load(f)
    for key, entries in extra.items():
        bench[key] = bench[key] + entries
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    os.symlink(os.path.join(ROOT, "perf"), tmp_path / "perf")
    monkeypatch.setattr(run, "ROOT", str(tmp_path))
    return extra["workloads"][0]["name"]


def _run(args, **kw):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "perf/run.py", *args],
                          cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=600, **kw)


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_is_never_a_result(cell):
    p = _run(["--workload", cell, "--seed", "2147483659", "--seconds",
              "1", "--trace", "1", "--rehearse-cpu"])
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["device"]["platform"] == "cpu"
    assert line["rehearsal"] is True and line["metrics"] == {}
    assert "busy_s" not in line["device"]
    assert line["correct"] is True, line["checks"]
    assert list(line)[-1] == "checks"
    assert "[perf cpu/cpu/x4]" in p.stderr
    last = [ln for ln in p.stderr.strip().splitlines()
            if ln.startswith("[perf")][-len(line["checks"]):]
    assert all(" check " in ln for ln in last)


def test_no_tpu_no_result():
    p = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
              "--trace", "0"])
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def _main_line(capsys, cell, seed="5"):
    rc = run.main(["--workload", cell, "--seed", seed, "--seconds", "1",
                   "--trace", "0", "--rehearse-cpu"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", ["state_unchanged", "half_left_out",
                                   "token_altered"])
@pytest.mark.parametrize("cell", LDA)
def test_lda_faults_come_out_not_correct(cell, fault, monkeypatch,
                                         capsys):
    import jax.numpy as jnp
    from multiverso_tpu.apps.lightlda import LightLDA
    sweep = LightLDA.sweep

    def state_unchanged(self):
        return None

    def half_left_out(self):
        calls = self._calls
        self._calls = calls[: len(calls) // 2]
        try:
            sweep(self)
        finally:
            self._calls = calls

    def token_altered(self):
        sweep(self)
        z = self._z
        self._z = z.at[0, 0].set((z[0, 0] + 1) % self.K)

    monkeypatch.setattr(LightLDA, "sweep", locals()[fault])
    line = _main_line(capsys, cell)
    assert line["correct"] is False, (fault, line["checks"])
    bad = [k for k, c in line["checks"].items()
           if c["value"] > c["limit"]]
    expect = {"state_unchanged": "moved_share_gap_s1",
              "half_left_out": "moved_share_gap_s1",
              "token_altered": "count_tables_mismatch"}[fault]
    assert expect in bad


def test_lda_control_in_bfloat16_is_not_correct():
    """The control: the reference itself with the posterior and the
    running sum in bfloat16, in the program's place, at a size a test
    holds (the cell's own K, documents as long as the cell's). The
    bfloat16 running sum stalls once a topic's mass falls under half an
    ulp of it: topics a document does not hold yet are all but never
    drawn, so documents keep fewer distinct topics."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from perf import corpus
    from perf.reference import lda as ref

    cfg = run.load_cell(LDA[0])["config_data"]
    s = dict(cfg["program"], docs=200)
    D, V, K = s["docs"], s["vocab_size"], s["num_topics"]
    lens = corpus.doc_lengths(3, D, s["doc_len_mean"], s["doc_len_sd"],
                              s["doc_len_min"], s["doc_len_max"])
    words = np.asarray(corpus.zipf_words(3, int(lens.sum()), V,
                                         s["zipf_exponent"]))
    docs = np.repeat(np.arange(D, dtype=np.int32), lens)
    w, d, m = (jnp.asarray(x) for x in ref.pad_stream(words, docs))
    kw = dict(alpha=s["alpha"], beta=s["beta"])

    def chain(salt, **fault):
        key = corpus.prng_key(3, salt)
        return ref.follow(ref.random_start(key, w.shape, K), w, d, m, key,
                          1, D=D, V=V, K=K, every=1, **kw, **fault)[0]

    n = len(words)
    good = chain(5)
    sound = ref.gaps(chain(6), good, n)
    ctrl = ref.gaps(chain(6, precision="bfloat16"), good, n)
    half = ref.gaps(chain(6, keep=2), good, n)
    assert ctrl["doc_topics_gap"] > 3 * sound["doc_topics_gap"]
    assert ctrl["doc_topics_gap"] > \
        cfg["correct"]["limits"]["doc_topics_gap_s1"]
    assert half["moved_share_gap"] > 0.2     # one chunk of three


@pytest.mark.parametrize("fault", ["state_unchanged", "half_left_out",
                                   "answer_altered"])
def test_w2v_faults_come_out_not_correct(fault, monkeypatch, capsys):
    cells = [c for c in CELLS if c.startswith("w2v_")]
    import numpy as np
    from multiverso_tpu.apps.word_embedding import WordEmbedding
    dispatch = WordEmbedding._dispatch

    def state_unchanged(self, srcs, tgts, call_no, est_calls):
        import jax.numpy as jnp
        return jnp.float32(4.158883)         # the first step's loss

    def half_left_out(self, srcs, tgts, call_no, est_calls):
        h = srcs.shape[1] // 2
        return dispatch(self, np.concatenate([srcs[:, :h]] * 2, axis=1),
                        np.concatenate([tgts[:, :h]] * 2, axis=1),
                        call_no, est_calls)

    def answer_altered(self, srcs, tgts, call_no, est_calls):
        loss = dispatch(self, srcs, tgts, call_no, est_calls)
        self.w_out.put_raw(self.w_out.raw().at[0, 0].add(0.5))
        return loss

    monkeypatch.setattr(WordEmbedding, "_dispatch", locals()[fault])
    line = _main_line(capsys, cells[0])
    assert line["correct"] is False, (fault, line["checks"])


def test_served_kv_rehearsal_is_correct(kv_cell, capsys):
    line = _main_line(capsys, kv_cell)
    assert line["correct"] is True, line["checks"]
    assert line["device"]["platform"] == "cpu" and line["metrics"] == {}


@pytest.mark.parametrize("fault", ["state_unchanged", "half_left_out",
                                   "answer_altered"])
def test_served_kv_faults_come_out_not_correct(fault, kv_cell,
                                               monkeypatch, capsys):
    from multiverso_tpu.tables.base import Handle
    from multiverso_tpu.tables.kv_table import KVTable
    add, get = KVTable.add, KVTable.get

    def state_unchanged(self, keys, deltas, option=None, sync=False):
        return Handle(table=self, generation=self.generation)

    def half_left_out(self, keys, deltas, option=None, sync=False):
        h = max(len(keys) // 2, 1)
        return add(self, keys[:h], deltas[:h], option, sync)

    def answer_altered(self, keys):
        values, found = get(self, keys)
        return values + 0.5, found

    if fault == "answer_altered":
        monkeypatch.setattr(KVTable, "get", answer_altered)
    else:
        monkeypatch.setattr(KVTable, "add", locals()[fault])
    line = _main_line(capsys, kv_cell)
    assert line["correct"] is False, (fault, line["checks"])
    assert line["checks"]["ftrl_value_gap"]["value"] > \
        line["checks"]["ftrl_value_gap"]["limit"]


def test_ftrl_control_in_bfloat16_is_not_correct(kv_cell):
    """The reference with every FTRL result rounded to bfloat16, in the
    program's place: its weights leave the float32 reference's by far
    more than the cell's limit."""
    import numpy as np
    from perf import kv_traffic
    from perf.reference import ftrl

    cell = run.load_cell(kv_cell)
    sizes = dict(cell["config_data"]["program"])
    traffic = dict(cell["traffic_data"], minibatch=32)
    frames = [kv_traffic.minibatch(9, 0, i, sizes, traffic)
              for i in range(40)]
    universe = np.unique(np.concatenate([k for k, _ in frames]))
    tables = {d: ftrl.Table(universe, dtype=d, **sizes["ftrl"])
              for d in ("float32", "bfloat16")}
    for keys, grads in frames:
        for t in tables.values():
            t.add(keys, grads)
    good, ctrl = tables["float32"].w, tables["bfloat16"].w
    assert (good != 0).sum() > 100
    gap = np.max(np.abs(ctrl - good) / np.maximum(np.abs(good), 1e-3))
    assert gap > 10 * cell["config_data"]["correct"]["limits"][
        "ftrl_value_gap"]
