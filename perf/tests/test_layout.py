"""Every cell of ``BENCHMARK.json`` resolves to files under ``perf/``,
every per-layer metric names only cells that report what it moves, and a
new cell, configuration, traffic mix and metric are files plus entries:
no file that is there needs an edit."""

import json
import os
import shutil

import pytest

import perf.layer_readers as layer_readers
import perf.run as run
from conftest import ROOT, benchmark


def test_cells_resolve_to_files():
    bench = benchmark()
    for w in bench["workloads"]:
        cell = run.load_cell(w["name"])
        driver = cell["config_data"]["driver"]
        assert os.path.exists(os.path.join(
            ROOT, "perf", "drivers", f"{driver}.py"))
        names = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2
        assert cell["per_layer"], w["name"]
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        data = run.load_json(os.path.join(ROOT, c["file"]))
        assert data["reduced"] == c["reduced"]
        assert data["source"] == c["source"] and len(c["source"]) <= 200


def test_per_layer_metrics_name_cells_that_report_their_moves():
    bench = benchmark()
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert isinstance(m["workloads"], list) and m["workloads"]
        moved = e2e[m["moves"]]
        reporting = set(moved.get("workloads", cells))
        assert set(m["workloads"]) <= reporting, m["name"]
        spec = layer_readers.load_metric(m["name"])
        for key in ("layer", "unit", "moves", "better", "source"):
            assert spec[key] == m[key], (m["name"], key)
        own = os.path.join(ROOT, "perf", "layer_metrics",
                           f"{m['name']}.py")
        assert spec["reader"]["kind"] in layer_readers.KINDS \
            or os.path.exists(own)


UNTOUCHED = ("run.py", "layer_readers.py", "work_models.py",
             "reduce_trace.py", "drivers/lda.py")


@pytest.fixture
def checkout(tmp_path, monkeypatch):
    """A copy of the benchmark that the harness reads in place of the
    repo's: ``(tree, bench, commit)``. ``commit(bench)`` writes the
    entries; leaving the test checks that no file that was there
    changed."""
    tree = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "perf"), tree / "perf",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: (tree / "perf" / p).read_bytes() for p in UNTOUCHED}
    monkeypatch.setattr(run, "ROOT", str(tree))
    monkeypatch.setattr(run, "HERE", str(tree / "perf"))
    monkeypatch.setattr(layer_readers, "HERE", str(tree / "perf"))

    def commit(bench):
        (tree / "BENCHMARK.json").write_text(json.dumps(bench))

    yield tree, benchmark(), commit
    for p, data in before.items():
        assert (tree / "perf" / p).read_bytes() == data, p


def test_a_new_cell_is_files_and_entries(checkout):
    """ADD a configuration, a traffic mix, a per-layer metric with a
    reader of its own and one entry each; the harness resolves the new
    cell with no existing file changed."""
    tree, bench, commit = checkout
    cfg = run.load_json(os.path.join(ROOT, bench["configs"][0]["file"]))
    cfg["name"] = "lda_other"
    (tree / "perf/configs/lda_other.json").write_text(json.dumps(cfg))
    (tree / "perf/traffic/two_sweeps.json").write_text(json.dumps(
        {"name": "two_sweeps", "kind": "whole_sweeps",
         "checked_sweeps": 1}))
    (tree / "perf/layer_metrics/lda_new_metric.py").write_text(
        "def read(ctx):\n    return 2.0 * ctx['values']['x']\n")
    bench["configs"].append(dict(bench["configs"][0], name="lda_other",
                                 file="perf/configs/lda_other.json"))
    bench["workloads"].append(
        {"name": "lda_other_cell", "config": "lda_other",
         "traffic": "two_sweeps", "chips": 1, "why": "test"})
    bench["end_to_end"][0]["workloads"].append("lda_other_cell")
    bench["per_layer"].append(
        {"name": "lda_new_metric", "unit": "x", "better": "lower",
         "source": "program_counter", "layer": "device",
         "moves": bench["end_to_end"][0]["name"],
         "workloads": ["lda_other_cell"]})
    commit(bench)
    cell = run.load_cell("lda_other_cell")
    assert cell["traffic_data"]["checked_sweeps"] == 1
    assert cell["config_data"]["name"] == "lda_other"
    assert [m["name"] for m in cell["per_layer"]] == ["lda_new_metric"]
    assert layer_readers.read("lda_new_metric",
                              {"values": {"x": 21.0}}) == 42.0


# a third trainer, as a later PR would bring it: plain SGD on a table of
# rows, written to the contract in ``perf/run.py``'s docstring
THIRD_DRIVER = '''
import time
import numpy as np

class Cell:
    def __init__(self, *, config, traffic, seed, seconds, chips, devices,
                 tiny, log):
        self.sizes = dict(config["program"])
        self.limit = config["correct"]["limits"]["table_gap"]
        self.seq = int(traffic["sequence_tokens"])
        self.seed, self.log = seed, log

    def setup(self):
        import jax, jax.numpy as jnp
        self.table = jnp.zeros((self.sizes["rows"], self.sizes["dim"]))
        self.step = jax.jit(lambda t, g: t - 0.5 * g)
        self.grad = jnp.ones_like(self.table)
        self.table = self.step(self.table, self.grad)    # compiles
        self.steps = 1

    def registry_snapshot(self):
        return {"counters": {}, "histograms": {}}

    def window(self, seconds):
        t0, n = time.perf_counter(), 0
        while n < 3:
            self.table = self.step(self.table, self.grad)
            n += 1
        self.table.block_until_ready()
        took = time.perf_counter() - t0
        self.steps += n
        tokens = n * self.seq
        return {"attempted": n, "failed": 0,
                "metrics": {"train_tokens_per_s": tokens / took},
                "work": {"tokens": tokens}, "values": {}}

    def collect(self):
        self.got = np.asarray(self.table)
        self.table = self.grad = None

    def check(self):
        want = np.full_like(self.got, -0.5 * self.steps)
        return [{"name": "table_gap", "limit": self.limit,
                 "value": float(np.abs(self.got - want).max())}]

    def close(self):
        pass
'''
THIRD_WORK = '''
def work(sizes, work):
    """Per token: one row read and written; two operations a lane."""
    return {"bytes": work["tokens"] * 8.0 * sizes["dim"],
            "flops": work["tokens"] * 2.0 * sizes["dim"]}
'''


def add_third_trainer(tree, bench):
    (tree / "perf/drivers/third.py").write_text(THIRD_DRIVER)
    (tree / "perf/work").mkdir()
    (tree / "perf/work/third_step.py").write_text(THIRD_WORK)
    (tree / "perf/configs/third_rows.json").write_text(json.dumps(
        {"name": "third_rows", "driver": "third",
         "program": {"rows": 64, "dim": 128},
         "correct": {"limits": {"table_gap": 0.0}}}))
    (tree / "perf/traffic/third_stream.json").write_text(json.dumps(
        {"name": "third_stream", "sequence_tokens": 256}))
    metric = {"name": "third_step_mfu", "unit": "%", "better": "higher",
              "source": "device_trace", "layer": "third trainer whole step",
              "moves": "train_tokens_per_s"}
    (tree / "perf/layer_metrics/third_step_mfu.json").write_text(
        json.dumps(dict(metric, reader={"kind": "step_mfu",
                                        "work_model": "third_step"})))
    bench["configs"].append(
        {"name": "third_rows", "source": "test", "reduced": [],
         "file": "perf/configs/third_rows.json", "why": "test"})
    bench["workloads"].append(
        {"name": "third_cell", "config": "third_rows",
         "traffic": "third_stream", "chips": 1, "why": "test"})
    rate, = [m for m in bench["end_to_end"]
             if m["name"] == "train_tokens_per_s"]
    rate["workloads"].append("third_cell")
    bench["per_layer"].append(dict(metric, workloads=["third_cell"]))


def test_a_third_trainer_is_files_and_entries(checkout, capsys):
    """A trainer the benchmark has never seen: a driver of its own, its
    cell appended to ``train_tokens_per_s``, a ``step_mfu`` metric whose
    work model is a file under ``perf/work/``. The harness resolves the
    cell, drives the driver from set-up to ``correct``, and reads the
    metric — and no file that was there changed."""
    tree, bench, commit = checkout
    add_third_trainer(tree, bench)
    commit(bench)
    cell = run.load_cell("third_cell")
    assert [m["name"] for m in cell["end_to_end"]] == \
        ["train_tokens_per_s", "setup_s"]
    assert [m["name"] for m in cell["per_layer"]] == ["third_step_mfu"]
    # the two trainers that were there report what they did
    assert [m["name"] for m in run.load_cell(
        bench["workloads"][0]["name"])["end_to_end"]] == \
        ["lda_doc_tokens_per_s", "setup_s"]

    assert run.main(["--workload", "third_cell", "--seed", "2147483659",
                     "--seconds", "1", "--rehearse-cpu"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["attempted"] == 3
    assert line["checks"]["table_gap"] == {"value": 0.0, "limit": 0.0}

    # 1,000 tokens of 128 lanes: 1.024 MB at 819 GB/s over a 1 ms window
    ctx = {"trace": {"window_s": 1e-3}, "work": {"tokens": 1000},
           "sizes": cell["config_data"]["program"],
           "device_kind": "TPU v5 lite", "chips": 1}
    assert layer_readers.read("third_step_mfu", ctx) == pytest.approx(
        100.0 * (1000 * 8 * 128 / 819e9) / 1e-3)


def test_an_unknown_work_model_names_both_places(checkout):
    tree, bench, commit = checkout
    ctx = {"trace": {"window_s": 1.0}, "work": {"tokens": 1},
           "sizes": {}, "device_kind": "TPU v5 lite", "chips": 1}
    with pytest.raises(KeyError) as err:
        layer_readers.KINDS["step_mfu"](ctx, {"work_model": "nowhere"})
    assert "perf/work_models.py" in str(err.value)
    assert os.path.join(str(tree), "perf", "work", "nowhere.py") \
        in str(err.value)
    # the models that are there are found where they were
    assert layer_readers.work_model("w2v_pairs") is \
        layer_readers.work_models.MODELS["w2v_pairs"]


def test_readers_return_nothing_when_there_is_nothing_to_read():
    ctx = {"trace": {"op_seconds": {"jit_a/fusion": 1.0}, "busy_s": 1.0,
                     "window_s": 2.0, "idle_share_pct": 50.0,
                     "collective_exposed_s": 0.0, "module_launches": {}},
           "before": {"counters": {}, "histograms": {}},
           "after": {"counters": {}, "histograms": {}},
           "work": {"tokens": 1000, "sweeps": 1}, "values": {},
           "sizes": {"num_topics": 1024, "block_tokens": 512,
                     "vocab_size": 1000},
           "device_kind": "TPU v5 lite", "chips": 1}
    k = layer_readers.KINDS
    assert k["op_roofline"](ctx, {"ops": "gibbs", "work_model":
                                  "lda_sampler"}) is None
    assert k["op_share"](ctx, {"ops": "gibbs"}) is None
    assert k["collective_exposed_share"](ctx, {}) is None
    assert k["registry_rate"](ctx, {"metric": "profile.calls",
                                    "per": "sweeps"}) is None
    assert k["registry_mean"](ctx, {"metric": "x"}) is None
    assert k["module_launches"](ctx, {"per": "sweeps"}) is None
    ctx["trace"]["module_launches"] = {"jit_run": 124, "jit_rebuild": 1}
    assert k["module_launches"](ctx, {"per": "sweeps"}) == 125.0
    assert k["module_launches"](ctx, {"per": "absent"}) is None
    assert k["value"](ctx, {"key": "absent"}) is None
    assert k["op_share"](ctx, {"ops": "^jit_a/"}) == pytest.approx(100.0)
    got = k["op_roofline"](ctx, {"ops": "fusion", "work_model":
                                 "lda_sampler"})
    assert got == pytest.approx(100.0 * 1000 * 2192 / 819e9)


def test_the_training_rate_names_no_app():
    """``train_tokens_per_s`` is there for a trainer added later: with a
    ``workloads`` list (a cell appends its name; the cells outside it
    are not asked for it) and the bound both other rates have. The list
    may not be empty, so the word2vec cell, whose words are training
    tokens, carries it beside its own name for the same number."""
    rate, = [m for m in benchmark()["end_to_end"]
             if m["name"] == "train_tokens_per_s"]
    assert rate["workloads"] == ["w2v_gnews300_train"]
    assert {k: v for k, v in rate.items() if k != "workloads"} == {
        "name": "train_tokens_per_s", "unit": "tokens/s",
        "better": "higher", "bound": 0.01, "source": "host_clock"}
    for w in benchmark()["workloads"]:
        names = [m["name"] for m in run.load_cell(w["name"])["end_to_end"]]
        if w["name"] in rate["workloads"]:
            assert names == ["w2v_words_per_s", "train_tokens_per_s",
                             "setup_s"]
        else:
            assert "train_tokens_per_s" not in names


def test_benchmark_json_keeps_the_contract():
    """The limits of the builder's contract that a test can hold."""
    import re
    bench = benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    sources = {"device_trace", "program_span", "program_counter",
               "host_clock"}

    def line(text):
        return 1 <= len(text) <= 200 and "\n" not in text \
            and "\t" not in text

    assert 1 <= bench["run_seconds"] <= 51
    cells = bench["workloads"]
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 \
        + 1200 <= 43200
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert name.match(c["name"]) and line(c["source"]) \
            and line(c["why"]) and c["file"].startswith("perf/")
        assert all(name.match(k) for k in c["reduced"])
    pairs = set()
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert name.match(w["name"]) and name.match(w["traffic"]) \
            and line(w["why"]) and w["chips"] in (1, 4)
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(cells)
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in bench["end_to_end"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        # a list, where given, names cells: never []
        assert m.get("workloads", True), m["name"]
        assert set(m.get("workloads", ())) <= {w["name"] for w in cells}
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert line(m["layer"]) and m["source"] in sources
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    assert len(json.dumps(bench)) < 64 * 1024
