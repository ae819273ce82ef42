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


def test_a_new_cell_is_files_and_entries(tmp_path, monkeypatch):
    """Copy the benchmark, ADD a configuration, a traffic mix, a
    per-layer metric with a reader of its own and one entry each; the
    harness resolves the new cell with no existing file changed."""
    tree = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "perf"), tree / "perf",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = benchmark()
    before = {p: (tree / "perf" / p).read_bytes() for p in
              ("run.py", "layer_readers.py", "drivers/lda.py")}
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
    (tree / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(run, "ROOT", str(tree))
    monkeypatch.setattr(run, "HERE", str(tree / "perf"))
    monkeypatch.setattr(layer_readers, "HERE", str(tree / "perf"))
    cell = run.load_cell("lda_other_cell")
    assert cell["traffic_data"]["checked_sweeps"] == 1
    assert cell["config_data"]["name"] == "lda_other"
    assert [m["name"] for m in cell["per_layer"]] == ["lda_new_metric"]
    assert layer_readers.read("lda_new_metric",
                              {"values": {"x": 21.0}}) == 42.0
    for p, data in before.items():
        assert (tree / "perf" / p).read_bytes() == data


def test_readers_return_nothing_when_there_is_nothing_to_read():
    ctx = {"trace": {"op_seconds": {"jit_a/fusion": 1.0}, "busy_s": 1.0,
                     "window_s": 2.0, "idle_share_pct": 50.0,
                     "collective_exposed_s": 0.0},
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
    assert k["value"](ctx, {"key": "absent"}) is None
    assert k["op_share"](ctx, {"ops": "^jit_a/"}) == pytest.approx(100.0)
    got = k["op_roofline"](ctx, {"ops": "fusion", "work_model":
                                 "lda_sampler"})
    assert got == pytest.approx(100.0 * 1000 * 2192 / 819e9)


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
