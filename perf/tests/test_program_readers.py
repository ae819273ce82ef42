"""The per-layer metrics that read what the PROGRAM records about itself
(its spans' histograms, its compile timings, its map of compiled
instructions to scopes): each has its files, reads the right number off
a hand-made run, and reads nothing — never 0, never an error — off a
program that records none of it (the parent of the PR that added them).
"""

import os

import pytest

import perf.layer_readers as layer_readers
import perf.program_readers as program_readers
from conftest import ROOT, benchmark

ACCEPTED = ["lda_step_mfu", "lda_sampler_roofline", "lda_rebuild_share",
            "lda_dispatches_per_sweep", "lda_device_idle_share",
            "lda_collective_exposed_share", "w2v_step_mfu",
            "w2v_superstep_roofline", "w2v_device_idle_share"]
RETIRED = ["w2v_gen_words_per_s"]      # PR 27: PERF.md, Findings
W2V = ["w2v_gnews300_train"]
LDA = ["lda_nytimes_dp1", "lda_nytimes_2x2"]
# metric -> (span it reads, cells)
SPAN_MS = {"w2v_wait_data_ms": ("w2v.wait_data", W2V),
           "w2v_place_ms": ("w2v.place", W2V),
           "w2v_dispatch_ms": ("w2v.superstep", W2V),
           "w2v_produce_ms": ("w2v.pairs.produce", W2V),
           "lda_dispatch_ms": ("lda.dispatch", LDA),
           "lda_sweep_issue_ms": ("lda.sweep", LDA)}
SETUP_S = {"lda_setup_pack_s": LDA, "w2v_setup_init_s": W2V,
           "setup_compile_s": [LDA[0], W2V[0], LDA[1]]}
SCOPE_SHARE = {"w2v_scatter_share": W2V, "w2v_gather_share": W2V,
               "w2v_unscoped_share": W2V, "lda_gather_share": LDA,
               "lda_carry_share": LDA, "lda_unscoped_share": LDA}
OWN = {**SETUP_S, **SCOPE_SHARE}


def hist(count, total):
    return {"bounds": [1.0], "counts": [count, 0], "count": count,
            "sum": total}


def make_ctx(before=None, after=None, op_seconds=None, busy_s=10.0):
    return {"trace": {"op_seconds": op_seconds or {}, "busy_s": busy_s,
                      "window_s": 20.0},
            "before": {"counters": {}, "histograms": before or {}},
            "after": {"counters": {}, "histograms": after or {}},
            "work": {}, "values": {}, "sizes": {},
            "device_kind": "TPU v5 lite", "chips": 1}


def test_new_metrics_are_appended_files_and_entries():
    per_layer = benchmark()["per_layer"]
    names = [m["name"] for m in per_layer]
    assert names[:len(ACCEPTED)] == ACCEPTED     # nothing moved or gone
    assert not set(RETIRED) & set(names)
    assert sorted(names[len(ACCEPTED):]) == sorted({**SPAN_MS, **OWN})
    cells = {m["name"]: m["workloads"] for m in per_layer}
    here = os.path.join(ROOT, "perf", "layer_metrics")
    for name, (_, where) in SPAN_MS.items():
        assert cells[name] == where
        spec = layer_readers.load_metric(name)
        assert spec["reader"]["kind"] == "registry_mean"
        assert spec["unit"] == "ms" and spec["source"] == "program_span"
        assert not os.path.exists(os.path.join(here, f"{name}.py"))
    for name, where in OWN.items():
        assert cells[name] == where
        assert os.path.exists(os.path.join(here, f"{name}.py"))
        assert layer_readers.load_metric(name)["unit"] == (
            "s" if name in SETUP_S else "%")


def test_span_names_are_what_the_registry_recorded():
    snap = make_ctx(after={
        program_readers.span_series("lda.dispatch"): hist(3, 1.0),
        program_readers.span_series("w2v.pairs.produce"): hist(1, 1.0),
        "profile.compile.seconds{fn=run}": hist(1, 2.0),
        "span.seconds": hist(1, 1.0)})["after"]
    assert program_readers.span_names(snap) == ["lda.dispatch",
                                                "w2v.pairs.produce"]
    assert program_readers.span_names(make_ctx()["after"]) == []


@pytest.mark.parametrize("name", sorted(SPAN_MS))
def test_span_ms_is_the_windows_mean(name):
    series = program_readers.span_series(SPAN_MS[name][0])
    other = program_readers.span_series("some.other")
    ctx = make_ctx(before={series: hist(2, 1.0), other: hist(1, 9.0)},
                   after={series: hist(6, 3.0), other: hist(5, 99.0)})
    # (3.0 - 1.0) s over 6 - 2 spans, in ms
    assert layer_readers.read(name, ctx) == pytest.approx(500.0)
    assert layer_readers.read(name, make_ctx()) is None
    # recorded in set-up only: nothing of the window's to read
    ctx = make_ctx(before={series: hist(2, 1.0)},
                   after={series: hist(2, 1.0)})
    assert layer_readers.read(name, ctx) is None


def test_setup_seconds_are_totals_after_the_window():
    s = program_readers.span_series
    after = {s("lda.setup.pack"): hist(1, 9.5),
             s("lda.setup.counts"): hist(1, 2.0),
             s("w2v.setup.init_tables"): hist(1, 20.0),
             s("w2v.setup.vocab_tables"): hist(1, 4.0),
             "profile.lower.seconds{fn=superstep.a}": hist(1, 0.5),
             "profile.lower.seconds{fn=b}": hist(2, 0.25),
             "profile.compile.seconds{fn=superstep.a}": hist(1, 3.0),
             "profile.compiles{fn=b}": hist(7, 70.0)}
    ctx = make_ctx(after=after)
    assert layer_readers.read("lda_setup_pack_s", ctx) == 9.5
    assert layer_readers.read("w2v_setup_init_s", ctx) == 24.0
    assert layer_readers.read("setup_compile_s", ctx) == 3.75
    for name in SETUP_S:
        assert layer_readers.read(name, make_ctx()) is None


def test_scope_shares_join_the_trace_with_the_programs_map(monkeypatch):
    scopes = {
        "superstep.w2v_superstep": {"module": "jit_run", "scopes": {
            "fusion.62": "w2v.scatter_out", "fusion.61": "w2v.scatter_in",
            "fusion.58": "w2v.gather_out", "fusion.57": "w2v.gather_in",
            "fusion.5": "w2v.negatives", "fusion.9": "w2v.math",
            "copy.1": "unscoped", "twice": "w2v.math"}},
        # a second program of the same module name: "twice" disagrees
        "superstep.other": {"module": "jit_run", "scopes": {
            "twice": "w2v.scatter_in"}},
        "elsewhere": {"module": "jit_rebuild", "scopes": {
            "fusion.62": "w2v.gather_in"}}}
    monkeypatch.setattr(program_readers, "program_op_scopes",
                        lambda: scopes)
    ops = {"jit_run/fusion.62": 4.0, "jit_run/fusion.61": 1.0,
           "jit_run/fusion.58": 0.5, "jit_run/fusion.57": 0.25,
           "jit_run/fusion.5": 0.25, "jit_run/fusion.9": 1.0,
           "jit_run/copy.1": 0.5, "jit_run/twice": 0.25,
           "jit_run/not.in.the.map": 0.25,
           "jit_rebuild/fusion.62": 100.0, "fusion.62": 100.0}
    ctx = make_ctx(op_seconds=ops, busy_s=10.0)
    assert layer_readers.read("w2v_scatter_share", ctx) == 50.0
    assert layer_readers.read("w2v_gather_share", ctx) == 10.0
    assert layer_readers.read("w2v_unscoped_share", ctx) == 10.0
    # the map has no lda scope: a share of nothing is None, not 0 ...
    assert layer_readers.read("lda_gather_share", ctx) is None
    assert layer_readers.read("lda_carry_share", ctx) is None
    # ... but what the map cannot name is a reading
    assert layer_readers.read("lda_unscoped_share", ctx) == 10.0
    everything_named = make_ctx(op_seconds={"jit_run/fusion.9": 1.0})
    assert layer_readers.read("w2v_unscoped_share",
                              everything_named) == 0.0


def test_scope_shares_read_nothing_without_a_map(monkeypatch):
    ctx = make_ctx(op_seconds={"jit_run/fusion.62": 4.0})
    monkeypatch.setattr(program_readers, "program_op_scopes", lambda: {})
    for name in SCOPE_SHARE:
        assert layer_readers.read(name, ctx) is None
    # a program with no ``op_scopes`` at all (the parent): the same
    from multiverso_tpu.telemetry import profiling
    monkeypatch.undo()
    monkeypatch.delattr(profiling, "op_scopes")
    assert program_readers.program_op_scopes() == {}
    for name in SCOPE_SHARE:
        assert layer_readers.read(name, ctx) is None


def test_lda_scope_shares_on_a_hand_made_run(monkeypatch):
    monkeypatch.setattr(program_readers, "program_op_scopes", lambda: {
        "superstep.lda_docblock": {"module": "jit_run", "scopes": {
            "fusion": "lda.gather_words", "copy.3": "lda.carry",
            "copy.4": "lda.carry", "gibbs_sample_docblock.2":
            "lda.sample", "broadcast_select_fusion": "unscoped"}}})
    ctx = make_ctx(busy_s=20.0, op_seconds={
        "jit_run/fusion": 5.0, "jit_run/copy.3": 0.5,
        "jit_run/copy.4": 0.5, "jit_run/gibbs_sample_docblock.2": 5.0,
        "jit_run/broadcast_select_fusion": 2.0,
        "jit_rebuild/fusion": 3.0})
    assert layer_readers.read("lda_gather_share", ctx) == 25.0
    assert layer_readers.read("lda_carry_share", ctx) == 5.0
    assert layer_readers.read("lda_unscoped_share", ctx) == 10.0
