"""The six ``lm_*`` metrics that read the program's body-aware map of
compiled instructions to scopes (PR 36): each has its files and its
entry, reads the right number off a hand-made run, and reads nothing —
never 0, never an error — off a map without the ``inferred`` key (the
parent's shape of map) or off a program that keeps no map at all."""

import os

import pytest

import perf.layer_readers as layer_readers
import perf.program_readers as program_readers
from conftest import ROOT, benchmark

LM3 = ["dsv2_lite_ep8_train_pack4k", "olmo_hybrid_7b_vp8_train_pack4k",
       "lfm2_8b_a1b_ep4_train_pack4k"]
EXPERTS = [LM3[0], LM3[2]]
# metric -> (cells, what it reads off OPS under MAP, in % of 20 s busy)
METRICS = {"lm_inferred_scope_share": (LM3, 30.0),
           "lm_mixed_scope_share": (LM3, 10.0),
           "lm_moe_experts_share": (EXPERTS, 35.0),
           "lm_moe_permute_share": (EXPERTS, 15.0),
           "lm_moe_accumulate_share": (EXPERTS, 5.0),
           "lm_head_share": (LM3, 20.0)}
SCOPES = {"fusion.1": "lm.moe.experts",        # its own op_name's
          "fusion.2": "lm.moe.experts",        # the compiler's: operands
          "fusion.3": "lm.moe.permute",        # a body of two scopes
          "fusion.4": "lm.moe.permute",        # a body that agrees
          "add.5": "lm.moe.accumulate",
          "fusion.6": "lm.head_loss",
          "fusion.7": "lm.head_loss",          # a body of three scopes
          "copy.8": "unscoped",
          "twice": "lm.head_loss"}
INFERRED = {"fusion.2": 0, "fusion.3": 2, "fusion.4": 1, "fusion.7": 3,
            "twice": 1}
OPS = {"jit_run/fusion.1": 5.0, "jit_run/fusion.2": 2.0,
       "jit_run/fusion.3": 1.0, "jit_run/fusion.4": 2.0,
       "jit_run/add.5": 1.0, "jit_run/fusion.6": 3.0,
       "jit_run/fusion.7": 1.0, "jit_run/copy.8": 2.0,
       "jit_run/twice": 1.0, "jit_run/not.in.the.map": 1.0,
       "jit_rebuild/fusion.2": 100.0, "fusion.3": 100.0}


def make_ctx(op_seconds=OPS, busy_s=20.0):
    return {"trace": {"op_seconds": op_seconds, "busy_s": busy_s,
                      "window_s": 20.5},
            "before": {"counters": {}, "histograms": {}},
            "after": {"counters": {}, "histograms": {}},
            "work": {}, "values": {}, "sizes": {},
            "device_kind": "TPU v5 lite", "chips": 1}


def program_map(inferred=True):
    step = {"module": "jit_run", "scopes": dict(SCOPES)}
    # a second program of the module's name: "twice" disagrees, so it
    # is unscoped whatever either program inferred for it
    other = {"module": "jit_run", "scopes": {"twice": "lm.adam"}}
    elsewhere = {"module": "jit_rebuild",
                 "scopes": {"fusion.2": "lm.head_loss"}}
    if inferred:
        step["inferred"] = dict(INFERRED)
        other["inferred"] = {}
        elsewhere["inferred"] = {"fusion.2": 2}
    return {"superstep.lm_superstep": step, "superstep.other": other,
            "elsewhere": elsewhere}


@pytest.mark.parametrize("name", list(METRICS))
def test_the_metric_is_files_and_an_appended_entry(name):
    cells, _ = METRICS[name]
    per_layer = benchmark()["per_layer"]
    names = [m["name"] for m in per_layer]
    assert names[-len(METRICS):] == list(METRICS)       # at the end
    entry = per_layer[names.index(name)]
    assert entry["workloads"] == cells
    assert (entry["unit"], entry["better"], entry["source"],
            entry["moves"]) == ("%", "lower", "device_trace",
                                "train_tokens_per_s")
    here = os.path.join(ROOT, "perf", "layer_metrics")
    spec = layer_readers.load_metric(name)
    assert spec["reader"]["kind"] == "own"
    assert spec["layer"] == entry["layer"]
    assert os.path.exists(os.path.join(here, f"{name}.py"))
    # a layer the benchmark already names keeps its name, letter for
    # letter; the head's is new with this PR
    layers = {m["layer"] for m in per_layer[:-len(METRICS)]}
    assert entry["layer"] in layers or name == "lm_head_share"


@pytest.mark.parametrize("name", list(METRICS))
def test_the_metric_on_a_hand_made_run(name, monkeypatch):
    monkeypatch.setattr(program_readers, "program_op_scopes", program_map)
    assert layer_readers.read(name, make_ctx()) == METRICS[name][1]


@pytest.mark.parametrize("name", list(METRICS))
def test_the_metric_reads_nothing_off_the_parent_s_map(name, monkeypatch):
    ctx = make_ctx()
    # the parent's shape of map: scopes by each fusion's root, no
    # account of what was inferred
    monkeypatch.setattr(program_readers, "program_op_scopes",
                        lambda: program_map(inferred=False))
    assert layer_readers.read(name, ctx) is None
    # a program that keeps no map, or has no ``op_scopes`` at all
    monkeypatch.setattr(program_readers, "program_op_scopes", lambda: {})
    assert layer_readers.read(name, ctx) is None
    from multiverso_tpu.telemetry import profiling
    monkeypatch.undo()
    monkeypatch.delattr(profiling, "op_scopes")
    assert layer_readers.read(name, ctx) is None


def test_what_nothing_was_inferred_for_reads_zero_not_nothing(monkeypatch):
    monkeypatch.setattr(program_readers, "program_op_scopes", lambda: {
        "superstep.lm_superstep": {"module": "jit_run", "inferred": {},
                                   "scopes": {"fusion.1": "lm.head_loss"}}})
    ctx = make_ctx({"jit_run/fusion.1": 5.0})
    assert layer_readers.read("lm_inferred_scope_share", ctx) == 0.0
    assert layer_readers.read("lm_mixed_scope_share", ctx) == 0.0
    assert layer_readers.read("lm_head_share", ctx) == 25.0
    # a share of a scope nothing ran under is nothing, as every scope
    # share's
    assert layer_readers.read("lm_moe_accumulate_share", ctx) is None
    assert layer_readers.read("lm_head_share",
                              make_ctx({"jit_run/fusion.1": 5.0}, 0.0)) is None


def test_the_map_the_program_keeps_is_the_one_the_readers_expect():
    """The real ``op_scopes`` of a compiled program has the key the
    readers ask for."""
    import jax.numpy as jnp
    import numpy as np
    from multiverso_tpu import telemetry
    from multiverso_tpu.telemetry import profiling

    @telemetry.scope("t.scope.metrics")
    def inner(x):
        return jnp.tanh(x) * 2.0

    f = profiling.profiled_jit(lambda x: inner(x).sum(), name="t.metrics")
    f(np.ones(8, np.float32))
    held = program_readers.program_op_scopes()["t.metrics"]
    assert "inferred" in held
    profiling._OP_SCOPES.pop("t.metrics", None)
