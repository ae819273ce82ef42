"""The Nemotron-H cell: it resolves to its files, its configuration holds
the published keys beside the program's, its work models are their
closed forms at hand-counted tiny shapes, and at the rehearsal's tiny
sizes it runs set-up -> window -> check, is ``correct``, and is not
correct against its controls; a program that cannot build it ends at
once."""

import json
import os

import pytest

import perf.layer_readers as layer_readers
from perf import nemotron_h_work
import perf.run as run
from conftest import ROOT, benchmark

CELL = "nemotron3_nano_ep16_train_pack8k"
# this model's own, and what the trainer it shares with the other
# language-model cells reports under the first cells' names
OWN = {"nemh_step_mfu", "nemh_ssm_share", "nemh_ssm_scan_share",
       "nemh_ssm_scan_roofline"}
SHARED = {
    "dsv2_moe_share", "dsv2_expert_imbalance", "dsv2_optimizer_share",
    "dsv2_embed_share", "dsv2_unscoped_share", "dsv2_device_idle_share",
    "dsv2_wait_data_ms", "dsv2_place_ms", "dsv2_dispatch_ms",
    "dsv2_setup_init_s", "setup_compile_s", "lm_inferred_scope_share",
    "lm_mixed_scope_share", "lm_moe_experts_share", "lm_moe_permute_share",
    "lm_moe_accumulate_share", "lm_head_share"}
KINDS = ("ce_gap", "grad_norm_gap_ssm", "grad_norm_gap_attention",
         "grad_norm_gap_router", "grad_norm_gap_experts",
         "grad_norm_gap_vocabulary",
         "grad_norm_gap_norms", "embed_grad_gap", "expert_grad_gap",
         "ssm_decay_grad_gap", "routing_mismatch")
EXACT = ("bias_mismatch", "tokens_dropped", "routed_counts_mismatch")


def test_the_cell_resolves_to_its_files():
    cell = run.load_cell(CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "packed_docs_8k_s3"
    assert cell["config_data"]["driver"] == "nemotron_h"
    assert os.path.exists(os.path.join(ROOT, "perf", "drivers",
                                       "nemotron_h.py"))
    assert [m["name"] for m in cell["end_to_end"]] == \
        ["train_tokens_per_s", "setup_s"]
    names = {m["name"] for m in cell["per_layer"]}
    assert OWN <= names and SHARED <= names
    for m in cell["per_layer"]:
        spec = layer_readers.load_metric(m["name"])
        own = os.path.join(ROOT, "perf", "layer_metrics",
                           f"{m['name']}.py")
        assert spec["reader"]["kind"] in layer_readers.KINDS \
            or os.path.exists(own), m["name"]
        assert spec["layer"] == m["layer"] and spec["unit"] == m["unit"]
    bench = benchmark()
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == "nemotron3_nano_30b_a3b_ep16"
    assert [m["name"] for m in bench["per_layer"][-4:]] == [
        "nemh_step_mfu", "nemh_ssm_share", "nemh_ssm_scan_share",
        "nemh_ssm_scan_roofline"]
    for w in bench["workloads"]:
        if w["name"] != CELL:
            others = {m["name"]
                      for m in run.load_cell(w["name"])["per_layer"]}
            assert not any(n.startswith("nemh_") for n in others)


def test_the_configuration_holds_the_published_keys():
    cell = run.load_cell(CELL)
    cfg, traffic = cell["config_data"], cell["traffic_data"]
    published, program = cfg["published"], cfg["program"]
    cut = {"num_hidden_layers": (9, 52), "n_routed_experts": (8, 128),
           "vocab_size": (16384, 131072)}
    assert cfg["reduced"] == list(cut)
    for key, value in published.items():
        if key in cut:
            assert (cfg[key], value) == cut[key], key
            assert program[key] == cfg[key], key
        else:       # every width and every other key as published
            assert cfg[key] == program[key] == value, key
    assert program["ep_size"] * program["n_routed_experts"] == 128
    assert program["vocab_shard"] * program["vocab_size"] == 131072
    assert program["hybrid_override_pattern"][:9] == "MEMEM*EME"
    assert (traffic["sequences"], traffic["sequence_length"]) == (
        program["sequences"], program["sequence_length"]) == (3, 8192)
    assert program["open_sequences"] == 32
    assert traffic["doc_length"] == {"law": "lognormal", "median": 512,
                                     "sigma": 1.0, "min": 16, "max": 8192}
    assert traffic["token_zipf_exponent"] == 1.05
    assert traffic["stream_steps"] == 64
    assert "16 v5e chips share each expert layer" in cfg["deployment"]
    assert set(cfg["correct"]["limits"]) == set(KINDS) | set(EXACT) | {
        "table_change_gap"}
    assert all(cfg["correct"]["limits"][k] == 0 for k in EXACT)
    for key in ("rotary", "time_step_limit", "init", "router",
                "expert_bias", "optimizer", "sequence_length",
                "recomputation"):
        assert cfg["assumed"][key], key
    assert cfg["source"] == ("https://huggingface.co/nvidia/"
                             "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/"
                             "config.json")
    from multiverso_tpu.apps.transformer_lm import LMConfig
    c = LMConfig.from_dict(program)
    c.check()
    assert c.router_width == 128 and c.use_expert_bias
    assert nemotron_h_work.parameters(program)["held"] == 666_963_456


# a hand-counted tiny shape: D 8, 2 ssm heads of 4 in 1 group of a state
# of 2, 2 taps; attention 2 query heads on 1 key-value head of 4; experts
# of 3 (2 held, ep 2), a shared one of 5, a dense layer of 6; vocab 10
TINY = {"hidden_size": 8, "num_hidden_layers": 4,
        "hybrid_override_pattern": "ME*-M", "mamba_num_heads": 2,
        "mamba_head_dim": 4, "n_groups": 1, "ssm_state_size": 2,
        "conv_kernel": 2, "num_attention_heads": 2,
        "num_key_value_heads": 1, "head_dim": 4,
        "moe_intermediate_size": 3,
        "moe_shared_expert_intermediate_size": 5, "n_shared_experts": 1,
        "n_routed_experts": 2, "ep_size": 2, "intermediate_size": 6,
        "vocab_size": 10}


def test_the_work_model_counts_a_hand_counted_shape():
    sizes = TINY
    p = nemotron_h_work.parameters(sizes)
    # in_proj 8 x (z 8 | x 8 | B 2 | C 2 | dt 2) + out_proj 8 x 8
    assert p["ssm"] == 8 * 22 + 64 == 240
    assert p["attention"] == 2 * 8 * 8 + 2 * 8 * 4 == 192
    assert (p["expert"], p["shared"], p["router"], p["dense"], p["head"]) \
        == (48, 80, 32, 96, 80)
    # held: the M layer's small tensors (filter + bias 3 x 12, A_log,
    # dt_bias, D, the gated norm 8), the E layer's 2 experts, shared,
    # router and bias, the norms (5 rows of 8)
    small = 3 * 12 + 3 * 2 + 8
    assert p["held"] == (240 + small) + 192 + (2 * 48 + 80 + 32 + 4) + 96 \
        + 2 * 80 + 5 * 8
    work = {"tokens": 100, "assignments": 30, "attended_keys": 700,
            "steps": 2}
    # per token: 2 x (240 + 192 + 80 + 32 + 96 + 80); the scan 4 N P H
    scan = 4 * 2 * 4 * 2
    forward = (2 * (240 + 192 + 112 + 96 + 80) + scan) * 100 \
        + 2 * 2 * 2 * 4 * 700 + 2 * 48 * 30
    step = nemotron_h_work.step(sizes, work)
    assert step["flops"] == pytest.approx(3 * forward)
    assert step["bytes"] == pytest.approx(28 * p["held"] * 2)
    scanned = nemotron_h_work.scan(sizes, work)
    assert scanned["flops"] == pytest.approx(3 * scan * 100)
    # x, B, C, dt read and y written, float32, once a pass
    assert scanned["bytes"] == pytest.approx(3 * 4 * (16 + 4 + 2) * 100)


def test_the_work_model_counts_the_cell():
    sizes = run.load_cell(CELL)["config_data"]["program"]
    p = nemotron_h_work.parameters(sizes)
    assert p["ssm"] == 27_697_152 + 11_010_048
    assert (p["expert"], p["shared"]) == (9_977_856, 19_955_712)
    assert nemotron_h_work.layers(sizes) == {"ssm": 4, "attention": 1,
                                             "experts": 4, "dense": 0}
    # the token-by-token scan: 4 N P H a layer
    work = {"tokens": 1, "assignments": 0, "attended_keys": 0, "steps": 0}
    assert nemotron_h_work.scan(sizes, work)["flops"] == \
        3 * 4 * 4 * 128 * 64 * 64


def test_the_scan_roofline_reads_the_scope_and_none_without_it(
        monkeypatch):
    from perf import program, program_readers
    spec = layer_readers.load_metric("nemh_ssm_scan_roofline")
    assert spec["unit"] == "%"
    read = program.load_module(
        "nemh_scan_roofline", os.path.join(
            ROOT, "perf", "layer_metrics",
            "nemh_ssm_scan_roofline.py")).read
    sizes = run.load_cell(CELL)["config_data"]["program"]
    ctx = {"work": {"tokens": 10_000, "assignments": 0,
                    "attended_keys": 0, "steps": 1},
           "sizes": sizes, "device_kind": "TPU v5 lite", "chips": 1}
    monkeypatch.setattr(program_readers, "scope_seconds",
                        lambda ctx, module: {"lm.ssm.scan": 1.0})
    least = nemotron_h_work.scan(sizes, ctx["work"])["bytes"] / 819.0e9
    assert read(ctx) == pytest.approx(100.0 * least)
    # a parent with no such scope reads nothing
    monkeypatch.setattr(program_readers, "scope_seconds",
                        lambda ctx, module: {})
    assert read(ctx) is None


def test_rehearsal_runs_the_cell_from_set_up_to_correct(capsys):
    rc = run.main(["--workload", CELL, "--seed", "2147483659",
                   "--seconds", "1", "--trace", "1", "--rehearse-cpu"])
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    assert line["attempted"] >= 1 and line["failed"] == 0
    checks = line["checks"]
    for name in EXACT:
        assert checks[name] == {"value": 0.0, "limit": 0}, name
    assert checks["compiles_in_window"] == {"value": 0, "limit": 0}
    assert {f"{kind}_s{s}" for s in (1, 2, 3) for kind in KINDS} \
        <= set(checks)


def test_a_program_without_the_pattern_ends_at_once(monkeypatch):
    """The parent's ``LMConfig`` reads no ``hybrid_override_pattern``:
    set-up says so and the run ends with no result."""
    import dataclasses
    import jax
    from multiverso_tpu.apps import transformer_lm
    data = run.load_cell(CELL)
    cell = run.load_driver("nemotron_h").Cell(
        config=data["config_data"], traffic=data["traffic_data"], seed=5,
        seconds=1.0, chips=1, devices=jax.devices()[:1], tiny=True,
        log=lambda m: None)
    fields = dataclasses.fields
    monkeypatch.setattr(
        dataclasses, "fields", lambda cls: tuple(
            f for f in fields(cls) if not (
                cls is transformer_lm.LMConfig
                and f.name == "hybrid_override_pattern")))
    with pytest.raises(SystemExit, match="hybrid_override_pattern"):
        cell.setup()


@pytest.fixture(scope="module")
def calibration():
    import calibrate_nemotron_h as tool
    return tool.calibrate(CELL, 2147483659, tiny=True,
                          controls=tool.CONTROLS, log=lambda m: None)


def _failed(readings: dict, limits: dict) -> set:
    import re
    return {k for k, v in readings.items()
            if v > limits[re.sub(r"_s\d+$", "", k)]}


def test_the_program_passes_and_the_controls_fail(calibration):
    """At the rehearsal's sizes the program passes and the controls that
    are no fault of the scan fail. The scan's two (its decay summed in
    bfloat16, the state carried across a document) move the tiny
    model's readings by less than its loose limits: a state of 16 over
    documents of a few dozen tokens holds little. They are separated at
    the cell's own size on the chip (PERF.md section 6)."""
    import calibrate_nemotron_h as tool
    limits = dict(run.load_cell(CELL)["config_data"]["correct"]["limits"],
                  **run.load_driver("nemotron_h").TINY["limits"])
    assert _failed(calibration["program"], limits) == set()
    failed = {c: _failed(r, limits)
              for c, r in calibration["controls"].items()}
    assert set(failed) == set(tool.CONTROLS)
    for control in ("bfloat16", "bias_frozen", "unchanged"):
        assert failed[control], control
    assert any(k.startswith("table_change_gap")
               for k in failed["unchanged"])
    assert "bias_mismatch" in failed["bias_frozen"]
    for control in ("decay_bfloat16", "no_restart"):
        assert set(calibration["controls"][control]) \
            == set(calibration["program"]), control
