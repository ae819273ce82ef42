"""The LFM2-MoE cell: it resolves to files, its configuration holds the
published keys beside the program's, its work models are their closed
forms, and its driver at the rehearsal's tiny sizes runs set-up ->
window -> check, is ``correct``, and is not correct against its
controls."""

import json
import os

import pytest

import perf.layer_readers as layer_readers
from perf import lfm2_work
import perf.run as run
from conftest import ROOT, benchmark

CELL = "lfm2_8b_a1b_ep4_train_pack4k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# what is this model's own, and what the trainer it shares with the
# other language-model cells reports under the first cell's names
METRICS = {
    "lfm2_step_mfu", "lfm2_conv_share", "lfm2_conv_mix_roofline",
    "lfm2_attend_share", "lfm2_attend_roofline", "lfm2_experts_roofline",
    "dsv2_moe_share", "dsv2_expert_imbalance", "dsv2_optimizer_share",
    "dsv2_embed_share", "dsv2_unscoped_share", "dsv2_device_idle_share",
    "dsv2_wait_data_ms", "dsv2_place_ms", "dsv2_dispatch_ms",
    "dsv2_setup_init_s", "setup_compile_s"}
KINDS = ("ce_gap", "grad_norm_gap", "embed_grad_gap", "conv_in_grad_gap",
         "routing_mismatch")
EXACT = ("bias_mismatch", "tokens_dropped", "routed_counts_mismatch")


def test_the_cell_resolves_to_files():
    cell = run.load_cell(CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "packed_docs_4k_s4"
    assert os.path.exists(os.path.join(
        ROOT, "perf", "drivers", f"{cell['config_data']['driver']}.py"))
    assert [m["name"] for m in cell["end_to_end"]] == \
        ["train_tokens_per_s", "setup_s"]
    assert {m["name"] for m in cell["per_layer"]} == METRICS
    for m in cell["per_layer"]:
        spec = layer_readers.load_metric(m["name"])
        own = os.path.join(ROOT, "perf", "layer_metrics",
                           f"{m['name']}.py")
        assert spec["reader"]["kind"] in layer_readers.KINDS \
            or os.path.exists(own), m["name"]
        assert spec["layer"] == m["layer"] and spec["unit"] == m["unit"]
    # the other cells report what they did
    bench = benchmark()
    assert len(bench["workloads"]) == 6
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    for w in bench["workloads"]:
        if w["name"] != CELL:
            names = {m["name"]
                     for m in run.load_cell(w["name"])["per_layer"]}
            assert not any(n.startswith("lfm2_") for n in names)


def test_the_configuration_holds_the_published_keys():
    cell = run.load_cell(CELL)
    cfg, traffic = cell["config_data"], cell["traffic_data"]
    published, program = cfg["published"], cfg["program"]
    cut = {"num_hidden_layers": (5, 24), "num_dense_layers": (1, 2),
           "num_experts": (8, 32), "vocab_size": (16384, 65536)}
    assert cfg["reduced"] == list(cut)
    for key, value in published.items():
        if key in cut:
            assert (cfg[key], value) == cut[key], key
            assert program[key] == cfg[key], key
        elif key == "layer_types":      # the program's: published 1-5
            assert cfg[key] == value
            assert program[key] == value[1:6] == [
                "conv", "full_attention", "conv", "conv", "conv"]
        else:       # every width and every other key as published
            assert cfg[key] == program[key] == value, key
    assert program["ep_size"] * program["num_experts"] == 32
    assert program["vocab_shard"] * program["vocab_size"] == 65536
    assert program["tie_word_embeddings"] is True
    assert program["sequences"] * program["sequence_length"] == 16384
    assert program["open_sequences"] == 32
    assert "4 v5e chips, one host, share each layer" in cfg["deployment"]
    assert (traffic["sequences"], traffic["sequence_length"]) \
        == (program["sequences"], program["sequence_length"])
    assert traffic["doc_length"] == {"law": "lognormal", "median": 512,
                                     "sigma": 1.0, "min": 16, "max": 4096}
    assert traffic["token_zipf_exponent"] == 1.05
    assert traffic["stream_steps"] == 128
    assert set(cfg["correct"]["limits"]) == set(KINDS) | set(EXACT) | {
        "table_change_gap"}
    assert all(cfg["correct"]["limits"][k] == 0 for k in EXACT)
    for key in ("tie_word_embeddings", "expert_bias", "optimizer", "init",
                "head_dim", "rotary", "sequence_length", "recomputation"):
        assert cfg["assumed"][key], key
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row, = [json.loads(line) for line in f
                    if '"LFM2-8B-A1B"' in line]
        assert published == row["config"]
        assert cfg["source"] == row["source_url"]
    # the program builds it from these keys, at the issue's count
    from multiverso_tpu.apps.transformer_lm import (LMConfig, table_layout,
                                                    table_shapes)
    import numpy as np
    c = LMConfig.from_dict(program)
    c.check()
    shapes, layout = table_shapes(c), table_layout(c)
    assert sum(int(np.prod(np.empty(s, np.int8)[index].shape))
               for name, s in shapes.items()
               for index in layout[name].values()) == 507_820_288
    assert lfm2_work.parameters(program)["held"] == 507_820_288


@pytest.mark.parametrize("tokens, pairs, keys, steps", [
    (1000, 700, 300_000, 1), (16_200, 15_900, 9_000_000, 2)])
def test_work_models_against_hand_counts(tokens, pairs, keys, steps):
    sizes = run.load_cell(CELL)["config_data"]["program"]
    work = {"tokens": tokens, "assignments": pairs, "attended_keys": keys,
            "steps": steps}
    D = 2048
    conv, full = 4 * D * D, 2 * D * D + 2 * D * 512
    dense, expert, router, head = 3 * D * 7168, 3 * D * 1792, D * 32, \
        D * 16384
    assert conv + 3 * D == 16_783_360 and full + 128 == 10_485_888
    assert (dense, expert) == (44_040_192, 11_010_048)
    # a token's products: 4 conv + 1 attention mixers, 1 dense
    # feed-forward, 4 routers, the head — and the gates and taps
    per_token = 2 * (4 * conv + full + dense + 4 * router + head) \
        + 4 * (2 * 3 + 2) * D
    per_key = 2 * 2 * 32 * 64           # one attention layer
    step = lfm2_work.step(sizes, work)
    assert step["flops"] == pytest.approx(
        3.0 * (per_token * tokens + per_key * keys + 2 * expert * pairs))
    assert step["bytes"] == pytest.approx(28.0 * 507_820_288 * steps)
    # the issue's sizing: 199.3M parameters' products a token at an even
    # router (4 of 32 experts a token, a quarter of them held: 1 a layer)
    even = per_token - 4 * 8 * D + 2 * expert * 4
    assert even / 2 == pytest.approx(199.3e6, rel=2e-3)
    assert 3 * even == pytest.approx(1.20e9, rel=5e-3)
    mix = lfm2_work.conv_mix(sizes, work)
    assert mix["bytes"] == pytest.approx(3.0 * 4 * 4 * D * 4 * tokens)
    assert mix["flops"] == pytest.approx(3.0 * 4 * 8 * D * tokens)
    attend = lfm2_work.attend(sizes, work)
    assert attend["flops"] == pytest.approx(3.0 * per_key * keys)
    assert attend["bytes"] == pytest.approx(
        3.0 * 2 * (2 * D + 2 * 512) * tokens)
    experts = lfm2_work.experts(sizes, work)
    assert experts["flops"] == pytest.approx(3.0 * 2 * expert * pairs)
    assert experts["bytes"] == pytest.approx(
        steps * 4 * 8 * expert * 10.0 + pairs * 3 * D * 6.0)


def test_the_seed_draws_the_documents_and_not_the_tables():
    import jax
    import numpy as np
    data = run.load_cell(CELL)
    driver = run.load_driver("lfm2")
    cells = [driver.Cell(config=data["config_data"],
                         traffic=data["traffic_data"], seed=seed,
                         seconds=1.0, chips=1, devices=jax.devices()[:1],
                         tiny=True, log=lambda m: None)
             for seed in (5, 2147483659)]
    starts, first_docs = [], []
    for cell in cells:
        try:
            cell.setup()
            assert cell.config.seed == \
                data["config_data"]["program"]["init_seed"]
            assert cell.config.hidden_size == 64        # this model's tiny
            assert cell.config.router_width == 8
            starts.append([np.asarray(cell._start(i, n))
                           for i, n in enumerate(cell.shapes)])
            first_docs.append(cell.batches[0]["tokens"])
        finally:
            cell.close()
    assert all(np.array_equal(a, b) for a, b in zip(*starts))
    assert not np.array_equal(first_docs[0], first_docs[1])


def test_rehearsal_runs_the_cell_from_set_up_to_correct(capsys):
    rc = run.main(["--workload", CELL, "--seed", "2147483659",
                   "--seconds", "1", "--trace", "1", "--rehearse-cpu"])
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert line["metrics"] == {}
    checks = line["checks"]
    for name in EXACT:
        assert checks[name] == {"value": 0.0, "limit": 0}, name
    assert checks["compiles_in_window"] == {"value": 0, "limit": 0}
    assert set(checks) >= {f"{kind}_s{s}" for s in (1, 2, 3)
                           for kind in KINDS} | {"table_change_gap_s3"}


def test_a_program_without_the_short_convolution_ends_at_once(monkeypatch):
    """The parent's ``LMConfig`` reads no ``conv_L_cache``: set-up says
    so and the run ends with no result."""
    import dataclasses
    import jax
    from multiverso_tpu.apps import transformer_lm
    data = run.load_cell(CELL)
    cell = run.load_driver("lfm2").Cell(
        config=data["config_data"], traffic=data["traffic_data"], seed=5,
        seconds=1.0, chips=1, devices=jax.devices()[:1], tiny=True,
        log=lambda m: None)
    fields = dataclasses.fields
    monkeypatch.setattr(
        dataclasses, "fields", lambda cls: tuple(
            f for f in fields(cls) if not (
                cls is transformer_lm.LMConfig
                and f.name == "conv_L_cache")))
    with pytest.raises(SystemExit, match="conv_L_cache"):
        cell.setup()


@pytest.fixture(scope="module")
def calibration():
    import calibrate_lfm2 as tool
    return tool.calibrate(CELL, 2147483659, tiny=True,
                          controls=tool.CONTROLS, log=lambda m: None)


def _failed(readings: dict, limits: dict) -> set:
    """Checks over their kind's limit (``ce_gap`` for ``ce_gap_s2``)."""
    import re
    return {k for k, v in readings.items()
            if v > limits[re.sub(r"_s\d+$", "", k)]}


def test_the_program_passes_and_the_controls_fail(calibration):
    import calibrate_lfm2 as tool
    limits = dict(run.load_cell(CELL)["config_data"]["correct"]["limits"],
                  **run.load_driver("lfm2").TINY["limits"])
    assert _failed(calibration["program"], limits) == set()
    failed = {c: _failed(r, limits)
              for c, r in calibration["controls"].items()}
    assert set(failed) == set(tool.CONTROLS)
    assert "table_change_gap_s3" in failed["unchanged"]
    assert "table_change_gap_s3" in failed["bfloat16"]
    assert "bias_mismatch" in failed["bias_frozen"]
    # three steps move a bias by 0.003 at most: what it adds to a weight
    # is less than the rehearsal's bfloat16 program differs from its
    # float32 reference, and the tier-1 tests hold the float32 program to
    # the reference where the biases are large: read, and free to pass
    # HERE
    failed.pop("bias_in_weights")
    assert all(failed.values()), failed
