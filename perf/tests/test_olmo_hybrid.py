"""The Olmo-Hybrid cell: it resolves to files, its configuration holds
the published keys beside the program's, its work models are their
closed forms, and its driver at the rehearsal's tiny sizes runs set-up
-> window -> check, is ``correct``, and is not correct against any of
its controls."""

import json
import os

import pytest

import perf.layer_readers as layer_readers
from perf import olmo_hybrid_work
import perf.run as run
from conftest import ROOT, benchmark

CELL = "olmo_hybrid_7b_vp8_train_pack4k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# what is this model's own, and what the trainer it shares with the
# other language-model cell reports under that cell's names
METRICS = {
    "olmoh_step_mfu", "olmoh_gdn_share", "olmoh_gdn_recur_share",
    "olmoh_gdn_recur_roofline", "olmoh_attend_share",
    "olmoh_attend_roofline", "olmoh_mlp_share", "olmoh_pad_share",
    "dsv2_optimizer_share", "dsv2_embed_share", "dsv2_unscoped_share",
    "dsv2_device_idle_share", "dsv2_wait_data_ms", "dsv2_place_ms",
    "dsv2_dispatch_ms", "dsv2_setup_init_s", "setup_compile_s"}
KINDS = ("ce_gap", "grad_norm_gap", "embed_grad_gap", "gdn_k_grad_gap")


def test_the_cell_resolves_to_files():
    cell = run.load_cell(CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "packed_docs_4k_b2"
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
    for w in benchmark()["workloads"]:
        if w["name"] != CELL:
            names = {m["name"]
                     for m in run.load_cell(w["name"])["per_layer"]}
            assert not any(n.startswith("olmoh_") for n in names)


def test_the_configuration_holds_the_published_keys():
    cfg = run.load_cell(CELL)["config_data"]
    published, program = cfg["published"], cfg["program"]
    assert cfg["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["vocab_size"]) == (4, 12544)
    assert (published["num_hidden_layers"], published["vocab_size"]) \
        == (32, 100352)
    for key, value in published.items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key       # what the driver's check reads
        if key == "layer_types":                # the program's first four
            assert program[key] == value[:4] == \
                ["linear_attention"] * 3 + ["full_attention"]
        else:
            assert program[key] == cfg[key], key
    assert program["vocab_shard"] * program["vocab_size"] == 100352
    assert program["sequences"] * program["sequence_length"] == 4096
    assert program["open_sequences"] == 32
    assert "8 v5e chips share the embedding and the head" in cfg["deployment"]
    traffic = run.load_cell(CELL)["traffic_data"]
    assert (traffic["sequences"], traffic["sequence_length"]) \
        == (program["sequences"], program["sequence_length"])
    assert set(cfg["correct"]["limits"]) == set(KINDS) | {
        "table_change_gap", "tokens_dropped"}
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row, = [json.loads(line) for line in f
                    if '"Olmo-Hybrid-7B"' in line]
        assert published == row["config"]
        assert cfg["source"] == row["source_url"]


@pytest.mark.parametrize("tokens, keys, steps", [
    (1000, 300_000, 1), (8_100, 4_000_000, 2)])
def test_work_models_against_closed_forms(tokens, keys, steps):
    sizes = run.load_cell(CELL)["config_data"]["program"]
    work = {"tokens": tokens, "attended_keys": keys, "steps": steps}
    linear = 2 * 3840 * 2880 + 2 * 3840 * 5760 + 2 * 3840 * 30 \
        + 5760 * 3840                           # the mixer's matrices
    small = 4 * 11520 + 60 + 192
    assert linear + small == 88_750_332
    mlp, full, head = 3 * 3840 * 11008, 4 * 3840 * 3840, 3840 * 12544
    recurrence = 6 * 96 * 192 * 30 * 3
    per_token = 2 * (3 * linear + full + 4 * mlp + head) + recurrence
    per_key = 2 * 30 * (128 + 128)
    step = olmo_hybrid_work.step(sizes, work)
    assert step["flops"] == pytest.approx(
        3.0 * (per_token * tokens + per_key * keys))
    held = 3 * (linear + small) + full + 2 * 3840 + 4 * (mlp + 2 * 3840) \
        + 2 * head + 3840
    assert step["bytes"] == pytest.approx(28.0 * held * steps)
    assert held == pytest.approx(928.9e6, rel=1e-3)
    recur = olmo_hybrid_work.recur(sizes, work)
    assert recur["flops"] == pytest.approx(3.0 * recurrence * tokens)
    assert recur["bytes"] == pytest.approx(
        3.0 * 4 * 30 * (2 * 96 + 2 * 192 + 2) * 3 * tokens)
    attend = olmo_hybrid_work.attend(sizes, work)
    assert attend == {"flops": pytest.approx(3.0 * per_key * keys),
                      "bytes": 0.0}
    # the issue's reckoning: the recurrence's least work is 0.24 TFLOP of
    # a step of 8,192 tokens, its products 43 TFLOP
    assert 3.0 * recurrence * 8192 == pytest.approx(0.245e12, rel=0.01)
    assert 3.0 * per_token * 8192 == pytest.approx(43e12, rel=0.03)


def test_the_seed_draws_the_documents_and_not_the_tables():
    import jax
    import numpy as np
    data = run.load_cell(CELL)
    driver = run.load_driver("olmo_hybrid")
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
            assert cell.config.hidden_size == 48        # this model's tiny
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
    assert checks["tokens_dropped"] == {"value": 0.0, "limit": 0}
    assert checks["compiles_in_window"] == {"value": 0, "limit": 0}
    assert set(checks) >= {f"{kind}_s{s}" for s in (1, 2, 3)
                           for kind in KINDS} | {"table_change_gap_s3"}


@pytest.mark.parametrize("lengths, checked, graded_from", [
    ([20, 30, 64, 64, 10], 3, 0),           # documents a step: 1, 1, 3
    ([64, 64, 64, 64, 20, 30, 30, 40], 5, 1),       # 1, 1, 1, 1, 2
    ([64] * 7 + [30, 30, 40, 40], 8, 4)])           # seven of 1, then 2
def test_the_checked_steps_hold_a_document_boundary(lengths, checked,
                                                    graded_from):
    """First fit closes the fullest sequence first, so a stream may open
    with steps of ONE document each, in which nothing can cross a
    boundary: the driver checks on until a step holds one, and compares
    gradients on the steps the program kept them of (its last four)."""
    import types
    import numpy as np
    from multiverso_tpu.apps.transformer_lm import AUX_KEEP
    data = run.load_cell(CELL)
    cell = run.load_driver("olmo_hybrid").Cell(
        config=data["config_data"], traffic=data["traffic_data"], seed=5,
        seconds=1.0, chips=1, devices=None, tiny=True, log=lambda m: None)
    cell.config = types.SimpleNamespace(sequences=1, sequence_length=64,
                                        open_sequences=2)
    cell.docs = run.load_driver("dsv2").Epochs(
        [np.ones(n, np.int32) for n in lengths])
    assert cell.checked == checked
    documents = [int(b["doc"].max()) for b in cell._pack(checked)]
    assert max(documents) > 1
    assert checked == 3 or max(documents[:-1]) == 1
    assert max(0, checked - AUX_KEEP) == graded_from


@pytest.fixture(scope="module")
def calibration():
    import calibrate_olmo_hybrid as tool
    return tool.calibrate(CELL, 2147483659, tiny=True,
                          controls=tool.CONTROLS, log=lambda m: None)


def _failed(readings: dict, limits: dict) -> set:
    """Checks over their kind's limit (``ce_gap`` for ``ce_gap_s2``)."""
    return {k for k, v in readings.items()
            if v > limits[k.rsplit("_s", 1)[0]]}


def test_the_program_passes_and_the_controls_fail(calibration):
    limits = dict(run.load_cell(CELL)["config_data"]["correct"]["limits"],
                  **run.load_driver("olmo_hybrid").TINY["limits"])
    assert _failed(calibration["program"], limits) == set()
    failed = {c: _failed(r, limits)
              for c, r in calibration["controls"].items()}
    assert set(failed) == set(
        __import__("calibrate_olmo_hybrid").CONTROLS)
    assert "table_change_gap_s3" in failed["unchanged"]
    assert "table_change_gap_s3" in failed["bfloat16"]
    # the state rounded between chunks moves the readings less than the
    # rehearsal's float32 program differs from its reference at these
    # sizes' limits: read, and free to pass HERE (the chip's run of this
    # tool decides at the cell's size)
    failed.pop("state_bfloat16")
    assert all(failed.values()), failed
