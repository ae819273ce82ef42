"""The DeepSeek-V2-Lite cell: it resolves to files, its configuration
holds the published widths, its work models are their closed forms, and
its driver at the rehearsal's tiny sizes runs set-up -> window -> check,
is ``correct``, and is not correct against any of its controls."""

import json
import os

import pytest

import perf.layer_readers as layer_readers
from perf import dsv2_work
import perf.run as run
from conftest import ROOT, benchmark

CELL = "dsv2_lite_ep8_train_pack4k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
METRICS = {
    "dsv2_step_mfu", "dsv2_moe_share", "dsv2_experts_roofline",
    "dsv2_mla_share", "dsv2_optimizer_share", "dsv2_embed_share",
    "dsv2_unscoped_share", "dsv2_device_idle_share", "dsv2_wait_data_ms",
    "dsv2_place_ms", "dsv2_dispatch_ms", "dsv2_expert_imbalance",
    "dsv2_setup_init_s", "setup_compile_s"}


def test_the_cell_resolves_to_files():
    cell = run.load_cell(CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "packed_docs_4k"
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
    # the other cells report what they did
    bench = benchmark()
    for w in bench["workloads"][:3]:
        names = {m["name"] for m in run.load_cell(w["name"])["per_layer"]}
        assert not any(n.startswith("dsv2_") for n in names)


def test_the_configuration_holds_the_published_widths():
    cfg = run.load_cell(CELL)["config_data"]
    published, program = cfg["published"], cfg["program"]
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (6, 8, 12800)
    assert (published["num_hidden_layers"], published["n_routed_experts"],
            published["vocab_size"]) == (27, 64, 102400)
    for key, value in published.items():
        if key not in cfg["reduced"]:
            # at the top level (what the driver's check reads) and in
            # the block the program is built from
            assert cfg[key] == value, key
        assert program[key] == cfg[key], key
    assert program["ep_size"] * program["n_routed_experts"] == 64
    assert program["vocab_shard"] * program["vocab_size"] == 102400
    assert program["sequences"] * program["sequence_length"] == 32768
    assert "8 v5e chips share each layer" in cfg["deployment"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row, = [json.loads(line) for line in f
                    if '"DeepSeek-V2-Lite"' in line]
        assert published == row["config"]
        assert cfg["source"] == row["source_url"]


@pytest.mark.parametrize("tokens, pairs, keys, steps", [
    (1000, 750, 300_000, 1), (31_800, 122_880, 9_000_000, 2)])
def test_work_models_against_closed_forms(tokens, pairs, keys, steps):
    sizes = run.load_cell(CELL)["config_data"]["program"]
    work = {"tokens": tokens, "assignments": pairs, "attended_keys": keys,
            "steps": steps}
    attention = 13_763_072 - 512                # less the norm's weights
    per_token = 2 * (6 * attention + 3 * 2048 * 10944
                     + 5 * (3 * 2048 * 2816 + 2048 * 64) + 2048 * 12800)
    per_key = 2 * 16 * 6 * (128 + 64 + 128)
    per_pair = 2 * 3 * 2048 * 1408
    step = dsv2_work.step(sizes, work)
    assert step["flops"] == pytest.approx(
        3.0 * (per_token * tokens + per_key * keys + per_pair * pairs))
    held = 6 * attention + 3 * 2048 * 10944 \
        + 5 * (3 * 2048 * 2816 + 2048 * 64 + 8 * 3 * 2048 * 1408) \
        + 2 * 2048 * 12800 + 19 * 2048
    assert step["bytes"] == pytest.approx(28.0 * held * steps)
    assert held == pytest.approx(635.5e6, rel=1e-3)
    experts = dsv2_work.experts(sizes, work)
    assert experts["flops"] == pytest.approx(3.0 * per_pair * pairs)
    assert experts["bytes"] == pytest.approx(
        steps * 5 * 8 * 3 * 2048 * 1408 * 10.0 + pairs * 3 * 2048 * 6.0)
    # the issue's reckoning: about 0.59 GFLOP a token forward without
    # the attention scores (all six layers' routed share at 0.75 a token)
    forward = per_token + per_pair * 5 * 0.75
    assert forward == pytest.approx(0.59e9, rel=0.02)


def test_the_seed_draws_the_documents_and_not_the_tables():
    """Every seed starts from the configuration's one draw of the
    tables (how many rows the held experts receive follows the router's
    start, and a run's work may not hang on its seed); the documents
    are the seed's."""
    import jax
    import numpy as np
    data = run.load_cell(CELL)
    driver = run.load_driver("dsv2")
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
            starts.append(np.asarray(cell._start(0, "embed")))
            first_docs.append(cell.batches[0]["tokens"])
        finally:
            cell.close()
    assert np.array_equal(starts[0], starts[1])
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
    assert checks["routed_counts_mismatch"] == {"value": 0.0, "limit": 0}
    assert checks["compiles_in_window"] == {"value": 0, "limit": 0}
    assert set(checks) >= {f"ce_gap_s{i}" for i in (1, 2, 3)} | {
        "grad_norm_gap_s1", "embed_grad_gap_s1", "expert_grad_gap_s1",
        "routing_mismatch_s1", "table_change_gap_s3"}


@pytest.fixture(scope="module")
def calibration():
    import calibrate_dsv2
    return calibrate_dsv2.calibrate(CELL, 2147483659, tiny=True,
                                    log=lambda m: None)


def _failed(readings: dict, limits: dict) -> set:
    return {k for k, v in readings.items() if v > limits[k]}


def test_the_program_passes_and_every_control_fails(calibration):
    limits = dict(run.load_cell(CELL)["config_data"]["correct"]["limits"],
                  **run.load_driver("dsv2").TINY["limits"])
    assert _failed(calibration["program"], limits) == set()
    failed = {c: _failed(r, limits)
              for c, r in calibration["controls"].items()}
    probes = {c: failed.pop(c) for c in
              __import__("calibrate_dsv2").PROBES}     # read, free to pass
    assert set(probes) == {"bfloat16_compute"}
    assert set(failed) == set(__import__("calibrate_dsv2").CONTROLS)
    assert "grad_norm_gap_s1" in failed["half_sequences"]
    assert "expert_grad_gap_s1" in failed["no_routed"]
    # the overflow shows in the probed expert's gradient if that expert
    # overflowed, and in the worst table's gradient norm whichever did
    assert failed["capacity_1"] & {"expert_grad_gap_s1", "grad_norm_gap_s1"}
    assert "grad_norm_gap_s1" in failed["no_doc_mask"]
    assert "table_change_gap_s3" in failed["unchanged"]
    # tables in bfloat16 cannot take Adam's small steps: a norm weight
    # of 1 stays 1
    assert "table_change_gap_s3" in failed["bfloat16"]
    assert all(failed.values()), failed
