#!/usr/bin/env python3
"""Calibration script, not a test: the readings of the Nemotron-H cell's
``correct`` and of its controls at the cell's own size
(on a machine with the chip: ``python3
perf/tests/calibrate_nemotron_h.py <cell> <seed>...``;
``--tiny`` for the Nemotron-H driver's ``TINY`` sizes on a CPU, ``--controls a,b`` for
some only, ``--witness key=value,...`` for the program built with other
``program`` keys — ``compute_dtype=float32`` runs its products in
float32 at ``highest`` precision, which shows how much of the program's
distance from the reference its bfloat16 operands are).

For each seed: the program driven through its checked steps exactly as
a run drives it, then ``check()`` against the right reference and
against each control — a deliberately wrong reference in the sound
one's place: the scan's decay in bfloat16, the state carried across a
document start, everything in bfloat16, the selection bias never
stepped — each over all the checked steps, as a run compares them; the
state left unchanged over two. One
JSON line a reading, as it is made: ``{"seed", "witness", "control"
(null: the right reference), "replayed", "documents" (a checked step),
"readings": {check: value}, "memory_peak_bytes"}``.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CONTROLS = ("decay_bfloat16", "no_restart", "bfloat16", "bias_frozen",
            "unchanged")


def calibrate(cell_name: str, seed: int, tiny: bool, controls=CONTROLS,
              log=lambda m: print(m, file=sys.stderr),
              program: bool = True, witness=None,
              emit=lambda line: None) -> dict:
    """``{"program": readings, "controls": {name: readings}}`` of one
    seed; ``emit`` is given each reading's line as it is made.
    ``witness``: keys of the configuration's ``program`` to build the
    program with in the published ones' place."""
    import jax
    import perf.run as run

    data = run.load_cell(cell_name)
    config = data["config_data"]
    if witness:
        config = dict(config, program=dict(config["program"], **witness))
        if witness.get("compute_dtype") == "float32":
            jax.config.update("jax_default_matmul_precision", "highest")
    driver = run.load_driver(config["driver"])
    cell = driver.Cell(config=config, traffic=data["traffic_data"],
                       seed=seed, seconds=1.0, chips=1,
                       devices=jax.devices()[:1], tiny=tiny, log=log)
    out = {"seed": seed, "controls": {}}
    try:
        cell.setup()
        stats = jax.devices()[0].memory_stats() or {}
        cell.collect()
        documents = [int(b["doc"].max()) for b in cell.batches]
        for control in ((None,) if program else ()) + tuple(controls):
            cell.control = control
            cell.replay_steps = 2 if control == "unchanged" else None
            readings = {c["name"]: c["value"] for c in cell.check()}
            emit({"seed": seed, "witness": witness, "control": control,
                  "replayed": cell.replay_steps or len(documents),
                  "documents": documents, "readings": readings,
                  "memory_peak_bytes": stats.get("peak_bytes_in_use")})
            if control is None:
                out["program"] = readings
            else:
                out["controls"][control] = readings
    finally:
        cell.close()
    return out


def main(argv) -> int:
    import argparse
    import perf.run as run
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("seeds", type=int, nargs="+")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--controls", default=",".join(CONTROLS))
    ap.add_argument("--controls-only", action="store_true",
                    help="leave out the check against the right "
                         "reference (every run of the cell reads it)")
    ap.add_argument("--witness", default="",
                    help="program keys to build the program with, "
                         "key=value,... (values as JSON, else strings)")
    args = ap.parse_args(argv)
    witness = {}
    for pair in filter(None, args.witness.split(",")):
        key, value = pair.split("=", 1)
        try:
            witness[key] = json.loads(value)
        except ValueError:
            witness[key] = value
    if not args.tiny:
        run.place_compile_cache()
    for seed in args.seeds:
        calibrate(args.cell, seed, args.tiny,
                  tuple(filter(None, args.controls.split(","))),
                  program=not args.controls_only, witness=witness or None,
                  emit=lambda line: print(json.dumps(line), flush=True))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
