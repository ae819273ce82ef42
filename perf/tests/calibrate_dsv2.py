#!/usr/bin/env python3
"""Chip tool, not a test: the readings of the DeepSeek-V2-Lite cell's
``correct`` and of its controls at the cell's own size (``chiprun --
python3 perf/tests/calibrate_dsv2.py <cell> <seed>...``; ``--tiny`` for
the driver's small sizes on a CPU, ``--controls a,b`` for some only).

For each seed: the program driven through its checked steps exactly as
a run drives it, then ``check()`` against the right reference and
against each control — a deliberately wrong reference in the sound
one's place: half of a step's sequences left out, the routed experts'
term left out, overflow dropped at capacity factor 1.0, attention
across document boundaries (one replayed step each: what they must fail
is a first-step reading), the state left unchanged (two steps: the
second step's loss, and a change of the tables that reads 1 whatever
the steps), and everything in bfloat16 — tables, tensors, products —
over all checked steps; beside them the probe ``bfloat16_compute``
(tensors and products in bfloat16, tables and Adam float32), which is
read and may pass. One JSON line a seed:
``{"seed", "program": {check: value}, "controls": {name: {check:
value}}, "memory_peak_bytes"}``.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CONTROLS = ("bfloat16", "half_sequences", "no_routed", "capacity_1",
            "no_doc_mask", "unchanged")
# read beside them, and free to pass: every tensor and product in
# bfloat16 but the tables and Adam float32 (how far the precision of the
# compute alone moves the readings)
PROBES = ("bfloat16_compute",)


def readings(cell) -> dict:
    return {c["name"]: c["value"] for c in cell.check()}


def calibrate(cell_name: str, seed: int, tiny: bool,
              controls=CONTROLS + PROBES,
              log=lambda m: print(m, file=sys.stderr),
              program: bool = True) -> dict:
    import jax
    import perf.run as run

    data = run.load_cell(cell_name)
    driver = run.load_driver(data["config_data"]["driver"])
    cell = driver.Cell(config=data["config_data"],
                       traffic=data["traffic_data"], seed=seed,
                       seconds=1.0, chips=1, devices=jax.devices()[:1],
                       tiny=tiny, log=log)
    try:
        cell.setup()
        stats = jax.devices()[0].memory_stats() or {}
        cell.collect()
        line = {"seed": seed, "controls": {},
                "memory_peak_bytes": stats.get("peak_bytes_in_use")}
        if program:
            line["program"] = readings(cell)
        for control in controls:
            cell.control = control
            cell.replay_steps = {"bfloat16": None, "bfloat16_compute": None,
                                 "unchanged": 2}.get(control, 1)
            line["controls"][control] = readings(cell)
    finally:
        cell.close()
    return line


def main(argv) -> int:
    import argparse
    import perf.run as run
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("seeds", type=int, nargs="+")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--controls", default=",".join(CONTROLS + PROBES))
    ap.add_argument("--controls-only", action="store_true",
                    help="leave out the check against the right "
                         "reference (every run of the cell reads it)")
    args = ap.parse_args(argv)
    if not args.tiny:
        run.place_compile_cache()
    for seed in args.seeds:
        print(json.dumps(calibrate(args.cell, seed, args.tiny,
                                   tuple(args.controls.split(",")),
                                   program=not args.controls_only)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
