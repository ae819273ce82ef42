#!/usr/bin/env python3
"""Chip-side tool, not a test: the control of the served FTRL cell at
the cell's own load (``python3 perf/tests/calibrate_kv.py <cell>
<iterations a worker> <seed>...``). The reference is numpy, so this
needs no chip: it replays every worker's frames through the float32
reference and through the bfloat16 control and prints the gap
``correct`` would read on one-writer keys, one JSON line a seed.
"""

import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv):
    import perf.run as run
    from perf import kv_traffic
    from perf.reference import ftrl

    cell, iters = argv[0], int(argv[1])
    data = run.load_cell(cell)
    sizes, traffic = data["config_data"]["program"], data["traffic_data"]
    for seed in (int(x) for x in argv[2:]):
        frames = [[kv_traffic.minibatch(seed, c, i, sizes, traffic)
                   for i in range(iters)]
                  for c in range(int(traffic["clients"]))]
        per = [np.unique(np.concatenate([k for k, _ in fr]))
               for fr in frames]
        allk, writers = np.unique(np.concatenate(per), return_counts=True)
        single = allk[writers == 1]
        t = {d: ftrl.Table(single, dtype=d, **sizes["ftrl"])
             for d in ("float32", "bfloat16")}
        for fr in frames:
            for keys, grads in fr:
                for tab in t.values():
                    tab.add(keys, grads)
        good, ctrl = t["float32"].w, t["bfloat16"].w
        gap = np.abs(ctrl - good) / np.maximum(np.abs(good), 1e-3)
        print(json.dumps({
            "seed": seed, "keys_written": len(allk),
            "one_writer": len(single), "non_zero": int((good != 0).sum()),
            "control_bf16_gap_max": float(gap.max()),
            "control_bf16_gap_median_nonzero":
                float(np.median(gap[good != 0]))}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
