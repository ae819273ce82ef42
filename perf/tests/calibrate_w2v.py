#!/usr/bin/env python3
"""Chip tool, not a test: the control and the planted fault of the
word2vec cell at the cell's own size (``chiprun -- python3
perf/tests/calibrate_w2v.py <cell> <seed>...``).

For each seed: the PROGRAM with its own lower-precision path switched on
(``W2VConfig.dtype="bfloat16"``: the control) driven through the checked
calls and compared with the float32 reference exactly as a run is; and
the reference with half of every step's pairs left out (the first half
taken twice), in the program's place. One JSON line a seed.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import perf.run as run
    from perf.reference import w2v as ref

    cell, seeds = argv[0], [int(x) for x in argv[1:]]
    run.place_compile_cache()
    data = run.load_cell(cell)
    driver = run.load_driver(data["config_data"]["driver"])
    for seed in seeds:
        cfg = json.loads(json.dumps(data["config_data"]))
        cfg["program"]["dtype"] = "bfloat16"            # the control
        c = driver.Cell(config=cfg, traffic=data["traffic_data"],
                        seed=seed, seconds=1.0, chips=1,
                        devices=jax.devices()[:1], tiny=False,
                        log=lambda m: print(m, file=sys.stderr))
        c.setup()
        c.collect()
        c.sizes["dtype"] = "float32"        # against the reference
        c.limits = {k: float("inf") for k in c.limits}
        line = {"seed": seed, "control_bf16_program": {
            x["name"]: x["value"] for x in c.check()}}
        # the fault, planted in the reference: the float32 reference
        # stands in for the sound program
        s = c.sizes
        src, tgt = c.pairs
        h = src.shape[1] // 2
        bad = (jnp.asarray(src[:, :h].repeat(2, axis=1)),
               jnp.asarray(tgt[:, :h].repeat(2, axis=1)))
        good = (jnp.asarray(src), jnp.asarray(tgt))
        table = jnp.asarray(ref.unigram_table(
            c.counts, s["unigram_power"], s["ns_table_size"]))
        key = jax.random.fold_in(jax.random.PRNGKey(c.prog_seed), 0)
        lrs = jnp.asarray(ref.learning_rates(
            0, 1, s["steps_per_call"], s["learning_rate"],
            s["min_lr_frac"]))
        w0 = ref.init_input_vectors(c.prog_seed, s["vocab_size"],
                                    s["embedding_dim"])
        rows = jnp.asarray(c.rows)
        rows0 = w0[c.rows]
        out = {}
        for name, (a, b) in (("good", good), ("half", bad)):
            w_in = jnp.asarray(w0)
            w_out = jnp.zeros_like(w_in)
            losses, norms = [], []
            for i in range(c.checked):
                w_in, w_out, loss = ref.call(w_in, w_out, a, b, key, lrs,
                                             table, negative=s["negative"])
                losses.append(float(loss))
                if i in (0, c.checked - 1):
                    norms.append({
                        "w_in": driver._norm(np.asarray(w_in[rows])
                                             - rows0),
                        "w_out": float(ref.change_norm(w_out, 0.0))})
            out[name] = (losses, norms)
            del w_in, w_out
        g, b = out["good"], out["half"]
        line["fault_half"] = {
            **{f"loss_gap_c{i + 1}": abs(b[0][i] - g[0][i]) / abs(g[0][i])
               for i in range(c.checked)},
            "first_change_norm_gap": max(
                abs(b[1][0][k] - g[1][0][k]) / g[1][0][k] for k in g[1][0]),
            f"change_norm_gap_c{c.checked}": max(
                abs(b[1][1][k] - g[1][1][k]) / g[1][1][k]
                for k in g[1][1])}
        line["memory_peak_bytes"] = jax.devices()[0].memory_stats()[
            "peak_bytes_in_use"]
        print(json.dumps(line), flush=True)
        del w0, table
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
