#!/usr/bin/env python3
"""Chip tool, not a test: the readings the LDA cells' limits are set
from, at the cell's own size (``chiprun -- python3
perf/tests/calibrate_lda.py <cell> <seed>...``).

For each seed it follows the reference's first sweeps and, put in the
program's place against it: a second sound chain (another random start:
the floor that chance alone sets), the CONTROL (posterior and running
sum in bfloat16) and the planted faults (every other chunk left out; the
state returned unchanged). One JSON line a seed: the gaps ``correct``
would read for each.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import perf.run as run
    from perf import corpus
    from perf.reference import lda as ref

    cell, seeds = argv[0], [int(x) for x in argv[1:]]
    run.place_compile_cache()
    data = run.load_cell(cell)
    cfg = data["config_data"]
    s = cfg["program"]
    sweeps = int(data["traffic_data"]["checked_sweeps"])
    every = int(cfg["correct"]["loglik_every"])
    D, V, K = s["docs"], s["vocab_size"], s["num_topics"]
    kw = dict(alpha=float(s["alpha"]), beta=float(s["beta"]))
    dev = jax.devices()[0]
    for seed in seeds:
        lens = corpus.doc_lengths(seed, D, s["doc_len_mean"],
                                  s["doc_len_sd"], s["doc_len_min"],
                                  s["doc_len_max"])
        n = int(lens.sum())
        words = np.asarray(corpus.zipf_words(seed, n, V,
                                             s["zipf_exponent"]))
        docs = np.repeat(np.arange(D, dtype=np.int32), lens)
        w, d, m = (jnp.asarray(x) for x in ref.pad_stream(words, docs))

        def chain(salt, **fault):
            t0 = time.perf_counter()
            key = corpus.prng_key(seed, salt)
            out = ref.follow(ref.random_start(key, w.shape, K), w, d, m,
                             key, sweeps, D=D, V=V, K=K, every=every,
                             **kw, **fault)
            return out, time.perf_counter() - t0

        base, t_base = chain(2)
        line = {"seed": seed, "tokens": n, "reference_s": t_base,
                "reference": [{k: v for k, v in st.items()
                               if k != "topic_sizes"} for st in base]}
        for name, args in (("sound", dict(salt=3)),
                           ("control_bf16", dict(salt=3,
                                                 precision="bfloat16")),
                           ("fault_half", dict(salt=3, keep=2)),
                           ("fault_unchanged", dict(salt=3,
                                                    frozen=True))):
            got, took = chain(**args)
            line[name] = {f"{k}_s{i + 1}": v for i in range(sweeps)
                          for k, v in ref.gaps(got[i], base[i], n).items()}
            line[name]["seconds"] = took
        line["memory_peak_bytes"] = dev.memory_stats()["peak_bytes_in_use"]
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
