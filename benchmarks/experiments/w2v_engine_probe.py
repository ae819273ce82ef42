"""Probe w2v engine headroom on the real chip: where does the 875us/step go?

Variants timed (same math, same workload as bench.py):
  base      — the production superstep as-is (threefry PRNG, f32).
  rbg       — jax_default_prng_impl=rbg (TPU-native PRNG; threefry is a
              known multi-us-per-draw cost on TPU).
  b8192     — batch 8192 x 32 steps (same pairs/call; fewer scan iters).
  b16384    — batch 16384 x 16 steps.

Run:  python benchmarks/experiments/w2v_engine_probe.py [variant ...]
"""
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "..", ".."))

VOCAB = 10_000
TOKENS = 1_000_000
DIM = 100
WINDOW = 5
SUBSAMPLE = 1e-3
LR = 0.01
WARMUP, TIMED = 2, 8


def run_variant(name: str, batch: int, steps: int) -> dict:
    import jax
    import jax.numpy as jnp
    from multiverso_tpu import core
    from multiverso_tpu.apps.word_embedding import W2VConfig, WordEmbedding
    from multiverso_tpu.data.corpus import Corpus, synthetic_text
    from multiverso_tpu.tables import base as table_base
    import tempfile

    mesh = core.init()
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "c.txt")
        synthetic_text(path, num_tokens=TOKENS, vocab_size=VOCAB, seed=1)
        corpus = Corpus.from_file(path, min_count=1, subsample=SUBSAMPLE)
    cfg = W2VConfig(embedding_dim=DIM, window=WINDOW, negative=5,
                    batch_size=batch, steps_per_call=steps,
                    learning_rate=LR, epochs=1, subsample=SUBSAMPLE, seed=1)
    app = WordEmbedding(corpus, cfg, mesh=mesh, name=f"probe_{name}")

    need = WARMUP + TIMED
    host_calls, bs, bt = [], [], []
    for src, tgt in corpus.skipgram_batches(batch, window=WINDOW, seed=1,
                                            epochs=need):
        bs.append(src)
        bt.append(tgt)
        if len(bs) == steps:
            host_calls.append((np.stack(bs), np.stack(bt)))
            bs, bt = [], []
            if len(host_calls) >= need:
                break
    calls = [app._place(s, t) for s, t in host_calls]
    lrs = core.place(np.full(steps, LR, np.float32), mesh=mesh)

    def dispatch(i, placed):
        key = jax.random.fold_in(app._key, i)
        _, (loss, _) = app._fused((), placed, key, lrs)
        return loss

    wl = None
    for i in range(WARMUP):
        wl = dispatch(i, calls[i])
    float(wl)
    t0 = time.perf_counter()
    loss = None
    for i in range(WARMUP, need):
        loss = dispatch(i, calls[i])
    loss = float(loss)
    dt = time.perf_counter() - t0
    pairs = TIMED * batch * steps
    out = {"variant": name, "batch": batch, "steps": steps,
           "pairs_per_sec": round(pairs / dt, 1),
           "us_per_step": round(dt / (TIMED * steps) * 1e6, 1),
           "loss": round(loss, 4)}
    table_base.reset_tables()
    core.shutdown()
    return out


def main():
    which = sys.argv[1:] or ["base", "rbg", "b8192", "b16384"]
    results = []
    for name in which:
        if name == "rbg":
            import jax
            jax.config.update("jax_default_prng_impl", "rbg")
            results.append(run_variant("rbg", 4096, 64))
            jax.config.update("jax_default_prng_impl", "threefry2x32")
        elif name == "base":
            results.append(run_variant("base", 4096, 64))
        elif name == "b8192":
            results.append(run_variant("b8192", 8192, 32))
        elif name == "b16384":
            results.append(run_variant("b16384", 16384, 16))
        else:
            raise SystemExit(f"unknown variant {name}")
        print(json.dumps(results[-1]), flush=True)
    print(json.dumps({"all": results}))


if __name__ == "__main__":
    main()
