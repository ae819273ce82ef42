"""Reference-scale out-of-core LDA demonstration, self-contained.

One entry point with no dependency on pre-existing /tmp state:

  1. regenerates the corpus cache if missing (zipf_corpus_cached is
     fully guarded: corrupt/foreign/truncated caches regenerate),
  2. runs each requested scale through lda_stream_100m.py in a fresh
     process (clean HBM + honest RSS accounting per scale; one process
     holds the chip at a time, and this parent never touches jax),
  3. leaves lda_stream_{N}m.json committed-ready in this directory.

A scale whose process fails — no chip included — stops the ladder with
a non-zero exit.

Usage:
  python lda_stream_scale.py                      # 300M then 1B
  python lda_stream_scale.py --tokens 300000000   # one scale

Corpus caches default to /tmp/lda_corpus_{N}m.npz (scratch only — they
are recreated when absent; ~2.4 GB at 300M, ~8 GB at 1B, generation
~6 min/100M tokens single-threaded). Override the directory with
MVTPU_CORPUS_DIR.
"""
import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
RUNNER = os.path.join(HERE, "lda_stream_100m.py")

def run_scale(tokens: int) -> dict | None:
    """Run one scale in a fresh process; return the artifact dict."""
    mname = tokens // 1_000_000
    cache_dir = os.environ.get("MVTPU_CORPUS_DIR", "/tmp")
    cache = os.path.join(cache_dir, f"lda_corpus_{mname}m.npz")
    artifact = os.path.join(HERE, f"lda_stream_{mname}m.json")
    # generation ~6 min/100M if the cache is missing, staging ~2 min/100M,
    # 3 sweeps at the measured stream rate ~1 min/100M each
    budget = 1200 + int(tokens / 1e6 * 8)
    env = dict(os.environ, MVTPU_CORPUS_NPZ=cache)
    print(f"--- {mname}M tokens (budget {budget}s, cache {cache}) ---",
          flush=True)
    t0 = time.monotonic()
    proc = subprocess.run([sys.executable, RUNNER, str(tokens)],
                          env=env, timeout=budget)
    print(f"{mname}M: rc={proc.returncode} "
          f"({time.monotonic() - t0:.0f}s)", flush=True)
    if proc.returncode != 0 or not os.path.exists(artifact):
        return None
    with open(artifact) as f:
        result = json.load(f)
    return result if "loglik" in result else None


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", default="300000000,1000000000",
                    help="comma-separated token counts")
    args = ap.parse_args()
    scales = [int(t) for t in args.tokens.split(",")]

    ok = 0
    for tokens in scales:
        result = run_scale(tokens)
        if result is None:
            print(f"scale {tokens} FAILED — stopping the ladder "
                  "(larger scales share the same path)", flush=True)
            break
        best = max(s["tok_per_sec"] for s in result["sweeps"])
        print(f"scale {tokens}: best {best:,.0f} tok/s, "
              f"loglik/token {result['loglik']:.4f}, "
              f"hbm {result['hbm_mb_after_init']}MB", flush=True)
        ok += 1
    return 0 if ok == len(scales) else 1


if __name__ == "__main__":
    sys.exit(main())
