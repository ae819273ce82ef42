"""LightLDA benchmark: TPU sampler vs the faithful C++ MH baseline.

Protocol (recorded in benchmarks/lda_results.json):

- Matched synthetic workload: V=50k zipf-1.1 vocab, 100k docs, 10M
  tokens. The CPU side runs K=1000 (the BASELINE config's "1k topics");
  the TPU side runs K=1024 (lane-aligned) — MORE work per token than the
  baseline, i.e. the round-up is generous to the reference.
- CPU: native/lda_bench.cpp — the reference sampler implemented
  faithfully (O(1) MH: per-sweep word-proposal alias tables + z-array doc
  proposal, 2 MH rounds), one worker. The 16-worker cluster is scored as
  16x this (perfect scaling, zero PS cost — generous to the reference).
- TPU: the PRODUCTION sampler — the doc-blocked pallas Gibbs kernel
  (apps/lightlda sampler='tiled', with its sweep-stale bf16 word-count
  mirror): collapsed Gibbs with in-register
  own-token removal, batch-stale doc counts within a 512-token block,
  and word counts stale per sweep — the SAME staleness model the
  reference runs (word rows fetched per slice, updates pushed at block
  end; its alias tables are additionally stale, which ours are not).
  Batch 512k tokens. Steady-state sweep incl. the per-sweep word-master
  rebuild, compile excluded, host-transfer fence. The exact per-run
  config is recorded in lda_results.json (sampler/block_* fields).
- Quality asymmetry still favors the baseline: every Gibbs variant here
  mixes faster per sweep than the baseline's MH proposals, and the
  doc-blocked sampler is held to the exact gibbs one by invariant +
  likelihood-convergence tests (tests/test_lightlda.py).

Run: python benchmarks/measure_lda.py   (rewrites lda_results.json)
"""

import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
OUT = os.path.join(HERE, "lda_results.json")
sys.path.insert(0, REPO)

def _env_int(name: str, default: int) -> int:
    """Workload-constant override hook: bench.py's MVTPU_BENCH_TINY mode
    shrinks the workload so the INTEGRATED pipeline can be exercised on
    a CPU backend (the baseline workload-match guards key off the same
    constants, so a tiny run can never be scored against the pinned
    full-size CPU artifact)."""
    try:
        return int(os.environ.get(name, ""))
    except ValueError:
        return default


V = _env_int("MVTPU_LDA_V", 50_000)
D = _env_int("MVTPU_LDA_D", 100_000)
T = _env_int("MVTPU_LDA_T", 10_000_000)
K_CPU = _env_int("MVTPU_LDA_K_CPU", 1000)
K_TPU = _env_int("MVTPU_LDA_K_TPU", 1024)
BATCH = _env_int("MVTPU_LDA_BATCH", 500_000)


def measure_cpu(sweeps: int = 2, curve: bool = False) -> dict:
    subprocess.run(["make", "-C", os.path.join(REPO, "native"),
                    "lda_bench"], check=True, capture_output=True)
    binary = os.path.join(REPO, "native", "build", "lda_bench")
    args = [binary, "-vocab", str(V), "-docs", str(D), "-tokens", str(T),
            "-topics", str(K_CPU), "-sweeps", str(sweeps), "-seed", "1"]
    if curve:
        args += ["-curve", "1"]
    out = subprocess.run(args, check=True, capture_output=True,
                         text=True).stdout
    return json.loads(out)


def zipf_corpus_cached(vocab: int, docs: int, tokens: int, seed: int,
                       cache_path: str = None):
    """(tw, td) for the zipf-1.1 synthetic workload, disk-cached.

    The draw costs minutes at 100M+ tokens and ~40s even at 10M —
    regenerating inside every bench.py run wastes the driver's time
    budget and risks its timeout. Shared by the bench tier and the
    out-of-core artifact script (one implementation, one validation
    scheme). The load is fully guarded (corrupt/foreign/truncated cache
    → regenerate, never crash: a driver kill mid-write must not poison
    every later run) and validated against embedded workload metadata;
    the write is atomic (tmp + os.replace)."""
    import numpy as np
    if cache_path and not cache_path.endswith(".npz"):
        cache_path += ".npz"             # np.savez appends it on write
    if cache_path and os.path.exists(cache_path):
        try:
            with np.load(cache_path) as d:
                tw, td = d["tw"], d["td"]
                meta = tuple(int(d[k]) for k in ("V", "D", "seed"))
            if meta == (vocab, docs, seed) and len(tw) == tokens \
                    and len(td) == tokens and int(tw.max()) < vocab \
                    and int(td.max()) < docs:
                return tw, td
            print(f"corpus cache {cache_path} is for another workload "
                  f"({meta} vs {(vocab, docs, seed)}); regenerating",
                  file=sys.stderr)
        except Exception as e:           # truncated/foreign/unreadable
            print(f"corpus cache {cache_path} unusable ({e!r}); "
                  "regenerating", file=sys.stderr)
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, vocab + 1) ** 1.1
    p /= p.sum()
    tw = rng.choice(vocab, tokens, p=p).astype(np.int32)
    td = np.sort(rng.integers(0, docs, tokens)).astype(np.int32)
    if cache_path:
        try:
            tmp = f"{cache_path[:-4]}.tmp{os.getpid()}.npz"
            np.savez(tmp, tw=tw, td=td, V=vocab, D=docs, seed=seed)
            os.replace(tmp, cache_path)
        except OSError:
            pass                         # cache is best-effort
    return tw, td


def _tpu_app(sampler: str, steps_per_call: int = 1):
    from multiverso_tpu import core
    from multiverso_tpu.apps.lightlda import LightLDA, LDAConfig

    tw, td = zipf_corpus_cached(
        V, D, T, seed=0,
        cache_path=os.path.join("/tmp", f"mvtpu_lda_bench_{V}_{D}_{T}_s0"))
    core.init()
    tiled = sampler == "tiled"
    # doc-blocked batches must be a block_tokens (512) multiple; scale
    # down with tiny workloads (T < the production 512k call size)
    tiled_batch = min(512_000, max(512, (T // 4) // 512 * 512))
    return LightLDA(tw, td, V, LDAConfig(
        num_topics=K_TPU,
        batch_tokens=tiled_batch if tiled else min(BATCH, T),
        # steps_per_call=1 was the fastest setting on the 2026-07 v5e
        # host (4 and 10 were ~20-27% slower); not re-measured on the
        # current machine — pass another value as argv[2] to compare
        steps_per_call=steps_per_call, seed=1, sampler=sampler))


def measure_tpu(sampler: str = "tiled", timed_sweeps: int = 3,
                steps_per_call: int = 1, time_budget_s: float = None,
                eval_loglik: bool = True) -> dict:
    """``time_budget_s`` caps the TIMED phase's wall-clock (an
    unbounded loop on a slow device blows the caller's timeout) — stop
    after the budget as long as 2 sweeps landed.  ``eval_loglik=False``
    also skips the final likelihood eval (a full eval pass, ~the cost of
    a sweep) for time-budgeted callers that only need throughput."""
    import numpy as np
    app = _tpu_app(sampler, steps_per_call)
    app.sweep()                                   # compile + first sweep

    def sync():
        return float(np.asarray(app.summary.raw())[0])
    sync()
    runs = []
    budget_t0 = time.perf_counter()
    for _ in range(timed_sweeps):                 # the host is noisy:
        t0 = time.perf_counter()                  # report mean +- spread
        app.sweep()
        sync()
        runs.append(time.perf_counter() - t0)
        if time_budget_s is not None and len(runs) >= 2 \
                and time.perf_counter() - budget_t0 > time_budget_s:
            break
    cfg = app.config
    rates = [T / r for r in runs]
    return {"doc_tokens_per_sec": T * len(runs) / sum(runs),
            "runs_tok_per_sec": [round(r, 1) for r in rates],
            "spread_pct": round(
                100 * (max(rates) - min(rates)) / max(rates), 1),
            "secs_per_sweep": [round(r, 4) for r in runs],
            "topics": K_TPU,
            # record the MEASURED configuration, not the defaults
            "batch_tokens": cfg.batch_tokens, "sampler": cfg.sampler,
            "block_tokens": cfg.block_tokens,
            "block_docs": cfg.block_docs,
            # packing fill scales kernel efficiency — record the
            # measured workload's value (None: sampler doesn't pack)
            "packing_fill": (round(app.packing_fill, 4)
                             if hasattr(app, "packing_fill") else None),
            "loglik_after": app.loglik() if eval_loglik else None}


def quality_curve(tpu_sweeps: int = 40, cpu_sweeps: int = 12) -> dict:
    """loglik-vs-TRAINING-wallclock, TPU doc_blocked vs CPU MH on the
    matched workload (eval excluded from both clocks). Substantiates
    'the Gibbs sampler mixes at least as fast per second' with data."""
    import numpy as np
    cpu = measure_cpu(sweeps=cpu_sweeps, curve=True)

    # the TPU curve starts from the random init, so its first point
    # INCLUDES compile (~15s) — documented with the data; a separate
    # warm-up app would not help (each app instance jits its own
    # superstep closure)
    app = _tpu_app("tiled")

    def sync():
        return float(np.asarray(app.summary.raw())[0])
    tcurve = []
    train = 0.0
    for s in range(tpu_sweeps):
        t0 = time.perf_counter()
        app.sweep()
        sync()
        train += time.perf_counter() - t0
        tcurve.append({"sweep": s + 1, "secs": round(train, 3),
                       "loglik": round(app.loglik(), 4)})
    return {
        "workload": {"vocab": V, "docs": D, "tokens": T},
        "cpu_mh": {"topics": K_CPU, "curve": cpu["curve"]},
        "tpu_doc_blocked": {"topics": K_TPU, "curve": tcurve},
        "notes": "training wallclock only (eval excluded on both "
                 "sides); TPU runs K=1024 vs CPU K=1000; same zipf-1.1 "
                 "synthetic corpus shape, seed 1.",
    }


def pinned_cpu() -> dict:
    """The 1-core benchmark host is noisy/shared: keep the BEST recorded
    cpu_worker measurement (generous to the reference) instead of letting
    a slow re-run inflate vs_baseline."""
    fresh = measure_cpu()
    try:
        with open(OUT) as f:
            prev = json.load(f)["cpu_worker"]
        same_workload = all(
            prev.get(k) == fresh.get(k)
            for k in ("tokens", "sweeps", "topics", "vocab", "docs"))
        if same_workload and \
                prev["doc_tokens_per_sec"] > fresh["doc_tokens_per_sec"]:
            prev["note"] = "best recorded measurement (host is noisy)"
            return prev
    except (OSError, KeyError, ValueError):
        pass
    return fresh


if __name__ == "__main__":
    #   python benchmarks/measure_lda.py [gibbs|tiled]
    # 'tiled' is the doc-blocked sampler the benchmark's cells run;
    # 'curve' writes the loglik-vs-wallclock comparison instead
    sampler_arg = sys.argv[1] if len(sys.argv) > 1 else "tiled"
    if sampler_arg == "curve":
        result = quality_curve()
        out_path = os.path.join(HERE, "lda_quality_curve.json")
        with open(out_path, "w") as f:
            json.dump(result, f, indent=2)
            f.write("\n")
        print(json.dumps(result, indent=2))
        sys.exit(0)
    cpu = pinned_cpu()
    spc = int(sys.argv[2]) if len(sys.argv) > 2 else 1
    tpu = measure_tpu(sampler_arg, steps_per_call=spc)
    import jax
    import roofline
    result = {
        "metric": "LightLDA doc-tokens/sec",
        "cpu_worker": cpu,
        "tpu_chip": tpu,
        # peaks keyed by the device that ran it; an unlisted kind (a
        # CPU run of this script) raises
        "roofline": roofline.lda_utilization(
            max(tpu["runs_tok_per_sec"]), K_TPU, V, T,
            tpu.get("block_tokens") or 512,
            device_kind=jax.devices()[0].device_kind),
        "vs_baseline": tpu["doc_tokens_per_sec"] / cpu["doc_tokens_per_sec"],
        "workload": {"vocab": V, "docs": D, "tokens": T},
        "notes": "TPU runs K=1024 (more work) vs CPU K=1000; TPU sampler "
                 "is O(K) collapsed Gibbs in the doc-blocked pallas "
                 "kernel with a per-sweep bf16 stale word-count mirror "
                 "(the reference's own slice-level staleness model) vs "
                 "the baseline's approximate MH with stale alias tables. "
                 "16-worker cluster scored as 16x cpu_worker.",
    }
    with open(OUT, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    print(json.dumps(result, indent=2))
