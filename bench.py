"""Driver benchmark: word2vec steady-state training throughput on the
default JAX devices (the TPU chip under the driver), plus the LightLDA
metric of record.

Prints ONE JSON line on success —
  {"metric": "w2v_words_per_sec_per_chip", "value": N, "unit": "words/s",
   "vs_baseline": R, "platform": "tpu", "device_kind": ..., "devices": n,
   ..., "lda_doc_tokens_per_sec": N2, "lda_vs_baseline": R2}
— after both tiers ran. A tier that raises ends the run non-zero with no
metric line; so does a process that finds no TPU (outside
MVTPU_BENCH_TINY, which is a CPU integration run at toy sizes and says
so on its line). The process that measures is the process that checks.

vs_baseline = per-chip words/sec divided by one CPU worker's words/sec
from benchmarks/baseline_cpu.json (the faithful reference-hot-loop
re-measurement — see benchmarks/measure_cpu_baseline.py for why and for
the 16-worker scaling contract). North star (BASELINE.json): >= 8.

Methodology: the corpus/model config mirrors the CPU baseline binary
(vocab 10k zipf-1.2 corpus, dim 100, window 5, 5 negatives, subsample
1e-3 — the reference default, applied by BOTH benches; words/sec counts
raw corpus tokens). Compile time excluded via warmup dispatches; the
warmup and timing fences are ``block_until_ready`` on the last loss,
which depends on every earlier call through the donated table carry.

Three-tier pipeline decomposition (each reported in the JSON line):

- engine (`value`): pre-staged device operands — pure training engine.
- engine_fed (`engine_fed_words_per_sec`): host batches pre-GENERATED,
  but every call runs the REAL per-call placement + dispatch path with
  async overlap (one combined [S, B, ctx+1] int16 placement per call —
  ids ship as int16 when the vocab fits, halving H2D bytes; placements
  overlap compute). Its fraction of engine is the cost of placement
  that compute does not hide; not measured on the current machine.
- e2e (`e2e_words_per_sec`): the whole pipeline including host pair
  GENERATION. `gen_words_per_sec` reports the whole-host generation
  rate (native C++ backend, one thread). An n-chip mesh consumes n × the
  engine rate: feeding it needs ~n generation threads (the prefetch
  pipeline accepts parallel producers) — compare gen_words_per_sec
  against n_chips × value before extrapolating.
"""

import json
import os
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

BASELINE_PATH = os.path.join(HERE, "benchmarks", "baseline_cpu.json")
sys.path.insert(0, os.path.join(HERE, "benchmarks"))
import roofline  # noqa: E402  (the achieved-vs-chip accounting model)

# MVTPU_BENCH_TINY=1: run the WHOLE integrated pipeline (w2v tiers ->
# table reset/GC handoff -> LDA tier -> final JSON assembly) at toy
# sizes on the CPU backend. The numbers are meaningless (and no
# roofline block is printed: there are no peaks for a CPU); the point
# is that every integration seam executes without a chip.
TINY = os.environ.get("MVTPU_BENCH_TINY", "").lower() \
    not in ("", "0", "false", "no")

VOCAB = 2_000 if TINY else 10_000
TOKENS = 120_000 if TINY else 1_000_000
DIM = 100
WINDOW = 5
NEGATIVE = 5
SUBSAMPLE = 1e-3     # the reference default; both benches apply it
BATCH = 256 if TINY else 4096
# 512 steps/call amortizes the fixed per-dispatch cost (its size is not
# measured on the current machine). The prefetch pipeline batches to
# the same depth.
STEPS_PER_CALL = 16 if TINY else 512
WARMUP_CALLS = 2
TIMED_CALLS = 2 if TINY else 8
E2E_CALLS = 2 if TINY else 10
LR = 0.01


def measure_lda_tier(device_kind: "str | None") -> dict:
    """The second metric of record (BASELINE.json): LightLDA
    doc-tokens/sec on the production doc-blocked pallas sampler, vs the
    pinned 1-worker CPU MH baseline (benchmarks/measure_lda.py protocol —
    V=50k, 10M tokens, K=1024 vs the CPU's K=1000).

    Reuses the pinned CPU measurement from benchmarks/lda_results.json
    (the best recorded run — generous to the reference); re-measures
    with the native binary when the artifact is missing or is for
    another workload. Raises on failure, and so does the bench.

    `lda_doc_tokens_per_sec` is the BEST of up to 10 timed sweeps inside
    a 45 s budget; the mean and spread ride along so the dispersion is
    on the record. ``device_kind`` keys the roofline block's peaks
    (``None`` — the TINY CPU run — prints no roofline block).
    """
    import measure_lda

    try:
        with open(os.path.join(HERE, "benchmarks", "lda_results.json")) as f:
            cpu = json.load(f)["cpu_worker"]
        # same workload-match guard as measure_lda.pinned_cpu: a stale
        # artifact from changed workload constants must not skew the
        # metric of record
        want = {"tokens": measure_lda.T, "topics": measure_lda.K_CPU,
                "vocab": measure_lda.V, "docs": measure_lda.D}
        if any(cpu.get(k) != v for k, v in want.items()):
            raise KeyError("cpu_worker workload mismatch")
    except (OSError, KeyError, ValueError, TypeError, AttributeError):
        # TypeError/AttributeError: structurally corrupt artifact (top
        # level not a dict, cpu_worker not a dict) — same re-measure
        cpu = measure_lda.pinned_cpu()
    tpu = measure_lda.measure_tpu("tiled", timed_sweeps=10,
                                  time_budget_s=45.0, eval_loglik=False)
    best = max(tpu["runs_tok_per_sec"])
    out = {
        "lda_doc_tokens_per_sec": round(best, 1),
        "lda_vs_baseline": round(best / cpu["doc_tokens_per_sec"], 3),
        "lda_mean_doc_tokens_per_sec": round(tpu["doc_tokens_per_sec"], 1),
        "lda_spread_pct": tpu["spread_pct"],
        "lda_baseline_cpu_doc_tokens_per_sec": cpu["doc_tokens_per_sec"],
    }
    if device_kind is not None:
        # achieved-vs-chip accounting (benchmarks/roofline.py model)
        out["lda_roofline"] = roofline.lda_utilization(
            best, measure_lda.K_TPU, measure_lda.V, measure_lda.T,
            tpu.get("block_tokens") or 512, device_kind=device_kind)
    return out


def build_bench_corpus():
    """The matched w2v workload both the bench and its probes measure."""
    from multiverso_tpu.data.corpus import Corpus, synthetic_text
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "corpus.txt")
        synthetic_text(path, num_tokens=TOKENS, vocab_size=VOCAB, seed=1)
        return Corpus.from_file(path, min_count=1, subsample=SUBSAMPLE)


def stage_host_calls(corpus, need_calls: int):
    """Pre-generate host pair batches: [(srcs, tgts)] x need_calls,
    each [STEPS_PER_CALL, BATCH]."""
    host_calls = []
    buf_s, buf_t = [], []
    it = corpus.skipgram_batches(BATCH, window=WINDOW, seed=1,
                                 epochs=need_calls)  # replay as needed
    for src, tgt in it:
        buf_s.append(src)
        buf_t.append(tgt)
        if len(buf_s) == STEPS_PER_CALL:
            host_calls.append((np.stack(buf_s), np.stack(buf_t)))
            buf_s, buf_t = [], []
            if len(host_calls) >= need_calls:
                break
    if len(host_calls) < need_calls:
        raise SystemExit(f"corpus too small: staged {len(host_calls)} "
                         f"calls, need {need_calls}")
    return host_calls


def make_dispatch(app):
    """The per-call dispatch closure (fold_in key + fused superstep)."""
    import jax
    import jax.numpy as jnp
    lrs_dev = jnp.asarray(np.full(STEPS_PER_CALL, LR, np.float32))

    def dispatch(i, placed):
        key = jax.random.fold_in(app._key, i)
        # aux: the call's mean loss and what the row writer met
        _, (loss, _) = app._fused((), placed, key, lrs_dev)
        return loss

    return dispatch


def load_baseline() -> float:
    try:
        with open(BASELINE_PATH) as f:
            return float(json.load(f)["words_per_sec"])
    except (OSError, KeyError, ValueError):
        # fall back to measuring on the spot (slow path)
        sys.path.insert(0, os.path.join(HERE, "benchmarks"))
        from measure_cpu_baseline import measure
        return float(measure(repeats=1)["words_per_sec"])


# diagnostic telemetry artifact: main() binds these to the repo-local
# snapshot/trace paths and every tier boundary writes a fresh registry
# snapshot, so a run that dies still leaves `bench_telemetry.json` for
#   python -m multiverso_tpu.telemetry.report bench_telemetry.json
# _WATCHDOG is the flight recorder's stall side: armed for the whole
# bench via MVTPU_BENCH_WATCHDOG seconds (default 900; "0" disables),
# beaten at every tier boundary — a stall anywhere in the bench dumps
# stacks/metrics/trace-tail into MVTPU_DUMP_DIR.
_TELEMETRY = None
_TELE_PATH = None
_WATCHDOG = None


def _beat() -> None:
    """Tier-boundary heartbeat (no-op when the watchdog is disabled)."""
    if _WATCHDOG is not None:
        _WATCHDOG.beat()


def _counter_snapshot(*prefixes: str) -> dict:
    """Flat ``{counter_key: value}`` for counters under the given name
    prefixes — the engine/health provenance the BENCH line embeds so a
    capture self-identifies (which kernels actually ran Pallas vs fell
    back, whether the numerics audit flagged anything) without needing
    the sidecar telemetry snapshot."""
    counters = _TELEMETRY.snapshot().get("counters", {})
    return {k: v for k, v in sorted(counters.items())
            if k.startswith(prefixes)}


def _write_telemetry_snapshot() -> None:
    if _TELEMETRY is not None:
        try:
            _TELEMETRY.write_snapshot(_TELE_PATH)
        except OSError as e:     # diagnostics must never kill the bench
            print(f"bench: telemetry snapshot failed: {e!r}",
                  file=sys.stderr)


def main() -> None:
    if TINY:
        # integration dry-run: tiny workloads on the CPU backend, pinned
        # here so the run never takes a chip
        os.environ.setdefault("MVTPU_LDA_V", "2000")
        os.environ.setdefault("MVTPU_LDA_D", "1000")
        os.environ.setdefault("MVTPU_LDA_T", "102400")
        os.environ.setdefault("MVTPU_LDA_K_CPU", "128")
        os.environ.setdefault("MVTPU_LDA_K_TPU", "128")
    import jax
    if TINY:
        jax.config.update("jax_platforms", "cpu")
    # the process that measures is the process that checks: no chip is
    # a failure with no metric line, never a CPU number
    dev = jax.devices()[0]
    n_chips = len(jax.devices())
    if not TINY and dev.platform != "tpu":
        raise SystemExit(
            f"bench: jax found platform {dev.platform!r} "
            f"({dev.device_kind!r}), not a TPU — nothing measured "
            "(MVTPU_BENCH_TINY=1 is the CPU integration run)")
    device_kind = None if TINY else dev.device_kind

    # telemetry spine: snapshot + trace artifacts next to the bench
    global _TELEMETRY, _TELE_PATH, _WATCHDOG
    import atexit
    from multiverso_tpu.telemetry import metrics as _TELEMETRY
    from multiverso_tpu.telemetry import trace as telemetry_trace
    from multiverso_tpu.telemetry import watchdog as wd_mod
    _TELE_PATH = os.environ.get(
        "MVTPU_BENCH_TELEMETRY",
        os.path.join(HERE, "bench_telemetry.json"))
    atexit.register(_write_telemetry_snapshot)
    print(f"bench: telemetry -> {_TELE_PATH} (render with: python -m "
          "multiverso_tpu.telemetry.report <path>)", file=sys.stderr)
    os.environ.setdefault("MVTPU_DUMP_DIR",
                          os.path.join(HERE, "mvtpu_dump"))
    raw_wd = os.environ.get("MVTPU_BENCH_WATCHDOG", "900")
    try:
        wd_deadline = float(raw_wd)
    except ValueError:
        print(f"bench: ignoring malformed MVTPU_BENCH_WATCHDOG="
              f"{raw_wd!r}; using 900s", file=sys.stderr)
        wd_deadline = 900.0
    if wd_deadline > 0:
        # action "dump", never "kill": the caller's own timeout is the
        # executioner — the watchdog's job is to leave the post-mortem
        _WATCHDOG = wd_mod.Watchdog(wd_deadline, name="bench",
                                    action="dump").start()
        print(f"bench: watchdog armed ({wd_deadline:.0f}s deadline; "
              f"dumps -> {os.environ['MVTPU_DUMP_DIR']})",
              file=sys.stderr)
    telemetry_trace.set_trace_file(os.environ.get(
        "MVTPU_BENCH_TRACE", os.path.join(HERE, "bench_trace.jsonl")))
    from multiverso_tpu import core
    from multiverso_tpu.apps.word_embedding import W2VConfig, WordEmbedding

    baseline = load_baseline()
    mesh = core.init()
    _beat()                      # backend up + mesh built: progress

    corpus = build_bench_corpus()
    _beat()                      # corpus staged
    cfg = W2VConfig(embedding_dim=DIM, window=WINDOW, negative=NEGATIVE,
                    batch_size=BATCH, steps_per_call=STEPS_PER_CALL,
                    learning_rate=LR, epochs=1, subsample=SUBSAMPLE, seed=1)
    app = WordEmbedding(corpus, cfg, mesh=mesh, name="bench_w2v")

    # pre-generate host pair batches once; the engine loop pre-stages
    # them on device, the engine-fed loop re-places them per call
    need_calls = WARMUP_CALLS + TIMED_CALLS
    host_calls = stage_host_calls(corpus, need_calls)
    calls = [app._place(s, t) for s, t in host_calls]
    # pairs/token ratio for converting pairs/sec -> words/sec, measured
    # from one full epoch's worth of generation — TIMED, because the
    # host generation rate is the fourth pipeline tier: if it exceeds
    # the engine rate, a multi-core host's overlapped e2e == engine_fed
    t0 = time.perf_counter()
    gen_pairs = 0
    for src, _ in corpus.skipgram_batches(BATCH, window=WINDOW, seed=7,
                                          epochs=1):
        gen_pairs += len(src)
    gen_dt = time.perf_counter() - t0
    pairs_per_token = gen_pairs / corpus.num_tokens
    gen_words_per_sec = corpus.num_tokens / gen_dt

    dispatch = make_dispatch(app)

    warm_loss = None
    for i in range(WARMUP_CALLS):
        warm_loss = dispatch(i, calls[i])
    # the last loss depends on every earlier call through the donated
    # table carry, so the timed window starts truly idle
    jax.block_until_ready(warm_loss)
    _beat()                      # warmup (compile) done

    # optional device capture of the engine tier (MVTPU_PROFILE_DIR)
    from multiverso_tpu.telemetry.profiling import (profile_window,
                                                    record_device_memory)
    with profile_window("bench_w2v_engine"):
        t0 = time.perf_counter()
        loss = None
        for i in range(WARMUP_CALLS, need_calls):
            loss = dispatch(i, calls[i])
        jax.block_until_ready(loss)
        dt = time.perf_counter() - t0
    loss = float(loss)
    _beat()                      # engine tier done

    pairs_done = TIMED_CALLS * BATCH * STEPS_PER_CALL
    pairs_per_sec = pairs_done / dt
    words_per_sec = pairs_per_sec / pairs_per_token
    per_chip = words_per_sec / max(n_chips, 1)

    # engine-fed: host batches already generated; run the REAL per-call
    # placement + dispatch path. Isolates the transfer/dispatch design
    # from host pair-generation cost: engine (pre-staged) vs engine-fed
    # (placement included) vs e2e (generation included) decomposes the
    # pipeline. Dispatches stay async until the final loss fence, so
    # placements overlap compute exactly as the prefetch pipeline would.
    # Best of 3 passes (the run-to-run spread of this tier is not
    # measured on the current machine).
    ef_loss = dispatch(0, app._place(*host_calls[0]))   # warm the path
    jax.block_until_ready(ef_loss)
    ef_dt = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for i, (s, t) in enumerate(host_calls[WARMUP_CALLS:]):
            ef_loss = dispatch(i, app._place(s, t))
        jax.block_until_ready(ef_loss)
        ef_dt = min(ef_dt, time.perf_counter() - t0)
        _beat()                  # one engine-fed pass landed
    ef_pairs = TIMED_CALLS * BATCH * STEPS_PER_CALL
    ef_words = ef_pairs / ef_dt / pairs_per_token / max(n_chips, 1)

    # end-to-end: the real corpus -> pair-generation -> dispatch pipeline.
    # One warmup call first: train() places lr arrays with the mesh
    # sharding (unlike the pre-staged engine loop above), which is a
    # separate jit cache entry — compile must stay out of the timing.
    e2e_calls = E2E_CALLS
    app.train(total_steps=STEPS_PER_CALL)
    e2e_words, e2e_dt = 0.0, float("inf")
    for _ in range(3):          # best of 3, as the engine-fed tier
        steps_before = app._step_no
        t0 = time.perf_counter()
        app.train(total_steps=e2e_calls * STEPS_PER_CALL)
        dt_pass = time.perf_counter() - t0
        # count the steps actually dispatched: a corpus epoch exhausting
        # early would otherwise silently inflate the number
        e2e_pairs = (app._step_no - steps_before) * BATCH
        if e2e_pairs == 0:
            raise SystemExit("e2e run dispatched no steps "
                             "(corpus exhausted)")
        words = e2e_pairs / pairs_per_token / dt_pass / max(n_chips, 1)
        if words > e2e_words:          # keep rate and clock of the SAME
            e2e_words, e2e_dt = words, dt_pass       # best pass
        _beat()                  # one e2e pass landed

    print(json.dumps({
        "pairs_per_sec": round(pairs_per_sec, 1),
        "pairs_per_token": round(pairs_per_token, 3),
        "final_loss": round(loss, 4),
        "n_chips": n_chips,
        "secs": round(dt, 3),
        "e2e_secs": round(e2e_dt, 3),
        "baseline_cpu_words_per_sec": baseline,
    }), file=sys.stderr)

    line = {
        "metric": "w2v_words_per_sec_per_chip",
        # a stray MVTPU_BENCH_TINY in the caller's env must be
        # self-identifying in the capture, not a silent toy number
        **({"bench_tiny": True} if TINY else {}),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "devices": n_chips,
        "value": round(per_chip, 1),
        "unit": "words/s",
        "vs_baseline": round(per_chip / baseline, 3),
        "engine_fed_words_per_sec": round(ef_words, 1),
        "engine_fed_frac_of_engine": round(ef_words / per_chip, 3),
        "gen_words_per_sec": round(gen_words_per_sec, 1),
        "e2e_words_per_sec": round(e2e_words, 1),
        "e2e_vs_baseline": round(e2e_words / baseline, 3),
    }
    if device_kind is not None:
        # achieved-vs-chip accounting (benchmarks/roofline.py model)
        line["w2v_roofline"] = roofline.w2v_utilization(
            pairs_per_sec / max(n_chips, 1), DIM, NEGATIVE,
            device_kind=device_kind)
    # snapshot NOW: the w2v tier's table/op accounting is on disk, with
    # its working set's device-memory gauges, whatever the LDA tier does
    record_device_memory()
    _write_telemetry_snapshot()
    _beat()                      # w2v tier done

    # free the w2v working set (10 staged ~46MB placement buffers + the
    # embedding tables) before the LDA tier allocates its own tables —
    # the two benchmarks must not need to co-fit in HBM
    import gc
    from multiverso_tpu.tables import base as table_base
    del calls, app, dispatch
    table_base.reset_tables()
    gc.collect()

    # second metric of record, carried on the SAME final JSON line:
    # LightLDA doc-tokens/sec. A failure here fails the bench.
    line.update(measure_lda_tier(device_kind))
    record_device_memory()
    _beat()                      # lda tier done
    # provenance: engine selections that kept XLA + training-health
    # violations at capture time (numeric leaves ride bench_diff
    # unwatched)
    line["counters"] = _counter_snapshot("kernels.fallbacks",
                                         "health.violations")
    print(json.dumps(line))


if __name__ == "__main__":
    main()
