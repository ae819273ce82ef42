"""Chip-independent logic tests for bench.py's metric plumbing (the
driver records the LAST complete JSON line bench.py prints; these pin
the parts of that contract that don't need the real chip)."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def bench_mod():
    sys.path.insert(0, REPO)
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    import bench
    import measure_lda
    yield bench, measure_lda


def test_lda_tier_reports_best_sweep_and_protocol(bench_mod, monkeypatch):
    bench, measure_lda = bench_mod
    calls = {}

    def fake_measure_tpu(sampler, timed_sweeps=3, steps_per_call=1,
                         time_budget_s=None, eval_loglik=True):
        calls.update(sampler=sampler, sweeps=timed_sweeps,
                     budget=time_budget_s, eval=eval_loglik)
        return {"doc_tokens_per_sec": 19e6,
                "runs_tok_per_sec": [18e6, 19.7e6, 19.2e6, 16e6],
                "spread_pct": 18.8}

    monkeypatch.setattr(measure_lda, "measure_tpu", fake_measure_tpu)
    # hermetic: never fall through to the native-binary baseline path
    # even if the committed artifact goes missing or changes workload
    monkeypatch.setattr(
        measure_lda, "pinned_cpu",
        lambda: {"doc_tokens_per_sec": 2029587.7,
                 "tokens": measure_lda.T, "topics": measure_lda.K_CPU,
                 "vocab": measure_lda.V, "docs": measure_lda.D})
    out = bench.measure_lda_tier("TPU v5 lite")
    # protocol: production sampler, budgeted, no final eval
    assert calls == {"sampler": "tiled", "sweeps": 10, "budget": 45.0,
                     "eval": False}
    # best sweep is the metric; mean + spread ride along
    assert out["lda_doc_tokens_per_sec"] == 19.7e6
    assert out["lda_mean_doc_tokens_per_sec"] == 19e6
    assert out["lda_spread_pct"] == 18.8
    assert out["lda_vs_baseline"] == round(
        19.7e6 / out["lda_baseline_cpu_doc_tokens_per_sec"], 3)
    # achieved-vs-chip accounting rides the same line, computed from the
    # BEST sweep (and the stub lacks block_tokens -> the 512 default)
    rl = out["lda_roofline"]
    assert rl["achieved_hbm_gbps"] == pytest.approx(
        19.7e6 * rl["model_hbm_bytes_per_token"] / 1e9, rel=1e-3)
    assert rl["hbm_peak_gbps"] == 819.0
    assert rl["device_kind"] == "TPU v5 lite"
    # the TINY CPU run has no device kind and prints no roofline block
    assert "lda_roofline" not in bench.measure_lda_tier(None)


def test_lda_tier_rejects_stale_workload_baseline(bench_mod, monkeypatch,
                                                  tmp_path):
    """A lda_results.json from CHANGED workload constants must not feed
    the metric of record — the tier falls back to pinned_cpu()."""
    bench, measure_lda = bench_mod
    stale = {"cpu_worker": {"doc_tokens_per_sec": 1.0, "tokens": 123,
                            "topics": measure_lda.K_CPU,
                            "vocab": measure_lda.V,
                            "docs": measure_lda.D}}
    path = tmp_path / "lda_results.json"
    path.write_text(json.dumps(stale))
    monkeypatch.setattr(bench, "HERE", str(tmp_path.parent))
    # redirect the artifact lookup to the stale file
    real_open = open

    def fake_open(p, *a, **k):
        if str(p).endswith("lda_results.json"):
            return real_open(path, *a, **k)
        return real_open(p, *a, **k)

    monkeypatch.setattr("builtins.open", fake_open)
    pinned = {"doc_tokens_per_sec": 2e6, "tokens": measure_lda.T,
              "topics": measure_lda.K_CPU, "vocab": measure_lda.V,
              "docs": measure_lda.D}
    monkeypatch.setattr(measure_lda, "pinned_cpu", lambda: pinned)
    monkeypatch.setattr(
        measure_lda, "measure_tpu",
        lambda *a, **k: {"doc_tokens_per_sec": 16e6,
                         "runs_tok_per_sec": [16e6], "spread_pct": 0.0})
    out = bench.measure_lda_tier(None)
    assert out["lda_baseline_cpu_doc_tokens_per_sec"] == 2e6  # not 1.0
    assert out["lda_vs_baseline"] == 8.0


def test_measure_tpu_time_budget_breaks_early(bench_mod, monkeypatch):
    """The timed loop must stop once the budget elapses with >=2 sweeps
    landed — a slow device must not turn ten sweeps into a timeout."""
    bench, measure_lda = bench_mod

    class FakeApp:
        config = type("C", (), {
            "batch_tokens": 1, "sampler": "tiled", "stale_words": True,
            "doc_blocked": True, "block_tokens": 1, "block_docs": 1})()
        packing_fill = 1.0

        def sweep(self):
            pass

        class _Summary:
            @staticmethod
            def raw():
                import numpy as np
                return np.zeros(1, np.float32)
        summary = _Summary()

        def loglik(self):
            raise AssertionError("eval_loglik=False must skip loglik")

    monkeypatch.setattr(measure_lda, "_tpu_app",
                        lambda sampler, spc: FakeApp())
    # each fake sweep "takes" 30s of perf_counter time
    t = {"now": 0.0}

    def fake_pc():
        t["now"] += 15.0          # two reads per sweep iteration
        return t["now"]

    monkeypatch.setattr(measure_lda.time, "perf_counter", fake_pc)
    out = measure_lda.measure_tpu("tiled", timed_sweeps=10,
                                  time_budget_s=45.0, eval_loglik=False)
    # budget 45s at ~30s/sweep -> exactly 2 timed sweeps, not 10
    assert len(out["runs_tok_per_sec"]) == 2
    assert out["loglik_after"] is None


def test_zipf_corpus_cache_guards(bench_mod, tmp_path):
    """The shared corpus cache must regenerate on corrupt or
    wrong-workload files (a driver kill mid-write must not poison every
    later bench run) and reload validated content otherwise."""
    import numpy as np
    _, measure_lda = bench_mod
    cache = str(tmp_path / "c.npz")
    tw, td = measure_lda.zipf_corpus_cached(500, 40, 2000, seed=0,
                                            cache_path=cache)
    assert len(tw) == 2000 and int(tw.max()) < 500 and int(td.max()) < 40
    tw2, td2 = measure_lda.zipf_corpus_cached(500, 40, 2000, seed=0,
                                              cache_path=cache)
    np.testing.assert_array_equal(tw, tw2)       # warm load, same corpus
    np.testing.assert_array_equal(td, td2)
    # corrupt file -> regenerate, not crash
    with open(cache, "wb") as f:
        f.write(b"PK\x03\x04 truncated garbage")
    tw3, _ = measure_lda.zipf_corpus_cached(500, 40, 2000, seed=0,
                                            cache_path=cache)
    np.testing.assert_array_equal(tw, tw3)       # deterministic redraw
    # wrong-workload metadata -> regenerate for the requested workload
    tw4, td4 = measure_lda.zipf_corpus_cached(700, 40, 2000, seed=0,
                                              cache_path=cache)
    assert len(tw4) == 2000 and int(tw4.max()) < 700
    assert not np.array_equal(tw4, tw)           # different vocab draw


def test_roofline_models():
    """The utilization arithmetic is chip-independent: pin the model
    terms and the achieved/peak division at known rates."""
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    import roofline

    kind = "TPU v5 lite"
    peak = roofline.peaks(kind)
    assert peak["source"] and peak["gather_ceiling_source"]
    w = roofline.w2v_utilization(10e6, dim=100, negative=5,
                                 device_kind=kind)
    assert w["device_kind"] == kind
    assert w["model_flops_per_pair"] == 6 * 6 * 100
    assert w["model_hbm_bytes_per_pair"] == 3 * 7 * 4 * 100
    assert w["achieved_tflops"] == pytest.approx(10e6 * 3600 / 1e12)
    assert 0 < w["mxu_util_pct"] < 1          # w2v is NOT MXU-bound
    assert w["hbm_util_pct"] == pytest.approx(
        100 * 10e6 * 8400 / 1e9 / peak["hbm_gbps"], abs=0.02)

    li = roofline.lda_utilization(19.6e6, num_topics=1024, vocab=50_000,
                                  tokens=10_000_000, block_tokens=512,
                                  device_kind=kind)
    # the dominant term is the 2KB bf16 word-row gather
    assert li["model_hbm_bytes_per_token"] == pytest.approx(
        2048 + 8 + 8 + 64 * 1024 / 512 + 6 * 50_000 * 1024 / 10e6,
        rel=1e-3)
    assert li["w_gather_gbps"] == pytest.approx(19.6e6 * 2048 / 1e9,
                                                rel=1e-3)
    # scored against the measured random-gather ceiling, not just peak
    assert li["gather_ceiling_util_pct"] > li["hbm_util_pct"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v9", ""])
def test_roofline_rejects_unknown_device_kind(kind):
    """Peaks are never assumed: a kind the table does not list — the
    CPU included — raises instead of scoring against some other chip."""
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    import roofline

    with pytest.raises(KeyError, match="no peak figures"):
        roofline.peaks(kind)
    with pytest.raises(KeyError, match="no peak figures"):
        roofline.w2v_utilization(1e6, dim=100, negative=5,
                                 device_kind=kind)
    with pytest.raises(KeyError, match="no peak figures"):
        roofline.lda_utilization(1e6, 1024, 50_000, 10_000_000,
                                 device_kind=kind)


def test_bench_without_a_chip_exits_nonzero_with_no_metric_line():
    """Non-TINY bench.py in a process that finds no TPU: non-zero exit,
    nothing on stdout. The check is made by the measuring process."""
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("MVTPU_BENCH_TINY", None)
    proc = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                          capture_output=True, text=True, timeout=120,
                          env=env)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "not a TPU" in proc.stderr


def test_bench_with_a_broken_lda_tier_exits_nonzero(tmp_path):
    """A tier that raises fails the bench: non-zero exit and no metric
    line — the word2vec tier having passed does not rescue it. (Broken
    here by a topic count the tiled sampler refuses; TINY keeps it on
    the CPU and short.)"""
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu", MVTPU_BENCH_TINY="1",
               MVTPU_LDA_K_TPU="100", MVTPU_BENCH_WATCHDOG="0",
               MVTPU_BENCH_TELEMETRY=str(tmp_path / "t.json"),
               MVTPU_BENCH_TRACE=str(tmp_path / "t.jsonl"),
               MVTPU_DUMP_DIR=str(tmp_path / "dump"))
    proc = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                          capture_output=True, text=True, timeout=300,
                          env=env)
    assert proc.returncode != 0
    assert "num_topics % 128" in proc.stderr
    assert not [ln for ln in proc.stdout.splitlines()
                if ln.startswith("{") and "metric" in ln]


def test_kernel_bench_capture_parses_with_sharded_metrics(tmp_path):
    """The ``make kernel-bench`` lane through the driver's capture
    contract: the TINY run's LAST stdout JSON line must parse non-null
    and carry the sharded-lane metrics ``tools/bench_diff.py`` watches
    (TINY forces 2 virtual CPU devices, so the model=2 shard_map lane
    always runs) — with both sharded sections actually on the
    lane-sliced Pallas engine and zero engine fallbacks."""
    import subprocess
    env = dict(os.environ, MVTPU_KERNEL_BENCH_TINY="1",
               MVTPU_KERNEL_BENCH_JSON=str(tmp_path / "tk.json"))
    # the bench pins its own XLA_FLAGS device-count before importing
    # jax; the conftest's 8-device flag must not leak in and skew it
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks",
                                      "table_kernels.py")],
        capture_output=True, text=True, timeout=240, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    parsed = None
    for ln in proc.stdout.splitlines():     # driver: last complete line
        try:
            doc = json.loads(ln)
        except ValueError:
            continue
        if isinstance(doc, dict):
            parsed = doc
    assert parsed is not None, "bench emitted no JSON metric line"
    for key in ("kv_probe_ops_per_sec_pallas_sharded",
                "coo_scatter_ops_per_sec_pallas_sharded",
                "kv_probe_ops_per_sec_xla_sharded",
                "coo_scatter_ops_per_sec_xla_sharded"):
        assert parsed.get(key, 0) > 0, f"missing sharded metric {key}"
    assert parsed["kv_engine_sharded"] == "pallas"
    assert parsed["coo_engine_sharded"] == "pallas"
    assert parsed["kv_layout_sharded"] == "sharded"
    assert parsed["coo_layout_sharded"] == "sharded"
    assert parsed["kernels_fallbacks"] == 0
    assert parsed["parity_checked"] is True
