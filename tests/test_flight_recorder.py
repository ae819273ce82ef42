"""Flight recorder tests (ISSUE 2): stall watchdog post-mortems,
profiled_jit compile metrics on the CPU mesh, Chrome-trace export
round-trips, and the bench_diff CI tool.

The watchdog is exercised with sub-second deadlines (a deliberate
stall must dump; healthy beats must not), including the two process
contracts bench.py relies on: standalone file-path loading with NO
package/jax import, and the kill escalation exiting with
SELF_TERMINATE_RC after the dump lands.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from multiverso_tpu import telemetry
from multiverso_tpu.telemetry import metrics, report, trace
from multiverso_tpu.telemetry import watchdog as wd

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WATCHDOG_PY = os.path.join(REPO, "multiverso_tpu", "telemetry",
                           "watchdog.py")


@pytest.fixture(autouse=True)
def _fresh_registry():
    metrics.registry().reset()
    trace.set_trace_file(None)
    yield
    metrics.registry().reset()
    trace.set_trace_file(None)


def _wait_for(predicate, timeout_s=10.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout_s:
        if predicate():
            return True
        time.sleep(0.05)
    return False


# -- stall watchdog --------------------------------------------------------


class TestWatchdog:
    def test_stall_dumps_postmortem(self, tmp_path):
        """A deliberate stall must leave thread stacks, a metrics
        snapshot, and the trace tail — all parseable (the acceptance
        contract)."""
        trace.set_trace_file(str(tmp_path / "trace.jsonl"))
        with telemetry.span("pre.stall.region"):
            pass
        telemetry.counter("stall.ops").inc(7)
        with wd.watchdog(0.25, name="t.stall",
                         dump_dir=str(tmp_path / "dumps")) as w:
            w.beat()
            assert _wait_for(lambda: w.last_dump_path is not None)
            dump = w.last_dump_path
        stacks = open(os.path.join(dump, "stacks.txt")).read()
        assert "File " in stacks            # real frames, every thread
        assert "mvtpu-watchdog" in stacks or "Thread" in stacks
        snap = json.load(open(os.path.join(dump, "metrics.json")))
        assert snap["kind"] == metrics.SNAPSHOT_KIND
        assert snap["counters"]["stall.ops"] == 7
        # the watchdog's own stall counter rode the snapshot
        assert snap["counters"]["watchdog.stalls{watchdog=t.stall}"] == 1
        tail = [json.loads(l) for l in
                open(os.path.join(dump, "trace_tail.jsonl"))]
        assert any(r.get("name") == "pre.stall.region" for r in tail)
        manifest = json.load(open(os.path.join(dump, "watchdog.json")))
        assert manifest["kind"] == wd.DUMP_KIND
        assert manifest["name"] == "t.stall"
        assert manifest["pid"] == os.getpid()
        assert manifest["silent_s"] >= 0.25

    def test_healthy_beats_no_dump(self, tmp_path):
        # generous deadline vs beat cadence: a loaded 1-core CI host
        # stretching one sleep must not fake a stall
        with wd.watchdog(2.0, name="t.healthy",
                         dump_dir=str(tmp_path / "dumps")) as w:
            for _ in range(10):          # ~1s of life, beats well inside
                time.sleep(0.1)
                telemetry.beat()         # module-level beat reaches it
        assert w.stalls == 0
        assert w.last_dump_path is None
        assert not os.path.exists(str(tmp_path / "dumps"))

    def test_warn_action_never_dumps(self, tmp_path):
        with wd.watchdog(0.15, name="t.warn", action="warn",
                         dump_dir=str(tmp_path / "dumps")) as w:
            assert _wait_for(lambda: w.stalls >= 1)
        assert w.last_dump_path is None
        assert not os.path.exists(str(tmp_path / "dumps"))

    def test_beat_rearms_after_stall(self, tmp_path):
        """A transient stall dumps once, then a beat re-arms the ladder
        for the next stall (two dumps, not a dump storm)."""
        with wd.watchdog(0.15, name="t.rearm",
                         dump_dir=str(tmp_path / "dumps")) as w:
            # the count moves before the dump is written: wait for both
            assert _wait_for(lambda: w.stalls == 1 and w.last_dump_path)
            first = w.last_dump_path
            time.sleep(0.3)              # tripped: no second dump yet
            assert w.stalls == 1
            w.beat()                     # recover -> re-arm
            assert _wait_for(lambda: w.stalls == 2
                             and w.last_dump_path != first)
        assert len(os.listdir(str(tmp_path / "dumps"))) == 2

    def test_kill_action_terminates_after_dump(self, tmp_path):
        """The kill rung: a stuck process must die with
        SELF_TERMINATE_RC, post-mortem already on disk."""
        dumps = str(tmp_path / "dumps")
        src = (
            "import importlib.util, time;"
            f"s = importlib.util.spec_from_file_location("
            f"'wdmod', {WATCHDOG_PY!r});"
            "m = importlib.util.module_from_spec(s);"
            "s.loader.exec_module(m);"
            f"m.Watchdog(0.3, name='t.kill', action='kill', "
            f"dump_dir={dumps!r}).start();"
            "time.sleep(60)")
        proc = subprocess.run([sys.executable, "-c", src],
                              capture_output=True, text=True, timeout=30)
        assert proc.returncode == wd.SELF_TERMINATE_RC, proc.stderr
        assert "self-terminating" in proc.stderr
        (entry,) = os.listdir(dumps)
        assert os.path.exists(os.path.join(dumps, entry, "stacks.txt"))

    def test_standalone_no_package_no_jax(self, tmp_path):
        """The jax-free-process contract: watchdog.py loaded by file
        path must dump WITHOUT multiverso_tpu or jax ever importing
        (a stuck `import jax` is exactly what it instruments)."""
        dumps = str(tmp_path / "dumps")
        src = (
            "import importlib.util, sys, time;"
            f"s = importlib.util.spec_from_file_location("
            f"'wdmod', {WATCHDOG_PY!r});"
            "m = importlib.util.module_from_spec(s);"
            "s.loader.exec_module(m);"
            f"w = m.Watchdog(0.2, name='t.alone', dump_dir={dumps!r})"
            ".start();\n"
            "time.sleep(2)\n"
            "assert 'jax' not in sys.modules, 'watchdog dragged in jax'\n"
            "assert 'multiverso_tpu' not in sys.modules\n"
            "assert w.last_dump_path, 'no dump'\n"
            "print('OK', w.last_dump_path)")
        proc = subprocess.run([sys.executable, "-c", src],
                              capture_output=True, text=True, timeout=30)
        assert proc.returncode == 0, proc.stderr
        assert proc.stdout.startswith("OK")
        (entry,) = os.listdir(dumps)
        # standalone: stacks + manifest always; metrics/trace only when
        # the sibling modules are loaded (here they are not)
        files = set(os.listdir(os.path.join(dumps, entry)))
        assert "stacks.txt" in files and "watchdog.json" in files
        assert "metrics.json" not in files

    def test_maybe_watchdog_env_gate(self, tmp_path, monkeypatch):
        monkeypatch.delenv("MVTPU_WATCHDOG", raising=False)
        with wd.maybe_watchdog("t.off") as w:
            assert w is None
        monkeypatch.setenv("MVTPU_WATCHDOG", "0.5")
        with wd.maybe_watchdog("t.on") as w:
            assert isinstance(w, wd.Watchdog)
            assert w.deadline_s == 0.5
        monkeypatch.setenv("MVTPU_WATCHDOG", "not-a-number")
        with wd.maybe_watchdog("t.bad") as w:
            assert w is None             # malformed -> disabled, loud

    def test_rejects_nonpositive_deadline(self):
        with pytest.raises(ValueError):
            wd.Watchdog(0.0)


# -- compile/memory profiling ----------------------------------------------


class _FailingJit:
    """A jitted-function stand-in whose AOT path fails the way Mosaic
    does, recording which entry points the wrapper touched."""

    def __init__(self, log, executable=None):
        self.log = log
        self.executable = executable

    def lower(self, *args, **kwargs):
        self.log.append("lower")
        if self.executable is None:
            raise RuntimeError("Mosaic failed to compile TPU kernel")
        exe = self.executable
        return type("Lowered", (), {"compile": lambda self: exe})()

    def __call__(self, *args, **kwargs):
        self.log.append("call")
        return "plain-jit-result"


class TestProfiledJit:
    def test_compile_metrics_per_signature(self):
        import jax.numpy as jnp
        calls = {"n": 0}

        def f(x):
            calls["n"] += 1
            return (x * 2.0).sum()

        pf = telemetry.profiled_jit(f, name="t.f")
        assert float(pf(jnp.ones(8))) == 16.0
        assert float(pf(jnp.ones(8))) == 16.0      # cache hit: no retrace
        assert float(pf(jnp.ones(4))) == 8.0       # new signature
        snap = metrics.snapshot()
        assert snap["counters"]["profile.compiles{fn=t.f}"] == 2
        h = snap["histograms"]["profile.compile.seconds{fn=t.f}"]
        assert h["count"] == 2 and h["sum"] > 0
        assert snap["histograms"]["profile.lower.seconds{fn=t.f}"][
            "count"] == 2
        assert snap["gauges"]["profile.compile.last_s{fn=t.f}"] > 0
        # one trace per AOT compile, not per call
        assert calls["n"] == 2

    def test_matches_plain_jit_and_donation(self):
        import jax
        import jax.numpy as jnp

        def step(p, d):
            return p + d

        pf = telemetry.profiled_jit(step, name="t.donate",
                                    donate_argnums=(0,))
        p = jnp.zeros(16)
        out = pf(p, jnp.ones(16))
        np.testing.assert_allclose(np.asarray(out), np.ones(16))
        out2 = pf(out, jnp.ones(16))   # donated carry, same signature
        np.testing.assert_allclose(np.asarray(out2), np.full(16, 2.0))
        assert metrics.snapshot()["counters"][
            "profile.compiles{fn=t.donate}"] == 1

        # under an outer trace (grad) the wrapper must bypass to the
        # plain jitted path, not try to AOT-compile tracers
        g = jax.grad(lambda x: pf(x, jnp.ones(3)).sum())(jnp.zeros(3))
        np.testing.assert_allclose(np.asarray(g), np.ones(3))

    def test_compile_and_runtime_errors_reach_the_caller(self,
                                                         monkeypatch):
        """No second attempt through plain jit: an accelerator-shaped
        failure in lower/compile (Mosaic refusing a kernel) or in the
        compiled call raises, and raises again on the next call — the
        wrapper never latches onto another path."""
        import jax
        import jax.numpy as jnp

        pf = telemetry.profiled_jit(lambda x: x + 1, name="t.raise")
        plain_calls = []
        monkeypatch.setattr(pf, "_jit", _FailingJit(plain_calls))
        for _ in range(2):
            with pytest.raises(RuntimeError, match="Mosaic failed"):
                pf(jnp.ones(4))
        assert plain_calls == ["lower", "lower"]     # never __call__

        class Exe:
            def cost_analysis(self):
                return {}

            def memory_analysis(self):
                return None

            def as_text(self):
                return "HloModule jit_fake\n"

            def __call__(self, *a, **k):
                raise jax.errors.JaxRuntimeError("INTERNAL: device halt")

        pf2 = telemetry.profiled_jit(lambda x: x + 1, name="t.raise2")
        plain_calls2 = []
        monkeypatch.setattr(pf2, "_jit", _FailingJit(plain_calls2, Exe()))
        for _ in range(2):
            with pytest.raises(jax.errors.JaxRuntimeError,
                               match="device halt"):
                pf2(jnp.ones(4))
        assert "call" not in plain_calls2

    def test_new_input_sharding_is_a_new_signature(self, devices):
        """An AOT executable takes exactly the shardings it was
        compiled for. The signature keys on them, so a differently
        sharded input compiles its own program (as jit would) instead
        of failing the call — the case the removed blanket retry used
        to absorb."""
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.asarray(devices[:2]), ("x",))
        pf = telemetry.profiled_jit(lambda x: x * 2.0, name="t.shard")
        host = np.arange(8, dtype=np.float32)
        a = jax.device_put(host, NamedSharding(mesh, P("x")))
        b = jax.device_put(host, NamedSharding(mesh, P()))
        for arr in (a, b, a):
            np.testing.assert_allclose(np.asarray(pf(arr)), host * 2)
        assert metrics.snapshot()["counters"][
            "profile.compiles{fn=t.shard}"] == 2

    def test_superstep_is_profiled_on_mesh(self, mesh8):
        """The acceptance metric: a real fused superstep on the CPU
        mesh records its lowering/compile wall time."""
        from multiverso_tpu.tables import ArrayTable, reset_tables
        from multiverso_tpu.tables.superstep import make_superstep
        try:
            t = ArrayTable(64, "float32", updater="default")

            def body(params, states, locals_, options, delta):
                (p,) = params
                return (p + delta,), states, locals_, None

            ss = make_superstep((t,), body, name="fr_test")
            ss((), np.ones(64, np.float32))
            snap = metrics.snapshot()
            assert snap["counters"][
                "profile.compiles{fn=superstep.fr_test}"] == 1
            assert snap["gauges"][
                "profile.compile.last_s{fn=superstep.fr_test}"] > 0
            np.testing.assert_allclose(t.get(), np.ones(64))
        finally:
            reset_tables()

    def test_record_device_memory_gauges(self):
        import jax.numpy as jnp
        keep = jnp.ones(128)                       # a live buffer
        out = telemetry.record_device_memory(prefix="t.dev")
        assert out["live_buffers"] >= 1
        assert out["live_bytes"] >= keep.nbytes
        snap = metrics.snapshot()
        assert snap["gauges"]["t.dev.live_buffers"] == out["live_buffers"]

    def test_profile_window_env_gate(self, monkeypatch):
        monkeypatch.delenv("MVTPU_PROFILE_DIR", raising=False)
        from multiverso_tpu.telemetry.profiling import profile_window
        with profile_window("t.win") as path:
            assert path is None          # unset env: free no-op


# -- Chrome/Perfetto trace export ------------------------------------------


def _run_report(*argv):
    return subprocess.run(
        [sys.executable, "-m", "multiverso_tpu.telemetry.report", *argv],
        capture_output=True, text=True)


class TestChromeTrace:
    def _nested_trace(self, tmp_path):
        path = str(tmp_path / "trace.jsonl")
        trace.set_trace_file(path)
        with telemetry.span("outer", phase="x"):
            with telemetry.span("inner"):
                time.sleep(0.01)
        telemetry.step_timeline("app", 3, tokens=64)
        trace.set_trace_file(None)
        return path

    def test_roundtrip_events_nest(self, tmp_path):
        path = self._nested_trace(tmp_path)
        out = str(tmp_path / "chrome.json")
        proc = _run_report(path, "--chrome-trace", out)
        assert proc.returncode == 0, proc.stderr
        doc = json.load(open(out))                 # valid JSON
        events = doc["traceEvents"]
        by_name = {e["name"]: e for e in events if e.get("ph") == "X"}
        outer, inner = by_name["outer"], by_name["inner"]
        # phases nest: the child slice sits inside the parent slice on
        # the same (pid, tid) track
        assert inner["pid"] == outer["pid"]
        assert inner["tid"] == outer["tid"]
        assert inner["ts"] >= outer["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] \
            + 1.0                                  # float µs slack
        assert outer["args"]["phase"] == "x"
        # step heartbeat -> instant event; process track metadata exists
        assert any(e.get("ph") == "i" and "app step 3" == e["name"]
                   for e in events)
        assert any(e.get("ph") == "M" and e["name"] == "process_name"
                   for e in events)

    def test_stdout_default_and_snapshot_rejected(self, tmp_path):
        path = self._nested_trace(tmp_path)
        proc = _run_report(path, "--chrome-trace")
        assert proc.returncode == 0, proc.stderr
        doc = json.loads(proc.stdout)
        assert doc["traceEvents"]
        metrics.counter("x.ops").inc()
        snap_path = str(tmp_path / "snap.json")
        metrics.write_snapshot(snap_path)
        assert _run_report(snap_path, "--chrome-trace").returncode == 2

    def test_metric_events_become_counters(self, tmp_path):
        path = str(tmp_path / "events.jsonl")
        lines = [{"metric": "m.rate", "value": v, "ts": 1.0 + v,
                  "host": 0, "pid": 1} for v in (1.0, 2.0)]
        with open(path, "w") as f:
            f.writelines(json.dumps(l) + "\n" for l in lines)
        doc = report.to_chrome_trace(report._load(path)[1])
        counters = [e for e in doc["traceEvents"] if e.get("ph") == "C"]
        assert [c["args"]["value"] for c in counters] == [1.0, 2.0]

    def test_from_real_app_step_trace(self, tmp_path, mesh8):
        """The acceptance path end-to-end: train a real app with the
        trace sink bound, then export its step trace for Perfetto."""
        from multiverso_tpu.apps.logreg import (LogRegConfig,
                                                LogisticRegression,
                                                synthetic_blobs)
        from multiverso_tpu.tables import reset_tables
        path = str(tmp_path / "app_trace.jsonl")
        trace.set_trace_file(path)
        try:
            X, y = synthetic_blobs(96, 4, 3, seed=3)
            app = LogisticRegression(LogRegConfig(
                input_dim=4, num_classes=3, minibatch_size=32,
                epochs=1, steps_per_call=2))
            app.train(X, y)
        finally:
            trace.set_trace_file(None)
            reset_tables()
        proc = _run_report(path, "--chrome-trace")
        assert proc.returncode == 0, proc.stderr
        doc = json.loads(proc.stdout)
        names = {e["name"] for e in doc["traceEvents"]}
        assert any(n.startswith("logreg") for n in names), names
        # the compile spans the profiled superstep emitted ride along
        assert "profile.compile" in names

    def test_top_slowest_spans_and_counters(self, tmp_path):
        path = self._nested_trace(tmp_path)
        proc = _run_report(path, "--top", "2")
        assert proc.returncode == 0, proc.stderr
        assert "slowest spans" in proc.stdout
        assert "outer" in proc.stdout
        metrics.counter("hot.bytes", table="0:t").inc(1000)
        metrics.counter("cold.bytes", table="1:u").inc(1)
        snap_path = str(tmp_path / "snap.json")
        metrics.write_snapshot(snap_path)
        proc = _run_report(snap_path, "--top", "1")
        assert proc.returncode == 0, proc.stderr
        assert "hot.bytes" in proc.stdout
        assert "cold.bytes" not in proc.stdout


# -- bench_diff CI tool ----------------------------------------------------


class TestBenchDiff:
    def test_selftest(self):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "bench_diff.py"), "--selftest"],
            capture_output=True, text=True)
        assert proc.returncode == 0, proc.stderr + proc.stdout
        assert "selftest: ok" in proc.stdout

    def test_snapshot_vs_snapshot_exit_codes(self, tmp_path):
        metrics.gauge("w2v.words_per_sec").set(100.0)
        old = str(tmp_path / "old.json")
        metrics.write_snapshot(old)
        metrics.gauge("w2v.words_per_sec").set(50.0)
        new = str(tmp_path / "new.json")
        metrics.write_snapshot(new)
        tool = os.path.join(REPO, "tools", "bench_diff.py")
        ok = subprocess.run([sys.executable, tool, old, new],
                            capture_output=True, text=True)
        assert ok.returncode == 0            # not watched by default
        bad = subprocess.run(
            [sys.executable, tool, old, new,
             "--watch", "gauge:w2v.words_per_sec"],
            capture_output=True, text=True)
        assert bad.returncode == 1
        assert "REGRESSED" in bad.stdout + bad.stderr
