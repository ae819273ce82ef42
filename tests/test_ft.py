"""Fault-tolerance subsystem tests (multiverso_tpu.ft): retry policy,
chaos injection, run-level checkpoint manager, and the headline
kill/resume equivalence guarantee — a run killed at an arbitrary point
(including under an active chaos spec) resumes from its run dir to the
SAME final state as the uninterrupted run."""

import json
import os

import numpy as np
import pytest

from multiverso_tpu.ft.chaos import (ChaosCrash, ChaosError,
                                     install_chaos, parse_chaos_spec,
                                     uninstall_chaos)
from multiverso_tpu.ft.retry import RetryError, RetryPolicy
from multiverso_tpu.telemetry import metrics as telemetry


@pytest.fixture(autouse=True)
def _no_chaos_leak():
    """Chaos install is process-global — never leak into other tests."""
    yield
    uninstall_chaos()


def _counter_value(snap, prefix):
    return sum(v for k, v in snap["counters"].items()
               if k.startswith(prefix))


# -- RetryPolicy -----------------------------------------------------------

class TestRetryPolicy:
    def test_retries_transient_then_succeeds(self):
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise OSError("transient")
            return "ok"

        p = RetryPolicy(max_attempts=5, base_delay_s=0.0, seed=0,
                        name="t1")
        assert p.call(flaky) == "ok"
        assert len(calls) == 3

    def test_gives_up_after_max_attempts(self):
        def always():
            raise OSError("dead")

        p = RetryPolicy(max_attempts=3, base_delay_s=0.0, seed=0,
                        name="t2")
        with pytest.raises(RetryError) as ei:
            p.call(always)
        assert isinstance(ei.value.__cause__, OSError)

    def test_file_not_found_never_retried(self):
        calls = []

        def missing():
            calls.append(1)
            raise FileNotFoundError("nope")

        p = RetryPolicy(max_attempts=5, base_delay_s=0.0, seed=0)
        with pytest.raises(FileNotFoundError):
            p.call(missing)
        assert len(calls) == 1

    def test_non_oserror_not_retried(self):
        calls = []

        def corrupt():
            calls.append(1)
            raise ValueError("checksum mismatch")

        p = RetryPolicy(max_attempts=5, base_delay_s=0.0, seed=0)
        with pytest.raises(ValueError):
            p.call(corrupt)
        assert len(calls) == 1

    def test_chaos_crash_never_swallowed(self):
        def dying():
            raise ChaosCrash("killed")

        p = RetryPolicy(max_attempts=5, base_delay_s=0.0, seed=0)
        with pytest.raises(ChaosCrash):
            p.call(dying)

    def test_deadline_cap(self):
        def always():
            raise OSError("slow death")

        p = RetryPolicy(max_attempts=100, base_delay_s=10.0,
                        max_delay_s=10.0, deadline_s=0.01, seed=1)
        with pytest.raises(RetryError, match="deadline"):
            p.call(always)

    def test_backoff_deterministic_under_seed_and_capped(self):
        a = RetryPolicy(seed=42, base_delay_s=0.1, max_delay_s=0.5)
        b = RetryPolicy(seed=42, base_delay_s=0.1, max_delay_s=0.5)
        da = [a.backoff_s(i) for i in range(1, 8)]
        db = [b.backoff_s(i) for i in range(1, 8)]
        assert da == db
        assert all(0.0 <= d <= 0.5 for d in da)

    def test_telemetry_counters(self):
        before = telemetry.snapshot()
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 2:
                raise OSError("once")
            return 1

        RetryPolicy(max_attempts=3, base_delay_s=0.0, seed=0,
                    name="tele").call(flaky)
        after = telemetry.snapshot()
        d = (_counter_value(after, "retry.attempts{policy=tele}")
             - _counter_value(before, "retry.attempts{policy=tele}"))
        assert d == 2
        assert _counter_value(after, "retry.recoveries{policy=tele}") \
            >= 1


# -- chaos injector --------------------------------------------------------

class TestChaos:
    def test_spec_parse_rules(self):
        inj = parse_chaos_spec(
            "seed=7;io.write:error:p=0.5,times=3;io.*:latency:ms=2")
        assert inj.seed == 7
        assert len(inj.rules) == 2
        assert inj.rules[0].p == 0.5 and inj.rules[0].times == 3
        assert inj.rules[1].kind == "latency" and inj.rules[1].ms == 2.0

    def test_malformed_spec_raises(self):
        with pytest.raises(ValueError):
            parse_chaos_spec("io.write")          # no kind
        with pytest.raises(ValueError):
            parse_chaos_spec("io.write:explode")  # unknown kind
        with pytest.raises(ValueError):
            parse_chaos_spec("io.write:error:frequency=2")

    def test_error_after_and_times(self):
        inj = install_chaos("pt:error:after=2,times=1")
        inj.hit("pt")           # 1: skipped (after)
        inj.hit("pt")           # 2: skipped
        with pytest.raises(ChaosError):
            inj.hit("pt")       # 3: fires
        inj.hit("pt")           # 4: times exhausted
        assert inj.counts() == {"pt:error": 1}

    def test_glob_pattern_matches(self):
        inj = install_chaos("io.*:error:times=1")
        with pytest.raises(ChaosError):
            inj.hit("io.write")
        inj.hit("table.add")    # no match, no fire

    def test_probability_deterministic(self):
        def run():
            inj = parse_chaos_spec("seed=3;pt:error:p=0.5")
            fired = 0
            for _ in range(64):
                try:
                    inj.hit("pt")
                except ChaosError:
                    fired += 1
            return fired

        a, b = run(), run()
        assert a == b
        assert 0 < a < 64      # p=0.5 over 64 draws: neither extreme

    def test_injected_io_faults_retried_with_telemetry(self, mesh8,
                                                       tmp_path):
        """THE acceptance wiring: chaos-injected IO faults in the
        stream layer are retried by the RetryPolicy guarding
        savez_stream, with retry.* telemetry recorded."""
        from multiverso_tpu.tables import ArrayTable, reset_tables
        try:
            t = ArrayTable(9, "float32", name="chaos_arr")
            t.add(np.ones(9, np.float32))
            want = t.get()
            before = telemetry.snapshot()
            install_chaos("io.write:error:times=2")
            uri = str(tmp_path / "c.npz")
            t.store(uri)                      # survives via retry
            uninstall_chaos()
            after = telemetry.snapshot()
            fails = (_counter_value(after, "retry.failures")
                     - _counter_value(before, "retry.failures"))
            assert fails >= 2
            assert (_counter_value(after, "chaos.fired")
                    - _counter_value(before, "chaos.fired")) >= 2
            t2 = ArrayTable(9, "float32", name="chaos_arr2")
            t2.load(uri)
            np.testing.assert_array_equal(t2.get(), want)
        finally:
            reset_tables()

    def test_torn_write_leaves_last_good_payload(self, tmp_path):
        """'torn' kind at io.rename: payload write happens, commit
        rename does not — the prior good file survives untouched."""
        from multiverso_tpu.io import open_stream
        target = str(tmp_path / "t.bin")
        with open_stream(target, "wb") as s:
            s.write(b"v1")
        install_chaos("io.rename:torn:times=1")
        with pytest.raises(ChaosError):
            with open_stream(target, "wb") as s:
                s.write(b"v2-half")
        uninstall_chaos()
        with open(target, "rb") as f:
            assert f.read() == b"v1"


# -- checksum satellite (savez/loadz CRC32) --------------------------------

class TestPayloadChecksum:
    def _write(self, tmp_path, payload):
        from multiverso_tpu.tables.base import savez_stream
        uri = str(tmp_path / "ck.npz")
        savez_stream(uri, {"magic": "m.v1"}, payload)
        return uri

    def test_roundtrip_verifies(self, tmp_path):
        from multiverso_tpu.tables.base import loadz_stream
        arr = np.arange(32, dtype=np.float32)
        uri = self._write(tmp_path, {"a": arr})
        manifest, data = loadz_stream(uri, "m.v1")
        assert "a" in manifest["crc32"]
        np.testing.assert_array_equal(data["a"], arr)

    def test_bit_rot_fails_loudly(self, tmp_path):
        from multiverso_tpu.tables.base import loadz_stream
        uri = self._write(tmp_path,
                          {"a": np.arange(64, dtype=np.float32)})
        raw = bytearray(open(uri, "rb").read())
        # flip one bit near the end (inside the array payload, past the
        # zip headers + manifest entry)
        raw[-20] ^= 0xFF
        with open(uri, "wb") as f:
            f.write(bytes(raw))
        with pytest.raises((ValueError, Exception)) as ei:
            loadz_stream(uri, "m.v1")
        # either our checksum catches it or the zip CRC does — both are
        # LOUD; silent load is the failure mode
        assert ei.type is not None

    def test_manifest_crc_mismatch_detected(self, tmp_path):
        """Rewrite an array under the ORIGINAL manifest (valid zip, bad
        content) — only the per-array CRC can catch this."""
        from multiverso_tpu.tables.base import (loadz_stream,
                                                savez_stream)
        import io as _io
        uri = str(tmp_path / "swap.npz")
        savez_stream(uri, {"magic": "m.v1"},
                     {"a": np.arange(16, dtype=np.float32)})
        manifest, data = loadz_stream(uri, "m.v1")
        # forge: same manifest (with its old crc), different payload
        forged = {"magic": "m.v1", "crc32": manifest["crc32"]}
        buf = _io.BytesIO()
        np.savez(buf, manifest=json.dumps(forged),
                 a=np.zeros(16, np.float32))
        with open(uri, "wb") as f:
            f.write(buf.getvalue())
        with pytest.raises(ValueError, match="checksum mismatch"):
            loadz_stream(uri, "m.v1")

    def test_pre_crc_checkpoint_still_loads(self, tmp_path):
        """Back-compat: a checkpoint written without crc32 stamps (an
        older build) loads unverified instead of refusing."""
        import io as _io
        from multiverso_tpu.tables.base import loadz_stream
        uri = str(tmp_path / "old.npz")
        buf = _io.BytesIO()
        np.savez(buf, manifest=json.dumps({"magic": "m.v1"}),
                 a=np.ones(4, np.float32))
        with open(uri, "wb") as f:
            f.write(buf.getvalue())
        manifest, data = loadz_stream(uri, "m.v1")
        np.testing.assert_array_equal(data["a"], np.ones(4))


# -- RunCheckpointManager --------------------------------------------------

class TestRunCheckpointManager:
    def _table(self, name, n=11):
        from multiverso_tpu.tables import ArrayTable
        t = ArrayTable(n, "float32", updater="adagrad", name=name)
        t.add(np.arange(n, dtype=np.float32))
        return t

    def test_save_scan_resume_roundtrip(self, mesh8, tmp_path):
        from multiverso_tpu.ft.checkpoint import RunCheckpointManager
        from multiverso_tpu.tables import ArrayTable, reset_tables
        try:
            t = self._table("m_arr")
            want = t.get()
            with RunCheckpointManager(str(tmp_path), keep=3,
                                      tables=[t]) as mgr:
                mgr.save(5, {"cursor": 7, "rng": np.arange(3)})
                mgr.flush()
                assert [g.step for g in mgr.scan()] == [5]
            t2 = ArrayTable(11, "float32", updater="adagrad",
                            name="m_arr")
            mgr2 = RunCheckpointManager(str(tmp_path), tables=[t2],
                                        background=False)
            st = mgr2.resume()
            assert st is not None and st.step == 5
            assert st.get("cursor") == 7
            np.testing.assert_array_equal(st.get("rng"), np.arange(3))
            np.testing.assert_array_equal(t2.get(), want)
        finally:
            reset_tables()

    def test_retention_keeps_exactly_last_k(self, mesh8, tmp_path):
        from multiverso_tpu.ft.checkpoint import RunCheckpointManager
        from multiverso_tpu.tables import reset_tables
        try:
            t = self._table("gc_arr")
            mgr = RunCheckpointManager(str(tmp_path), keep=2,
                                       tables=[t], background=False)
            for step in (1, 2, 3, 4, 5):
                mgr.save(step)
            gens = mgr.scan()
            assert [g.step for g in gens] == [4, 5]
            # the deleted dirs are actually gone, not just unscanned
            names = sorted(os.listdir(tmp_path))
            assert names == ["gen-0000000004", "gen-0000000005"]
        finally:
            reset_tables()

    def test_incomplete_generation_ignored_and_fallback(self, mesh8,
                                                       tmp_path):
        from multiverso_tpu.ft.checkpoint import (MANIFEST_NAME,
                                                  RunCheckpointManager)
        from multiverso_tpu.tables import reset_tables
        try:
            t = self._table("fb_arr")
            mgr = RunCheckpointManager(str(tmp_path), keep=5,
                                       tables=[t], background=False)
            mgr.save(1)
            want = t.get()
            t.add(np.ones(11, np.float32))
            mgr.save(2)
            # generation 2's manifest gets torn (truncated json)
            m2 = os.path.join(str(tmp_path), "gen-0000000002",
                              MANIFEST_NAME)
            with open(m2, "w") as f:
                f.write('{"magic": "multiverso_tpu.run_ck')
            assert [g.step for g in mgr.scan()] == [1]
            st = mgr.resume()
            assert st.step == 1
            np.testing.assert_array_equal(t.get(), want)
        finally:
            reset_tables()

    def test_corrupt_payload_falls_back_with_counter(self, mesh8,
                                                     tmp_path):
        from multiverso_tpu.ft.checkpoint import RunCheckpointManager
        from multiverso_tpu.tables import reset_tables
        try:
            t = self._table("cp_arr")
            mgr = RunCheckpointManager(str(tmp_path), keep=5,
                                       tables=[t], background=False)
            mgr.save(1)
            want = t.get()
            t.add(np.ones(11, np.float32))
            mgr.save(2)
            # bit-rot generation 2's table payload (manifest intact)
            p2 = os.path.join(str(tmp_path), "gen-0000000002",
                              "table-cp_arr.npz")
            raw = bytearray(open(p2, "rb").read())
            raw[len(raw) // 2] ^= 0xFF
            with open(p2, "wb") as f:
                f.write(bytes(raw))
            before = telemetry.snapshot()
            st = mgr.resume()
            after = telemetry.snapshot()
            assert st.step == 1         # fell back to the good gen
            np.testing.assert_array_equal(t.get(), want)
            assert (_counter_value(after, "ft.recover.fallbacks")
                    - _counter_value(before,
                                     "ft.recover.fallbacks")) == 1
        finally:
            reset_tables()

    def test_fingerprint_mismatch_raises(self, mesh8, tmp_path):
        from multiverso_tpu.ft.checkpoint import RunCheckpointManager
        from multiverso_tpu.tables import reset_tables
        try:
            t = self._table("fp_arr")
            mgr = RunCheckpointManager(str(tmp_path), tables=[t],
                                       fingerprint="aaaa",
                                       background=False)
            mgr.save(1)
            mgr2 = RunCheckpointManager(str(tmp_path), tables=[t],
                                        fingerprint="bbbb",
                                        background=False)
            with pytest.raises(ValueError, match="fingerprint"):
                mgr2.resume()
        finally:
            reset_tables()

    def test_maybe_save_cadence(self, mesh8, tmp_path):
        from multiverso_tpu.ft.checkpoint import RunCheckpointManager
        from multiverso_tpu.tables import reset_tables
        try:
            t = self._table("cad_arr")
            mgr = RunCheckpointManager(str(tmp_path), every=3,
                                       tables=[t], background=False)
            evaluated = []

            def state():
                evaluated.append(1)
                return {"x": 1}

            for step in range(1, 8):
                mgr.maybe_save(step, state)
            assert [g.step for g in mgr.scan()] == [3, 6]
            assert len(evaluated) == 2    # lazily evaluated on cadence
            # repeated step never double-saves
            assert not mgr.maybe_save(6, state)
        finally:
            reset_tables()

    def test_background_write_failure_surfaces(self, mesh8, tmp_path):
        from multiverso_tpu.ft.checkpoint import RunCheckpointManager
        from multiverso_tpu.tables import reset_tables
        try:
            t = self._table("bg_arr")
            mgr = RunCheckpointManager(str(tmp_path), tables=[t])
            install_chaos("io.write:error")     # every attempt fails
            mgr.save(1)
            with pytest.raises(RuntimeError,
                               match="background run-checkpoint"):
                mgr.flush()
            uninstall_chaos()
            mgr.save(2)                         # manager still usable
            mgr.flush()
            assert [g.step for g in mgr.scan()] == [2]
            mgr.close()
        finally:
            uninstall_chaos()
            reset_tables()

    def test_watchdog_dump_names_restart_point(self, mesh8, tmp_path):
        from multiverso_tpu.ft import checkpoint as ckpt
        from multiverso_tpu.ft.checkpoint import RunCheckpointManager
        from multiverso_tpu.tables import reset_tables
        from multiverso_tpu.telemetry.watchdog import Watchdog
        try:
            t = self._table("wd_arr")
            mgr = RunCheckpointManager(str(tmp_path / "run"),
                                       tables=[t], background=False)
            mgr.save(9)
            assert ckpt.latest_good_checkpoint() is not None
            w = Watchdog(60.0, name="ft-test",
                         dump_dir=str(tmp_path / "dump"))
            path = w.dump()
            with open(os.path.join(path, "watchdog.json")) as f:
                doc = json.load(f)
            assert doc["latest_checkpoint"] \
                == ckpt.latest_good_checkpoint()
            assert "gen-0000000009" in doc["latest_checkpoint"]
        finally:
            reset_tables()

    def test_kv_table_covered(self, mesh8, tmp_path):
        from multiverso_tpu.ft.checkpoint import RunCheckpointManager
        from multiverso_tpu.tables import KVTable, reset_tables
        try:
            kv = KVTable(1 << 10, value_dim=2, name="mgr_kv")
            keys = np.array([3, 11, 12345], np.uint64)
            kv.add(keys, np.ones((3, 2), np.float32))
            want, _ = kv.get(keys)
            mgr = RunCheckpointManager(str(tmp_path), tables=[kv],
                                       background=False)
            mgr.save(1)
            kv2 = KVTable(1 << 10, value_dim=2, name="mgr_kv")
            mgr2 = RunCheckpointManager(str(tmp_path), tables=[kv2],
                                        background=False)
            st = mgr2.resume()
            assert st.step == 1
            got, found = kv2.get(keys)
            assert found.all()
            np.testing.assert_array_equal(got, want)
        finally:
            reset_tables()


# -- the headline guarantee: kill/resume equivalence -----------------------

class _Kill(BaseException):
    """Simulated eviction: BaseException so nothing 'recovers' it."""


class TestKillResumeEquivalence:
    def _logreg(self, name):
        from multiverso_tpu.apps.logreg import (LogisticRegression,
                                                LogRegConfig)
        cfg = LogRegConfig(input_dim=10, num_classes=3,
                           minibatch_size=32, steps_per_call=2,
                           epochs=4, learning_rate=0.1,
                           updater="adagrad", seed=3)
        return LogisticRegression(cfg, name=name)

    def test_logreg_killed_under_chaos_resumes_equal(self, mesh8,
                                                     tmp_path):
        """Kill a checkpointed logreg run mid-epoch WITH an active
        chaos spec injecting IO faults into every checkpoint write;
        resume in a fresh app; final weights (param AND adagrad state)
        match the uninterrupted run bit-for-bit."""
        from multiverso_tpu.apps.logreg import synthetic_blobs
        from multiverso_tpu.ft.checkpoint import RunCheckpointManager
        from multiverso_tpu.tables import reset_tables
        X, y = synthetic_blobs(192, 10, 3, seed=5)
        try:
            full = self._logreg("eq_lr")
            full.train(X, y)
            want = full.table.get()
            want_state = [np.asarray(l) for l in
                          __import__("jax").tree.leaves(
                              full.table.state)]
            reset_tables()

            # interrupted run: chaos faults every store's first write,
            # killed during epoch 3 (2 complete checkpoints on disk)
            app = self._logreg("eq_lr")
            mgr = RunCheckpointManager(str(tmp_path), keep=2, every=1,
                                       tables=[app.table])
            app.run_ckpt = mgr
            # deterministic fault schedule: write calls 1, 6 and 12
            # fail (never two adjacent, so the 3-attempt retry always
            # recovers — the point is faults DURING checkpointing, not
            # a dead filesystem)
            install_chaos("io.write:error:times=1;"
                          "io.write:error:after=5,times=1;"
                          "io.write:error:after=11,times=1")
            orig = app.train_epoch
            seen = []

            def dying_epoch(X, y, shuffle_seed=None):
                if len(seen) == 2:
                    raise _Kill()
                r = orig(X, y, shuffle_seed=shuffle_seed)
                seen.append(1)
                return r

            app.train_epoch = dying_epoch
            with pytest.raises(_Kill):
                app.train(X, y)
            mgr.flush()
            mgr.close()
            uninstall_chaos()
            reset_tables()

            # fresh process-equivalent: new app, resume, finish
            res = self._logreg("eq_lr")
            mgr2 = RunCheckpointManager(str(tmp_path), keep=2, every=1,
                                        tables=[res.table])
            st = mgr2.resume()
            assert st is not None and st.step == 2
            res.restore_run_state(st)
            assert res._epoch_done == 2
            res.run_ckpt = mgr2
            res.train(X, y)
            mgr2.close()
            np.testing.assert_array_equal(res.table.get(), want)
            got_state = [np.asarray(l) for l in
                         __import__("jax").tree.leaves(
                             res.table.state)]
            for a, b in zip(got_state, want_state):
                np.testing.assert_array_equal(a, b)
        finally:
            uninstall_chaos()
            reset_tables()

    def test_lightlda_sweep_resume_equal(self, mesh_dp8, tmp_path):
        """LDA: z + doc counts + tables all ride the manager; a run
        resumed at a sweep boundary matches the uninterrupted one
        (counts are integers — equality is exact). Pure-DP mesh like
        the other LDA tests."""
        from multiverso_tpu.apps.lightlda import LDAConfig, LightLDA
        from multiverso_tpu.ft.checkpoint import RunCheckpointManager
        from multiverso_tpu.tables import reset_tables
        rng = np.random.default_rng(0)
        T, D, V = 600, 24, 40
        td = np.sort(rng.integers(0, D, T)).astype(np.int32)
        tw = rng.integers(0, V, T).astype(np.int32)
        cfg = dict(num_topics=8, batch_tokens=64, steps_per_call=2,
                   num_iterations=4, eval_every=10, seed=2)
        try:
            full = LightLDA(tw, td, V, LDAConfig(**cfg), name="eq_lda")
            full.train()
            want_wt = full.word_topics()
            want_dt = full.doc_topics()
            reset_tables()

            app = LightLDA(tw, td, V, LDAConfig(**cfg), name="eq_lda")
            mgr = RunCheckpointManager(str(tmp_path), keep=2, every=1,
                                       tables=[app.word_topic,
                                               app.summary])
            app.run_ckpt = mgr
            app.train(num_iterations=2)         # "killed" after sweep 2
            mgr.flush()
            mgr.close()
            reset_tables()

            res = LightLDA(tw, td, V, LDAConfig(**cfg), name="eq_lda")
            mgr2 = RunCheckpointManager(str(tmp_path), keep=2, every=1,
                                        tables=[res.word_topic,
                                                res.summary])
            st = mgr2.resume()
            assert st is not None and st.step == 2
            res.restore_run_state(st)
            assert res._sweep_done == 2
            res.run_ckpt = mgr2
            res.train()                          # sweeps 3..4
            mgr2.close()
            np.testing.assert_array_equal(res.word_topics(), want_wt)
            np.testing.assert_array_equal(res.doc_topics(), want_dt)
        finally:
            reset_tables()


# -- app wiring (flags + env knobs) ----------------------------------------

class TestWireApp:
    def test_env_knobs_enable_manager_and_resume(self, mesh8, tmp_path,
                                                 monkeypatch):
        from multiverso_tpu.apps.logreg import (LogisticRegression,
                                                LogRegConfig,
                                                synthetic_blobs)
        from multiverso_tpu.ft.checkpoint import (define_run_flags,
                                                  wire_app)
        from multiverso_tpu.tables import reset_tables
        define_run_flags()
        X, y = synthetic_blobs(96, 8, 2, seed=0)
        cfg = LogRegConfig(input_dim=8, num_classes=2,
                           minibatch_size=32, epochs=2, seed=1)
        try:
            monkeypatch.setenv("MVTPU_RUN_DIR", str(tmp_path))
            monkeypatch.setenv("MVTPU_CKPT_EVERY", "1")
            app = LogisticRegression(cfg, name="env_lr")
            mgr = wire_app(app, [app.table])
            assert mgr is not None and mgr.every == 1
            app.train(X, y)
            mgr.close()
            assert [g.step for g in mgr.scan()] == [1, 2]
            reset_tables()

            monkeypatch.setenv("MVTPU_RESUME", "1")
            app2 = LogisticRegression(cfg, name="env_lr")
            mgr2 = wire_app(app2, [app2.table])
            assert app2._epoch_done == 2        # restored the cursor
            np.testing.assert_array_equal(app2.table.get(),
                                          app.table.get())
            mgr2.close()
        finally:
            reset_tables()

    def test_changed_config_fails_loudly(self, mesh8, tmp_path,
                                         monkeypatch):
        from multiverso_tpu.apps.logreg import (LogisticRegression,
                                                LogRegConfig,
                                                synthetic_blobs)
        from multiverso_tpu.ft.checkpoint import (define_run_flags,
                                                  wire_app)
        from multiverso_tpu.tables import reset_tables
        define_run_flags()
        X, y = synthetic_blobs(64, 8, 2, seed=0)
        try:
            monkeypatch.setenv("MVTPU_RUN_DIR", str(tmp_path))
            monkeypatch.setenv("MVTPU_CKPT_EVERY", "1")
            app = LogisticRegression(
                LogRegConfig(input_dim=8, num_classes=2,
                             minibatch_size=32, epochs=1),
                name="fp_lr")
            mgr = wire_app(app, [app.table])
            app.train(X, y)
            mgr.close()
            reset_tables()

            monkeypatch.setenv("MVTPU_RESUME", "1")
            app2 = LogisticRegression(
                LogRegConfig(input_dim=8, num_classes=2,
                             minibatch_size=32, epochs=1,
                             learning_rate=0.5),    # changed config
                name="fp_lr")
            with pytest.raises(ValueError, match="fingerprint"):
                wire_app(app2, [app2.table])
        finally:
            reset_tables()
