"""Client pipeline (multiverso_tpu/client): coalescing dispatch contract,
staleness-bounded cache, async staging — on the virtual CPU mesh.

The dispatch-count assertions ride profiled_jit's per-function
``profile.calls`` counters (every table kernel is a profiled_jit), so
"K coalesced adds produce ONE fused apply dispatch" is checked against
the same metric the micro-bench and a production run report.
"""

import numpy as np
import pytest

from multiverso_tpu import client, telemetry
from multiverso_tpu.tables import (ArrayTable, KVTable, MatrixTable,
                                   SparseMatrixTable, make_superstep)


def _calls(fn_name: str) -> float:
    return telemetry.registry().counter("profile.calls", fn=fn_name).value


class TestCoalescingDense:
    def test_k_adds_one_dispatch(self, mesh8):
        t = ArrayTable(32, "float32", name="cl_dense1")
        buf = client.CoalescingBuffer(t, max_deltas=4)
        c0 = _calls("table.apply.cl_dense1")
        hs = [buf.add(np.full(32, float(i + 1), np.float32))
              for i in range(4)]
        # 4th add crossed max_deltas: auto-flushed as ONE apply dispatch
        assert buf.flush_generation == 1
        assert buf.pending_deltas == 0
        assert _calls("table.apply.cl_dense1") - c0 == 1
        hs[0].wait()
        np.testing.assert_allclose(t.get(), 10.0)

    def test_wait_forces_flush(self, mesh8):
        t = ArrayTable(8, "float32", name="cl_dense2")
        buf = client.CoalescingBuffer(t, max_deltas=100)
        h = buf.add(np.ones(8, np.float32))
        assert not h.flushed() and not h.done()
        assert float(t.get()[0]) == 0.0     # buffered = invisible
        h.wait()                            # forces the flush
        assert h.flushed()
        np.testing.assert_allclose(t.get(), 1.0)

    def test_flush_returns_handle_and_observes_all(self, mesh8):
        t = ArrayTable(8, "float32", name="cl_dense3")
        buf = client.CoalescingBuffer(t, max_deltas=100)
        buf.add(np.ones(8, np.float32))
        buf.add(2 * np.ones(8, np.float32))
        h = buf.flush()
        h.wait()
        np.testing.assert_allclose(t.get(), 3.0)
        assert buf.flush() is None          # empty flush: no dispatch

    def test_byte_budget_triggers(self, mesh8):
        t = ArrayTable(8, "float32", name="cl_dense4")
        buf = client.CoalescingBuffer(t, max_deltas=100, max_bytes=64)
        buf.add(np.ones(8, np.float32))     # 32 bytes: under budget
        assert buf.flush_generation == 0
        buf.add(np.ones(8, np.float32))     # 64 bytes: flush
        assert buf.flush_generation == 1

    def test_option_change_flushes_boundary(self, mesh8):
        from multiverso_tpu.updaters import AddOption
        t = ArrayTable(8, "float32", updater="sgd", name="cl_dense5")
        buf = client.CoalescingBuffer(t, max_deltas=100)
        buf.add(np.ones(8, np.float32), AddOption(learning_rate=0.5))
        buf.add(np.ones(8, np.float32), AddOption(learning_rate=1.0))
        # differing options cannot share a fused apply: first group
        # flushed at the boundary
        assert buf.flush_generation == 1
        buf.flush()
        # -0.5*1 - 1.0*1
        np.testing.assert_allclose(t.get(), -1.5)

    def test_sgd_coalescing_exact(self, mesh8):
        """Linear updaters: K coalesced adds == K sequential adds."""
        a = ArrayTable(16, "float32", updater="sgd", name="cl_seq")
        b = ArrayTable(16, "float32", updater="sgd", name="cl_coal")
        rng = np.random.default_rng(0)
        deltas = [rng.normal(size=16).astype(np.float32)
                  for _ in range(6)]
        for d in deltas:
            a.add(d)
        buf = client.CoalescingBuffer(b, max_deltas=6)
        for d in deltas:
            buf.add(d)
        np.testing.assert_allclose(a.get(), b.get(), rtol=1e-5)

    def test_superstep_flushes_buffer_first(self, mesh8):
        t = ArrayTable(8, "float32", name="cl_ss")
        buf = client.CoalescingBuffer(t, max_deltas=100)

        def body(params, states, locals_, options):
            (p,), (s,) = params, states
            return (p * 2.0,), (s,), locals_, None

        step = make_superstep((t,), body, name="cl_ss_step")
        buf.add(np.ones(8, np.float32))
        step(())
        # buffered delta landed BEFORE the fused double: (0+1)*2
        np.testing.assert_allclose(t.get(), 2.0)

    def test_store_includes_buffered(self, mesh8):
        t = ArrayTable(8, "float32", name="cl_store")
        buf = client.CoalescingBuffer(t, max_deltas=100)
        buf.add(np.ones(8, np.float32))
        t.store("mem://cl_store.npz")
        t2 = ArrayTable(8, "float32", name="cl_store2")
        t2.load("mem://cl_store.npz")
        np.testing.assert_allclose(t2.get(), 1.0)


class TestCoalescingKV:
    def test_dup_keys_presummed_one_dispatch(self, mesh8):
        kv = KVTable(1024, value_dim=2, name="cl_kv1")
        buf = client.CoalescingBuffer(kv, max_deltas=3)
        c0 = _calls("kv.apply.cl_kv1")
        buf.add_kv(np.array([1, 2], np.uint64), np.ones((2, 2), np.float32))
        buf.add_kv(np.array([2, 3], np.uint64), np.ones((2, 2), np.float32))
        buf.add_kv(np.array([3, 4], np.uint64), np.ones((2, 2), np.float32))
        assert _calls("kv.apply.cl_kv1") - c0 == 1
        vals, found = kv.get(np.array([1, 2, 3, 4], np.uint64))
        assert found.all()
        np.testing.assert_allclose(vals[:, 0], [1.0, 2.0, 2.0, 1.0])

    def test_wait_observes_buffered(self, mesh8):
        kv = KVTable(512, value_dim=0, name="cl_kv2")
        buf = client.CoalescingBuffer(kv, max_deltas=100)
        h = buf.add_kv(np.array([7], np.uint64), np.ones(1, np.float32))
        h.wait()
        vals, found = kv.get(np.array([7], np.uint64))
        assert found[0] and vals[0] == 1.0


class TestCoalescingRows:
    def test_rows_coalesce_one_scatter(self, mesh8):
        t = MatrixTable(16, 4, "float32", name="cl_rows")
        buf = client.CoalescingBuffer(t, max_deltas=2)
        c0 = _calls("table.scatter_add.cl_rows")
        buf.add_rows([1, 3], np.ones((2, 4), np.float32))
        buf.add_rows([3, 5], np.ones((2, 4), np.float32))
        assert _calls("table.scatter_add.cl_rows") - c0 == 1
        got = t.get_rows([1, 3, 5])
        np.testing.assert_allclose(got[:, 0], [1.0, 2.0, 1.0])

    def test_rows_stateful_updater_dedup(self, mesh8):
        # duplicate row ids across buffered adds: the flush pre-sums,
        # satisfying the stateful-updater unique-ids rule
        t = MatrixTable(16, 4, "float32", updater="adagrad",
                        name="cl_rows_st")
        buf = client.CoalescingBuffer(t, max_deltas=2)
        buf.add_rows([2], np.ones((1, 4), np.float32))
        buf.add_rows([2], np.ones((1, 4), np.float32))
        got = t.get_rows([2])
        assert np.all(got != 0)


class TestCoalescingCOO:
    def test_coo_coalesce(self, mesh8):
        t = SparseMatrixTable(16, 8, "int32", name="cl_coo")
        buf = client.CoalescingBuffer(t, max_deltas=2)
        c0 = _calls("table.coo_scatter_add.cl_coo")
        buf.add_sparse([1, 2], [3, 4], [1, 1])
        buf.add_sparse([2, 5], [4, 6], [1, 1])
        assert _calls("table.coo_scatter_add.cl_coo") - c0 == 1
        got = t.get_rows([1, 2, 5])
        assert got[0, 3] == 1 and got[1, 4] == 2 and got[2, 6] == 1


class TestCachedView:
    def test_never_exceeds_staleness_bound(self, mesh8):
        t = ArrayTable(16, "float32", name="cl_view1")
        view = client.CachedView(t, max_staleness=2)
        try:
            for i in range(10):
                t.add(np.ones(16, np.float32))
                view.get()
                assert t.generation - view.generation <= 2, \
                    f"bound violated at step {i}"
        finally:
            view.close()

    def test_hit_serves_cached_without_dispatch(self, mesh8):
        t = ArrayTable(16, "float32", name="cl_view2")
        view = client.CachedView(t, max_staleness=0, background=False)
        c0 = _calls("table.snapshot.cl_view2")
        for _ in range(5):
            view.get()      # unchanged table: pure cache hits
        assert _calls("table.snapshot.cl_view2") - c0 == 0
        lbl = f"{t.table_id}:{t.name}"
        reg = telemetry.registry()
        assert reg.counter("client.cache.hits", table=lbl).value >= 5

    def test_refresh_after_update_sync(self, mesh8):
        t = ArrayTable(8, "float32", name="cl_view3")
        view = client.CachedView(t, max_staleness=0, background=False)
        t.add(np.ones(8, np.float32))
        np.testing.assert_allclose(view.get(), 1.0)
        lbl = f"{t.table_id}:{t.name}"
        assert telemetry.registry().counter(
            "client.cache.misses", table=lbl).value >= 1

    def test_background_refresh_catches_up(self, mesh8):
        import time
        t = ArrayTable(8, "float32", name="cl_view4")
        view = client.CachedView(t, max_staleness=1)
        try:
            t.add(np.ones(8, np.float32))   # wakes the refresher
            deadline = time.time() + 5.0
            while view.staleness() > 0 and time.time() < deadline:
                view.get()          # absorbs finished refreshes
                time.sleep(0.01)
            assert view.staleness() == 0
            np.testing.assert_allclose(view.get(), 1.0)
        finally:
            view.close()

    def test_superstep_advances_generation_for_view(self, mesh8):
        t = ArrayTable(8, "float32", name="cl_view5")
        view = client.CachedView(t, max_staleness=0, background=False)

        def body(params, states, locals_, options):
            (p,), (s,) = params, states
            return (p + 1.0,), (s,), locals_, None

        step = make_superstep((t,), body, name="cl_view5_step")
        step(())
        np.testing.assert_allclose(view.get(), 1.0)

    def test_close_idempotent(self, mesh8):
        t = ArrayTable(8, "float32", name="cl_view6")
        view = client.CachedView(t, max_staleness=1)
        view.close()
        view.close()

    def test_per_get_staleness_override(self, mesh8):
        """One view, two readers: ``get(max_staleness=...)`` overrides
        the constructor bound for THAT read only — a tolerant read hits
        cache where the default would refresh, and ``0`` forces
        freshness on a view whose default would tolerate the lag."""
        t = ArrayTable(8, "float32", name="cl_view7")
        view = client.CachedView(t, max_staleness=0, background=False)
        view.get()                          # prime the snapshot
        c0 = _calls("table.snapshot.cl_view7")
        t.add(np.ones(8, np.float32))
        # tolerant read: 1 generation behind is fine HERE, despite the
        # strict default — no snapshot, stale value served
        got = view.get(max_staleness=5)
        assert _calls("table.snapshot.cl_view7") - c0 == 0
        np.testing.assert_allclose(got, 0.0)
        # strict read on the same view: must refresh
        np.testing.assert_allclose(view.get(max_staleness=0), 1.0)
        assert _calls("table.snapshot.cl_view7") - c0 >= 1
        # the default bound is untouched by the overrides
        t.add(np.ones(8, np.float32))
        np.testing.assert_allclose(view.get(), 2.0)
        with pytest.raises(ValueError):
            view.get(max_staleness=-1)


class TestStaging:
    def test_staged_equals_direct(self, mesh8):
        rng = np.random.default_rng(1)
        batches = []
        for _ in range(6):
            keys = rng.choice(np.arange(1, 64, dtype=np.uint64),
                              size=16, replace=False)
            batches.append((keys, rng.normal(size=16).astype(np.float32)))
        a = KVTable(512, value_dim=0, name="cl_st_direct")
        for keys, deltas in batches:
            a.add(keys, deltas)
        b = KVTable(512, value_dim=0, name="cl_st_staged")
        h = client.stage_kv_adds(b, batches, depth=2)
        h.wait()
        probe = np.arange(1, 64, dtype=np.uint64)
        va, fa = a.get(probe)
        vb, fb = b.get(probe)
        np.testing.assert_array_equal(fa, fb)
        np.testing.assert_allclose(va, vb, rtol=1e-6)

    def test_prepare_error_surfaces(self, mesh8):
        kv = KVTable(512, value_dim=0, name="cl_st_err")
        w = client.KVStagingWriter(kv, depth=1)
        w.add(np.array([1, 1], np.uint64), np.ones(2, np.float32))
        with pytest.raises(ValueError, match="duplicate keys"):
            w.flush()
        w.close()

    def test_non_pow2_batch_padded(self, mesh8):
        # prepare_add buckets lengths: a 5-key add works and padding
        # lanes are inert (no phantom keys appear)
        kv = KVTable(512, value_dim=0, name="cl_st_pad")
        kv.add(np.arange(1, 6, dtype=np.uint64), np.ones(5, np.float32))
        assert len(kv) == 5
        vals, found = kv.get(np.arange(1, 9, dtype=np.uint64))
        assert found[:5].all() and not found[5:].any()
        np.testing.assert_allclose(vals[:5], 1.0)

    def test_bucketed_signature_reuse(self, mesh8):
        # variable-length adds within one pow2 bucket share ONE compiled
        # signature (the retrace-churn fix the coalescer relies on)
        kv = KVTable(512, value_dim=0, name="cl_st_sig")
        reg = telemetry.registry()
        kv.add(np.arange(1, 6, dtype=np.uint64), np.ones(5, np.float32))
        c0 = reg.counter("profile.compiles", fn="kv.apply.cl_st_sig").value
        kv.add(np.arange(10, 17, dtype=np.uint64), np.ones(7, np.float32))
        kv.add(np.arange(20, 26, dtype=np.uint64), np.ones(6, np.float32))
        assert reg.counter("profile.compiles",
                           fn="kv.apply.cl_st_sig").value == c0


class TestGetAsync:
    def test_kv_get_async_device_future(self, mesh8):
        import jax
        kv = KVTable(512, value_dim=0, name="cl_ga")
        kv.add(np.array([3], np.uint64), np.ones(1, np.float32))
        h = kv.get_async(np.array([3, 4], np.uint64))
        vals, found = h.wait()
        assert isinstance(vals, jax.Array)      # device, not host
        assert float(vals[0]) == 1.0 and bool(found[0])
        assert not bool(found[1])

    def test_table_get_async_device_future(self, mesh8):
        import jax
        t = ArrayTable(8, "float32", name="cl_ga2")
        v = t.get_async().wait()
        assert isinstance(v, jax.Array)


class TestOverflowDeferral:
    def test_flag_without_is_ready_stays_pending(self, mesh8):
        kv = KVTable(64, value_dim=0, name="cl_over")
        kv.add(np.array([1], np.uint64), np.ones(1, np.float32))
        # a flag with no is_ready() must be DEFERRED by the add-path
        # poll (readiness unknowable without a blocking readback) ...
        kv._pending_over.append(np.int32(3))
        kv._poll_overflow()
        # (the real add's own entry is a (flag, buckets) pair and is
        # still in the list whenever its device flag is not ready yet —
        # on a loaded host that is most of the time — so normalize
        # before reading: comparing the raw entries made this test
        # depend on how fast the add above happened to finish)
        assert any(int(np.asarray(kv._over_entry(p)[0])) == 3
                   for p in kv._pending_over)
        # ... and surface at the next blocking table op
        with pytest.raises(RuntimeError, match="overflowed"):
            kv.wait()


class TestEnvKnobs:
    def test_coalesce_from_env(self, monkeypatch, mesh8):
        monkeypatch.delenv("MVTPU_COALESCE", raising=False)
        assert client.coalesce_from_env() == 0
        monkeypatch.setenv("MVTPU_COALESCE", "8")
        assert client.coalesce_from_env() == 8
        t = ArrayTable(8, "float32", name="cl_env1")
        buf = client.maybe_coalescing(t)
        assert isinstance(buf, client.CoalescingBuffer)
        assert buf.max_deltas == 8
        monkeypatch.setenv("MVTPU_COALESCE", "junk")
        assert client.coalesce_from_env() == 0

    def test_staleness_from_env(self, monkeypatch, mesh8):
        monkeypatch.delenv("MVTPU_STALENESS", raising=False)
        assert client.staleness_from_env() is None
        t = ArrayTable(8, "float32", name="cl_env2")
        assert client.maybe_cached_view(t) is None
        monkeypatch.setenv("MVTPU_STALENESS", "0")
        assert client.staleness_from_env() == 0
        view = client.maybe_cached_view(t)
        assert isinstance(view, client.CachedView)
        view.close()

    def test_sparse_logreg_coalesced_trains(self, monkeypatch, mesh8):
        from multiverso_tpu.apps.sparse_logreg import (
            SparseLogisticRegression, SparseLRConfig, synthetic_sparse)
        monkeypatch.setenv("MVTPU_COALESCE", "4")
        rows, y = synthetic_sparse(256, 100, 2, nnz=5, seed=3)
        app = SparseLogisticRegression(
            SparseLRConfig(num_classes=2, max_features=8, capacity=4096,
                           minibatch_size=32, learning_rate=0.5,
                           epochs=3),
            name="cl_env_slr")
        assert app._coalescer is not None
        app.train(rows, y)
        # predict flushes, so eval sees every delta (incl. the tail
        # partial group) — and SSP-delayed pushes still converge
        acc = app.accuracy(rows, y)
        assert acc > 0.6, f"train accuracy {acc:.3f}"
        assert len(app.table) > 0

    def test_logreg_cached_weights(self, monkeypatch, mesh8):
        from multiverso_tpu.apps.logreg import (LogisticRegression,
                                                LogRegConfig,
                                                synthetic_blobs)
        monkeypatch.setenv("MVTPU_STALENESS", "1")
        X, y = synthetic_blobs(128, 8, 2, seed=0)
        app = LogisticRegression(
            LogRegConfig(input_dim=8, num_classes=2, minibatch_size=32,
                         epochs=1), name="cl_env_lr")
        assert app._view is not None
        try:
            app.train(X, y)
            w, b = app.weights()        # served through the view
            assert w.shape == (8, 2)
            assert app.table.generation - app._view.generation <= 1
        finally:
            app._view.close()
