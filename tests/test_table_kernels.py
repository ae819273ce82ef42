"""Kernel engine (multiverso_tpu/ops/table_kernels.py): Pallas-vs-XLA
parity fuzz plus the MVTPU_KERNELS selection/fallback contract.

The Pallas kernels run INTERPRETED on the CPU test rig (the
ops/lda_sampler.py precedent) and must be BIT-EQUAL to the XLA path —
randomized keys, cross-batch duplicates, padding lanes, and bucket
overflow all compared on the final table triple, not just happy-path
lookups. Selection/fallback is asserted through the telemetry spine:
``kernels.fallbacks{reason=...}`` counters and the per-engine
``profile.calls{fn=...}`` dispatch counts.
"""

import functools
import zlib

import numpy as np
import pytest

import jax

from multiverso_tpu import core, telemetry
from multiverso_tpu.ops import table_kernels as tk
from multiverso_tpu.tables import (KVTable, MatrixTable,
                                   SparseMatrixTable, make_superstep)


@pytest.fixture()
def mesh1(devices):
    """Single-device mesh: the flat Pallas engine's shape (whole-batch
    grids, no shard_map wrapper — sharded meshes select the per-shard
    lane-sliced engine instead, see TestShardedParity)."""
    m = core.init(devices=devices[:1], data_parallel=1, model_parallel=1)
    yield m
    core.shutdown()


@pytest.fixture()
def mesh_mp2(devices):
    """Cheapest sharded mesh (model=2): two interpret-mode per-shard
    grids per dispatch — the sharded-engine workhorse fixture."""
    m = core.init(devices=devices[:2], data_parallel=1, model_parallel=2)
    yield m
    core.shutdown()


def _engine_pair(monkeypatch, build):
    """The same table under each engine: (xla_table, pallas_table)."""
    monkeypatch.setenv("MVTPU_KERNELS", "xla")
    tx = build("xla")
    monkeypatch.setenv("MVTPU_KERNELS", "pallas")
    tp = build("pallas")
    return tx, tp


# Two lowerings of one float expression may round differently: XLA
# fuses and reassociates adam's divide/sqrt/pow chain, the kernel
# evaluates it op by op. A few float32 ulps, fixed before looking at any
# run; everything whose arithmetic is exact in both (keys, slot choice,
# integer-valued adds) stays bit-equal.
FLOAT_RTOL = 8 * float(np.finfo(np.float32).eps)


def _assert_kv_equal(tx, tp, where="", rtol=0.0):
    assert np.array_equal(np.asarray(tx.keys), np.asarray(tp.keys)), \
        f"keys diverged {where}"
    np.testing.assert_allclose(
        np.asarray(tp.values), np.asarray(tx.values), rtol=rtol, atol=0,
        err_msg=f"values diverged {where}")
    for lx, lp in zip(jax.tree.leaves(tx.state),
                      jax.tree.leaves(tp.state)):
        np.testing.assert_allclose(
            np.asarray(lp), np.asarray(lx), rtol=rtol, atol=0,
            err_msg=f"updater state diverged {where}")


class TestKVParity:
    @pytest.mark.parametrize("updater,value_dim", [
        ("default", 0), ("sgd", 3), ("adagrad", 3), ("adam", 0),
    ])
    def test_probe_update_and_lookup_fuzz(self, mesh1, monkeypatch,
                                          updater, value_dim):
        """Randomized add/lookup stream: cross-batch duplicate keys
        (re-probe the matched slot), non-pow2 batch lengths (padding
        lanes), missing-key gets — final triple bit-equal."""
        # (hash() of a str is salted per process: seeding from it made
        # this test's data, and its outcome, differ run to run)
        rng = np.random.default_rng(
            zlib.crc32(f"{updater}/{value_dim}".encode()))
        tx, tp = _engine_pair(monkeypatch, lambda m: KVTable(
            2048, value_dim=value_dim, slots_per_bucket=8,
            updater=updater, mesh=mesh1,
            name=f"kvf_{updater}_{value_dim}_{m}"))
        assert tp._probe_update.engine == "pallas"
        assert tx._probe_update.engine == "xla"
        universe = np.arange(1, 400, dtype=np.uint64)
        for step in range(4):
            n = int(rng.integers(1, 25))       # non-pow2: padding lanes
            keys = rng.choice(universe, size=n, replace=False)
            shape = (n, value_dim) if value_dim else (n,)
            deltas = rng.integers(-4, 5, size=shape).astype(np.float32)
            tx.add(keys, deltas)
            tp.add(keys, deltas)
        tx.wait()
        tp.wait()
        rtol = FLOAT_RTOL if updater == "adam" else 0.0
        _assert_kv_equal(tx, tp, f"({updater}, {value_dim})", rtol)
        assert len(tx) == len(tp)
        # lookups: mix of present and missing keys, duplicates allowed
        q = rng.choice(np.arange(1, 600, dtype=np.uint64), size=19,
                       replace=True)
        vx, fx = tx.get(q)
        vp, fp = tp.get(q)
        assert np.array_equal(fx, fp)
        np.testing.assert_allclose(vp, vx, rtol=rtol, atol=0)

    def test_overflow_drops_whole_batch_on_both_engines(self, mesh1,
                                                        monkeypatch):
        """All-or-nothing: a batch mixing one matched update with
        overflowing new keys must leave the table UNTOUCHED (and raise)
        on both engines."""
        tx, tp = _engine_pair(monkeypatch, lambda m: KVTable(
            8, slots_per_bucket=1, updater="default", mesh=mesh1,
            name=f"kv_over_{m}"))
        b0 = tx._buckets_of(np.asarray([1], np.uint64))[0]
        same = [k for k in range(1, 8000)
                if tx._buckets_of(np.asarray([k], np.uint64))[0] == b0]
        assert len(same) >= 3
        k0 = np.asarray(same[:1], np.uint64)
        for t in (tx, tp):
            t.add(k0, np.asarray([5.0], np.float32), sync=True)
        _assert_kv_equal(tx, tp, "(pre-overflow)")
        batch = np.asarray(same[:3], np.uint64)   # k0 matches; 2 overflow
        d = np.asarray([1.0, 2.0, 3.0], np.float32)
        for t in (tx, tp):
            t.add(batch, d)
            with pytest.raises(RuntimeError, match="overflowed"):
                t.wait()
        _assert_kv_equal(tx, tp, "(post-overflow)")
        # the matched lane's update dropped with the batch
        vx, _ = tx.get(k0)
        assert vx[0] == 5.0

    def test_prepare_add_sorted_by_bucket(self, mesh1, monkeypatch):
        """The Pallas probe contract: prepare_add stable-sorts lanes by
        bucket, padding parked on the last bucket."""
        monkeypatch.setenv("MVTPU_KERNELS", "xla")
        t = KVTable(256, updater="default", mesh=mesh1, name="kv_sorted")
        keys = np.arange(1, 12, dtype=np.uint64)
        prep = t.prepare_add(keys, np.zeros(11, np.float32))
        buckets = np.asarray(prep.buckets)
        assert (np.diff(buckets) >= 0).all()
        assert (buckets[11:] == t.num_buckets - 1).all()


class TestRowParity:
    def test_gather_and_scatter_add_fuzz(self, mesh1, monkeypatch):
        rng = np.random.default_rng(3)
        tx, tp = _engine_pair(monkeypatch, lambda m: MatrixTable(
            60, 12, updater="default", mesh=mesh1, name=f"rows_{m}"))
        assert tp._scatter_add.engine == "pallas"
        for _ in range(3):
            n = int(rng.integers(1, 40))
            ids = rng.integers(0, 60, size=n)          # duplicates ok
            deltas = rng.integers(-5, 6, size=(n, 12)).astype(np.float32)
            tx.add_rows(ids, deltas)
            tp.add_rows(ids, deltas)
        assert np.array_equal(tx.get(), tp.get())
        q = rng.integers(0, 60, size=13)               # duplicates ok
        assert np.array_equal(tx.get_rows(q), tp.get_rows(q))

    def test_sgd_scatter_parity(self, mesh1, monkeypatch):
        tx, tp = _engine_pair(monkeypatch, lambda m: MatrixTable(
            20, 5, updater="sgd", mesh=mesh1, name=f"rows_sgd_{m}"))
        ids = np.asarray([3, 3, 7, 0])
        deltas = np.ones((4, 5), np.float32)
        tx.add_rows(ids, deltas)
        tp.add_rows(ids, deltas)
        assert np.array_equal(tx.get(), tp.get())


class TestCOOParity:
    @pytest.mark.parametrize("dtype,num_cols,tiled", [
        ("int32", 40, False), ("float32", 40, False),
        ("int32", 256, True),
    ])
    def test_coo_scatter_add_fuzz(self, mesh1, monkeypatch, dtype,
                                  num_cols, tiled):
        rng = np.random.default_rng(num_cols)
        tx, tp = _engine_pair(monkeypatch, lambda m: SparseMatrixTable(
            30, num_cols, dtype=dtype, updater="default", tiled=tiled,
            mesh=mesh1, name=f"coo_{dtype}_{num_cols}_{m}"))
        assert tp._coo_scatter_add.engine == "pallas"
        for _ in range(3):
            n = int(rng.integers(1, 50))
            rows = rng.integers(0, 30, size=n)
            cols = rng.integers(0, num_cols, size=n)
            vals = rng.integers(-4, 5, size=n).astype(dtype)
            tx.add_sparse(rows, cols, vals)      # duplicate (r,c) ok
            tp.add_sparse(rows, cols, vals)
        assert np.array_equal(tx.get(), tp.get())

    def test_tiled_row_path_parity(self, mesh1, monkeypatch):
        """Tiled storage re-registers gather/scatter with tiles=C/128."""
        rng = np.random.default_rng(11)
        tx, tp = _engine_pair(monkeypatch, lambda m: SparseMatrixTable(
            24, 256, dtype="int32", updater="default", tiled=True,
            mesh=mesh1, name=f"coo_rows_{m}"))
        ids = rng.integers(0, 24, size=9)
        deltas = rng.integers(0, 7, size=(9, 256)).astype(np.int32)
        tx.add_rows(ids, deltas)
        tp.add_rows(ids, deltas)
        assert np.array_equal(tx.get(), tp.get())
        q = rng.integers(0, 24, size=5)
        assert np.array_equal(tx.get_rows(q), tp.get_rows(q))


class TestSelection:
    def _fallbacks(self, name, reason):
        return telemetry.registry().counter(
            "kernels.fallbacks", kernel=name, reason=reason).value

    def test_auto_on_cpu_falls_back_counted(self, mesh1, monkeypatch):
        monkeypatch.setenv("MVTPU_KERNELS", "auto")
        name = "kv.apply.kv_auto_cpu"
        before = self._fallbacks(name, "cpu")
        t = KVTable(64, updater="default", mesh=mesh1, name="kv_auto_cpu")
        assert t._probe_update.engine == "xla"
        assert self._fallbacks(name, "cpu") == before + 1

    def test_explicit_xla_no_fallback_count(self, mesh1, monkeypatch):
        monkeypatch.setenv("MVTPU_KERNELS", "xla")
        name = "kv.apply.kv_xla_mode"
        before = self._fallbacks(name, "cpu")
        t = KVTable(64, updater="default", mesh=mesh1, name="kv_xla_mode")
        assert t._probe_update.engine == "xla"
        assert self._fallbacks(name, "cpu") == before

    def test_sharded_mesh_selects_sharded_pallas(self, mesh8,
                                                 monkeypatch):
        """The acceptance criterion: on a dp×mp mesh every table kernel
        dispatches Pallas under shard_map — reason=sharded stays ZERO."""
        monkeypatch.setenv("MVTPU_KERNELS", "pallas")
        name = "kv.apply.kv_sharded"
        before = self._fallbacks(name, "sharded")
        t = KVTable(64, updater="default", mesh=mesh8, name="kv_sharded")
        assert t._probe_update.engine == "pallas"
        assert t._probe_update.layout == "sharded"
        assert t._lookup.engine == "pallas"
        assert t._lookup.layout == "sharded"
        assert self._fallbacks(name, "sharded") == before
        # ...and works end-to-end on the sharded mesh
        t.add(np.asarray([3], np.uint64), np.asarray([1.0], np.float32),
              sync=True)
        assert len(t) == 1

    def test_sharded_no_factory_counts_reason_sharded(self, mesh_mp2,
                                                      monkeypatch):
        """A sharded mesh with no sharded Pallas factory keeps XLA under
        the ORIGINAL reason label."""
        monkeypatch.setenv("MVTPU_KERNELS", "pallas")
        before = self._fallbacks("unit.nosharded", "sharded")
        eng = tk.select_kernel("unit.nosharded", xla=lambda: "x",
                               pallas=lambda: (lambda: "p"),
                               mesh=mesh_mp2)
        assert eng.engine == "xla" and eng.layout == "flat"
        assert self._fallbacks("unit.nosharded", "sharded") == before + 1

    def test_unsupported_layout_reason_split(self, mesh_mp2,
                                             monkeypatch):
        """A sharded factory refusing the layout gets its OWN reason
        label (satellite: sharded vs sharded_unsupported_layout)."""
        monkeypatch.setenv("MVTPU_KERNELS", "pallas")

        def bad_factory():
            raise tk.UnsupportedShardingLayout("lead % shards != 0")

        before = self._fallbacks("unit.badlayout",
                                 "sharded_unsupported_layout")
        eng = tk.select_kernel("unit.badlayout", xla=lambda: "x",
                               pallas=lambda: (lambda: "p"),
                               pallas_sharded=bad_factory,
                               mesh=mesh_mp2)
        assert eng.engine == "xla" and eng.layout == "flat"
        assert self._fallbacks("unit.badlayout",
                               "sharded_unsupported_layout") == before + 1

    def test_fallback_log_latched_per_mesh_shape(self, devices,
                                                 monkeypatch):
        """Satellite: the fallback log latch keys on (kernel, reason,
        mesh shape) — a second mesh SHAPE logs its own line (with the
        mesh axis names), a repeat of the same shape stays silent, and
        the counter never latches."""
        monkeypatch.setenv("MVTPU_KERNELS", "pallas")
        logged = []
        monkeypatch.setattr(tk.log, "warn",
                            lambda fmt, *a: logged.append(fmt % a))
        name = "unit.latch"
        before = self._fallbacks(name, "sharded")
        shapes = [(1, 2), (2, 2), (1, 2)]       # third repeats the first
        lines = []
        for dp, mp in shapes:
            m = core.init(devices=devices[:dp * mp], data_parallel=dp,
                          model_parallel=mp)
            logged.clear()
            tk.select_kernel(name, xla=lambda: "x",
                             pallas=lambda: (lambda: "p"), mesh=m)
            lines.append([s for s in logged if "falling back" in s])
            core.shutdown()
        assert len(lines[0]) == 1
        assert "data=1" in lines[0][0] and "model=2" in lines[0][0]
        assert len(lines[1]) == 1               # new shape → new line
        assert "data=2" in lines[1][0]
        assert len(lines[2]) == 0               # repeat shape → latched
        assert self._fallbacks(name, "sharded") == before + 3

    def test_pallas_dispatches_counted_on_pallas_profile(self, mesh1,
                                                         monkeypatch):
        """The acceptance telemetry: under MVTPU_KERNELS=pallas the
        interpreted kernels carry the dispatches
        (profile.calls{fn=....pallas}), not the XLA path."""
        monkeypatch.setenv("MVTPU_KERNELS", "pallas")
        t = KVTable(64, updater="default", mesh=mesh1, name="kv_pdisp")
        reg = telemetry.registry()
        xla_calls = reg.counter("profile.calls", fn="kv.apply.kv_pdisp")
        pal_calls = reg.counter("profile.calls",
                                fn="kv.apply.kv_pdisp.pallas")
        x0, p0 = xla_calls.value, pal_calls.value
        t.add(np.asarray([1, 2], np.uint64),
              np.asarray([1.0, 2.0], np.float32), sync=True)
        assert pal_calls.value == p0 + 1
        assert xla_calls.value == x0

    def test_engine_failure_raises_and_selection_stays(self, mesh1,
                                                       monkeypatch):
        """An accelerator-shaped failure (Mosaic refusing a kernel at
        its first call) reaches the caller: the engine is not swapped,
        XLA is not called, nothing is counted as a fallback."""
        monkeypatch.setenv("MVTPU_KERNELS", "pallas")
        calls = {"pallas": 0, "xla": 0}

        def bad_pallas(*a):
            calls["pallas"] += 1
            raise RuntimeError("Mosaic failed to compile TPU kernel")

        def xla(*a):
            calls["xla"] += 1
            return "xla-result"

        reg = telemetry.registry()
        eng = tk.select_kernel("unit.kernel", xla=xla,
                               pallas=lambda: bad_pallas, mesh=mesh1)
        assert eng.engine == "pallas"
        for _ in range(2):
            with pytest.raises(RuntimeError, match="Mosaic failed"):
                eng(1, 2)
        assert eng.engine == "pallas"
        assert calls == {"pallas": 2, "xla": 0}
        assert not [k for k in reg.snapshot()["counters"]
                    if "kernel=unit.kernel" in k and "reason=error" in k]

    def test_build_failure_raises(self, mesh1, mesh_mp2, monkeypatch):
        """A factory that fails to BUILD raises too — only
        UnsupportedShardingLayout is a decision."""
        monkeypatch.setenv("MVTPU_KERNELS", "pallas")

        def bad_factory():
            raise ValueError("block shape refused")

        with pytest.raises(ValueError, match="block shape refused"):
            tk.select_kernel("unit.badbuild", xla=lambda: "x",
                             pallas=bad_factory, mesh=mesh1)
        with pytest.raises(ValueError, match="block shape refused"):
            tk.select_kernel("unit.badbuild", xla=lambda: "x",
                             pallas=lambda: (lambda: "p"),
                             pallas_sharded=bad_factory, mesh=mesh_mp2)

    def test_selection_keys_on_the_mesh_platform(self, mesh1,
                                                 monkeypatch):
        """`auto` and interpret mode read the MESH's platform, not the
        process default backend — one test, in one place
        (core.platform), shared with LightLDA."""
        monkeypatch.setenv("MVTPU_KERNELS", "auto")
        assert tk.interpret_mode(mesh1) is True
        monkeypatch.setattr(core, "platform", lambda m=None: "tpu")
        assert tk.interpret_mode(mesh1) is False
        eng = tk.select_kernel("unit.platform", xla=lambda: "x",
                               pallas=lambda: (lambda: "p"), mesh=mesh1)
        assert eng.engine == "pallas" and eng() == "p"

    def test_unknown_mode_is_auto(self, monkeypatch):
        monkeypatch.setenv("MVTPU_KERNELS", "turbo")
        assert tk.kernel_mode() == "auto"


class TestShardedParity:
    """Per-shard lane-sliced Pallas engines vs the flat XLA oracle on
    real multi-device CPU meshes (dp-only, mp-only, dp×mp). The XLA
    table runs the FLAT whole-batch path (GSPMD-partitioned), so these
    compare two genuinely different lowerings; parity must be bit-exact
    on the logical contents."""

    def test_kv_sharded_fuzz_and_dispatch(self, mesh8, monkeypatch):
        """dp×mp mesh: randomized add/lookup stream with cross-batch
        duplicate keys landing on different shards; every dispatch must
        hit the sharded Pallas engine (profile.calls{fn=....pallas})
        with reason=sharded at zero."""
        rng = np.random.default_rng(17)
        tx, tp = _engine_pair(monkeypatch, lambda m: KVTable(
            512, value_dim=3, slots_per_bucket=8, updater="adagrad",
            mesh=mesh8, name=f"kvsh_{m}"))
        assert tp._probe_update.layout == "sharded"
        assert tx._probe_update.layout == "flat"
        reg = telemetry.registry()
        pal_calls = reg.counter("profile.calls",
                                fn="kv.apply.kvsh_pallas.pallas")
        shard_fb = reg.counter("kernels.fallbacks",
                               kernel="kv.apply.kvsh_pallas",
                               reason="sharded")
        p0, f0 = pal_calls.value, shard_fb.value
        universe = np.arange(1, 300, dtype=np.uint64)
        steps = 4
        for _ in range(steps):
            n = int(rng.integers(1, 20))       # non-pow2: padding lanes
            keys = rng.choice(universe, size=n, replace=False)
            deltas = rng.integers(-4, 5, size=(n, 3)).astype(np.float32)
            tx.add(keys, deltas)
            tp.add(keys, deltas)
        tx.wait()
        tp.wait()
        _assert_kv_equal(tx, tp, "(sharded adagrad)")
        assert len(tx) == len(tp)
        q = rng.choice(np.arange(1, 600, dtype=np.uint64), size=19,
                       replace=True)
        vx, fx = tx.get(q)
        vp, fp = tp.get(q)
        assert np.array_equal(fx, fp)
        assert np.array_equal(vx, vp)
        assert pal_calls.value == p0 + steps   # every add went Pallas
        assert shard_fb.value == f0            # reason=sharded stayed 0

    def test_kv_sharded_overflow_atomicity(self, mesh_mp2, monkeypatch):
        """A bucket overflow on ONE shard must drop the whole batch on
        EVERY shard (the global n_over gates each shard's commit)."""
        tx, tp = _engine_pair(monkeypatch, lambda m: KVTable(
            64, slots_per_bucket=1, updater="default", mesh=mesh_mp2,
            name=f"kvsho_{m}"))
        assert tp._probe_update.layout == "sharded"
        bks = np.asarray(tx._buckets_of(np.arange(1, 4000,
                                                  dtype=np.uint64)))
        b0 = bks[0]
        bps = tx.num_buckets // 2
        same = 1 + np.flatnonzero(bks == b0)        # same bucket as key 1
        other = 1 + np.flatnonzero(bks // bps != b0 // bps)  # other shard
        assert len(same) >= 3 and len(other) >= 2
        for t in (tx, tp):
            t.add(np.asarray([same[0], other[0]], np.uint64),
                  np.asarray([5.0, 9.0], np.float32), sync=True)
        # batch: one matched lane + 2 overflowing + a fine other-shard key
        batch = np.asarray(list(same[:3]) + [other[1]], np.uint64)
        d = np.asarray([1.0, 2.0, 3.0, 4.0], np.float32)
        for t in (tx, tp):
            t.add(batch, d)
            with pytest.raises(RuntimeError, match="overflowed"):
                t.wait()
        _assert_kv_equal(tx, tp, "(sharded post-overflow)")
        vq = np.asarray([same[0], other[0], other[1]], np.uint64)
        for t in (tx, tp):
            v, f = t.get(vq)
            assert v[0] == 5.0 and v[1] == 9.0     # pre-batch intact
            assert not f[2]       # other-shard lane dropped with batch
            assert len(t) == 2

    @pytest.mark.parametrize("updater", ["default", "sgd"])
    def test_rows_sharded_fuzz(self, mesh_mp2, monkeypatch, updater):
        rng = np.random.default_rng(23)
        tx, tp = _engine_pair(monkeypatch, lambda m: MatrixTable(
            60, 12, updater=updater, mesh=mesh_mp2,
            name=f"rowsh_{updater}_{m}"))
        assert tp._scatter_add.layout == "sharded"
        assert tp._gather_rows.layout == "sharded"
        for _ in range(3):
            n = int(rng.integers(1, 40))
            ids = rng.integers(0, 60, size=n)      # duplicates ok
            deltas = rng.integers(-5, 6, size=(n, 12)).astype(np.float32)
            tx.add_rows(ids, deltas)
            tp.add_rows(ids, deltas)
        assert np.array_equal(tx.get(), tp.get())
        q = rng.integers(0, 60, size=13)           # duplicates ok
        assert np.array_equal(tx.get_rows(q), tp.get_rows(q))

    @pytest.mark.parametrize("num_cols,tiled", [(40, False),
                                                (256, True)])
    def test_coo_sharded_fuzz(self, mesh_mp2, monkeypatch, num_cols,
                              tiled):
        rng = np.random.default_rng(num_cols)
        tx, tp = _engine_pair(monkeypatch, lambda m: SparseMatrixTable(
            30, num_cols, dtype="int32", updater="default", tiled=tiled,
            mesh=mesh_mp2, name=f"coosh_{num_cols}_{m}"))
        assert tp._coo_scatter_add.layout == "sharded"
        for _ in range(3):
            n = int(rng.integers(1, 50))
            rows = rng.integers(0, 30, size=n)
            cols = rng.integers(0, num_cols, size=n)
            vals = rng.integers(-4, 5, size=n).astype(np.int32)
            tx.add_sparse(rows, cols, vals)        # duplicate (r,c) ok
            tp.add_sparse(rows, cols, vals)
        assert np.array_equal(tx.get(), tp.get())
        ix, cx, vx = tx.get_rows_sparse([0, 5, 7])
        ip, cp, vp = tp.get_rows_sparse([0, 5, 7])
        assert np.array_equal(ix, ip)
        assert np.array_equal(cx, cp)
        assert np.array_equal(vx, vp)

    def test_tiled_rows_sharded_parity(self, mesh_mp2, monkeypatch):
        """Tiled storage's sharded re-registration (tiles=C/128)."""
        rng = np.random.default_rng(29)
        tx, tp = _engine_pair(monkeypatch, lambda m: SparseMatrixTable(
            24, 256, dtype="int32", updater="default", tiled=True,
            mesh=mesh_mp2, name=f"coosh_rows_{m}"))
        assert tp._scatter_add.layout == "sharded"
        ids = rng.integers(0, 24, size=9)
        deltas = rng.integers(0, 7, size=(9, 256)).astype(np.int32)
        tx.add_rows(ids, deltas)
        tp.add_rows(ids, deltas)
        assert np.array_equal(tx.get(), tp.get())
        q = rng.integers(0, 24, size=5)
        assert np.array_equal(tx.get_rows(q), tp.get_rows(q))

    def test_superstep_sharded_functional_kernels(self, mesh8,
                                                  monkeypatch):
        """A fused body's functional gather/scatter kernels run the
        masked-lane shard_map form under kernel_mesh_scope on a dp×mp
        mesh and match the XLA oracle."""
        from multiverso_tpu.tables import superstep as ss

        def build(mode):
            monkeypatch.setenv("MVTPU_KERNELS", mode)
            t = MatrixTable(48, 8, updater="default", mesh=mesh8,
                            name=f"sssh_{mode}")

            def body(params, states, locals_, options, ids, deltas,
                     rows, cols, vals):
                (p,) = params
                g = ss.gather_rows(p, ids)
                p = ss.row_scatter_add(p, ids, g * 0.5 + deltas)
                p = ss.coo_scatter_add(p, rows, cols, vals)
                return (p,), states, locals_, g.sum()

            return t, make_superstep([t], body, name=f"sssh_{mode}")

        rng = np.random.default_rng(31)
        ids = rng.integers(0, 48, size=16).astype(np.int32)
        deltas = rng.normal(size=(16, 8)).astype(np.float32)
        rows = rng.integers(0, 48, size=16).astype(np.int32)
        cols = rng.integers(0, 8, size=16).astype(np.int32)
        vals = rng.integers(-3, 4, size=16).astype(np.float32)
        outs = {}
        for mode in ("xla", "pallas"):
            t, step = build(mode)
            t.add_rows(ids[:4], deltas[:4], sync=True)
            args = [core.place(a, mesh=t.mesh)
                    for a in (ids, deltas, rows, cols, vals)]
            _, aux = step((), *args)
            t.wait()
            outs[mode] = (t.get(), float(aux))
        assert np.array_equal(outs["xla"][0], outs["pallas"][0])
        # g.sum() is a float reduction over a gather two lowerings are
        # free to order differently (it was compared with == and failed
        # every run: -2.2081804 vs -2.2081809)
        assert outs["pallas"][1] == pytest.approx(
            outs["xla"][1], rel=FLOAT_RTOL * 16)


class TestSuperstepBodies:
    def test_fused_body_picks_up_engine_kernels(self, mesh1,
                                                monkeypatch):
        """A fused superstep body using the re-exported
        gather_rows/row_scatter_add runs the Pallas engine in-trace and
        matches the plain-XLA oracle."""
        from multiverso_tpu.tables import superstep as ss

        def build(mode):
            monkeypatch.setenv("MVTPU_KERNELS", mode)
            t = MatrixTable(32, 8, updater="default", mesh=mesh1,
                            name=f"ss_{mode}")

            def body(params, states, locals_, options, ids, deltas):
                (p,) = params
                rows = ss.gather_rows(p, ids)
                p = ss.row_scatter_add(p, ids, deltas + 0 * rows)
                return (p,), states, locals_, rows.sum()

            step = make_superstep([t], body, name=f"ss_{mode}")
            return t, step

        ids = np.asarray([1, 1, 5, 30], np.int32)
        deltas = np.arange(32, dtype=np.float32).reshape(4, 8)
        outs = {}
        for mode in ("xla", "pallas"):
            t, step = build(mode)
            _, aux = step((), core.place(ids, mesh=t.mesh),
                          core.place(deltas, mesh=t.mesh))
            t.wait()
            outs[mode] = (t.get(), float(aux))
        assert np.array_equal(outs["xla"][0], outs["pallas"][0])
        assert outs["pallas"][1] == pytest.approx(
            outs["xla"][1], rel=FLOAT_RTOL * 16)


def _tpu_topology():
    """A described (not attached) v5e 2x2, or None where libtpu cannot
    describe one — then only the cross-lowering half runs."""
    try:
        from jax.experimental import topologies
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception:       # noqa: BLE001 — any refusal means "no"
        return None


def _kernel_cases():
    """(id, build(mesh) -> (fn, arg shapes+dtypes+specs)) for every
    Pallas kernel `auto` can select on a TPU: the five table kernels
    (flat, masked, sharded), the in-trace functional forms, and the
    two LDA sampler kernels and the latent-attention kernels — at
    chip_smoke.py's widths."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from multiverso_tpu.ops import lda_sampler as ls
    from multiverso_tpu.updaters import AddOption, get_updater

    f32, i32, u32, b1 = jnp.float32, jnp.int32, jnp.uint32, jnp.bool_
    M = core.MODEL_AXIS
    n, L, sh = 2048, 1024, 2
    cases = []

    def add(name, build):
        # flat kernels compile for ONE device (a bare pallas_call has no
        # partitioning rule); the shard_map forms for a 2x2 mesh
        cases.append(pytest.param(build, name.endswith("-sharded"),
                                  id=name))

    for tag, R, C, T, dt in (("flat", 10_002, 100, 0, f32),
                             ("tiled", 50_002, 1024, 8, i32)):
        pshape = (R, T, 128) if T else (R, C)
        pspec = P(M, None, None) if T else P(M, None)
        kw = dict(num_cols=C, tiles=T, interpret=False)
        add(f"gather-{tag}", lambda m, kw=kw, pshape=pshape, dt=dt: (
            tk.build_row_gather(**kw),
            [(pshape, dt, P()), ((n,), i32, P())]))
        for masked in (False, True):
            valid = [((n,), b1, P())] if masked else []
            add(f"scatter-{tag}{'-masked' if masked else ''}",
                lambda m, kw=kw, pshape=pshape, dt=dt, C=C,
                masked=masked, valid=valid: (
                    tk.build_row_scatter_add(**kw, masked=masked),
                    [(pshape, dt, P()), ((n,), i32, P()),
                     ((n, C), dt, P())] + valid))
            add(f"coo-{tag}{'-masked' if masked else ''}",
                lambda m, kw=kw, pshape=pshape, dt=dt, masked=masked,
                valid=valid: (
                    tk.build_coo_scatter_add(**kw, masked=masked),
                    [(pshape, dt, P()), ((n,), i32, P()),
                     ((n,), i32, P()), ((n,), dt, P())] + valid))
        skw = dict(axis=M, lead=R)
        lanes = ((sh, L), i32, P(M, None))
        vmask = ((sh, L), b1, P(M, None))
        add(f"gather-{tag}-sharded",
            lambda m, kw=kw, skw=skw, pshape=pshape, pspec=pspec, dt=dt: (
                tk.build_row_gather_sharded(**kw, **skw, mesh=m),
                [(pshape, dt, pspec), lanes, ((n,), i32, P())]))
        add(f"scatter-{tag}-sharded",
            lambda m, kw=kw, skw=skw, pshape=pshape, pspec=pspec, dt=dt,
            C=C: (tk.build_row_scatter_add_sharded(**kw, **skw, mesh=m),
                  [(pshape, dt, pspec), lanes,
                   ((sh, L, C), dt, P(M, None, None)), vmask]))
        add(f"coo-{tag}-sharded",
            lambda m, kw=kw, skw=skw, pshape=pshape, pspec=pspec, dt=dt: (
                tk.build_coo_scatter_add_sharded(**kw, **skw, mesh=m),
                [(pshape, dt, pspec), lanes, lanes,
                 ((sh, L), dt, P(M, None)), vmask]))

    NB, SL = 8192, 8
    opt = [((), f32, P())] * 4 + [((), i32, P())]
    for vd, updater in ((0, "default"), (8, "adagrad"), (0, "adam")):
        vshape = (NB, SL, vd) if vd else (NB, SL)
        vspec = P(M, None, None) if vd else P(M, None)
        upd = get_updater(updater)
        state = jax.eval_shape(upd.init_state,
                               jax.ShapeDtypeStruct(vshape, f32))
        nstate = len(jax.tree.leaves(state))
        kw = dict(slots=SL, value_dim=vd, interpret=False)
        ukw = dict(updater=upd, state_template=state)
        skw = dict(axis=M, num_buckets=NB)
        kshape = ((NB, SL, 2), u32)
        tag = f"{updater}-vdim{vd}"

        def unflatten(fn, nstate=nstate, state=state):
            # positional wrapper: (keys, vals, *state, ..., *option)
            def call(keys, vals, *rest):
                st = jax.tree.unflatten(jax.tree.structure(state),
                                        rest[:nstate])
                rest = rest[nstate:]
                return fn(keys, vals, st, *rest[:4],
                          AddOption(*rest[4:]))
            return call

        if updater != "adam":
            add(f"kv-lookup-{tag}", lambda m, kw=kw, vshape=vshape: (
                tk.build_kv_lookup(**kw, default_value=0.0),
                [kshape + (P(),), (vshape, f32, P()),
                 ((n, 2), u32, P()), ((n,), i32, P())]))
            add(f"kv-lookup-{tag}-sharded",
                lambda m, kw=kw, skw=skw, vshape=vshape, vspec=vspec: (
                    tk.build_kv_lookup_sharded(**kw, **skw, mesh=m,
                                               default_value=0.0),
                    [kshape + (P(M, None, None),), (vshape, f32, vspec),
                     ((sh, L, 2), u32, P(M, None, None)),
                     ((sh, L), i32, P(M, None)), ((n,), i32, P())]))
        dshape = (n, vd) if vd else (n,)
        add(f"kv-apply-{tag}",
            lambda m, kw=kw, ukw=ukw, vshape=vshape, nstate=nstate,
            dshape=dshape, unflatten=unflatten: (
                unflatten(tk.build_kv_probe_update(**kw, **ukw)),
                [kshape + (P(),), (vshape, f32, P())]
                + [(vshape, f32, P())] * nstate
                + [((n,), i32, P()), ((n, 2), u32, P()),
                   (dshape, f32, P()), ((n,), b1, P())] + opt))
        sdshape = (sh, L, vd) if vd else (sh, L)
        add(f"kv-apply-{tag}-sharded",
            lambda m, kw=kw, ukw=ukw, skw=skw, vshape=vshape,
            vspec=vspec, nstate=nstate, sdshape=sdshape,
            unflatten=unflatten: (
                unflatten(tk.build_kv_probe_update_sharded(
                    **kw, **ukw, **skw, mesh=m)),
                [kshape + (P(M, None, None),), (vshape, f32, vspec)]
                + [(vshape, f32, vspec)] * nstate
                + [((sh, L), i32, P(M, None)),
                   ((sh, L, 2), u32, P(M, None, None)),
                   (sdshape, f32, P(M, *([None] * (len(sdshape) - 1)))),
                   ((sh, L), b1, P(M, None))] + opt))

    def functional(m):
        def body(p, ids, deltas, cols, vals):
            with tk.kernel_mesh_scope(m, M):
                g = tk.gather_rows(p, ids)
                p = tk.row_scatter_add(p, ids, g + deltas)
                return tk.coo_scatter_add(p, ids, cols, vals)
        return body, [((10_002, 100), f32, P(M, None)), ((n,), i32, P()),
                      ((n, 100), f32, P()), ((n,), i32, P()),
                      ((n,), f32, P())]
    add("functional-forms-sharded", functional)

    # LightLDA samplers at measure_lda's widths: K=1024 (C=8), 512-token
    # blocks, 16 docs a block; W from the bf16 mirror (production) and
    # from the int32 master (the operands that overflow Mosaic's default
    # 16 MiB scoped VMEM)
    C, TB, MAXD, NBK = 8, 512, 16, 4
    B = NBK * TB
    tok = [((B,), i32, P())] * 3 + [((B,), f32, P())] * 2
    for wdt, wtag in ((jnp.bfloat16, "bf16"), (i32, "i32")):
        w3 = ((B, C, 128), wdt, P())
        sinv = ((C, 128), f32, P())
        add(f"lda-docblock-{wtag}", lambda m, w3=w3, sinv=sinv: (
            functools.partial(ls.gibbs_sample_docblock, alpha=0.05,
                              beta=0.01, tb=TB),
            [((NBK, MAXD, C, 128), jnp.int16, P()), w3, sinv] + tok))
        add(f"lda-docblock-build-{wtag}", lambda m, w3=w3, sinv=sinv: (
            functools.partial(ls.gibbs_sample_docblock_build, alpha=0.05,
                              beta=0.01, tb=TB, maxd=MAXD),
            [w3, sinv] + tok))

    # the latent-attention kernels (forward, and the backward kernel
    # behind jax.grad) at the language-model cell's shape: 4,096-token
    # sequences, 16 heads, score depth 128 + 64, value depth 128, blocks
    # of 512 — the head's whole query gradient has to fit VMEM
    from multiverso_tpu.ops import latent_attention as mla
    bf16, S, H = jnp.bfloat16, 4096, 16

    def attention(grad):
        def attend(q_nope, q_pe, k_nope, k_pe, v, doc):
            return mla.attend(q_nope, q_pe, k_nope, k_pe, v, doc,
                              scale=192 ** -0.5, block=512, interpret=False)

        def loss(*a):
            return jnp.sum(attend(*a).astype(f32))
        return (jax.grad(loss, argnums=(0, 1, 2, 3, 4)) if grad else attend,
                [((2, S, H, 128), bf16, P()), ((2, S, H, 64), bf16, P()),
                 ((2, S, H, 128), bf16, P()), ((2, S, 64), bf16, P()),
                 ((2, S, H, 128), bf16, P()), ((2, S), i32, P())])
    add("latent-attention-forward", lambda m: attention(False))
    add("latent-attention-backward", lambda m: attention(True))

    # the same kernels without rotary operands at the hybrid cell's
    # shape: 30 heads with keys and values of depth 128
    def plain_attention(grad):
        def attend(q, k, v, doc):
            return mla.attend_heads(q, k, v, doc, scale=128 ** -0.5,
                                    block=512, interpret=False)

        def loss(*a):
            return jnp.sum(attend(*a).astype(f32))
        return (jax.grad(loss, argnums=(0, 1, 2)) if grad else attend,
                [((1, S, 30, 128), bf16, P())] * 3 + [((1, S), i32, P())])
    add("plain-attention-forward", lambda m: plain_attention(False))
    add("plain-attention-backward", lambda m: plain_attention(True))

    # ... and grouped: 32 query heads on 8 key-value heads of depth 64
    # (padded to whole lanes), the third language-model cell's shape; a
    # key block's gradients come out one a QUERY head in float32
    def grouped_attention(grad):
        def attend(q, k, v, doc):
            return mla.attend_heads(q, k, v, doc, scale=64 ** -0.5,
                                    block=512, interpret=False)

        def loss(*a):
            return jnp.sum(attend(*a).astype(f32))
        return (jax.grad(loss, argnums=(0, 1, 2)) if grad else attend,
                [((4, S, 32, 64), bf16, P())] + [((4, S, 8, 64), bf16, P())]
                * 2 + [((4, S), i32, P())])
    add("grouped-attention-forward", lambda m: grouped_attention(False))
    add("grouped-attention-backward", lambda m: grouped_attention(True))

    # the chunked gated delta rule at the published widths — 30 heads of
    # 96 x 192 (no multiple of 128 lanes), chunks of 64: the chunk-local
    # terms and the triangular solve are blocked XLA, the state crosses
    # the chunks in two Mosaic kernels (forward; backward under jax.grad)
    from multiverso_tpu.ops import gated_delta as gdn
    shape = gdn.GatedDeltaShape(30, 96, 192, 4, True, 1e-6, 64, "bfloat16")

    def recurrence(grad):
        def recur(qkv, g, beta, doc):
            return gdn.recur(qkv, g, beta, doc, shape, interpret=False)

        def grads(qkv, g, beta, doc):
            return jax.grad(lambda *a: jnp.sum(recur(*a, doc)),
                            argnums=(0, 1, 2))(qkv, g, beta)
        return (grads if grad else recur,
                [((1, S, shape.conv_width), f32, P()),
                 ((1, S, 30), f32, P()), ((1, S, 30), f32, P()),
                 ((1, S), i32, P())])
    add("gated-delta-recurrence-forward", lambda m: recurrence(False))
    add("gated-delta-recurrence-backward", lambda m: recurrence(True))

    # the distinct-row writer at the word2vec cell's shape: 4,096 pairs
    # x (1 + 5) rows a step into 3M x 300 held as [3,000,008, 384], the
    # update rows formed from g and v in sorted order as the step does
    from multiverso_tpu.ops import distinct_rows

    def write_rows(table, ids, g, v):
        return distinct_rows.add_rows(
            table, ids, lambda lanes: -jnp.take(g, lanes)[:, None]
            * jnp.take(v, lanes // 6, axis=0), interpret=False)
    add("distinct-rows-add", lambda m: (
        write_rows, [(distinct_rows.aligned_shape(3_000_001, 300), f32, P()),
                     ((24_576,), i32, P()), ((24_576,), f32, P()),
                     ((4096, 384), f32, P())]))
    return cases


class TestTpuLowering:
    """The guard that needs no chip: every Pallas kernel `auto` can
    select on a TPU must get through the Pallas->Mosaic lowering for
    the "tpu" platform on this CPU rig (block shapes, memory spaces),
    and — where libtpu can describe a v5e — through Mosaic's own
    compile (layouts, shape casts, SMEM and scoped-VMEM budgets). At
    the parent of PR 21 every table kernel failed the first half and
    every LDA kernel the second; the chip had never been asked."""

    @pytest.fixture(scope="class")
    def topology(self):
        return _tpu_topology()

    @pytest.mark.parametrize("build,sharded", _kernel_cases())
    def test_lowers_and_compiles_for_tpu(self, build, sharded, topology,
                                         monkeypatch):
        from jax.sharding import Mesh, NamedSharding
        monkeypatch.setenv("MVTPU_KERNELS", "pallas")
        monkeypatch.setattr(core, "platform", lambda m=None: "tpu")
        dims = (2, 2) if sharded else (1, 1)

        def traced(devices):
            mesh = Mesh(np.asarray(devices[:dims[0] * dims[1]])
                        .reshape(dims), (core.DATA_AXIS, core.MODEL_AXIS))
            fn, specs = build(mesh)
            return jax.jit(fn).trace(*[
                jax.ShapeDtypeStruct(shape, dt,
                                     sharding=NamedSharding(mesh, sp))
                for shape, dt, sp in specs])

        traced(jax.devices("cpu")).lower(lowering_platforms=("tpu",))
        if topology is not None:
            traced(topology.devices).lower().compile()


class TestHashingHoist:
    def test_backcompat_reexports(self):
        """The hoisted helpers stay importable from their historical
        locations (satellite: tables/hashing.py)."""
        from multiverso_tpu.tables import hashing
        from multiverso_tpu.tables import kv_table, matrix_table
        assert matrix_table._bucket is hashing._bucket
        assert kv_table._bucket is hashing._bucket
        assert kv_table._hash_u64 is hashing._hash_u64
        assert kv_table._split_keys is hashing._split_keys
        assert kv_table.EMPTY_KEY == hashing.EMPTY_KEY
        assert hashing._bucket(1) == 8 and hashing._bucket(9) == 16
        roundtrip = hashing._join_keys(
            hashing._split_keys(np.asarray([0, 1, 2**40 + 7],
                                           np.uint64)))
        assert np.array_equal(roundtrip,
                              np.asarray([0, 1, 2**40 + 7], np.uint64))
