"""TableServer + client transport end to end: an in-process server on
a unix socket driven by WireClient (same-process package mode) and by
real jax-free worker SUBPROCESSES — roundtrips, coalescing over remote
tables, quantized-EF convergence, reconnect + exactly-once under
chaos, and process-fault isolation (SIGKILL a worker mid-run)."""

import json
import os
import signal
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from multiverso_tpu import client as mv_client
from multiverso_tpu import core
from multiverso_tpu.ft import chaos
from multiverso_tpu.server import wire
from multiverso_tpu.server.table_server import TableServer
from multiverso_tpu.tables import reset_tables

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "multiverso_tpu")


@pytest.fixture()
def server(tmp_path):
    s = TableServer(f"unix:{tmp_path}/wire.sock", name="twire")
    addr = s.start()
    try:
        yield s, addr
    finally:
        chaos.uninstall_chaos()
        s.stop()
        reset_tables()
        core.shutdown()


def _connect(addr, **kw):
    kw.setdefault("quant", None)
    return mv_client.connect(addr, **kw)


class TestRoundtrips:
    def test_array_create_add_get(self, server):
        _, addr = server
        with _connect(addr, client="w0") as c:
            t = c.create_array("ws_a", 64, updater="sgd")
            h = t.add(np.ones(64, np.float32),
                      {"learning_rate": 0.5}, sync=True)
            assert h.done()
            np.testing.assert_allclose(t.get(), -0.5)  # param -= lr*d

    def test_kv_add_get(self, server):
        _, addr = server
        with _connect(addr, client="w0") as c:
            t = c.create_kv("ws_kv", 1 << 10, value_dim=4)
            keys = np.arange(1, 9, dtype=np.uint64)
            t.add(keys, np.full((8, 4), 2.0, np.float32), sync=True)
            vals, found = t.get(keys)
            assert found.all()
            np.testing.assert_allclose(vals, 2.0)
            _, missing = t.get(np.array([999], np.uint64))
            assert not missing.any()

    def test_create_is_idempotent_by_name(self, server):
        _, addr = server
        with _connect(addr, client="w0") as c0, \
                _connect(addr, client="w1") as c1:
            t0 = c0.create_array("ws_shared", 16)
            t1 = c1.create_array("ws_shared", 16)
            assert t0.table_id == t1.table_id
            t0.add(np.ones(16, np.float32), sync=True)
            np.testing.assert_allclose(t1.get(), 1.0)

    def test_application_error_is_remote_error_not_retry(self, server):
        _, addr = server
        with _connect(addr, client="w0") as c:
            with pytest.raises(mv_client.RemoteError):
                c.call("get", {"table": 999})
            assert c.ping()            # connection survived the error

    def test_server_status_and_statusz_section(self, server):
        s, addr = server
        with _connect(addr, client="w0") as c:
            c.create_array("ws_st", 8)
            st = c.server_status()
            assert st["name"] == "twire" and st["tables"] >= 1
            assert st["connections"] >= 1
            # the status names the devices the tables live on, as jax
            # reports them (chip_smoke.py's client asserts "tpu" here)
            devs = list(core.mesh().devices.flat)
            assert st["platform"] == devs[0].platform == "cpu"
            assert st["device_kind"] == devs[0].device_kind
            assert st["devices"] == [d.id for d in devs]
        from multiverso_tpu.server import table_server
        assert any(row["name"] == "twire"
                   for row in table_server.status_all())


class TestClientPipeline:
    def test_pipelined_adds_in_order(self, server):
        _, addr = server
        with _connect(addr, client="w0") as c:
            t = c.create_array("ws_pipe", 32)
            handles = [t.add(np.full(32, float(i + 1), np.float32))
                       for i in range(2 * mv_client.transport
                                      .MAX_PIPELINE + 8)]
            handles[-1].wait()
            assert all(h.done() for h in handles)
            n = len(handles)
            np.testing.assert_allclose(t.get(), n * (n + 1) / 2)

    def test_coalescing_buffer_over_remote_table(self, server):
        """client/coalesce.py's CoalescingBuffer works over the wire
        unchanged — K local adds become ONE wire add."""
        s, addr = server
        with _connect(addr, client="w0") as c:
            t = c.create_array("ws_coal", 16)
            buf = mv_client.CoalescingBuffer(t, max_deltas=4)
            ops_before = s._ops
            for i in range(4):
                buf.add(np.full(16, float(i + 1), np.float32))
            t.wait()
            np.testing.assert_allclose(t.get(), 10.0)
            assert s._ops - ops_before <= 2   # ONE wire add (+ the get)

    def test_delta_batcher(self, server):
        _, addr = server
        with _connect(addr, client="w0") as c:
            t = c.create_array("ws_batch", 16)
            b = mv_client.DeltaBatcher(t, max_deltas=3)
            for _ in range(7):
                b.add(np.ones(16, np.float32))
            b.flush()
            t.wait()
            assert b.flushes == 3
            np.testing.assert_allclose(t.get(), 7.0)


class TestQuantizedWire:
    def test_one_bit_ef_converges_and_saves_bytes(self, server):
        _, addr = server
        rng = np.random.default_rng(11)
        deltas = [rng.normal(0, 1, 512).astype(np.float32)
                  for _ in range(150)]
        with _connect(addr, client="raw") as c:
            t = c.create_array("ws_qraw", 512)
            for d in deltas:
                t.add(d)
            t.wait()
            raw_tx, expect = c.tx_bytes, t.get()
        with _connect(addr, client="q1", quant="1bit", seed=0) as c:
            t = c.create_array("ws_q1b", 512)
            for d in deltas:
                t.add(d)
            t.wait()
            got = t.get()
            resid = c.residuals.take(t.table_id, "dense", (512,),
                                     c.block)
        # error feedback: the gap is bounded by the residual in flight
        assert np.abs(expect - got).max() \
            <= np.abs(resid).max() + 1e-3
        assert c.tx_bytes * 4 < raw_tx     # >= 4x fewer bytes on wire

    def test_int8_kv_quant_applies_unbiased(self, server):
        _, addr = server
        with _connect(addr, client="q8", quant="int8", seed=1) as c:
            t = c.create_kv("ws_q8", 1 << 10, value_dim=8)
            keys = np.arange(1, 33, dtype=np.uint64)
            d = np.full((32, 8), 0.25, np.float32)
            n = 50
            for _ in range(n):
                t.add(keys, d)
            t.wait()
            vals, found = t.get(keys)
            assert found.all()
            np.testing.assert_allclose(vals, 0.25 * n, rtol=0.05)


class TestFaultTolerance:
    def test_dedup_replay_never_double_applies(self, server):
        """Send the SAME add frame twice (what a post-reconnect resend
        does): the server must apply once and replay the cached ack."""
        s, addr = server
        from multiverso_tpu.telemetry import metrics as telemetry
        with _connect(addr, client="w0") as c:
            t = c.create_array("ws_dedup", 8)
            header = {"op": "add", "table": t.table_id, "rid": 777,
                      "quant": {"mode": "raw"}, "option": None}
            payload = [np.ones(8, np.float32)]
            replays = telemetry.registry().counter(
                "wire.dedup.replays", op="add")
            r0 = replays.value
            with c._lock:
                for _ in range(2):
                    c._tx(c._chan, header, payload)
                for _ in range(2):
                    h, _ = c._recv_reply()
                    assert h["ok"] and h["rid"] == 777
            np.testing.assert_allclose(t.get(), 1.0)   # applied ONCE
            assert replays.value == r0 + 1

    def test_chaos_storm_exactly_once(self, server):
        """Bounded drop/torn storm across both wire directions: every
        add lands exactly once and the client reconnects through it."""
        _, addr = server
        with _connect(addr, client="w0") as c:
            t = c.create_array("ws_storm", 32)
            chaos.install_chaos("seed=5;wire.send:drop:times=3;"
                                "wire.recv:torn:times=2")
            try:
                for i in range(40):
                    t.add(np.full(32, float(i + 1), np.float32))
                t.wait()
            finally:
                chaos.uninstall_chaos()
            np.testing.assert_allclose(t.get(), 40 * 41 / 2)
            assert c.reconnects >= 1

    def test_storm_result_bit_identical_to_quiet_run(self, server):
        """The ISSUE acceptance: a run that survived a wire storm ends
        bit-identical to the uninterrupted reference (same adds, same
        order — dedup means the storm is invisible to the table)."""
        _, addr = server
        rng = np.random.default_rng(13)
        deltas = [rng.normal(0, 1, 64).astype(np.float32)
                  for _ in range(30)]
        with _connect(addr, client="w0") as c:
            quiet = c.create_array("ws_quiet", 64, updater="sgd")
            for d in deltas:
                quiet.add(d, {"learning_rate": 0.1})
            quiet.wait()
            ref = quiet.get()
            stormy = c.create_array("ws_stormy", 64, updater="sgd")
            chaos.install_chaos("seed=9;wire.send:drop:times=2;"
                                "wire.recv:drop:times=2")
            try:
                for d in deltas:
                    stormy.add(d, {"learning_rate": 0.1})
                stormy.wait()
            finally:
                chaos.uninstall_chaos()
            got = stormy.get()
        assert ref.tobytes() == got.tobytes()

    def test_accept_chaos_sheds_connection_then_recovers(self, server):
        _, addr = server
        chaos.install_chaos("wire.accept:error:times=1")
        try:
            # the first dial dies at the handshake; the retry redials
            with _connect(addr, client="w0") as c:
                assert c.ping()
        finally:
            chaos.uninstall_chaos()


WORKER_SRC = textwrap.dedent("""
    import importlib.util, json, os, sys
    import numpy as np
    assert "jax" not in sys.modules
    pkg, addr, rank, steps = sys.argv[1:5]
    spec = importlib.util.spec_from_file_location(
        "multiverso_tpu.client.transport",
        os.path.join(pkg, "client", "transport.py"))
    transport = importlib.util.module_from_spec(spec)
    sys.modules["multiverso_tpu.client.transport"] = transport
    spec.loader.exec_module(transport)
    assert "jax" not in sys.modules, "worker pulled jax in"
    c = transport.connect(addr, client=f"w{rank}")
    t = c.create_array("ws_proc", 32)
    for i in range(int(steps)):
        t.add(np.ones(32, np.float32), sync=True)
        print(json.dumps({"rank": rank, "step": i}), flush=True)
    c.close()
    print(json.dumps({"rank": rank, "done": True}), flush=True)
""")


def _spawn_worker(tmp_path, addr, rank, steps):
    script = tmp_path / "worker.py"
    if not script.exists():
        script.write_text(WORKER_SRC)
    return subprocess.Popen(
        [sys.executable, str(script), PKG, addr, str(rank),
         str(steps)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)


class TestProcessFaultIsolation:
    def test_sigkill_worker_leaves_server_up(self, server, tmp_path):
        """ISSUE satellite 3: SIGKILL one worker mid-run — the server
        stays up, the survivor completes every step, and a FRESH
        worker can connect and finish its run."""
        s, addr = server
        victim = _spawn_worker(tmp_path, addr, 0, 400)
        survivor = _spawn_worker(tmp_path, addr, 1, 25)
        # let the victim make some progress, then kill it mid-stream
        first = victim.stdout.readline()
        assert first, "victim produced no output"
        victim.send_signal(signal.SIGKILL)
        victim.wait(timeout=10)
        assert victim.returncode == -signal.SIGKILL
        victim.stdout.close()
        victim.stderr.close()
        out, err = survivor.communicate(timeout=60)
        assert survivor.returncode == 0, err
        lines = [json.loads(x) for x in out.splitlines()]
        assert lines[-1].get("done"), "survivor did not finish"
        assert sum(1 for x in lines if "step" in x) == 25
        # server still healthy: a FRESH worker connects + completes
        fresh = _spawn_worker(tmp_path, addr, 2, 5)
        out, err = fresh.communicate(timeout=60)
        assert fresh.returncode == 0, err
        assert json.loads(out.splitlines()[-1]).get("done")
        with _connect(addr, client="scorer") as c:
            assert c.ping()
            t = c.create_array("ws_proc", 32)
            total = float(np.asarray(t.get())[0])
        # survivor 25 + fresh 5 landed exactly; the victim some prefix
        assert total >= 30.0
        assert total == int(total)        # whole adds only, no tears
        assert not s._stop.is_set()


def test_serving_mp_bench_compiles():
    """`make mp-smoke` spawns benchmarks/serving_mp.py as BOTH the
    parent and the --worker subprocess; a syntax error would only
    surface in CI — compile it here."""
    path = os.path.join(REPO, "benchmarks", "serving_mp.py")
    with open(path) as f:
        compile(f.read(), path, "exec")


def test_wire_env_knob_docs_match_code():
    """README documents MVTPU_WIRE_*; the knobs must exist in code."""
    assert wire.QUANT_ENV == "MVTPU_WIRE_QUANT"
    assert wire.BLOCK_ENV == "MVTPU_WIRE_BLOCK"
    from multiverso_tpu.io import shmring, wiresock
    from multiverso_tpu.server import table_server
    assert wiresock.TIMEOUT_ENV == "MVTPU_WIRE_TIMEOUT_S"
    assert table_server.FUSE_ENV == "MVTPU_SERVER_FUSE"
    assert table_server.DEDUP_ENV == "MVTPU_WIRE_DEDUP"
    assert table_server.DEDUP_CLIENTS_ENV == "MVTPU_WIRE_DEDUP_CLIENTS"
    assert shmring.RING_ENV == "MVTPU_SHM_RING_MB"
