"""Sharded server fleet end to end: PartitionMap ownership math, N
in-process ``TableServer`` shards on unix sockets behind the
scatter-gather ``FleetClient`` — bit-exact dense/KV reads spanning
every member, range reads touching only the owning shard, the version
handshake refusing a stale map at hello, resend-after-reconnect
landing exactly once per shard under a chaos wire storm, and one
member going down leaving the surviving partitions serving."""

import contextlib

import numpy as np
import pytest

from multiverso_tpu import core
from multiverso_tpu.client import router
from multiverso_tpu.client import transport
from multiverso_tpu.ft import chaos
from multiverso_tpu.server import partition
from multiverso_tpu.server import wire
from multiverso_tpu.server.table_server import TableServer
from multiverso_tpu.tables import reset_tables


class TestPartitionMap:
    def test_dense_bounds_cover_and_balance(self):
        pmap = partition.PartitionMap(3)
        b = pmap.dense_bounds(101)
        assert b[0] == 0 and b[-1] == 101
        sizes = [b[r + 1] - b[r] for r in range(3)]
        assert sum(sizes) == 101
        assert max(sizes) - min(sizes) <= 1     # balanced split
        for r in range(3):
            assert pmap.dense_range(101, r) == (b[r], b[r + 1])

    def test_kv_ownership_is_total_and_bucket_aligned(self):
        pmap = partition.PartitionMap(4)
        keys = np.arange(1, 4097, dtype=np.uint64)
        owner = pmap.kv_owner(keys)
        assert ((0 <= owner) & (owner < 4)).all()
        assert len(np.unique(owner)) == 4       # every rank owns keys
        # ownership is exactly "my bucket range holds the key's bucket"
        bucket = pmap.kv_bucket(keys)
        for r in range(4):
            lo, hi = pmap.bucket_range(r)
            np.testing.assert_array_equal(
                owner == r, (bucket >= lo) & (bucket < hi))
        # deterministic: same keys, same owners, any process
        np.testing.assert_array_equal(owner, pmap.kv_owner(keys))

    def test_wire_roundtrip_and_mismatch(self):
        pmap = partition.PartitionMap(2, version=3)
        assert partition.PartitionMap.from_wire(pmap.to_wire()) == pmap
        assert pmap.mismatch(pmap.to_wire()) is None
        # a non-map claim is itself a mismatch (the claimless-tooling
        # pass lives in the server, which skips the check entirely)
        assert pmap.mismatch(None) is not None
        stale = partition.PartitionMap(2, version=2).to_wire()
        assert "version" in pmap.mismatch(stale)
        wrong_n = partition.PartitionMap(3, version=3).to_wire()
        assert pmap.mismatch(wrong_n) is not None


@contextlib.contextmanager
def _fleet(tmp_path, n, **map_kw):
    """N in-process shard servers on unix sockets + teardown."""
    pmap = partition.PartitionMap(n, **map_kw)
    servers, addrs = [], []
    try:
        for r in range(n):
            s = TableServer(f"unix:{tmp_path}/fleet{r}.sock",
                            name=f"tfleet-{r}",
                            partition=partition.PartitionMember(pmap, r))
            addrs.append(s.start())
            servers.append(s)
        yield servers, addrs
    finally:
        chaos.uninstall_chaos()
        for s in servers:
            s.stop()
        reset_tables()
        core.shutdown()


def _connect(addrs, **kw):
    kw.setdefault("quant", None)
    return router.connect_fleet(addrs, **kw)


class TestScatterGather:
    def test_dense_get_spans_all_servers_bit_exact(self, tmp_path):
        """A 101-element table over 3 shards: adds split by ownership,
        the gathered read is bit-identical to the host-side sum."""
        with _fleet(tmp_path, 3) as (servers, addrs):
            fc = _connect(addrs, client="w0")
            t = fc.create_array("fl_w", 101)
            delta = np.arange(101, dtype=np.float32)
            t.add(delta, sync=True)
            t.add(delta, sync=True)
            got = t.get()
            assert got.tobytes() == (2 * delta).tobytes()
            # every shard served a nonempty piece of it
            b = fc.pmap.dense_bounds(101)
            for r in range(3):
                shard = t.get_shard(r).get()
                assert shard.shape == (b[r + 1] - b[r],)
                assert shard.tobytes() == got[b[r]:b[r + 1]].tobytes()
            fc.close()

    def test_range_read_touches_only_owning_shard(self, tmp_path):
        """``get_range`` inside one shard's bounds must not send a
        single request to the other member — the 1/N-bytes payoff."""
        with _fleet(tmp_path, 2) as (servers, addrs):
            fc = _connect(addrs, client="w0")
            t = fc.create_array("fl_rng", 64)
            t.add(np.arange(64, dtype=np.float32), sync=True)
            ops0, ops1 = servers[0]._ops, servers[1]._ops
            got = t.get_range(2, 20)            # entirely in rank 0
            assert got.tobytes() == np.arange(
                2, 20, dtype=np.float32).tobytes()
            assert servers[0]._ops > ops0
            assert servers[1]._ops == ops1      # rank 1 never contacted
            # a straddling range hits both and reassembles exactly
            got = t.get_range(20, 50)
            assert got.tobytes() == np.arange(
                20, 50, dtype=np.float32).tobytes()
            assert servers[1]._ops > ops1
            fc.close()

    def test_kv_routing_presums_duplicates(self, tmp_path):
        with _fleet(tmp_path, 2) as (_, addrs):
            fc = _connect(addrs, client="w0")
            kv = fc.create_kv("fl_kv", 256, value_dim=4)
            keys = np.array([1, 2, 3, 1000, 2, 99999], np.uint64)
            d = np.ones((6, 4), np.float32)
            d[:, 0] = np.arange(6)
            kv.add(keys, d, sync=True)
            vals, found = kv.get(keys)
            assert found.all()
            # duplicate key 2 (rows 1 and 4): one wire row carrying the
            # pre-sum; both result rows read it back
            exp = d[1] + d[4]
            assert np.array_equal(vals[1], exp)
            assert np.array_equal(vals[4], exp)
            assert np.array_equal(vals[0], d[0])
            _, missing = kv.get(np.array([123456789], np.uint64))
            assert not missing.any()
            fc.close()


class TestVersionHandshake:
    def test_stale_map_refused_at_hello(self, tmp_path):
        """A client claiming yesterday's geometry is refused BEFORE any
        data op — resharding can't silently misroute."""
        with _fleet(tmp_path, 2, version=4) as (_, addrs):
            stale = partition.PartitionMap(2, version=3).to_wire()
            with pytest.raises(wire.WireProtocolError,
                               match="partition map mismatch"):
                transport.WireClient(addrs[0], client="stale",
                                     partition=stale)
            # the matching map connects fine on the same socket
            fc = _connect(addrs, client="ok", version=4)
            assert fc.ping()
            fc.close()

    def test_wrong_fleet_size_refused(self, tmp_path):
        with _fleet(tmp_path, 2) as (_, addrs):
            claim = partition.PartitionMap(3).to_wire()
            with pytest.raises(wire.WireProtocolError,
                               match="partition map mismatch"):
                transport.WireClient(addrs[0], client="wrong",
                                     partition=claim)


class TestFleetFaultTolerance:
    def test_storm_resend_lands_exactly_once_per_shard(self, tmp_path):
        """Chaos drops/tears on the wire force reconnect + resend on
        whichever member connection they hit; dedup on EACH shard keeps
        every split add applied exactly once — the gathered result is
        bit-identical to the quiet sum."""
        with _fleet(tmp_path, 2) as (_, addrs):
            fc = _connect(addrs, client="w0")
            t = fc.create_array("fl_storm", 32)
            chaos.install_chaos("seed=5;wire.send:drop:times=3;"
                                "wire.recv:torn:times=2")
            try:
                for i in range(40):
                    t.add(np.full(32, float(i + 1), np.float32))
                t.wait()
            finally:
                chaos.uninstall_chaos()
            got = t.get()
            exp = np.full(32, 40 * 41 / 2, np.float32)
            assert got.tobytes() == exp.tobytes()
            assert sum(c.reconnects for c in fc.clients) >= 1
            fc.close()

    def test_member_down_survivors_keep_serving(self, tmp_path):
        """Stop rank 0: whole-table gathers fail, but rank 1's shard
        keeps answering — partial availability is per-partition."""
        with _fleet(tmp_path, 2) as (servers, addrs):
            fc = _connect(addrs, client="w0",
                          deadline_s=3.0)
            t = fc.create_array("fl_down", 64)
            delta = np.arange(64, dtype=np.float32)
            t.add(delta, sync=True)
            b = fc.pmap.dense_bounds(64)
            servers[0].stop()
            surv = t.get_shard(1).get()
            assert surv.tobytes() == delta[b[1]:b[2]].tobytes()
            with pytest.raises(Exception):
                t.get()                         # rank 0 is gone
            # rank 1 still healthy AFTER the failed gather
            surv2 = t.get_shard(1).get()
            assert surv2.tobytes() == surv.tobytes()
            try:
                fc.close()
            except Exception:
                pass                            # rank 0's close may fail


class TestOneProcessPerChip:
    """The launcher hands each chip-holding process exactly one chip
    before it imports jax, and refuses one process too many in words —
    instead of a libtpu lockfile error in some child, or a CPU server
    nobody asked for."""

    @pytest.fixture()
    def launcher(self, monkeypatch):
        from multiverso_tpu.server import __main__ as launcher
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
        monkeypatch.setattr(
            launcher.glob, "glob",
            lambda pat: [f"/dev/vfio/{i}" for i in range(4)]
            if pat.startswith("/dev/vfio") else [])
        return launcher

    def test_distinct_chips_and_one_chip_environment(self, launcher):
        chips = launcher._assign_chips(4)
        assert chips == [0, 1, 2, 3]
        envs = [launcher._chip_env({"KEEP": "1"}, c) for c in chips]
        assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == list("0123")
        for e in envs:
            assert e["KEEP"] == "1"
            assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
            assert e["TPU_PROCESS_BOUNDS"] == "1,1,1"

    def test_one_too_many_is_refused_in_words(self, launcher):
        with pytest.raises(SystemExit) as e:
            launcher._assign_chips(5)
        assert "5 chip-holding" in str(e.value)
        assert "one process per chip" in str(e.value)
        # --grow: chips already held by fleet members are not free
        assert launcher._assign_chips(1, taken=[0, 1, 2]) == [3]
        with pytest.raises(SystemExit, match="0 free chip"):
            launcher._assign_chips(1, taken=[0, 1, 2, 3])

    def test_a_restricted_launcher_hands_out_only_its_own(self, launcher,
                                                          monkeypatch):
        monkeypatch.setenv("TPU_VISIBLE_CHIPS", "2,3")
        assert launcher._assign_chips(2) == [2, 3]
        with pytest.raises(SystemExit, match="one process per chip"):
            launcher._assign_chips(3)

    def test_cpu_servers_only_when_pinned_explicitly(self, launcher,
                                                     monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        assert launcher._assign_chips(8) == [None] * 8
        env = {"A": "b"}
        assert launcher._chip_env(env, None) is env
        # "tpu,cpu" (the chip host's setting) still holds chips
        monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
        assert launcher._assign_chips(2) == [0, 1]

    def test_fleet_refuses_before_starting_anything(self, tmp_path):
        """End to end through the CLI on this chip-less host with no
        CPU pin: non-zero exit, that sentence, no fleet file, and the
        launcher itself never initialised a backend to find out."""
        import os
        import subprocess
        import sys
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = {k: v for k, v in os.environ.items()
               if k not in ("JAX_PLATFORMS", "TPU_VISIBLE_CHIPS")}
        env["PYTHONPATH"] = repo
        fleet_file = tmp_path / "fleet.json"
        proc = subprocess.run(
            [sys.executable, "-m", "multiverso_tpu.server", "--fleet", "2",
             "--address", f"unix:{tmp_path}/f.sock",
             "--fleet-file", str(fleet_file)],
            env=env, cwd=repo, capture_output=True, text=True, timeout=120)
        assert proc.returncode != 0
        assert "2 chip-holding server process(es) requested" in proc.stderr
        assert "one process per chip" in proc.stderr
        assert "Unable to initialize backend" not in proc.stderr
        assert not fleet_file.exists()
