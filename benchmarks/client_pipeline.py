"""Micro-bench: the worker-side client pipeline (multiverso_tpu/client).

Measures, on whatever mesh ``core.init()`` builds (CPU-safe):

- KV Add throughput, coalescing OFF vs ON (``CoalescingBuffer``,
  K batches per fused dispatch) vs STAGED (``KVStagingWriter`` double-
  buffered H2D) — add-ops/s plus the jitted apply dispatch counts from
  ``profile.calls{fn=kv.apply.*}`` (the proof the speedup is dispatch
  reduction, not noise),
- whole-table Get throughput, direct blocking ``table.get()`` vs
  ``CachedView`` bounded-staleness reads (adds interleaved so the cache
  actually refreshes).

Emits ONE final JSON line in the bench metric-line shape (flat numeric
keys — ``tools/bench_diff.py`` compares two runs and ``make ci`` gates
on the watched throughputs) and writes the same document to
``client_bench.json`` (override: ``MVTPU_CLIENT_BENCH_JSON``).

``MVTPU_CLIENT_BENCH_TINY=1`` shrinks every size for a CI smoke run and
pins the CPU platform (the integrated bench's MVTPU_BENCH_TINY analog).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

TINY = os.environ.get("MVTPU_CLIENT_BENCH_TINY", "").lower() \
    not in ("", "0", "false")
CPU = TINY or os.environ.get("MVTPU_CLIENT_BENCH_CPU", "").lower() \
    not in ("", "0", "false")

if CPU:
    # must precede any backend touch: the CPU smoke run must not take
    # the chip (one process per chip — see tests/conftest.py)
    import jax
    jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

from multiverso_tpu import client, core, telemetry  # noqa: E402
from multiverso_tpu.tables import ArrayTable, KVTable  # noqa: E402

# sizes: (kv batches, keys/batch, value_dim, coalesce K, gets, table n)
SIZES = dict(batches=64, keys=256, value_dim=8, k=8, gets=200,
             table_n=1 << 16)
if TINY:
    SIZES = dict(batches=16, keys=64, value_dim=4, k=4, gets=40,
                 table_n=1 << 10)


def _apply_calls(name: str) -> float:
    return telemetry.registry().counter("profile.calls", fn=name).value


def _kv_batches(seed: int):
    """Deterministic (keys, deltas) batches with cross-batch key overlap
    (the case coalescing pre-sums)."""
    rng = np.random.default_rng(seed)
    n, b, d = SIZES["batches"], SIZES["keys"], SIZES["value_dim"]
    out = []
    for _ in range(n):
        keys = rng.choice(np.arange(1, 4 * b, dtype=np.uint64), size=b,
                          replace=False)
        out.append((keys, rng.normal(size=(b, d)).astype(np.float32)))
    return out


def bench_kv_direct() -> dict:
    kv = KVTable(SIZES["keys"] * 16, value_dim=SIZES["value_dim"],
                 name="bench_kv_direct")
    batches = _kv_batches(0)

    def run():
        for keys, deltas in batches:
            kv.add(keys, deltas)
        kv.wait()

    run()       # warmup: compile the (bucketed) signature once
    c0 = _apply_calls("kv.apply.bench_kv_direct")
    t0 = time.perf_counter()
    run()
    dt = time.perf_counter() - t0
    return {"ops_s": len(batches) / dt,
            "dispatches": _apply_calls("kv.apply.bench_kv_direct") - c0}


def bench_kv_coalesced() -> dict:
    kv = KVTable(SIZES["keys"] * 16, value_dim=SIZES["value_dim"],
                 name="bench_kv_coal")
    buf = client.CoalescingBuffer(kv, max_deltas=SIZES["k"])
    batches = _kv_batches(0)

    def run():
        for keys, deltas in batches:
            buf.add_kv(keys, deltas)
        buf.flush()
        kv.wait()

    run()       # warmup
    c0 = _apply_calls("kv.apply.bench_kv_coal")
    t0 = time.perf_counter()
    run()
    dt = time.perf_counter() - t0
    return {"ops_s": len(batches) / dt,
            "dispatches": _apply_calls("kv.apply.bench_kv_coal") - c0}


def bench_kv_staged() -> dict:
    kv = KVTable(SIZES["keys"] * 16, value_dim=SIZES["value_dim"],
                 name="bench_kv_staged")
    batches = _kv_batches(0)

    def run():
        client.stage_kv_adds(kv, batches, depth=2)
        kv.wait()

    run()       # warmup
    t0 = time.perf_counter()
    run()
    dt = time.perf_counter() - t0
    return {"ops_s": len(batches) / dt}


def bench_kv_health() -> dict:
    """The direct lane re-run with the training-health audit ON: a
    temporary HealthMonitor (no rules — pure observation cost) makes
    every ``add`` dispatch the fused stats vector too. The ratio vs
    ``bench_kv_direct`` is the audit's hot-path overhead (the async
    poller does the D2H off-thread, so this should stay within a few
    percent)."""
    from multiverso_tpu.telemetry import health
    mon = health.install(health.HealthMonitor([]).start())
    try:
        kv = KVTable(SIZES["keys"] * 16, value_dim=SIZES["value_dim"],
                     name="bench_kv_health")
        batches = _kv_batches(0)

        def run():
            for keys, deltas in batches:
                kv.add(keys, deltas)
            kv.wait()

        run()       # warmup: compile apply + stats signatures once
        t0 = time.perf_counter()
        run()
        dt = time.perf_counter() - t0
        mon.drain()
        return {"ops_s": len(batches) / dt}
    finally:
        health.uninstall()


def bench_get_direct() -> dict:
    t = ArrayTable(SIZES["table_n"], "float32", name="bench_get_direct")
    delta = np.ones(SIZES["table_n"], np.float32)
    t.add(delta)
    t.get()     # warmup: compile snapshot + apply
    t0 = time.perf_counter()
    for i in range(SIZES["gets"]):
        if i % 10 == 0:
            t.add(delta)
        t.get()
    dt = time.perf_counter() - t0
    return {"ops_s": SIZES["gets"] / dt}


def bench_get_cached() -> dict:
    t = ArrayTable(SIZES["table_n"], "float32", name="bench_get_cached")
    delta = np.ones(SIZES["table_n"], np.float32)
    t.add(delta)
    t.get()     # warmup, matching the direct bench
    view = client.CachedView(t, max_staleness=4)
    t0 = time.perf_counter()
    for i in range(SIZES["gets"]):
        if i % 10 == 0:
            t.add(delta)
        view.get()
    dt = time.perf_counter() - t0
    view.close()
    reg = telemetry.registry()
    lbl = f"{t.table_id}:{t.name}"
    return {"ops_s": SIZES["gets"] / dt,
            "hits": reg.counter("client.cache.hits", table=lbl).value,
            "misses": reg.counter("client.cache.misses",
                                  table=lbl).value}


def main() -> None:
    core.init()
    telemetry.beat()
    direct = bench_kv_direct()
    coal = bench_kv_coalesced()
    staged = bench_kv_staged()
    health_on = bench_kv_health()
    g_direct = bench_get_direct()
    g_cached = bench_get_cached()
    line = {
        "metric": "client_kv_add_ops_per_sec",
        "value": round(coal["ops_s"], 2),
        "unit": "adds/s",
        "tiny": TINY,
        "kv_add_ops_per_sec_direct": round(direct["ops_s"], 2),
        "kv_add_ops_per_sec_coalesced": round(coal["ops_s"], 2),
        "kv_add_ops_per_sec_staged": round(staged["ops_s"], 2),
        "kv_add_ops_per_sec_health": round(health_on["ops_s"], 2),
        "kv_add_health_overhead": round(direct["ops_s"]
                                        / health_on["ops_s"], 3),
        "kv_add_coalesce_speedup": round(coal["ops_s"]
                                         / direct["ops_s"], 3),
        "kv_apply_dispatches_direct": direct["dispatches"],
        "kv_apply_dispatches_coalesced": coal["dispatches"],
        "get_ops_per_sec_direct": round(g_direct["ops_s"], 2),
        "get_ops_per_sec_cached": round(g_cached["ops_s"], 2),
        "get_cache_speedup": round(g_cached["ops_s"]
                                   / g_direct["ops_s"], 3),
        "cache_hits": g_cached["hits"],
        "cache_misses": g_cached["misses"],
    }
    out = os.environ.get("MVTPU_CLIENT_BENCH_JSON", "client_bench.json")
    with open(out, "w") as f:
        json.dump(line, f, indent=1)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
