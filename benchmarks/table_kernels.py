"""Micro-bench: server-side table kernels, XLA vs Pallas
(multiverso_tpu/ops/table_kernels.py).

Measures, on whatever backend ``core.init()`` finds (CPU-safe):

- **KV probe_update**: the fused probe + updater + scatter dispatch,
  driven at the engine level (device operands staged once, donated
  buffers carried through the loop) — the batch-wide argsort + full
  bucket-row HBM round-trip is what the Pallas engine deletes,
- **KV lookup**: the bucketed gather+match Get,
- **row gather** and **COO scatter-add**: the matrix/sparse row paths.

Each kernel runs through BOTH engines in one process (the tables are
built under ``MVTPU_KERNELS=xla`` then ``=pallas``; on CPU the Pallas
engine is interpret-mode — integration is real, the number is
meaningless and flagged ``interpret: true``). A parity check (same
batch through both engines, results compared bit-exact) guards every
timed section — a fast wrong kernel must fail the bench, not win it.

Bytes-moved accounting: ``*_bytes_per_op_model`` is the analytic
touched-rows model (touched rows × row bytes × read+write + batch
operands); where XLA reports cost analysis, the per-engine
``profile.bytes_accessed{fn=...}`` gauges ride the telemetry snapshot.

A SHARDED lane rides every run with ≥2 devices (TINY forces 2 virtual
CPU devices): a data=1 × model=2 mesh where the per-shard lane-sliced
Pallas engine is timed against the flat XLA engine GSPMD-partitioned
over the same mesh — the dispatch it replaces. Parity-guarded like the
flat lanes; emits ``*_ops_per_sec_{xla,pallas}_sharded``.

Emits ONE final JSON line in the bench metric-line shape (flat numeric
keys — ``tools/bench_diff.py`` watches ``kv_probe_ops_per_sec_pallas``,
``coo_scatter_ops_per_sec_pallas`` and their ``_sharded`` twins) and
writes the same document to ``table_kernels_bench.json`` (override:
``MVTPU_KERNEL_BENCH_JSON``).

``MVTPU_KERNEL_BENCH_TINY=1`` shrinks every size for the ``make
kernel-bench`` CI smoke and pins the CPU platform.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

TINY = os.environ.get("MVTPU_KERNEL_BENCH_TINY", "").lower() \
    not in ("", "0", "false")
CPU = TINY or os.environ.get("MVTPU_KERNEL_BENCH_CPU", "").lower() \
    not in ("", "0", "false")

if CPU:
    # must precede any backend touch: a CPU run must not take the chip
    # (see tests/conftest.py). Two virtual CPU devices so the SHARDED lane
    # (model=2 mesh, per-shard lane-sliced engines) always runs — the
    # watched *_sharded metrics must exist even on a laptop.
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = \
            (flags + " --xla_force_host_platform_device_count=2").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")

import jax  # noqa: E402
import numpy as np  # noqa: E402

from multiverso_tpu import core, telemetry  # noqa: E402
from multiverso_tpu.ops import table_kernels as tk  # noqa: E402
from multiverso_tpu.tables import (KVTable, MatrixTable,  # noqa: E402
                                   SparseMatrixTable)
from multiverso_tpu.tables.hashing import shard_lane_slices  # noqa: E402

# sizes: kv (capacity, batch, value_dim, slots), rows (rows, cols, n),
# coo (rows, cols, nnz), iters per timed engine loop
SIZES = dict(kv_capacity=1 << 16, kv_batch=4096, value_dim=8, slots=8,
             rows=1 << 14, cols=128, row_n=2048, coo_nnz=8192,
             coo_cols=1024, iters=32)
if TINY:
    # interpret-mode Pallas unrolls the grid at trace time on CPU —
    # tiny batches keep compile seconds, not minutes
    SIZES = dict(kv_capacity=4096, kv_batch=64, value_dim=4, slots=8,
                 rows=256, cols=32, row_n=32, coo_nnz=64, coo_cols=256,
                 iters=3)


def _with_mode(mode: str, build):
    prev = os.environ.get("MVTPU_KERNELS")
    os.environ["MVTPU_KERNELS"] = mode
    try:
        return build()
    finally:
        if prev is None:
            os.environ.pop("MVTPU_KERNELS", None)
        else:
            os.environ["MVTPU_KERNELS"] = prev


def _timed(fn, iters: int) -> float:
    fn()                         # warmup (compile)
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    return time.perf_counter() - t0


def bench_kv(mode: str) -> dict:
    """probe_update + lookup through one engine; returns ops/s plus the
    final table triple for the cross-engine parity check."""
    rng = np.random.default_rng(7)
    n, d = SIZES["kv_batch"], SIZES["value_dim"]
    keys = rng.choice(np.arange(1, 8 * n, dtype=np.uint64), size=n,
                      replace=False)
    deltas = rng.integers(-3, 4, size=(n, d)).astype(np.float32)
    t = _with_mode(mode, lambda: KVTable(
        SIZES["kv_capacity"], value_dim=d, slots_per_bucket=SIZES["slots"],
        updater="adagrad", name=f"bench_kv_{mode}"))
    prep = t.prepare_add(keys, deltas)
    carry = [t.keys, t.values, t.state]

    def probe_once():
        k, v, s, _ = t._probe_update(carry[0], carry[1], carry[2],
                                     prep.buckets, prep.query,
                                     prep.deltas, prep.valid, prep.option)
        carry[0], carry[1], carry[2] = k, v, s
        jax.block_until_ready(k)

    probe_dt = _timed(probe_once, SIZES["iters"])
    # lookup on the post-insert table (all keys present)
    qn = len(prep.buckets)

    def lookup_once():
        vals, found = t._lookup(carry[0], carry[1], prep.query,
                                prep.buckets)
        jax.block_until_ready(vals)

    lookup_dt = _timed(lookup_once, SIZES["iters"])
    row_bytes = SIZES["slots"] * (8 + 4 * d + 4 * d)   # keys+vals+state
    touched = len(np.unique(prep.buckets))
    return {
        "probe_ops_s": SIZES["iters"] / probe_dt,
        "probe_keys_s": SIZES["iters"] * n / probe_dt,
        "lookup_ops_s": SIZES["iters"] / lookup_dt,
        "bytes_per_op_model": touched * row_bytes * 2
        + qn * (8 + 4 * d + 4),
        "engine": t._probe_update.engine,
        "final": (np.asarray(carry[0]), np.asarray(carry[1])),
    }


def bench_rows(mode: str) -> dict:
    rng = np.random.default_rng(8)
    t = _with_mode(mode, lambda: MatrixTable(
        SIZES["rows"], SIZES["cols"], updater="default",
        name=f"bench_rows_{mode}"))
    ids = rng.integers(0, SIZES["rows"], size=SIZES["row_n"])
    deltas = rng.integers(-3, 4,
                          size=(SIZES["row_n"], SIZES["cols"])
                          ).astype(np.float32)
    padded, _, _, pd = t._pad_ids(ids, deltas, sort=True)
    gpad, _, _ = t._pad_ids(ids)
    carry = [t.param]

    def gather_once():
        jax.block_until_ready(t._gather_rows(carry[0], gpad))

    gather_dt = _timed(gather_once, SIZES["iters"])

    def scatter_once():
        carry[0] = t._scatter_add(carry[0], padded, pd)
        jax.block_until_ready(carry[0])

    scatter_dt = _timed(scatter_once, SIZES["iters"])
    return {
        "gather_ops_s": SIZES["iters"] / gather_dt,
        "scatter_ops_s": SIZES["iters"] / scatter_dt,
        "engine": t._gather_rows.engine,
        "final": np.asarray(carry[0]),
    }


def bench_coo(mode: str) -> dict:
    rng = np.random.default_rng(9)
    t = _with_mode(mode, lambda: SparseMatrixTable(
        SIZES["rows"], SIZES["coo_cols"], dtype="int32",
        updater="default", name=f"bench_coo_{mode}"))
    nnz = SIZES["coo_nnz"]
    rows = np.sort(rng.integers(0, SIZES["rows"], size=nnz)) \
        .astype(np.int32)
    cols = rng.integers(0, SIZES["coo_cols"], size=nnz).astype(np.int32)
    vals = rng.integers(-2, 3, size=nnz).astype(np.int32)
    carry = [t.param]

    def coo_once():
        carry[0] = t._coo_scatter_add(carry[0], rows, cols, vals)
        jax.block_until_ready(carry[0])

    dt = _timed(coo_once, SIZES["iters"])
    touched = len(np.unique(rows))
    return {
        "ops_s": SIZES["iters"] / dt,
        "bytes_per_op_model": touched * SIZES["coo_cols"] * 4 * 2
        + nnz * 12,
        "engine": t._coo_scatter_add.engine,
        "final": np.asarray(carry[0]),
    }


def bench_sharded() -> dict:
    """The sharded lane: a data=1 × model=2 mesh, comparing the
    per-shard lane-sliced Pallas engine against the FLAT XLA engine on
    the same mesh (GSPMD-partitioned — exactly the dispatch the sharded
    engine replaces). Returns {} when fewer than 2 devices exist."""
    if len(jax.devices()) < 2:
        return {}
    core.shutdown()
    core.init(devices=jax.devices()[:2], data_parallel=1,
              model_parallel=2)
    rng = np.random.default_rng(7)
    n, d = SIZES["kv_batch"], SIZES["value_dim"]
    keys = rng.choice(np.arange(1, 8 * n, dtype=np.uint64), size=n,
                      replace=False)
    deltas = rng.integers(-3, 4, size=(n, d)).astype(np.float32)

    kv = {}
    for mode in ("xla", "pallas"):
        t = _with_mode(mode, lambda: KVTable(
            SIZES["kv_capacity"], value_dim=d,
            slots_per_bucket=SIZES["slots"], updater="adagrad",
            name=f"bench_kv_sh_{mode}"))
        prep = t.prepare_add(keys, deltas)    # layout follows the engine
        carry = [t.keys, t.values, t.state]

        def probe_once():
            k, v, s, _ = t._probe_update(carry[0], carry[1], carry[2],
                                         prep.buckets, prep.query,
                                         prep.deltas, prep.valid,
                                         prep.option)
            carry[0], carry[1], carry[2] = k, v, s
            jax.block_until_ready(k)

        dt = _timed(probe_once, SIZES["iters"])
        kv[mode] = {"ops_s": SIZES["iters"] / dt,
                    "engine": t._probe_update.engine,
                    "layout": t._probe_update.layout,
                    "final": (np.asarray(carry[0]),
                              np.asarray(carry[1]))}
    for a, b in zip(kv["xla"]["final"], kv["pallas"]["final"]):
        assert np.array_equal(a, b), "sharded kv probe engines diverged"

    nnz = SIZES["coo_nnz"]
    rows = np.sort(rng.integers(0, SIZES["rows"], size=nnz)) \
        .astype(np.int32)
    cols = rng.integers(0, SIZES["coo_cols"], size=nnz).astype(np.int32)
    vals = rng.integers(-2, 3, size=nnz).astype(np.int32)
    coo = {}
    for mode in ("xla", "pallas"):
        t = _with_mode(mode, lambda: SparseMatrixTable(
            SIZES["rows"], SIZES["coo_cols"], dtype="int32",
            updater="default", name=f"bench_coo_sh_{mode}"))
        if t._coo_scatter_add.layout == "sharded":
            rps = t._rows_per_shard
            shard_ids = rows // rps
            (sr, sc, sv), valid, _ = shard_lane_slices(
                shard_ids, t._shards,
                [(rows - shard_ids * rps).astype(np.int32), cols, vals],
                [np.int32(rps - 1), np.int32(0), np.int32(0)])
            ops = (sr, sc, sv, valid)
        else:
            ops = (rows, cols, vals)
        carry = [t.param]

        def coo_once():
            carry[0] = t._coo_scatter_add(carry[0], *ops)
            jax.block_until_ready(carry[0])

        dt = _timed(coo_once, SIZES["iters"])
        coo[mode] = {"ops_s": SIZES["iters"] / dt,
                     "engine": t._coo_scatter_add.engine,
                     "layout": t._coo_scatter_add.layout,
                     "final": np.asarray(carry[0])[:SIZES["rows"]]}
    assert np.array_equal(coo["xla"]["final"], coo["pallas"]["final"]), \
        "sharded coo scatter engines diverged"

    return {
        "sharded_model_shards": 2,
        "kv_engine_sharded": kv["pallas"]["engine"],
        "kv_layout_sharded": kv["pallas"]["layout"],
        "coo_engine_sharded": coo["pallas"]["engine"],
        "coo_layout_sharded": coo["pallas"]["layout"],
        "kv_probe_ops_per_sec_xla_sharded":
            round(kv["xla"]["ops_s"], 2),
        "kv_probe_ops_per_sec_pallas_sharded":
            round(kv["pallas"]["ops_s"], 2),
        "kv_probe_speedup_pallas_sharded_vs_xla":
            round(kv["pallas"]["ops_s"] / kv["xla"]["ops_s"], 3),
        "coo_scatter_ops_per_sec_xla_sharded":
            round(coo["xla"]["ops_s"], 2),
        "coo_scatter_ops_per_sec_pallas_sharded":
            round(coo["pallas"]["ops_s"], 2),
        "coo_scatter_speedup_pallas_sharded_vs_xla":
            round(coo["pallas"]["ops_s"] / coo["xla"]["ops_s"], 3),
    }


def main() -> None:
    # flat lanes pinned to ONE device: the flat engines' numbers must
    # not shift with host device count (the sharded lane re-inits)
    core.init(devices=jax.devices()[:1], data_parallel=1,
              model_parallel=1)
    telemetry.beat()
    interpret = tk.interpret_mode()

    kv = {m: bench_kv(m) for m in ("xla", "pallas")}
    rowsb = {m: bench_rows(m) for m in ("xla", "pallas")}
    coo = {m: bench_coo(m) for m in ("xla", "pallas")}
    sharded = bench_sharded()

    # parity guard: a wrong kernel must fail loudly, not win the bench
    for a, b in zip(kv["xla"]["final"], kv["pallas"]["final"]):
        assert np.array_equal(a, b), "kv probe engines diverged"
    assert np.array_equal(rowsb["xla"]["final"], rowsb["pallas"]["final"]), \
        "row scatter engines diverged"
    assert np.array_equal(coo["xla"]["final"], coo["pallas"]["final"]), \
        "coo scatter engines diverged"

    counters = telemetry.registry().snapshot()["counters"]
    fallbacks = sum(v for k, v in counters.items()
                    if k.startswith("kernels.fallbacks"))

    line = {
        "metric": "kv_probe_ops_per_sec_pallas",
        "value": round(kv["pallas"]["probe_ops_s"], 2),
        "unit": "dispatch/s",
        "tiny": TINY,
        "interpret": interpret,
        "backend": jax.default_backend(),
        "parity_checked": True,
        # which engine each "pallas" section ACTUALLY ran (a layout the
        # lane slicer can't express keeps xla — the watched throughput
        # must not silently measure the wrong engine)
        "kv_engine": kv["pallas"]["engine"],
        "row_engine": rowsb["pallas"]["engine"],
        "coo_engine": coo["pallas"]["engine"],
        "kv_probe_ops_per_sec_xla": round(kv["xla"]["probe_ops_s"], 2),
        "kv_probe_ops_per_sec_pallas":
            round(kv["pallas"]["probe_ops_s"], 2),
        "kv_probe_speedup_pallas_vs_xla":
            round(kv["pallas"]["probe_ops_s"] / kv["xla"]["probe_ops_s"],
                  3),
        "kv_probe_keys_per_sec_xla": round(kv["xla"]["probe_keys_s"], 1),
        "kv_probe_keys_per_sec_pallas":
            round(kv["pallas"]["probe_keys_s"], 1),
        "kv_probe_bytes_per_op_model": kv["xla"]["bytes_per_op_model"],
        "kv_lookup_ops_per_sec_xla": round(kv["xla"]["lookup_ops_s"], 2),
        "kv_lookup_ops_per_sec_pallas":
            round(kv["pallas"]["lookup_ops_s"], 2),
        "row_gather_ops_per_sec_xla":
            round(rowsb["xla"]["gather_ops_s"], 2),
        "row_gather_ops_per_sec_pallas":
            round(rowsb["pallas"]["gather_ops_s"], 2),
        "row_scatter_ops_per_sec_xla":
            round(rowsb["xla"]["scatter_ops_s"], 2),
        "row_scatter_ops_per_sec_pallas":
            round(rowsb["pallas"]["scatter_ops_s"], 2),
        "coo_scatter_ops_per_sec_xla": round(coo["xla"]["ops_s"], 2),
        "coo_scatter_ops_per_sec_pallas":
            round(coo["pallas"]["ops_s"], 2),
        "coo_scatter_speedup_pallas_vs_xla":
            round(coo["pallas"]["ops_s"] / coo["xla"]["ops_s"], 3),
        "coo_scatter_bytes_per_op_model":
            coo["xla"]["bytes_per_op_model"],
        "kernels_fallbacks": fallbacks,
    }
    line.update(sharded)        # {} on single-device hosts
    out = os.environ.get("MVTPU_KERNEL_BENCH_JSON",
                         "table_kernels_bench.json")
    with open(out, "w") as f:
        json.dump(line, f, indent=1)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
