"""Micro-bench: run-level checkpoint store/restore throughput
(multiverso_tpu/ft).

Measures, on whatever mesh ``core.init()`` builds (CPU-safe):

- ``RunCheckpointManager.save`` committed synchronously — store MB/s
  over the full generation (table exports + npz + CRC stamp + atomic
  manifest commit),
- the background-overlap win: wall time the TRAINING thread spends in
  ``save()`` (dispatch half only) vs the synchronous commit,
- ``resume`` restore MB/s (scan + CRC-verified table loads + app state).

Emits ONE final JSON line in the bench metric-line shape (flat numeric
keys — ``tools/bench_diff.py`` compares two runs; ``ckpt_store_mb_per_sec``
is on its DEFAULT_WATCH list so a regression fails ``make bench-diff``)
and writes the same document to ``checkpoint_bench.json`` (override:
``MVTPU_CKPT_BENCH_JSON``).

``MVTPU_CKPT_BENCH_TINY=1`` shrinks sizes for the CI smoke run and pins
the CPU platform.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

TINY = os.environ.get("MVTPU_CKPT_BENCH_TINY", "").lower() \
    not in ("", "0", "false")
CPU = TINY or os.environ.get("MVTPU_CKPT_BENCH_CPU", "").lower() \
    not in ("", "0", "false")

if CPU:
    # must precede any backend touch: a CPU run must not take the chip
    # (one process per chip — see tests/conftest.py)
    import jax
    jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

from multiverso_tpu import core  # noqa: E402
from multiverso_tpu.ft.checkpoint import RunCheckpointManager  # noqa: E402
from multiverso_tpu.tables import ArrayTable, MatrixTable  # noqa: E402

# (dense rows, matrix rows x dim, repeats)
SIZES = dict(dense_n=1 << 20, rows=4096, dim=256, repeats=5)
if TINY:
    SIZES = dict(dense_n=1 << 12, rows=128, dim=16, repeats=2)


def _tables():
    t1 = ArrayTable(SIZES["dense_n"], "float32", updater="adagrad",
                    name="ckpt_bench_dense")
    t1.add(np.ones(SIZES["dense_n"], np.float32))
    t2 = MatrixTable(SIZES["rows"], SIZES["dim"], "float32",
                     name="ckpt_bench_matrix")
    t2.add(np.ones((SIZES["rows"], SIZES["dim"]), np.float32))
    return [t1, t2]


def _gen_bytes(run_dir: str, step: int) -> int:
    gen = os.path.join(run_dir, f"gen-{step:010d}")
    return sum(os.path.getsize(os.path.join(gen, f))
               for f in os.listdir(gen))


def main() -> None:
    core.init()
    tables = _tables()
    app_state = {"epoch_done": 3, "cursor": np.arange(1024)}
    run_dir = tempfile.mkdtemp(prefix="mvtpu_ckpt_bench_")
    out = {}
    try:
        # -- synchronous store throughput --------------------------------
        sync = RunCheckpointManager(run_dir, keep=2, tables=tables,
                                    background=False)
        sync.save(1, app_state)     # warmup (jit the export copiers)
        nbytes = _gen_bytes(run_dir, 1)
        t0 = time.perf_counter()
        for i in range(SIZES["repeats"]):
            sync.save(2 + i, app_state)
        dt = time.perf_counter() - t0
        out["ckpt_store_mb_per_sec"] = \
            nbytes * SIZES["repeats"] / dt / 1e6
        out["ckpt_generation_mb"] = nbytes / 1e6
        out["ckpt_store_s"] = dt / SIZES["repeats"]

        # -- background-overlap: caller-visible save cost ----------------
        bg = RunCheckpointManager(run_dir, keep=2, tables=tables)
        last = 2 + SIZES["repeats"]
        t0 = time.perf_counter()
        for i in range(SIZES["repeats"]):
            bg.save(last + i, app_state)
        dispatch_dt = time.perf_counter() - t0
        bg.flush()
        bg.close()
        out["ckpt_save_dispatch_s"] = dispatch_dt / SIZES["repeats"]
        out["ckpt_overlap_speedup"] = \
            out["ckpt_store_s"] / max(out["ckpt_save_dispatch_s"], 1e-9)

        # -- restore throughput ------------------------------------------
        restore = RunCheckpointManager(run_dir, keep=2, tables=tables,
                                       background=False)
        t0 = time.perf_counter()
        for _ in range(SIZES["repeats"]):
            st = restore.resume()
            assert st is not None
        dt = time.perf_counter() - t0
        out["ckpt_restore_mb_per_sec"] = \
            nbytes * SIZES["repeats"] / dt / 1e6
        out["ckpt_restore_s"] = dt / SIZES["repeats"]
    finally:
        shutil.rmtree(run_dir, ignore_errors=True)

    out["tiny"] = int(TINY)
    doc = json.dumps({k: (round(v, 4) if isinstance(v, float) else v)
                      for k, v in out.items()})
    path = os.environ.get("MVTPU_CKPT_BENCH_JSON", "checkpoint_bench.json")
    with open(path, "w") as f:
        f.write(doc + "\n")
    print(doc)


if __name__ == "__main__":
    main()
