"""Micro-bench: TieredKVTable training with a device budget SMALLER
than the table (multiverso_tpu/storage).

The acceptance shape of the tiered store (ISSUE 10): an embedding
table larger than the configured HBM budget trains to completion with
ZERO overflow raises — capacity pressure becomes demotion + retry
through host RAM and the disk spill file — and a tiered checkpoint
resumes bit-identically. This bench drives exactly that:

- a skewed get/add stream (hot set that fits on device + a uniform
  cold tail that cannot) over a ``TieredKVTable`` whose
  ``device_buckets`` budget is a fraction of the logical geometry,
- throughput of the add and get paths under the fault-in churn,
- the tier telemetry deltas (``storage.{hits,misses,demotions,
  fills}``) — the run FAILS if nothing demoted or no fill came back
  from disk, i.e. if the bench silently stopped exercising the tiers,
- a ``RunCheckpointManager`` save + resume into a fresh table, with a
  bit-identity check over every written key.

Emits ONE final JSON line in the bench metric-line shape
(``tools/bench_diff.py`` compares runs; ``tiered_kv_get_ops_per_sec``
is on DEFAULT_WATCH, ``tiered_kv_miss_ratio`` is a LOWER-is-better
watch) and writes the same document to ``tiered_kv_bench.json``
(override: ``MVTPU_TIER_BENCH_JSON``).

``MVTPU_TIER_BENCH_TINY=1`` shrinks sizes for the CI smoke run and
pins the CPU platform.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

TINY = os.environ.get("MVTPU_TIER_BENCH_TINY", "").lower() \
    not in ("", "0", "false")
CPU = TINY or os.environ.get("MVTPU_TIER_BENCH_CPU", "").lower() \
    not in ("", "0", "false")

if CPU:
    # must precede any backend touch: a CPU run must not take the chip
    # (one process per chip — see tests/conftest.py)
    import jax
    jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

from multiverso_tpu import core, telemetry  # noqa: E402
from multiverso_tpu.ft.checkpoint import RunCheckpointManager  # noqa: E402
from multiverso_tpu.storage import TieredKVTable  # noqa: E402

# population keys, batch, steps; budgets in BUCKETS (slots=8 lanes
# each) — device holds ~1/16 of the logical geometry, host ~1/32, the
# rest is disk/virgin, so the cold tail MUST ride all three tiers
SIZES = dict(population=1 << 14, batch=1 << 10, steps=6, value_dim=8,
             slots=8, device_buckets=256, host_buckets=128,
             hot_frac=0.75)
if TINY:
    SIZES = dict(population=1 << 10, batch=1 << 7, steps=3, value_dim=4,
                 slots=8, device_buckets=32, host_buckets=16,
                 hot_frac=0.75)


def _counter_sum(snap: dict, name: str, **labels) -> float:
    """Sum snapshot counters named ``name`` whose label string carries
    every given ``k=v`` pair (label order in the key is not ours)."""
    total = 0.0
    want = [f"{k}={v}" for k, v in labels.items()]
    for key, val in snap.get("counters", {}).items():
        base, _, lbl = key.partition("{")
        if base == name and all(w in lbl for w in want):
            total += val
    return total


def _batch(rng, hot, population, n):
    """Skewed unique key batch: ``hot_frac`` from the device-sized hot
    set, the rest uniform over the whole population (the miss tail)."""
    n_hot = int(n * SIZES["hot_frac"])
    cold = rng.choice(population, size=n - n_hot, replace=False)
    mix = np.unique(np.concatenate(
        [rng.choice(hot, size=n_hot, replace=False),
         cold.astype(np.uint64) + np.uint64(len(hot))]))
    rng.shuffle(mix)
    return mix


def main() -> None:
    core.init()
    rng = np.random.default_rng(0)
    population = SIZES["population"]
    dim = SIZES["value_dim"]
    # hot set sized to ~half the device budget so it really stays hot
    hot = np.arange(1, SIZES["device_buckets"] * SIZES["slots"] // 2,
                    dtype=np.uint64)
    spill_dir = tempfile.mkdtemp(prefix="mvtpu_tier_bench_")
    run_dir = tempfile.mkdtemp(prefix="mvtpu_tier_bench_ckpt_")
    out = {}
    try:
        kw = dict(value_dim=dim, updater="adagrad",
                  slots_per_bucket=SIZES["slots"],
                  device_buckets=SIZES["device_buckets"],
                  host_buckets=SIZES["host_buckets"],
                  spill_dir=spill_dir)
        t = TieredKVTable(population * 2, name="tiered_bench", **kw)
        assert t.tiers.device_buckets < t.total_buckets, \
            "bench must run with device budget < table size"
        # warmup: compile the probe/lookup + tier gather/scatter jits
        wk = _batch(rng, hot, population, SIZES["batch"])
        t.add(wk, np.ones((len(wk), dim), np.float32), sync=True)
        t.get(wk[: SIZES["batch"] // 4])

        snap0 = telemetry.snapshot()
        written = [wk]
        t0 = time.perf_counter()
        n_add = 0
        for _ in range(SIZES["steps"]):
            keys = _batch(rng, hot, population, SIZES["batch"])
            t.add(keys, rng.normal(size=(len(keys), dim))
                  .astype(np.float32), sync=True)
            written.append(keys)
            n_add += len(keys)
        add_dt = time.perf_counter() - t0

        t0 = time.perf_counter()
        n_get = 0
        for _ in range(SIZES["steps"]):
            keys = _batch(rng, hot, population, SIZES["batch"])
            np.asarray(t.get(keys)[0])
            n_get += len(keys)
        get_dt = time.perf_counter() - t0

        snap1 = telemetry.snapshot()

        def delta(name, **labels):
            return _counter_sum(snap1, name, table="tiered_bench",
                                **labels) - \
                _counter_sum(snap0, name, table="tiered_bench", **labels)

        hits = delta("storage.hits")
        misses = delta("storage.misses")
        demotions = delta("storage.demotions")
        disk_fills = delta("storage.fills", tier="disk")
        # the acceptance gates: the tiers were genuinely exercised
        assert demotions > 0, "no demotions — budget not under pressure"
        assert disk_fills > 0, "no disk fills — cold tier never read"

        # -- tiered checkpoint: bit-identical resume ---------------------
        ckpt = RunCheckpointManager(run_dir, keep=2, tables=[t],
                                    background=False)
        ckpt.save(1, {"step": SIZES["steps"]})
        # the restore table gets its OWN spill dir: two live tables
        # with one spill path would clobber each other's cold records
        kw_r = dict(kw, spill_dir=os.path.join(spill_dir, "resume"))
        r = TieredKVTable(population * 2, name="tiered_bench", **kw_r)
        restore = RunCheckpointManager(run_dir, keep=2, tables=[r],
                                       background=False)
        assert restore.resume() is not None
        all_keys = np.unique(np.concatenate(written))
        va, fa = t.get(all_keys)
        vb, fb = r.get(all_keys)
        assert np.array_equal(fa, fb), "found flags diverged on resume"
        assert np.array_equal(va, vb), \
            "resumed values are not bit-identical"
        assert len(r) == len(t)

        out.update({
            "metric": "tiered_kv_get_ops_per_sec",
            "value": round(n_get / get_dt, 2),
            "unit": "keys/s",
            "tiered_kv_get_ops_per_sec": round(n_get / get_dt, 2),
            "tiered_kv_add_ops_per_sec": round(n_add / add_dt, 2),
            "tiered_kv_miss_ratio":
                round(misses / max(hits + misses, 1.0), 4),
            "tiered_kv_demotions": demotions,
            "tiered_kv_disk_fills": disk_fills,
            "tiered_kv_overflow_raises": 0,
            "tiered_kv_resume_bitident": 1,
            "tiered_kv_total_buckets": t.total_buckets,
            "tiered_kv_device_buckets": t.tiers.device_buckets,
            "tiny": int(TINY),
        })
    finally:
        shutil.rmtree(spill_dir, ignore_errors=True)
        shutil.rmtree(run_dir, ignore_errors=True)

    path = os.environ.get("MVTPU_TIER_BENCH_JSON", "tiered_kv_bench.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
