"""Driver of the served key-value cells: this process holds the chip and
hosts the ``TableServer`` exactly as ``server/__main__.py`` builds it
(``core.init()``, then the server on a unix address), so that it can
trace the device; the workers are jax-free child processes
(``kv_client.py``) in a closed loop. Nothing outlives the run.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np

from perf import kv_traffic, program
from perf.reference import ftrl as ref

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
RUN_DIR = os.path.join(ROOT, ".perf_run")

TINY = {
    "program": {"capacity": 1 << 16,
                "field_cardinalities": [8, 16, 300, 2000, 50]},
    "traffic": {"clients": 2, "minibatch": 16, "least_padded": 64,
                "readback_keys": 2048, "most_iterations_per_s": 60},
}


class Cell:
    def __init__(self, *, config, traffic, seed, seconds, chips, devices,
                 tiny, log):
        self.cfg = dict(config)
        self.sizes = dict(config["program"])
        self.traffic = dict(traffic)
        self.limits = dict(config["correct"]["limits"])
        if tiny:
            self.sizes.update(TINY["program"])
            self.traffic.update(TINY["traffic"])
        self.seed, self.seconds = int(seed), float(seconds)
        self.chips, self.devices, self.tiny, self.log = (chips, devices,
                                                         tiny, log)
        self.server = None
        self.procs = []

    # -- set-up ------------------------------------------------------------

    def setup(self) -> None:
        from multiverso_tpu.server.table_server import TableServer

        program.init_mesh(self.cfg, self.traffic, self.chips,
                          self.devices)
        shutil.rmtree(RUN_DIR, ignore_errors=True)
        os.makedirs(RUN_DIR)
        self.server = TableServer(
            "unix:" + os.path.join(RUN_DIR, "kv.sock"), name="bench")
        self.address = self.server.start()
        env = dict(os.environ)
        env.pop("JAX_PLATFORMS", None)
        n = int(self.traffic["clients"])
        # every worker's frames, as far as the window can reach: the
        # program compiles one slice a distinct request size, so the
        # first worker warms every size that is to come
        reach = 2 + int(self.seconds
                        * float(self.traffic["most_iterations_per_s"]))
        self.frames = [[kv_traffic.minibatch(self.seed, c, i, self.sizes,
                                             self.traffic)
                        for i in range(reach)] for c in range(n)]
        lens = sorted({len(k) for fr in self.frames for k, _ in fr})
        warm_gets = lens
        warm_adds = sorted({(x - 1).bit_length() for x in lens})
        warm_adds = [(1 << b) - 1 for b in warm_adds]
        for i in range(n):
            spec = {"root": ROOT, "address": self.address, "client": i,
                    "seed": self.seed, "seconds": self.seconds,
                    "reach": reach,
                    "table": "criteo_w", "sizes": self.sizes,
                    "traffic": self.traffic,
                    "warm_gets": warm_gets if i == 0 else [],
                    "warm_adds": warm_adds if i == 0 else [],
                    "out": os.path.join(RUN_DIR, f"client{i}.npz")}
            path = os.path.join(RUN_DIR, f"client{i}.json")
            with open(path, "w") as f:
                json.dump(spec, f)
            self.procs.append(subprocess.Popen(
                [sys.executable, os.path.join(HERE, "kv_client.py"), path],
                cwd=ROOT, env=env, stdin=subprocess.PIPE,
                stdout=subprocess.PIPE, text=True))
            if i == 0:      # the first creates the table and warms it
                self._expect_ready(self.procs[0])
        for p in self.procs[1:]:
            self._expect_ready(p)

    def _expect_ready(self, proc) -> None:
        line = proc.stdout.readline()
        if line.strip() != "ready":
            raise RuntimeError(f"a worker did not come up: {line!r} "
                               f"(rc={proc.poll()})")

    # -- the window ----------------------------------------------------------

    registry_snapshot = staticmethod(program.registry_snapshot)

    def window(self, seconds: float) -> dict:
        import jax
        start = time.time() + 0.25
        with jax.profiler.TraceAnnotation("bench.serve.window"):
            for p in self.procs:
                p.stdin.write(f"go {start!r}\n")
                p.stdin.flush()
            rcs = [p.wait(timeout=seconds + 120) for p in self.procs]
        if any(rcs):
            raise RuntimeError(f"workers exited with {rcs}")
        self.procs = []
        outs = [np.load(os.path.join(RUN_DIR, f"client{i}.npz"))
                for i in range(len(rcs))]
        self.iterations = [int(o["iterations"]) for o in outs]
        lat = np.concatenate([o["latency"] for o in outs])
        keys = np.concatenate([o["keys"] for o in outs])
        is_get = np.concatenate([o["is_get"] for o in outs])
        ok = np.isfinite(lat)
        # from the common start to the last worker's last acknowledgement
        elapsed = max(float(o["finished"]) for o in outs) - start
        self.unread = sum(int(o["unread"]) for o in outs)
        failed = int((~ok).sum())
        p95 = float(np.percentile(np.where(ok, lat, np.inf), 95,
                                  method="higher")) * 1e3
        return {"attempted": len(lat), "failed": failed,
                "metrics": {"served_keys_per_s":
                            float(keys[ok].sum()) / elapsed,
                            "served_p95_ms": p95},
                "work": {"get_keys": int(keys[ok & is_get].sum()),
                         "add_keys": int(keys[ok & ~is_get].sum()),
                         "requests": int(ok.sum())},
                "values": {"serve_p50_ms": float(np.median(lat[ok])) * 1e3,
                           "window_s": elapsed}}

    # -- what correct compares ---------------------------------------------

    def collect(self) -> None:
        """Replay every worker's acknowledged adds to learn who wrote
        which key, read a sample of the written keys back over the wire,
        then stop the server and free the table."""
        from multiverso_tpu.client import transport

        self.frames = [fr[:n] for fr, n in zip(self.frames,
                                               self.iterations)]
        per_client = [np.unique(np.concatenate([k for k, _ in fr]))
                      if fr else np.zeros(0, np.uint64)
                      for fr in self.frames]
        allk, writers = np.unique(np.concatenate(per_client),
                                  return_counts=True)
        rng = np.random.default_rng([self.seed, 0xC0FFEE])
        take = min(int(self.traffic["readback_keys"]), len(allk))
        pick = np.sort(rng.choice(len(allk), take, replace=False))
        self.sample, self.sample_writers = allk[pick], writers[pick]
        values, found = [], []
        with transport.connect(self.address, client="bench-readback",
                               quant=None, deadline_s=None) as client:
            table = client.create_kv(
                "criteo_w", self.sizes["capacity"],
                value_dim=self.sizes["value_dim"],
                dtype=self.sizes["dtype"], updater=self.sizes["updater"])
            step = kv_traffic.padded_sizes(self.sizes, self.traffic)[0] - 1
            for lo in range(0, take, step):
                v, f = table.get(self.sample[lo:lo + step])
                values.append(np.asarray(v, np.float32).reshape(-1))
                found.append(np.asarray(f, bool))
        self.values = np.concatenate(values)
        self.found = np.concatenate(found)
        self._stop_server()
        program.free()

    def check(self) -> list:
        t0 = time.perf_counter()
        single = self.sample[self.sample_writers == 1]
        table = ref.Table(single, dtype="float32", **self.sizes["ftrl"])
        for frames in self.frames:
            for keys, grads in frames:
                table.add(keys, grads)
        got = self.values[self.sample_writers == 1]
        scale = np.maximum(np.abs(table.w), np.float32(1e-3))
        gap = float(np.max(np.abs(got - table.w) / scale)) \
            if len(single) else None
        self.log(f"read back {len(self.sample)} keys, {len(single)} with "
                 f"one writer, {int((table.w != 0).sum())} of them "
                 f"non-zero; reference took "
                 f"{time.perf_counter() - t0:.1f} s")
        return [
            {"name": "ftrl_value_gap", "value": gap,
             "limit": self.limits["ftrl_value_gap"]},
            {"name": "written_keys_not_found",
             "value": int((~self.found).sum()), "limit": 0},
            {"name": "acked_adds_not_read", "value": self.unread,
             "limit": 0},
        ]

    def _stop_server(self) -> None:
        if self.server is not None:
            self.server.stop()
            self.server = None

    def close(self) -> None:
        for p in self.procs:
            p.kill()
            p.wait()
        self.procs = []
        self._stop_server()
        shutil.rmtree(RUN_DIR, ignore_errors=True)
