#!/usr/bin/env python3
"""One parameter-server worker of the served cells: a jax-free process
in a closed loop over ``client/transport.py`` (loaded by file path: the
package's ``__init__`` imports jax, and the chip belongs to the server).

    kv_client.py <spec.json>

It connects, opens the table, warms the shapes it is told to, prints
``ready``, waits for ``go <epoch seconds>`` on stdin, then loops until
the deadline: get the minibatch's unique keys, wait, add their gradients
``sync=True``, wait. It writes what it saw to ``spec["out"]`` (.npz) and
exits 0.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import time

import numpy as np


def _load(root: str, name: str, *relpath: str):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(root, *relpath))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main(argv) -> int:
    with open(argv[0]) as f:
        spec = json.load(f)
    root = spec["root"]
    sys.path.insert(0, root)
    transport = _load(root, "multiverso_tpu.client.transport",
                      "multiverso_tpu", "client", "transport.py")
    traffic_gen = _load(root, "perf_kv_traffic", "perf", "kv_traffic.py")
    sizes, traffic = spec["sizes"], spec["traffic"]
    me, seed = int(spec["client"]), int(spec["seed"])
    option = {"learning_rate": sizes["ftrl"]["alpha"],
              "momentum": sizes["ftrl"]["beta"],
              "lam": sizes["ftrl"]["l1"], "rho": sizes["ftrl"]["l2"]}
    lat, kinds, nkeys = [], [], []
    unread = failed = it = 0
    mine = set()
    with transport.connect(spec["address"], client=f"bench-{me}",
                           quant=None, deadline_s=None) as client:
        table = client.create_kv(
            spec["table"], sizes["capacity"], value_dim=sizes["value_dim"],
            dtype=sizes["dtype"], updater=sizes["updater"])
        for n in spec["warm_adds"]:
            keys, grads = traffic_gen.warm_frame(n, me)
            table.add(keys, grads, option=option, sync=True)
        for n in spec["warm_gets"]:
            table.get(traffic_gen.warm_frame(n, me)[0])
        print("ready", flush=True)
        go = sys.stdin.readline().split()
        start, end = float(go[1]), float(go[1]) + float(spec["seconds"])
        while time.time() < start:
            time.sleep(0.0005)
        while time.time() < end and it < int(spec["reach"]):
            keys, grads = traffic_gen.minibatch(seed, me, it, sizes,
                                                traffic)
            for kind in traffic["loop"]:
                t0 = time.perf_counter()
                try:
                    if kind == "get":
                        _values, found = table.get(keys)
                        if mine:
                            was = np.fromiter(
                                (int(k) in mine for k in keys), bool,
                                len(keys))
                            unread += int((was & ~np.asarray(
                                found, bool)).sum())
                    else:
                        table.add(keys, grads, option=option, sync=True)
                        mine.update(keys.tolist())
                except Exception as exc:  # noqa: BLE001 — counted, shown
                    failed += 1
                    print(f"client {me} {kind} failed: {exc!r}",
                          file=sys.stderr, flush=True)
                    lat.append(float("inf"))
                else:
                    lat.append(time.perf_counter() - t0)
                kinds.append(kind == "get")
                nkeys.append(len(keys))
            it += 1
        finished = time.time()
    np.savez(spec["out"], latency=np.asarray(lat), is_get=np.asarray(kinds),
             keys=np.asarray(nkeys), iterations=it, failed=failed,
             unread=unread, finished=finished)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
