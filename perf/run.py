#!/usr/bin/env python3
"""The benchmark's one command:

    python3 perf/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data found by name from ``BENCHMARK.json``:
its configuration (``perf/configs/<config>.json``), its traffic mix
(``perf/traffic/<traffic>.json``), the driver the configuration names
(``perf/drivers/<driver>.py``) and, in a traced run, the reader of each
per-layer metric (``perf/layer_metrics/<metric>.json`` or ``.py``).

A run builds the cell from ``--seed``, warms up every shape (set-up),
measures for ``--seconds``, reads the device's memory peak, frees the
program, decides ``correct`` against the plain reference, and prints ONE
JSON object as the last line of stdout. Without a TPU holding the chips
the cell asks for it exits non-zero and prints no result.

A driver (``perf/drivers/<name>.py``, named by the configuration's
``driver`` key) brings one class, ``Cell``. It is given, by keyword:
``config`` (the configuration's file), ``traffic`` (the mix's file),
``seed``, ``seconds``, ``chips``, ``devices`` (that many of jax's),
``tiny`` (true in a rehearsal: the driver's own small sizes) and ``log``
(a line to stderr). It provides, called in this order:

- ``setup()``: build the ONE object the window will drive, from the
  seed; drive it through its checked first steps and every shape the
  window uses. All of it is ``setup_s``.
- ``registry_snapshot()``: the program's counters and histograms
  (``perf/program.py``), taken before and after the window.
- ``window(seconds) -> {"metrics", "attempted", "failed", "work",
  "values"}``: the cell's end-to-end metrics other than ``setup_s`` by
  name; ``work`` is the counts of the window's work that the work models
  take, ``values`` whatever a ``value`` reader reads.
- ``collect()``: bring what ``correct`` compares to the host and free the
  program (``program.free``); the memory peak has been read before it.
- ``check() -> [{"name", "value", "limit"}]``: the plain reference
  (``perf/reference/``) runs HERE, after ``collect`` freed the program,
  in no metric; a value over its limit, or ``None``, is not correct.
- ``close()``: always called; stops what the driver started.

``config["program"]`` is what the work models (``perf/work_models.py``,
``perf/work/<name>.py``) and the readers get as ``sizes``.

``--rehearse-cpu`` is a debugging aid for a host without a chip: the
driver's own tiny sizes on virtual CPU devices. Its line says
``"platform": "cpu"`` and ``"rehearsal": true`` and carries no metric.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()       # process start, for setup_s

import argparse                      # noqa: E402
import json                          # noqa: E402
import os                            # noqa: E402
import shutil                        # noqa: E402
import sys                           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

EXIT_NO_CHIP = 3
TRACE_DIR = os.path.join(ROOT, ".perf_trace")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """The cell's entry with its configuration, traffic and metrics."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(has: {sorted(cells)})")
    cell = dict(cells[name])
    configs = {c["name"]: c for c in bench["configs"]}
    cell["config_data"] = load_json(
        os.path.join(ROOT, configs[cell["config"]]["file"]))
    cell["traffic_data"] = load_json(
        os.path.join(HERE, "traffic", f"{cell['traffic']}.json"))

    def reported(metric: dict) -> bool:
        return "workloads" not in metric or name in metric["workloads"]

    cell["end_to_end"] = [m for m in bench["end_to_end"] if reported(m)]
    e2e = {m["name"] for m in cell["end_to_end"]}
    cell["per_layer"] = [m for m in bench["per_layer"]
                         if reported(m) and m["moves"] in e2e]
    return cell


def load_driver(name: str):
    from perf import program
    return program.load_module(
        f"perf_driver_{name}", os.path.join(HERE, "drivers", f"{name}.py"))


class CompileCount:
    """Programs jax compiled (or fetched from the persistent cache),
    from its own monitoring events: none may fall inside the window."""

    def __init__(self) -> None:
        import jax.monitoring as mon
        self.n = 0
        mon.register_event_duration_secs_listener(self._duration)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def place_compile_cache() -> None:
    """Fixed path inside the checkout (the path is in the cache's key),
    unless ``JAX_COMPILATION_CACHE_DIR`` names one; the program's
    ``core.init`` follows the same rule and lands in the same place."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def device_block(devices, chips: int) -> dict:
    peak = 0
    for d in devices[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": chips, "memory_peak_bytes": peak}


def say(stamp: str, msg: str) -> None:
    print(f"[perf {stamp}] {msg}", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    chips = int(cell["chips"])
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4")

    import jax
    if args.rehearse_cpu:       # a rehearsal leaves no programs behind
        jax.config.update("jax_enable_compilation_cache", False)
    else:
        place_compile_cache()
    devices = jax.devices()
    platform = devices[0].platform
    stamp = f"{platform}/{devices[0].device_kind}/x{len(devices)}"
    if not args.rehearse_cpu and (platform != "tpu"
                                  or len(devices) < chips):
        say(stamp, f"{args.workload} needs {chips} TPU chip(s); "
            "no result")
        return EXIT_NO_CHIP
    compiles = CompileCount()

    driver = load_driver(cell["config_data"]["driver"])
    run = driver.Cell(config=cell["config_data"],
                      traffic=cell["traffic_data"], seed=args.seed,
                      seconds=args.seconds, chips=chips,
                      devices=devices[:chips],
                      tiny=args.rehearse_cpu,
                      log=lambda m: say(stamp, m))
    try:
        run.setup()
        setup_s = time.perf_counter() - T_START
        say(stamp, f"set-up {setup_s:.1f} s")

        trace_on = bool(args.trace) and not args.rehearse_cpu
        before = run.registry_snapshot()
        n0 = compiles.n
        if trace_on:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(TRACE_DIR)
        with jax.profiler.TraceAnnotation("bench.window"):
            result = run.window(args.seconds)
        if trace_on:
            jax.profiler.stop_trace()
        compiled_in_window = compiles.n - n0
        after = run.registry_snapshot()
        device = device_block(devices, chips)

        run.collect()                 # outputs to the host; program freed
        checks = run.check()          # the plain reference runs here
    finally:
        run.close()
    checks.append({"name": "compiles_in_window",
                   "value": compiled_in_window, "limit": 0})
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks)

    values = dict(result["metrics"])
    values["setup_s"] = setup_s
    line = {"correct": bool(correct),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "metrics": {},
            "device": device}
    if args.rehearse_cpu:
        line["rehearsal"] = True
    elif trace_on:
        from perf import layer_readers, program_readers, reduce_trace
        trace = reduce_trace.reduce(
            reduce_trace.load(reduce_trace.find_xplane(TRACE_DIR)),
            span_names=program_readers.span_names(after))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        ctx = {"trace": trace, "before": before, "after": after,
               "work": result["work"], "values": result.get("values", {}),
               "sizes": cell["config_data"]["program"],
               "device_kind": device["kind"], "chips": chips}
        for m in cell["per_layer"]:
            v = layer_readers.read(m["name"], ctx)
            if v is not None:
                line["metrics"][m["name"]] = {"value": v,
                                              "unit": m["unit"]}
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        line["breakdown"] = {"device_ops": trace["device_ops"],
                             "idle_gaps": trace["idle_gaps"]}
    else:
        for m in cell["end_to_end"]:
            if values.get(m["name"]) is not None:
                line["metrics"][m["name"]] = {"value": values[m["name"]],
                                              "unit": m["unit"]}
    line["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                      for c in checks}
    for c in checks:
        ok = c["value"] is not None and c["value"] <= c["limit"]
        say(stamp, f"check {c['name']}: {c['value']} (limit {c['limit']})"
            f"{'' if ok else '  <-- NOT CORRECT'}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
