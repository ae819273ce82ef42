"""Readers of per-layer metrics. A metric is a data file
``perf/layer_metrics/<name>.json`` whose ``reader`` names one of the
kinds below; a metric that needs another kind brings
``perf/layer_metrics/<name>.py`` with ``read(ctx) -> float | None``.

``ctx`` holds what one traced run gives: ``trace`` (the reduction of
``reduce_trace.reduce``), ``before`` / ``after`` (registry snapshots
around the window), ``work`` (counts of the window's work, from the
driver), ``values`` (numbers the driver measured itself), ``sizes`` (the
configuration's ``program`` block), ``device_kind`` and ``chips``.

A work model (``work_model`` of ``op_roofline`` and ``step_mfu``) is one
of ``perf/work_models.py`` or, for a trainer added later, a file
``perf/work/<name>.py`` with ``work(sizes, work) -> {"bytes", "flops"}``.

A reader that finds nothing to read returns ``None`` and the harness
leaves the metric out of the line; it never returns 0 for a share of a
roofline or of a peak.
"""

from __future__ import annotations

import json
import os
from typing import Callable, Dict, Optional

from perf import peaks, program, reduce_trace, work_models

HERE = os.path.dirname(os.path.abspath(__file__))


def work_model(name: str) -> Callable[[dict, dict], dict]:
    if name in work_models.MODELS:
        return work_models.MODELS[name]
    own = os.path.join(HERE, "work", f"{name}.py")
    if not os.path.exists(own):
        raise KeyError(
            f"no work model {name!r}: neither in perf/work_models.py "
            f"(has: {sorted(work_models.MODELS)}) nor a file {own}")
    return program.load_module(f"perf_work_{name}", own).work


def _least(ctx: dict, model: str) -> float:
    work = work_model(model)(ctx["sizes"], ctx["work"])
    return peaks.least_seconds(work, ctx["device_kind"],
                               ctx["chips"])["seconds"]


def op_roofline(ctx: dict, spec: dict) -> Optional[float]:
    """Least time for the ops' work over their device time, in %.
    Device time is the mean over the chips; the least time is that of
    all the chips together, so both are of one chip's share."""
    t = reduce_trace.op_time(ctx["trace"], spec["ops"])
    if t <= 0.0:
        return None
    return 100.0 * _least(ctx, spec["work_model"]) / t


def step_mfu(ctx: dict, spec: dict) -> Optional[float]:
    """Least time for the whole window's work over the window, in %."""
    if not ctx["work"] or ctx["trace"]["window_s"] <= 0.0:
        return None
    return 100.0 * _least(ctx, spec["work_model"]) \
        / ctx["trace"]["window_s"]


def op_share(ctx: dict, spec: dict) -> Optional[float]:
    """Device time of the ops over device busy time, in %."""
    busy = ctx["trace"]["busy_s"]
    t = reduce_trace.op_time(ctx["trace"], spec["ops"])
    if busy <= 0.0 or t <= 0.0:
        return None
    return 100.0 * t / busy


def idle_share(ctx: dict, spec: dict) -> Optional[float]:
    return ctx["trace"]["idle_share_pct"]


def collective_exposed_share(ctx: dict, spec: dict) -> Optional[float]:
    t = ctx["trace"]["collective_exposed_s"]
    if t <= 0.0:
        return None
    return 100.0 * t / ctx["trace"]["window_s"]


def module_launches(ctx: dict, spec: dict) -> Optional[float]:
    """Programs the first device launched in the window (every event of
    its ``XLA Modules`` line, whoever dispatched it) by a count of the
    work."""
    n = sum(ctx["trace"]["module_launches"].values())
    per = float(ctx["work"].get(spec["per"], 0))
    if n <= 0 or per <= 0:
        return None
    return n / per


def _registry_delta(ctx: dict, kind: str, name: str):
    """Difference over the window of every series of ``name``."""
    def pick(snap):
        return {k: v for k, v in snap[kind].items()
                if k == name or k.startswith(name + "{")}
    return pick(ctx["before"]), pick(ctx["after"])


def registry_rate(ctx: dict, spec: dict) -> Optional[float]:
    """A counter's growth over the window by a count of the work."""
    b, a = _registry_delta(ctx, "counters", spec["metric"])
    per = float(ctx["work"].get(spec["per"], 0))
    if not a or per <= 0:
        return None
    return (sum(a.values()) - sum(b.values())) / per


def registry_mean(ctx: dict, spec: dict) -> Optional[float]:
    """Mean of a histogram's observations made in the window."""
    b, a = _registry_delta(ctx, "histograms", spec["metric"])
    n = sum(h["count"] for h in a.values()) \
        - sum(h["count"] for h in b.values())
    s = sum(h["sum"] for h in a.values()) \
        - sum(h["sum"] for h in b.values())
    if n <= 0:
        return None
    return float(spec.get("scale", 1.0)) * s / n


def value(ctx: dict, spec: dict) -> Optional[float]:
    return ctx["values"].get(spec["key"])


KINDS: Dict[str, Callable[[dict, dict], Optional[float]]] = {
    f.__name__: f for f in (op_roofline, step_mfu, op_share, idle_share,
                            collective_exposed_share, module_launches,
                            registry_rate, registry_mean, value)}


def load_metric(name: str) -> dict:
    with open(os.path.join(HERE, "layer_metrics", f"{name}.json")) as f:
        return json.load(f)


def read(name: str, ctx: dict) -> Optional[float]:
    own = os.path.join(HERE, "layer_metrics", f"{name}.py")
    if os.path.exists(own):
        return program.load_module(f"perf_layer_metric_{name}",
                                   own).read(ctx)
    reader = load_metric(name)["reader"]
    return KINDS[reader["kind"]](ctx, reader)
