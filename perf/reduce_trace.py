"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's
device numbers: per device the busy and idle time of the window, device
time by operation name, the longest idle gaps with the innermost host
span that covers most of each, the exposed part of the collectives, and the
programs the first device launched.

A gap is named by a host span of the harness (``bench.*``, on any
thread) or of the program: the names the run's own registry recorded
(``span_names``), on the thread that holds ``bench.window`` alone — a
producer thread's span overlaps gaps it has no part in.

Read with nothing but jax (``jax.profiler.ProfileData``). The window is
the host span ``bench.window`` that ``perf/run.py`` wraps the measured
window in; device events are clipped to it. Operation names are
``<module>/<op>``: the event on the device's ``XLA Modules`` line that
contains the op (``jit_run(123)`` -> ``jit_run``), then the op's own
name, so the same op in two programs stays apart.
"""

from __future__ import annotations

import bisect
import glob
import heapq
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# XLA's collectives by their HLO names, and jax's by theirs: on the chip an
# all-reduce that ``lax.psum`` made is named ``psum.14``
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast|\bpsum\b|\bpmax\b|\bpmin\b|\bpmean\b"
    r"|all_gather|ppermute|all_to_all|psum_scatter")

Interval = Tuple[float, float]


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under a ``start_trace`` directory."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str):
    from jax.profiler import ProfileData
    if path.endswith(".textproto"):
        with open(path) as f:
            return ProfileData.from_text_proto(f.read())
    return ProfileData.from_file(path)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def measure(merged: List[Interval]) -> float:
    return sum(b - a for a, b in merged)


def subtract(merged: List[Interval], holes: List[Interval]
             ) -> List[Interval]:
    """``merged`` minus ``holes`` (both merged and sorted)."""
    out: List[Interval] = []
    j = 0
    for a, b in merged:
        cur = a
        while j < len(holes) and holes[j][1] <= cur:
            j += 1
        k = j
        while k < len(holes) and holes[k][0] < b:
            if holes[k][0] > cur:
                out.append((cur, holes[k][0]))
            cur = max(cur, holes[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def _clip(a: float, b: float, w: Interval) -> Optional[Interval]:
    a, b = max(a, w[0]), min(b, w[1])
    return (a, b) if b > a else None


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name).strip()


def _self_times(events: List[Tuple[float, float, str]]
                ) -> List[Tuple[str, float]]:
    """Self time of each event on one sequential line, where an event
    (a ``while``, a ``conditional``) may contain the events of its body:
    its duration less the part its children cover."""
    out: List[Tuple[str, float]] = []
    stack: List[list] = []       # [end, name, self_seconds]
    for a, b, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= a:
            end, nm, self_s = stack.pop()
            out.append((nm, self_s))
        if stack:
            stack[-1][2] -= min(b, stack[-1][0]) - a
        stack.append([b, name, b - a])
    while stack:
        end, nm, self_s = stack.pop()
        out.append((nm, self_s))
    return out


def _leaves(events: List[Tuple[float, float, str]]
            ) -> List[Tuple[float, float, str]]:
    """The events that contain no other (a ``while`` spans its body's
    ops and is none of them)."""
    ordered = sorted(events, key=lambda e: (e[0], -e[1]))
    return [e for i, e in enumerate(ordered)
            if i + 1 == len(ordered) or ordered[i + 1][0] >= e[1]]


def host_spans(pd, names: Iterable[str] = (), prefix: str = SPAN_PREFIX
               ) -> List[Tuple[float, float, str]]:
    """The harness's spans (``prefix``) on every host thread, and the
    spans called one of ``names`` on a thread that holds the window."""
    names = frozenset(names)
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            found = [(ev.start_ns, ev.start_ns + ev.duration_ns, name)
                     for ev in line.events for name in (ev.name,)
                     if name.startswith(prefix) or name in names]
            if not any(s[2] == WINDOW_SPAN for s in found):
                found = [s for s in found if s[2].startswith(prefix)]
            spans += found
    # by start, the longer first: of two spans that start together the
    # inner one comes later, as it does when it starts later
    return sorted(spans, key=lambda s: (s[0], -s[1], s[2]))


def op_name(event_name: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` (the HLO text a TPU trace
    names an op event by) -> ``fusion.3``."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()


def _span_over(spans, starts, gap: Interval) -> str:
    """What the host was doing while the device waited: the innermost
    span that covers more than half of the idle gap (``spans`` come by
    start, the outer first, so the last such one); where none does, the
    span that covers most of it."""
    half = (gap[1] - gap[0]) / 2.0
    inner, best, best_cover = None, "(no span open)", 0.0
    for a, b, name in spans[:bisect.bisect_right(starts, gap[1])]:
        cover = min(b, gap[1]) - max(a, gap[0])
        if cover > half:
            inner = name
        if cover > 0.0 and cover >= best_cover:
            best, best_cover = name, cover
    return inner or best


def reduce(pd, top: int = 10, span_names: Iterable[str] = ()) -> dict:
    """The numbers of one traced window; times in seconds. Given no
    ``span_names`` the gaps go to ``bench.*`` spans alone."""
    spans = host_spans(pd, span_names)
    wins = [(a, b) for a, b, n in spans if n == WINDOW_SPAN]
    devices = [p for p in pd.planes if p.name.startswith("/device:TPU:")
               and any(ln.name == OPS_LINE for ln in p.lines)]
    if not devices:
        raise ValueError("no device plane with an 'XLA Ops' line in the "
                         "trace: nothing ran on the device, or this is "
                         "not a TPU trace")
    if wins:
        window = (min(a for a, _ in wins), max(b for _, b in wins))
    else:
        evs = [(e.start_ns, e.start_ns + e.duration_ns)
               for p in devices for ln in p.lines if ln.name == OPS_LINE
               for e in ln.events]
        window = (min(a for a, _ in evs), max(b for _, b in evs))
    window_s = (window[1] - window[0]) / 1e9
    inner = [s for s in spans if s[2] != WINDOW_SPAN]
    starts = [s[0] for s in inner]

    per_device = []
    gaps: List[Interval] = []
    exposed = 0.0
    launches: Dict[str, int] = {}
    for plane in devices:
        mods = []
        ops = []
        for line in plane.lines:
            if line.name == MODULES_LINE:
                mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                               _module_name(e.name)) for e in line.events)
            elif line.name == OPS_LINE:
                for e in line.events:
                    c = _clip(e.start_ns, e.start_ns + e.duration_ns,
                              window)
                    if c:
                        ops.append((c[0], c[1], op_name(e.name)))
        mod_starts = [m[0] for m in mods]
        if plane is devices[0]:
            for a, _, name in mods:
                if window[0] <= a < window[1]:
                    launches[name] = launches.get(name, 0) + 1

        def named(a: float, name: str) -> str:
            i = bisect.bisect_right(mod_starts, a) - 1
            if i >= 0 and mods[i][1] > a:
                return f"{mods[i][2]}/{name}"
            return name

        busy = union((a, b) for a, b, _ in ops)
        busy_s = measure(busy) / 1e9
        per_device.append({"name": plane.name, "busy_s": busy_s,
                           "window_s": window_s,
                           "idle_share_pct": 100.0 * (1.0 - busy_s
                                                      / window_s)})
        gaps += subtract([window], busy)
        leaves = _leaves(ops)
        coll = union((a, b) for a, b, n in leaves if COLLECTIVE.search(n))
        comp = union((a, b) for a, b, n in leaves
                     if not COLLECTIVE.search(n))
        exposed += measure(subtract(coll, comp)) / 1e9
        qualified: Dict[str, float] = {}
        for name, self_ns in _self_times(
                [(a, b, named(a, nm)) for a, b, nm in ops]):
            qualified[name] = qualified.get(name, 0.0) + self_ns / 1e9
        per_device[-1]["ops"] = qualified

    n = len(devices)
    merged: Dict[str, float] = {}
    for d in per_device:
        for k, v in d.pop("ops").items():
            merged[k] = merged.get(k, 0.0) + v / n
    busy_mean = sum(d["busy_s"] for d in per_device) / n
    # only the gaps that can reach the list are named: every span is
    # weighed against each, and a window holds thousands of both
    cut = min(heapq.nlargest(top, (b - a for a, b in gaps)), default=0.0)
    longest = sorted((((b - a) / 1e9, _span_over(inner, starts, (a, b)))
                      for a, b in gaps if b - a >= cut), reverse=True)
    return {
        "window_s": window_s,
        "busy_s": busy_mean,
        "idle_share_pct": 100.0 * (1.0 - busy_mean / window_s),
        "devices": per_device,
        "op_seconds": merged,
        "device_ops": [[k, v] for k, v in sorted(
            merged.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[name, s] for s, name in longest[:top]],
        "collective_exposed_s": exposed / n,
        "spans": sorted({s[2] for s in spans}),
        "module_launches": launches,
    }


def op_time(reduced: dict, pattern: str) -> float:
    """Device seconds (mean over devices) of the ops whose qualified
    name matches ``pattern`` (a regular expression, searched)."""
    rx = re.compile(pattern)
    return sum(v for k, v in reduced["op_seconds"].items()
               if rx.search(k))
