"""What the per-layer metrics with a reader of their own
(``perf/layer_metrics/<name>.py``) share: the arithmetic over what the
PROGRAM records about itself.

- the names of the program's spans, from the registry's
  ``span.seconds{name=...}`` series: what the run itself recorded, so a
  trainer added later needs no list here.
- set-up's layers: the TOTAL of a registry histogram after the window
  (``ctx["after"]``). The window records none of the set-up names, so a
  total is set-up's own.
- the ops of a compiled module by the program's scopes: the device
  trace's ``<module>/<instruction>`` seconds joined with the map the
  program keeps of its compiled text
  (``multiverso_tpu.telemetry.profiling.op_scopes``).

A program that records no such span, or keeps no such map (the parent
of the PR that added them), gives ``None``: the harness then leaves the
metric out of the line.
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional

SPAN_SECONDS = "span.seconds"
UNSCOPED = "unscoped"


def span_series(name: str) -> str:
    """The registry key of a program span's histogram."""
    return f"{SPAN_SECONDS}{{name={name}}}"


def span_names(snapshot: dict) -> List[str]:
    """Every program span a registry snapshot holds a histogram of: the
    names the trace's reduction may give an idle gap to."""
    found = (re.fullmatch(re.escape(SPAN_SECONDS) + r"\{name=([^,}]+)\}",
                          key) for key in snapshot["histograms"])
    return sorted(m.group(1) for m in found if m)


def histogram_total_s(ctx: dict, metrics: Iterable[str]
                      ) -> Optional[float]:
    """Sum over every series of each metric name (``name`` itself or
    ``name{...}``) of the histogram's ``sum`` after the window."""
    hists = ctx["after"]["histograms"]
    total, seen = 0.0, 0
    for metric in metrics:
        for key, h in hists.items():
            if key == metric or key.startswith(metric + "{"):
                total += h["sum"]
                seen += h["count"]
    return total if seen else None


def span_total_s(ctx: dict, spans: Iterable[str]) -> Optional[float]:
    return histogram_total_s(ctx, [span_series(s) for s in spans])


def program_op_scopes() -> Dict[str, dict]:
    """The program's ``{fn: {"module", "scopes"}}``; empty where the
    program keeps none."""
    from multiverso_tpu.telemetry import profiling
    read = getattr(profiling, "op_scopes", None)
    return read() if read is not None else {}


def module_scopes(module: str) -> Dict[str, str]:
    """``{instruction: scope}`` over every program compiled as
    ``module``; a name two of them scope differently is unscoped."""
    merged: Dict[str, str] = {}
    for held in program_op_scopes().values():
        if held["module"] != module:
            continue
        for name, scope in held["scopes"].items():
            if merged.setdefault(name, scope) != scope:
                merged[name] = UNSCOPED
    return merged


def scope_seconds(ctx: dict, module: str) -> Optional[Dict[str, float]]:
    """Device seconds of ``module``'s ops by program scope; an op the
    map does not know is unscoped. ``None`` when there is no map."""
    scopes = module_scopes(module)
    if not scopes:
        return None
    prefix = module + "/"
    out: Dict[str, float] = {}
    for op, seconds in ctx["trace"]["op_seconds"].items():
        if op.startswith(prefix):
            scope = scopes.get(op[len(prefix):], UNSCOPED)
            out[scope] = out.get(scope, 0.0) + seconds
    return out


def scope_share(ctx: dict, module: str, scopes: Iterable[str]
                ) -> Optional[float]:
    """Device time of the ops under ``scopes`` over busy time, in %;
    ``None`` (never 0) when nothing ran under them."""
    by_scope = scope_seconds(ctx, module)
    busy = ctx["trace"]["busy_s"]
    if by_scope is None or busy <= 0.0:
        return None
    t = sum(by_scope.get(s, 0.0) for s in scopes)
    return 100.0 * t / busy if t > 0.0 else None


def unscoped_share(ctx: dict, module: str) -> Optional[float]:
    """Device time of ``module``'s ops with no program scope over busy
    time, in %: what the map cannot name yet. 0 is a reading here (every
    op named); ``None`` only when there is no map."""
    by_scope = scope_seconds(ctx, module)
    busy = ctx["trace"]["busy_s"]
    if by_scope is None or busy <= 0.0:
        return None
    return 100.0 * by_scope.get(UNSCOPED, 0.0) / busy
