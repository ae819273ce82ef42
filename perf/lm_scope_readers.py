"""What the ``lm_*`` per-layer metrics share: shares of device busy time
read from a map of the compiled text that says how each name was found
(``multiverso_tpu.telemetry.profiling.op_scopes``'s ``"inferred"``:
instruction -> the number of distinct scopes its body holds, for every
fusion whose scope is not its own ``op_name``'s; 0 = the compiler's own
fusion, named by its operands).

A program whose map has no such key (the parent of the PR that added it)
names fusions by their root alone: a share of a scope then leaves out
what was fused across the scope's edge, and every reader here gives
``None`` so that the harness leaves the metric out of the line.
"""

from __future__ import annotations

from typing import Dict, Iterable, Optional

from perf import program_readers


def inferred_counts(module: str) -> Optional[Dict[str, int]]:
    """``{instruction: scopes its body holds}`` over every program
    compiled as ``module`` (the larger count where two agree on the
    name); ``None`` where no program's map says what it inferred."""
    held = [h for h in program_readers.program_op_scopes().values()
            if h["module"] == module and "inferred" in h]
    if not held:
        return None
    counts: Dict[str, int] = {}
    for h in held:
        for name, n in h["inferred"].items():
            counts[name] = max(n, counts.get(name, n))
    return counts


def inferred_share(ctx: dict, module: str, least: int
                   ) -> Optional[float]:
    """Device time of ``module``'s ops whose scope was inferred from a
    body of at least ``least`` scopes, over busy time, in %. 0 is a
    reading (nothing inferred); ``None`` only without such a map."""
    counts = inferred_counts(module)
    busy = ctx["trace"]["busy_s"]
    if counts is None or busy <= 0.0:
        return None
    scopes = program_readers.module_scopes(module)
    prefix = module + "/"
    t = 0.0
    for op, seconds in ctx["trace"]["op_seconds"].items():
        name = op[len(prefix):]
        if op.startswith(prefix) and counts.get(name, -1) >= least \
                and scopes.get(name) != program_readers.UNSCOPED:
            t += seconds
    return 100.0 * t / busy


def scope_share(ctx: dict, module: str, scopes: Iterable[str]
                ) -> Optional[float]:
    """``program_readers.scope_share`` on a map that infers; ``None``
    on one that does not."""
    if inferred_counts(module) is None:
        return None
    return program_readers.scope_share(ctx, module, scopes)
