"""Least time for the routed experts' matrix products alone
(``perf/dsv2_work.py`` ``experts``: forward and backward, recomputation
not counted) over the device time of the ops of ``jit_run`` the program
scoped ``lm.moe.experts``."""

from perf import dsv2_work, peaks, program_readers


def read(ctx):
    by_scope = program_readers.scope_seconds(ctx, "jit_run")
    seconds = (by_scope or {}).get("lm.moe.experts", 0.0)
    if seconds <= 0.0:
        return None
    least = peaks.least_seconds(
        dsv2_work.experts(ctx["sizes"], ctx["work"]), ctx["device_kind"],
        ctx["chips"])
    return 100.0 * least["seconds"] / seconds
