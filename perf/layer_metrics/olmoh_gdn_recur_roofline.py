"""Least time for the recurrence's own work (``perf/olmo_hybrid_work.py``
``recur``: the token-by-token form's flops of forward and backward and
its operands' bytes once a pass; what the chunked form adds and what is
recomputed are not counted) over the device time of the ops of
``jit_run`` the program scoped ``lm.gdn.recur``, whatever implements it."""

from perf import olmo_hybrid_work, peaks, program_readers


def read(ctx):
    by_scope = program_readers.scope_seconds(ctx, "jit_run")
    seconds = (by_scope or {}).get("lm.gdn.recur", 0.0)
    if seconds <= 0.0 or not ctx["work"]:
        return None
    least = peaks.least_seconds(
        olmo_hybrid_work.recur(ctx["sizes"], ctx["work"]),
        ctx["device_kind"], ctx["chips"])
    return 100.0 * least["seconds"] / seconds
