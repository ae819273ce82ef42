"""Least time for the attention's own products (``perf/olmo_hybrid_work.py``
``attend``: scores and weighted values of the attended keys, forward
and backward, recomputation and masked pairs not counted) over the
device time of the ops of ``jit_run`` the program scoped
``lm.attn.attend``."""

from perf import olmo_hybrid_work, peaks, program_readers


def read(ctx):
    by_scope = program_readers.scope_seconds(ctx, "jit_run")
    seconds = (by_scope or {}).get("lm.attn.attend", 0.0)
    if seconds <= 0.0 or not ctx["work"]:
        return None
    least = peaks.least_seconds(
        olmo_hybrid_work.attend(ctx["sizes"], ctx["work"]),
        ctx["device_kind"], ctx["chips"])
    return 100.0 * least["seconds"] / seconds
