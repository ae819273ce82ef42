"""Least time for the routed experts' matrix products alone
(``perf/lfm2_work.py`` ``experts``: forward and backward over the
window's assignments at width 1,792, recomputation not counted) over the
device time of the ops of ``jit_run`` the program scoped
``lm.moe.experts``."""

from perf import lfm2_work


def read(ctx):
    return lfm2_work.scope_roofline(ctx, lfm2_work.experts, "lm.moe.experts")
