"""Least time for the attention's own products (``perf/lfm2_work.py``
``attend``: scores and weighted values of the attended keys at 32 query
heads of 64 dims, keys and values read once a group, forward and
backward; recomputation, masked pairs and padded lanes not counted) over
the device time of the ops of ``jit_run`` the program scoped
``lm.attn.attend``."""

from perf import lfm2_work


def read(ctx):
    return lfm2_work.scope_roofline(ctx, lfm2_work.attend, "lm.attn.attend")
