"""Least time for the gates and taps alone (``perf/lfm2_work.py``
``conv_mix``: per token and conv layer ``B``, ``C``, ``X`` read and one
row written in float32 forward, twice that backward; recomputation not
counted) over the device time of the ops of ``jit_run`` the program
scoped ``lm.conv.mix``."""

from perf import lfm2_work


def read(ctx):
    return lfm2_work.scope_roofline(ctx, lfm2_work.conv_mix, "lm.conv.mix")
