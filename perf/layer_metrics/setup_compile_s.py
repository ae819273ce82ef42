"""Seconds ``profiled_jit`` spent lowering and compiling (or fetching
from the persistent cache), every program of the run: none compiles in
the window, so the total is set-up's."""

from perf import program_readers


def read(ctx):
    return program_readers.histogram_total_s(
        ctx, ["profile.lower.seconds", "profile.compile.seconds"])
