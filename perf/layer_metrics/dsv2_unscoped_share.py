"""Share of device busy time in ops of ``jit_run`` that carry no program
scope: what the program cannot name yet."""

from perf import program_readers


def read(ctx):
    return program_readers.unscoped_share(ctx, "jit_run")
