"""Share of device busy time in the gated short convolution: ops of
``jit_run`` scoped ``lm.conv.project`` (the input and output products)
or ``lm.conv.mix`` (the two gates and the taps), forward, recomputation
and backward alike."""

from perf import program_readers


def read(ctx):
    return program_readers.scope_share(
        ctx, "jit_run", ["lm.conv.project", "lm.conv.mix"])
