"""Share of device busy time in the softmax attention alone: ops of
``jit_run`` scoped ``lm.mla.attend`` (forward, recomputed forward and
backward), the projections of ``lm.mla.project`` left out."""

from perf import program_readers


def read(ctx):
    return program_readers.scope_share(ctx, "jit_run", ["lm.mla.attend"])
