"""Share of device busy time in what the LightLDA scan hands on (the
assignments' window in and out): ops of ``jit_run`` scoped ``lda.carry``."""

from perf import program_readers


def read(ctx):
    return program_readers.scope_share(ctx, "jit_run", ["lda.carry"])
