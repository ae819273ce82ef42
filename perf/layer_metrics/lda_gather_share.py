"""Share of device busy time in the word-row gather of the LightLDA
superstep: ops of ``jit_run`` scoped ``lda.gather_words``."""

from perf import program_readers


def read(ctx):
    return program_readers.scope_share(ctx, "jit_run", ["lda.gather_words"])
