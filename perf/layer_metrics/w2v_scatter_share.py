"""Share of device busy time in the scatter-adds of the word2vec
superstep: ops of ``jit_run`` the program scoped ``w2v.scatter_out`` or
``w2v.scatter_in``."""

from perf import program_readers


def read(ctx):
    return program_readers.scope_share(
        ctx, "jit_run", ["w2v.scatter_out", "w2v.scatter_in"])
