"""Share of device busy time in the row gathers of the word2vec
superstep and the draw of its negatives: ops of ``jit_run`` scoped
``w2v.gather_in``, ``w2v.gather_out`` or ``w2v.negatives``."""

from perf import program_readers


def read(ctx):
    return program_readers.scope_share(
        ctx, "jit_run",
        ["w2v.gather_in", "w2v.gather_out", "w2v.negatives"])
