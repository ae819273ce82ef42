"""Seconds of ``WordEmbedding.__init__``'s host work: the start tables
(``w2v.setup.init_tables``) and the vocabulary tables
(``w2v.setup.vocab_tables``), totals of the program's spans."""

from perf import program_readers


def read(ctx):
    return program_readers.span_total_s(
        ctx, ["w2v.setup.init_tables", "w2v.setup.vocab_tables"])
