"""Seconds of ``_setup_docblock``'s host packing and placement: the
total of the program's span ``lda.setup.pack`` (set-up alone records it)."""

from perf import program_readers


def read(ctx):
    return program_readers.span_total_s(ctx, ["lda.setup.pack"])
