"""Seconds of ``TransformerLM.__init__``'s table building: the tables'
construction and their start values drawn on the device
(``lm.setup.init_tables``), total of the program's span."""

from perf import program_readers


def read(ctx):
    return program_readers.span_total_s(ctx, ["lm.setup.init_tables"])
