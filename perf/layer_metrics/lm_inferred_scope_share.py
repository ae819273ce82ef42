"""Share of device busy time in ops of ``jit_run`` whose scope is not
their own ``op_name``'s: fusions the program's map named by their body
or, where the compiler made them, by their operands — how much of every
``*_share`` rests on inference. 0 is a reading."""

from perf import lm_scope_readers


def read(ctx):
    return lm_scope_readers.inferred_share(ctx, "jit_run", 0)
