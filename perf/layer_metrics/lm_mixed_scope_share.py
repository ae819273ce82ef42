"""Share of device busy time in ops of ``jit_run`` named by a body that
holds two or more scopes: fusions that cross a scope's edge, whose
seconds nobody splits — the error bar of every ``*_share``. 0 is a
reading."""

from perf import lm_scope_readers


def read(ctx):
    return lm_scope_readers.inferred_share(ctx, "jit_run", 2)
