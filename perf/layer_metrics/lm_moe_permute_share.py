"""Share of device busy time under scope ``lm.moe.permute``: the sort of
the assignments, the blocks' row gathers and their scatter-adds."""

from perf import lm_scope_readers


def read(ctx):
    return lm_scope_readers.scope_share(ctx, "jit_run", ["lm.moe.permute"])
