"""Share of device busy time under scope ``lm.moe.experts`` alone: the
grouped products with the valid-row selects around them, forward,
recomputed and backward — what both ``*_experts_roofline`` divide by."""

from perf import lm_scope_readers


def read(ctx):
    return lm_scope_readers.scope_share(ctx, "jit_run", ["lm.moe.experts"])
