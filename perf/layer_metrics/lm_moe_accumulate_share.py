"""Share of device busy time under scope ``lm.moe.accumulate``: the held
experts' weights cast to the products' dtype once a pass, the three
weight-gradient accumulators read and written whole every block, the
row weights' gradient, the gradients' stack."""

from perf import lm_scope_readers


def read(ctx):
    return lm_scope_readers.scope_share(ctx, "jit_run",
                                        ["lm.moe.accumulate"])
