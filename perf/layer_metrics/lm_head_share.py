"""Share of device busy time under scope ``lm.head_loss``: the final
norm, the vocabulary product and the cross-entropy, a group of sequences
at a time, forward, recomputed and backward."""

from perf import lm_scope_readers


def read(ctx):
    return lm_scope_readers.scope_share(ctx, "jit_run", ["lm.head_loss"])
