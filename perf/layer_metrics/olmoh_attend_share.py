"""Share of device busy time in the softmax attention of the layers
that attend: ops of ``jit_run`` scoped ``lm.attn.attend`` (the kernels
without rotary operands, forward, recomputed forward and backward);
the projections of ``lm.attn.project`` left out."""

from perf import program_readers


def read(ctx):
    return program_readers.scope_share(
        ctx, "jit_run", ["lm.attn.attend"])
