"""Share of device busy time in the dense SwiGLU feed-forward of every
layer: ops of ``jit_run`` scoped ``lm.dense_mlp``."""

from perf import program_readers


def read(ctx):
    return program_readers.scope_share(
        ctx, "jit_run", ["lm.dense_mlp"])
