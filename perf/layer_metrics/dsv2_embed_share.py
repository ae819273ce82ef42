"""Share of device busy time in the embedding's row gather and the
scatter-add of its rows' gradients: ops of ``jit_run`` scoped
``lm.embed_gather`` or ``lm.embed_scatter``."""

from perf import program_readers


def read(ctx):
    return program_readers.scope_share(
        ctx, "jit_run", ["lm.embed_gather", "lm.embed_scatter"])
