"""Share of device busy time in the chunked SSD scan alone: ops of
``jit_run`` scoped ``lm.ssm.scan`` (the chunk-local terms and the two
kernels that carry the state from chunk to chunk; forward, recomputed
forward and backward)."""

from perf import program_readers


def read(ctx):
    return program_readers.scope_share(ctx, "jit_run", ["lm.ssm.scan"])
