"""Share of device busy time in the grouped-query attention kernels:
ops of ``jit_run`` scoped ``lm.attn.attend`` (forward, recomputation and
backward alike, the sum of the keys' and values' gradients over a
group's query heads included)."""

from perf import program_readers


def read(ctx):
    return program_readers.scope_share(ctx, "jit_run", ["lm.attn.attend"])
