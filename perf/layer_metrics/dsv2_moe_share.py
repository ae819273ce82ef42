"""Share of device busy time in the expert layer: ops of ``jit_run``
scoped ``lm.moe.route``, ``lm.moe.permute``, ``lm.moe.experts`` or
``lm.moe.shared`` (forward, recomputation and backward alike)."""

from perf import program_readers


def read(ctx):
    return program_readers.scope_share(
        ctx, "jit_run", ["lm.moe.route", "lm.moe.permute", "lm.moe.experts", "lm.moe.shared"])
