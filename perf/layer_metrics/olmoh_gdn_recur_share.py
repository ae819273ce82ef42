"""Share of device busy time in the chunked gated delta rule alone: ops
of ``jit_run`` scoped ``lm.gdn.recur`` (unit norms, the chunk-local
products and solve, the scan over chunks; forward, recomputed forward
and backward)."""

from perf import program_readers


def read(ctx):
    return program_readers.scope_share(
        ctx, "jit_run", ["lm.gdn.recur"])
