"""Share of device busy time in latent attention: ops of ``jit_run``
scoped ``lm.mla.project`` (projections in and out, norms, rotary) or
``lm.mla.attend`` (blocked softmax attention)."""

from perf import program_readers


def read(ctx):
    return program_readers.scope_share(
        ctx, "jit_run", ["lm.mla.project", "lm.mla.attend"])
