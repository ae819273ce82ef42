"""Share of device busy time in Adam: ops of ``jit_run`` scoped
``lm.adam`` (``table.updater.apply`` on every table)."""

from perf import program_readers


def read(ctx):
    return program_readers.scope_share(
        ctx, "jit_run", ["lm.adam"])
