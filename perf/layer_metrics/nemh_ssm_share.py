"""Share of device busy time in the Mamba-2 mixer: ops of ``jit_run``
scoped ``lm.ssm.project`` (the input projection and ``dt``),
``lm.ssm.conv`` (the filter with its bias and the ``silu``),
``lm.ssm.scan`` (the chunked scan) or ``lm.ssm.gate_out`` (``D x``, the
gated group norm and the output projection), forward, recomputation and
backward alike."""

from perf import program_readers


def read(ctx):
    return program_readers.scope_share(
        ctx, "jit_run", ["lm.ssm.project", "lm.ssm.conv", "lm.ssm.scan",
                         "lm.ssm.gate_out"])
