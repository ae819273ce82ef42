"""Share of device busy time in the linear-attention mixer: ops of
``jit_run`` scoped ``lm.gdn.project`` (the input projection, decay and
write strength), ``lm.gdn.conv`` (the short convolution), ``lm.gdn.recur``
(the chunked recurrence) or ``lm.gdn.gate_out`` (gated norm and output
projection), forward, recomputation and backward alike."""

from perf import program_readers


def read(ctx):
    return program_readers.scope_share(
        ctx, "jit_run", ["lm.gdn.project", "lm.gdn.conv", "lm.gdn.recur", "lm.gdn.gate_out"])
