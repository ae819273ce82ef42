"""Least time for the recurrence's own work (``perf/nemotron_h_work.py``
``scan``: the token-by-token form's flops of forward and backward and
its operands' bytes once a pass; what the chunked form adds and what is
recomputed are not counted) over the device time of the ops of
``jit_run`` the program scoped ``lm.ssm.scan``, whatever implements
it."""

from perf import nemotron_h_work, sdar_work


def read(ctx):
    return sdar_work.scope_roofline(ctx, nemotron_h_work.scan,
                                    "lm.ssm.scan")
