"""Least time for the whole window's work (``perf/olmo_hybrid_work.py``
``step``: forward and backward products, attention scores and the
recurrence token by token, recomputation and the chunked form's extra
products not counted, Adam's bytes) over the traced window, in %: the
share of the chip's peak the whole step reaches."""

from perf import olmo_hybrid_work, peaks


def read(ctx):
    if not ctx["work"] or ctx["trace"]["window_s"] <= 0.0:
        return None
    least = peaks.least_seconds(
        olmo_hybrid_work.step(ctx["sizes"], ctx["work"]),
        ctx["device_kind"], ctx["chips"])
    return 100.0 * least["seconds"] / ctx["trace"]["window_s"]
