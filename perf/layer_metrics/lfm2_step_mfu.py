"""Least time for the whole window's work (``perf/lfm2_work.py``
``step``: forward and backward products, attention scores, the short
convolutions' gates and taps and the held experts' products over the
window's assignments, recomputation and padded lanes not counted, Adam's
bytes) over the traced window, in %: the share of the chip's peak the
whole step reaches."""

from perf import lfm2_work, peaks


def read(ctx):
    if not ctx["work"] or ctx["trace"]["window_s"] <= 0.0:
        return None
    least = peaks.least_seconds(
        lfm2_work.step(ctx["sizes"], ctx["work"]), ctx["device_kind"],
        ctx["chips"])
    return 100.0 * least["seconds"] / ctx["trace"]["window_s"]
