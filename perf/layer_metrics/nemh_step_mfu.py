"""Least time for the whole window's work (``perf/nemotron_h_work.py``
``step``: forward and backward products of every real token through the
Mamba-2, attention and expert layers and the head, the recurrence token
by token, attention at the attended pairs, the held experts' products
over the window's assignments, recomputation and padded slots not
counted, Adam's bytes) over the traced window, in %: the share of the
chip's peak the whole step reaches."""

from perf import nemotron_h_work, peaks


def read(ctx):
    if not ctx["work"] or ctx["trace"]["window_s"] <= 0.0:
        return None
    least = peaks.least_seconds(
        nemotron_h_work.step(ctx["sizes"], ctx["work"]), ctx["device_kind"],
        ctx["chips"])
    return 100.0 * least["seconds"] / ctx["trace"]["window_s"]
