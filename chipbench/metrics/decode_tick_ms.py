"""Median host time of the measured window's decode ticks that admitted no
request: from the end of one decode step's tokens on the host to the end of
the next, so the scheduler's own Python, the dispatch and the step itself.
``serve()`` reads every step's tokens at once, so this is the pace of a
tick without prefill."""
import numpy as np


def read(run):
    win = getattr(run, "window", None)
    if win is None:
        return None
    ticks = win.ticks
    t = [b.t1 - a.t1 for a, b in zip(ticks, ticks[1:]) if b.admitted == 0]
    return 1e3 * float(np.median(t)) if t else None
