"""Mean occupied decode slots per decode step of the measured window: the
slots ``serve()`` marked active in each ``generate_step`` call."""
import numpy as np


def read(run):
    win = getattr(run, "window", None)
    if win is None or not win.ticks:
        return None
    return float(np.mean([len(k.slots) for k in win.ticks]))
