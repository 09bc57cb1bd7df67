"""Median host time of the measured window's decode ticks that admitted no
request: from the previous step's tokens on the host to this step's,
less the time the host waited for them (``serve.read``). So the
scheduler's own Python, its bookkeeping and the step's dispatch: the part
of ``decode_tick_ms`` the host spends not waiting for tokens. Read from
``serve()``'s own tick records (``ServeReport.ticks``); nothing to read
where the program keeps none."""
import numpy as np


def read(run):
    ticks = getattr(getattr(run, "report", None), "ticks", None)
    win = getattr(run, "window", None)
    if ticks is None or win is None:
        return None
    r = ticks.read_s
    k = np.arange(1, len(r))
    keep = ((win.t_open < r[k - 1]) & (r[k] <= win.t_close)
            & (ticks.admitted[k] == 0))
    host = (r[k] - r[k - 1] - ticks.wait_s[k])[keep]
    return 1e3 * float(np.median(host)) if len(host) else None
