"""95th percentile of the requests' wait in the queue, over the requests
due in the measured window that were served a decode step (the set whose
time to first token ``ttft_p95_ms`` reads): from the step counter's
reaching a request's arrival step to the start of its prefill, both from
``serve()``'s own request records (``ServeReport.requests``), on the clock
of the window's bounds. Nothing to read where the program keeps no such
records."""
import numpy as np


def read(run):
    rec = getattr(getattr(run, "report", None), "requests", None)
    timed, win = getattr(run, "timed", None), getattr(run, "window", None)
    if rec is None or timed is None or win is None:
        return None
    arr = np.asarray(timed._arrivals, float)[:len(rec.due_s)]
    # the window's requests arrive at or after its opening step, the first
    # ``win.requests`` of them in order of arrival
    later = np.flatnonzero(arr >= timed.open_step)
    due = later[np.argsort(arr[later], kind="stable")][:win.requests]
    due = due[rec.first_tick[due] >= 0]
    if not len(due):
        return None
    return 1e3 * float(np.percentile(rec.admit_s[due] - rec.due_s[due], 95))
