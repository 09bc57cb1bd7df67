"""Device time of the programs that the traced stretch's ``prefill`` calls
launched, per 1,000 prompt tokens they sent (page padding counts against
it). The trace puts each program run to the engine call that launched it."""


def read(run):
    tr = getattr(run, "trace", None)
    if tr is None:
        return None
    lo, hi = run.traced_prefills
    seconds = tr.by_call.get("prefill")
    tokens = sum(run.timed.prefills[lo:hi])
    if not seconds or not tokens:
        return None
    return 1e3 * seconds / (tokens / 1e3)
