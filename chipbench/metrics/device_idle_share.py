"""Share of the traced stretch in which no operation ran on the device (the
union of the operation intervals, averaged over the chips)."""


def read(run):
    tr = getattr(run, "trace", None)
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * tr.idle_share
