"""Share of its roofline that the decode step reaches: the least time of the
traced stretch's decode steps over the device time of the programs that
their ``generate_step`` calls launched.

The least time of a step is the larger of its FLOPs over the bf16 peak and
its bytes over the HBM peak (the configuration family's counts): every
weight once, plus the keys and values of the positions its live sequences
attend, whatever the program reads."""


def read(run):
    tr = getattr(run, "trace", None)
    if tr is None:
        return None
    lo, hi = run.traced_ticks
    device = tr.by_call.get("generate_step")
    if not device or tr.runs_by_call.get("generate_step") != hi - lo:
        return None
    least = sum(run.family.decode_step_least(
        run.conf, k.keys, run.peaks["bf16_flops"], run.peaks["hbm_bytes_per_s"])
        for k in run.timed.ticks[lo:hi])
    return 100.0 * least / device
