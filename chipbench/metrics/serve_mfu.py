"""Model FLOPs utilisation of the whole serving step over the traced
stretch: the forward FLOPs of the prompt tokens its prefills sent (pads not
counted) and of every token its decode steps made, attention at the
lengths each token attends, over the stretch's seconds times the chip's
bf16 peak."""


def read(run):
    tr = getattr(run, "trace", None)
    if tr is None or tr.window_s <= 0:
        return None
    fam, conf, timed = run.family, run.conf, run.timed
    lo, hi = run.traced_prefills
    flops = fam.prompt_flops(conf, timed.prefills[lo:hi])
    lo, hi = run.traced_ticks
    flops += sum(fam.token_flops(conf, k.keys) for k in timed.ticks[lo:hi])
    return 100.0 * flops / (tr.window_s * run.peaks["bf16_flops"])
