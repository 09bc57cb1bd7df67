"""Serving cells: the window drives the program's own ``serve()`` over a
``ContinuousEngine``, as ``repro.launch.serve.run`` builds it.

Set-up: the benchmark's weights from the seed (one jitted call), the engine,
and a warm-up ``serve()`` over one request for each prompt page the window
will use, so that every program the window runs is compiled or read from the
persistent cache before it opens. Then one ``serve()`` call over the
mix's requests, with their arrivals in decode steps, through a
:class:`~chipbench.timeline.TimedEngine`: requests arrive from step 0 until
the measured window closes. The window opens once the pool has been filling
for the mix's ``fill_steps`` and holds ``--seconds`` times its
``steps_per_s`` decode steps (``traffic.window``); what is served before it
opens or after it closes (the pool draining) is not measured. After the
call: the device's peak memory, then the engine's cache is freed and the
served tokens of a sample of requests are compared with the plain
reference.
"""
from __future__ import annotations

import gc
import sys
import time
from types import SimpleNamespace

import jax
import numpy as np

from chipbench import compare, timeline, trace, traffic


def _log(cell, what: str) -> None:
    print(f"[{time.perf_counter() - cell.t0:8.2f} s] {what}", file=sys.stderr,
          flush=True)


def _p95(x) -> float:
    """95th percentile, numpy's linear interpolation."""
    return float(np.percentile(np.asarray(x, float), 95))


def build(cell):
    """The engine and the requests of this cell and seed."""
    from repro.models import model as model_mod
    from repro.models.transformer import Runtime
    from repro.serving import ContinuousEngine, Request

    conf, fam, mix = cell.conf, cell.family, cell.mix
    pcfg = fam.program_config(conf)
    rt = Runtime(tp=1)
    params = fam.make_weights(conf, cell.seed32)
    want = jax.eval_shape(
        lambda k: model_mod.init_params(pcfg, rt, k)[0],
        jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in zip(
                jax.tree.leaves(want), jax.tree.leaves(got))):
        raise SystemExit("the program's parameter layout is not the one "
                         f"families/{conf['family']}.py builds")
    engine = ContinuousEngine(pcfg, rt, params,
                              max_slots=int(conf["serving"]["slots"]),
                              max_len=traffic.max_len(mix), seed=cell.seed32)
    n = traffic.arriving_before(mix, cell.window[1])
    tr = traffic.generate(mix, n, cell.seed, int(conf["vocab_size"]))
    requests = [Request(prompt=p, max_new_tokens=int(o))
                for p, o in zip(tr.prompts, tr.output_lens)]
    return engine, params, requests, tr


def _warm_requests(engine, requests, vocab: int):
    """One request per prompt page the window will use: each compiles (or
    loads) its prefill and insert programs; all of them step the pool."""
    from repro.serving import Request
    bucket = getattr(engine, "_bucket", None)
    lens = sorted({len(r.prompt) for r in requests})
    by_page = {}
    for L in lens:
        by_page.setdefault(bucket(L) if bucket else L, L)
    rng = np.random.default_rng(0)
    return [Request(prompt=rng.integers(0, vocab, L, dtype=np.int32),
                    max_new_tokens=2) for L in by_page.values()]


def run(cell) -> SimpleNamespace:
    from repro.serving import serve

    conf, fam = cell.conf, cell.family
    engine, params, requests, tr = build(cell)
    jax.block_until_ready(params)
    _log(cell, f"weights and engine built, {len(requests)} requests")
    temperature = float(conf["serving"].get("temperature", 0.0))
    warm = _warm_requests(engine, requests, int(conf["vocab_size"]))
    for chunk in range(0, len(warm), engine.max_slots):
        serve(engine, warm[chunk:chunk + engine.max_slots],
              temperature=temperature)
    jax.block_until_ready(engine._state)
    _log(cell, f"warm-up served {len(warm)} pages, "
               f"{cell.compiles.count} compiles")

    # the traced run profiles the middle fifth of the window's seconds
    tracer = (trace.Recorder(*getattr(cell, "trace_stretch",
                                      (0.3 * cell.seconds, 0.5 * cell.seconds)),
                             keep=getattr(cell, "keep_trace", None))
              if cell.trace else None)
    open_step, close_step = cell.window
    timed = timeline.TimedEngine(engine, requests, tr.arrivals,
                                 open_step=open_step, annotate=cell.trace,
                                 tracer=tracer)
    gc.collect()
    gc.freeze()
    compiles0 = cell.compiles.count
    setup_s = time.perf_counter() - cell.t0
    timed.start()
    report = serve(timed, requests, tr.arrivals, temperature=temperature)
    if tracer:
        tracer.stop(timed)
    compiles = cell.compiles.count - compiles0
    gc.unfreeze()
    n = len(requests)
    win = timeline.window(timed, n, open_step, close_step)
    _log(cell, f"served {n} requests in {report.wall_s:.3f} s, "
               f"{report.n_steps} steps; window {win.seconds:.3f} s, "
               f"{win.requests} requests, {len(win.ticks)} steps; "
               f"{compiles} compiles")
    summary = tracer.reduce() if tracer else None
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in jax.local_devices())

    failed = sum(1 for r, o in zip(requests, report.outputs)
                 if o is None or len(o) != r.max_new_tokens)
    e2e = {
        "output_tokens_per_s": win.tokens / win.seconds,
        "ttft_p95_ms": 1e3 * _p95(win.ttft_s),
        "itl_p95_ms": 1e3 * _p95(win.gaps_s),
        "setup_s": setup_s,
    }
    run_info = SimpleNamespace(
        conf=conf, family=fam, mix=cell.mix, peaks=cell.peaks,
        report=report, timed=timed, requests=requests, window=win,
        trace=summary, traced_ticks=tracer.ticks if tracer else None,
        traced_prefills=tracer.prefills if tracer else None)

    # the engine's cache goes before the reference runs
    engine._state = None
    gc.collect()
    checks, compared = compare.check_served(cell, params, requests,
                                            report.outputs)
    _log(cell, f"reference compared {compared}")
    return SimpleNamespace(
        end_to_end=e2e, attempted=n, failed=failed, checks=checks,
        info=dict(compared, compiles_in_window=compiles,
                  **({"device_s_by_call": summary.by_call,
                      "traced_ticks": tracer.ticks} if summary else {}),
                  decode_steps=report.n_steps, queue_peak=report.queue_peak,
                  requests=n, serve_s=report.wall_s,
                  window_s=win.seconds, window_requests=win.requests,
                  window_steps=len(win.ticks), window_tokens=win.tokens,
                  window_occupancy=(float(np.mean([len(k.slots)
                                                   for k in win.ticks]))
                                    if win.ticks else 0.0)),
        memory_peak_bytes=memory_peak, per_layer=run_info,
        busy_window=((summary.busy_s, summary.window_s) if summary
                     else None),
        breakdown=summary.breakdown() if summary else None)
