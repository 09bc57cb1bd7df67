"""What the program's own spans and records say of a cell, beside the
benchmark's readings.

    python3 chipbench/tests/spans_study.py --workload <cell> --seconds 51 \\
        --seeds 11 12 13 --trace 1 [--out results.jsonl]

(on a TPU, from the root).

For each seed, in one process: the cell's run as ``run.py`` makes it
(set-up, window, comparison), then one JSON line: ``correct``, the
end-to-end metrics, and TTFT p95 and ITL p95 from ``serve()``'s records
(``ServeReport.ticks`` / ``.requests``) beside the proxy's. Traced, also:
every per-layer metric; the device's idle time split across the program's
spans (``spans.idle_by_phase``) and ``host_idle_share``; the window's five
slowest ticks, each with its phases; the host time of the decode ticks
inside and outside the traced stretch; the programs the trace names. The
first line gives the cost of one span when no trace is being collected.
A program without the records gives ``null`` where they are read.
"""
import argparse
import gc
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from chipbench import harness, spans, trace  # noqa: E402


def span_cost_ns(n: int = 200_000) -> float:
    from jax.profiler import TraceAnnotation
    t0 = time.perf_counter()
    for _ in range(n):
        with TraceAnnotation("serve.cost"):
            pass
    return 1e9 * (time.perf_counter() - t0) / n


def _p95(x):
    return float(np.percentile(x, 95)) if len(x) else None


def from_records(run):
    """TTFT and the gaps between tokens, in ms, from the program's records,
    over the proxy's window and its set of requests."""
    rep, win, timed = run.report, run.window, run.timed
    t, r = getattr(rep, "ticks", None), getattr(rep, "requests", None)
    if t is None or r is None:
        return None
    arr = np.asarray(timed._arrivals, float)
    later = np.flatnonzero(arr >= timed.open_step)
    due = later[np.argsort(arr[later], kind="stable")][:win.requests]
    due = due[r.first_tick[due] >= 0]
    ttft = t.read_s[r.first_tick[due]] - r.due_s[due]
    gaps = []
    for a, b in zip(r.first_tick, r.last_tick):
        if a >= 0:
            ends = t.read_s[a + 1:b + 1]
            inside = (win.t_open < ends) & (ends <= win.t_close)
            gaps.append(np.diff(t.read_s[a:b + 1])[inside])
    gaps = np.concatenate([np.zeros(0)] + gaps)
    wait = r.admit_s[due] - r.due_s[due]
    return {"ttft_p95_ms": 1e3 * _p95(ttft), "itl_p95_ms": 1e3 * _p95(gaps),
            "queue_wait_p95_ms": 1e3 * _p95(wait),
            "ttft_requests": int(len(ttft)), "proxy_ttft_requests":
            int(len(win.ttft_s)), "gaps": int(len(gaps)),
            "proxy_gaps": int(len(win.gaps_s))}


def ticks_study(run):
    """The window's slowest ticks and the host time inside and outside the
    traced stretch."""
    t, win = run.report.ticks, run.window
    r = t.read_s
    k = np.arange(1, len(r))
    k = k[(win.t_open < r[k - 1]) & (r[k] <= win.t_close)]
    span = r[k] - r[k - 1]
    slow = []
    for j in k[np.argsort(-span)[:5]]:
        ms = {"tick": r[j] - r[j - 1], "book": t.start_s[j] - r[j - 1],
              "admit": t.admit_s[j], "step": t.step_s[j],
              "wait": t.wait_s[j]}
        ms = {name: 1e3 * float(v) for name, v in ms.items()}
        ms["other"] = ms["tick"] - sum(v for n, v in ms.items()
                                       if n != "tick")
        slow.append(dict({f"{n}_ms": v for n, v in ms.items()}, index=int(j),
                         admitted=int(t.admitted[j]),
                         prompt_tokens=int(t.prompt_tokens[j]),
                         active=int(t.active[j]),
                         kv_positions=int(t.kv_positions[j])))
    out = {"slowest_ticks": slow,
           "spans_per_tick": 4.0 + float(t.admitted.mean())}
    if run.traced_ticks:
        lo, hi = run.traced_ticks
        quiet = k[t.admitted[k] == 0]
        # ticks lo - 1 and hi - 1 hold the profiler's own start and stop
        traced = (quiet >= lo) & (quiet <= hi - 2)
        untraced = (quiet < lo - 1) | (quiet > hi - 1)
        tick = 1e3 * (r[quiet] - r[quiet - 1])
        host = tick - 1e3 * t.wait_s[quiet]
        for name, x in (("decode_tick_ms", tick), ("tick_host_ms", host)):
            out[name] = {"traced": float(np.median(x[traced])),
                         "untraced": float(np.median(x[untraced])),
                         "traced_n": int(traced.sum()),
                         "untraced_n": int(untraced.sum())}
    return out


def trace_study(path, summary):
    ev = trace.load(path)
    sp = spans.load(path)
    phases = spans.idle_by_phase(ev, sp)
    idle = summary.window_s - summary.busy_s
    lo, hi = [(s, e) for n, s, e in ev.spans if n == trace.WINDOW_SPAN][0]
    return {"idle_by_phase": phases,
            "idle_s": idle, "idle_by_phase_minus_idle_s":
            sum(phases.values()) - idle,
            "host_idle_share": spans.host_idle_share(phases,
                                                     summary.window_s),
            "window_s": summary.window_s, "busy_s": summary.busy_s,
            "idle_by_host": summary.idle_by_host,
            "serve_tick_spans": sum(1 for n, s, e in sp
                                    if n == "serve.tick" and lo <= s
                                    and e <= hi),
            "programs": summary.programs,
            "device_ops": summary.breakdown()["device_ops"]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=int, default=51)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    sys.path.insert(0, str(harness.HERE))
    import run as bench_run
    bench_run.use_cache()
    peaks = bench_run.gate(1)[1]

    def emit(line):
        text = json.dumps(line)
        print(text, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(text + "\n")

    emit({"workload": args.workload, "span_cost_ns": span_cost_ns()})
    for seed in args.seeds:
        t0 = time.perf_counter()
        cell = harness.cell(args.workload, seed, args.seconds,
                            bool(args.trace))
        cell.t0, cell.peaks = t0, peaks
        cell.compiles = bench_run.CompileCounter.shared()
        tmp = tempfile.mkdtemp(prefix="spans-")
        if args.trace:
            cell.keep_trace = os.path.join(tmp, "trace.xplane.pb")
        out = cell.driver.run(cell)
        run = out.per_layer
        line = {"workload": args.workload, "seed": seed,
                "trace": args.trace,
                "correct": all(c.ok for c in out.checks) and not out.failed,
                **{c.name: c.value for c in out.checks},
                "end_to_end": out.end_to_end,
                "records": from_records(run),
                "compiles_in_window": out.info["compiles_in_window"],
                "memory_peak_bytes": out.memory_peak_bytes}
        if hasattr(run.report, "ticks"):
            line.update(ticks_study(run))
        if args.trace:
            line["per_layer"] = {k: v["value"] for k, v in harness.per_layer(
                cell.bench, args.workload, run).items()}
            if run.trace is not None:
                line.update(trace_study(cell.keep_trace, run.trace))
        emit(line)
        shutil.rmtree(tmp, ignore_errors=True)
        del out, run
        gc.collect()


if __name__ == "__main__":
    main()
