"""The program's own spans in a profiler trace, and the device's idle time
split across them.

``repro.serving.serve()`` writes a ``serve.tick`` span around each decode
tick and, inside it, ``serve.admit`` (prefill + insert), ``serve.step``
(the dispatch), ``serve.read`` (the wait for the step's tokens) and
``serve.book`` (appends, evictions, ``observe``); a tick woken from an idle
pool has its admissions before it. They sit on the profiler's host plane,
the clock that ``trace.clock_shift`` puts the device on.

:func:`idle_by_phase` splits every stretch of the traced window in which no
operation ran on the device by overlap across the innermost ``serve.*``
span covering each part of it (``outside`` where none does), averaged over
the chips, so its values sum to the window's idle time. A trace of a
program without these spans gives everything to ``outside``.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from typing import Dict, List, Tuple

from chipbench import trace

PREFIX = "serve."
OUTSIDE = "outside"
#: the phase in which the host waits for the device's tokens
READ = "serve.read"

Span = Tuple[str, float, float]


def load(path: str) -> List[Span]:
    """The ``serve.*`` spans of a trace, in seconds, by start."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    return sorted(((e.name, e.start_ns * 1e-9,
                    (e.start_ns + e.duration_ns) * 1e-9)
                   for plane in pd.planes if plane.name.startswith("/host:")
                   for line in plane.lines for e in line.events
                   if e.name.startswith(PREFIX)), key=lambda s: (s[1], -s[2]))


def innermost(spans: List[Span]) -> List[Span]:
    """Nested spans (one thread's) as disjoint pieces, each named for the
    innermost span that covers it; what no span covers is left out."""
    out: List[Span] = []
    stack: List[Tuple[str, float]] = []     # (name, end), outermost first
    at = float("-inf")

    def emit(name, a, b):
        if b > a:
            out.append((name, a, b))

    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            top, end = stack.pop()
            emit(top, at, end)
            at = max(at, end)
        if stack:
            emit(stack[-1][0], at, s)
            e = min(e, stack[-1][1])
        at = max(at, s)
        stack.append((name, e))
    while stack:
        top, end = stack.pop()
        emit(top, at, end)
        at = max(at, end)
    return out


def idle_stretches(ev: trace.Events) -> Dict[str, List[Tuple[float, float]]]:
    """Per chip, the stretches of the window that no operation covers, on
    the host's clock (as ``trace.reduce`` finds them)."""
    win = [(s, e) for n, s, e in ev.spans if n == trace.WINDOW_SPAN]
    if not win:
        raise ValueError("the trace holds no window span")
    lo, hi = win[0]
    out = {}
    for chip in sorted(ev.ops) or sorted(ev.runs):
        runs = ev.runs.get(chip, [])
        shift = trace.clock_shift(runs, ev.enqueued)
        ops = ev.ops.get(chip) or [(r[0], r[1], r[2]) for r in runs]
        cov = trace.union([(max(s + shift, lo), min(e + shift, hi))
                           for _, s, e in ops
                           if e + shift > lo and s + shift < hi])
        edges = [lo] + [x for iv in cov for x in iv] + [hi]
        out[chip] = [(a, b) for a, b in zip(edges[::2], edges[1::2])
                     if b > a]
    return out


def idle_by_phase(ev: trace.Events, spans: List[Span]) -> Dict[str, float]:
    """Idle seconds of the window per innermost ``serve.*`` span."""
    pieces = innermost(spans)
    starts = [p[1] for p in pieces]
    stretches = idle_stretches(ev)
    out: Dict[str, float] = defaultdict(float)
    n = len(stretches)
    for gaps in stretches.values():
        for a, b in gaps:
            left = b - a
            k = max(bisect.bisect_right(starts, a) - 1, 0)
            while k < len(pieces) and pieces[k][1] < b:
                name, s, e = pieces[k]
                o = trace._overlap(a, b, s, e)
                if o > 0:
                    out[name] += o / n
                    left -= o
                k += 1
            out[OUTSIDE] += left / n
    return dict(out)


def host_idle_share(phases: Dict[str, float], window_s: float) -> float:
    """Percent of the window the device stood idle while the host was not
    waiting for its tokens: the idle that the host's own work causes."""
    return 100.0 * sum(v for k, v in phases.items() if k != READ) / window_s
