"""What the host saw of a serving run, and each request's times.

:class:`TimedEngine` stands between ``repro.serving.serve()`` and the
engine. It forwards every attribute and records the host clock at the three
calls ``serve()`` makes: ``prefill(request)``, ``insert(prefix, slot)`` and
``generate_step(active)``. The step's tokens are handed back in a wrapper
that notes the time at which ``serve()`` reads them onto the host, so the
proxy adds no read of its own: a step's end on this clock is when
``serve()`` held its tokens.

From those records, with the arrival steps the requests were given:

- due time: when ``serve()``'s step counter reached the request's arrival
  step (a decode step's end), or its admission if the pool stood idle and
  the counter jumped to it;
- token times: the end of each decode step in which its slot was active.
  The first of them delivers two tokens, the prefill's and the step's own
  (``serve()`` keeps the prefill's token on the device until then).

The measured window runs from the step counter's reaching ``open_step`` to
its reaching ``close_step`` (:func:`window`).
"""
from __future__ import annotations

import bisect
import math
import time
from dataclasses import dataclass
from typing import Dict, List, Optional

import jax
import numpy as np


@dataclass
class Tick:
    t1: float                    # its tokens on the host
    slots: np.ndarray            # active slot indices
    keys: np.ndarray             # positions each active slot attends
    admitted: int                # prefills since the previous step


class TimedEngine:
    """Proxy of a ``ContinuousEngine`` that records host times; with
    ``annotate``, each call is also a span of the profiler's trace."""

    def __init__(self, engine, requests, arrivals, *, open_step: int = 0,
                 annotate: bool = False, tracer=None):
        self._engine = engine
        self._tracer = tracer
        self._index = {id(r): i for i, r in enumerate(requests)}
        self._arrivals = np.asarray(arrivals, float)
        self._annotate = annotate
        self.open_step = int(open_step)
        self.opened_at: Optional[float] = None
        self.prefills: List[int] = []           # prompt tokens sent
        self.inserts: List[tuple] = []          # (request, slot, t1)
        self.ticks: List[Tick] = []
        self._slot_len = np.zeros(engine.max_slots, np.int64)
        self._pending = None
        self._unread = None                     # the step not yet read
        self._admitted = 0
        self.step = 0
        self.step_times: List[float] = []       # wall time of step k
        self.step_values: List[int] = []

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def start(self) -> None:
        self.step_values, self.step_times = [0], [time.perf_counter()]
        if self.open_step <= 0:
            self.opened_at = self.step_times[0]

    def _span(self, name):
        return (jax.profiler.TraceAnnotation(name) if self._annotate
                else _NoSpan())

    def _reach(self, step: int, t: float) -> None:
        self.step = step
        self.step_values.append(step)
        self.step_times.append(t)
        if self.opened_at is None and step >= self.open_step:
            self.opened_at = t

    def prefill(self, request, temperature: float = 0.0):
        self._settle()
        i = self._index[id(request)]
        t0 = time.perf_counter()
        if self._arrivals[i] > self.step:
            # the pool stood idle: serve() moved its counter to this arrival
            self._reach(int(math.ceil(self._arrivals[i])), t0)
        with self._span("prefill"):
            pf = self._engine.prefill(request, temperature)
        self.prefills.append(pf.length)
        self._pending = (i, pf.length)
        self._admitted += 1
        return pf

    def insert(self, prefix, slot: int) -> None:
        self._settle()
        with self._span("insert"):
            self._engine.insert(prefix, slot)
        i, length = self._pending
        self._slot_len[int(slot)] = length
        self.inserts.append((i, int(slot), time.perf_counter()))

    def generate_step(self, active=None):
        self._settle()
        with self._span("generate_step"):
            toks = self._engine.generate_step(active)
        act = (np.ones(self._engine.max_slots, bool) if active is None
               else np.asarray(active, bool))
        self._unread = np.flatnonzero(act)
        return _Tokens(toks, self)

    def _read(self, toks) -> np.ndarray:
        """``serve()`` reads a step's tokens: the step ends here."""
        with self._span("sync"):
            out = np.asarray(toks)
        self._end_step(time.perf_counter())
        return out

    def _settle(self) -> None:
        """A step whose tokens were never read ends at the next call."""
        if self._unread is not None:
            self._end_step(time.perf_counter())

    def _end_step(self, t1: float) -> None:
        slots, self._unread = self._unread, None
        if slots is None:
            return
        # a slot at position p writes p and attends p + 1 positions
        keys = self._slot_len[slots] + 1
        self._slot_len[slots] += 1
        self.ticks.append(Tick(t1, slots, keys, self._admitted))
        self._admitted = 0
        self._reach(self.step + 1, t1)
        if self._tracer is not None:
            self._tracer.tick(self, t1)

    def time_of_step(self, step: int) -> float:
        """When the counter first reached ``step`` (the last time recorded
        if it never did)."""
        k = bisect.bisect_left(self.step_values, step)
        return self.step_times[min(k, len(self.step_times) - 1)]

    # ------------------------------------------------------------ read-out
    def request_times(self, n: int):
        """Per request: (due, [decode step end times]) on the host clock."""
        self._settle()
        tokens: Dict[int, List[float]] = {i: [] for i in range(n)}
        slot_req = {}
        events = sorted([(t, 0, ("insert", i, s)) for i, s, t in self.inserts]
                        + [(k.t1, 1, ("tick", k)) for k in self.ticks],
                        key=lambda e: (e[0], e[1]))
        for _, _, ev in events:
            if ev[0] == "insert":
                slot_req[ev[2]] = ev[1]
            else:
                for s in ev[1].slots:
                    if int(s) in slot_req:
                        tokens[slot_req[int(s)]].append(ev[1].t1)
        due = [self.time_of_step(math.ceil(a - 1e-12))
               for a in self._arrivals[:n]]
        return due, tokens


class _Tokens:
    """A step's tokens, as ``generate_step`` returned them; reading them
    onto the host (``np.asarray``) ends the step on the proxy's clock."""

    def __init__(self, toks, timed: TimedEngine):
        self._toks, self._timed = toks, timed

    def __array__(self, dtype=None, copy=None):
        out = self._timed._read(self._toks)
        return out if dtype is None else out.astype(dtype)


class _NoSpan:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


@dataclass
class Window:
    """The measured window: its seconds, the tokens delivered in it, the
    time to first token of every request due in it and every gap between
    two consecutive deliveries of one request that ends in it."""
    t_open: float
    t_close: float
    tokens: int
    requests: int
    ttft_s: np.ndarray
    gaps_s: np.ndarray
    ticks: List[Tick]

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open


def window(timed: TimedEngine, n: int, open_step: int,
           close_step: int) -> Window:
    due, tokens = timed.request_times(n)
    lo, hi = timed.time_of_step(open_step), timed.time_of_step(close_step)

    def inside(t):
        return lo < t <= hi

    arr = timed._arrivals[:n]
    ttft, gaps, delivered, count = [], [], 0, 0
    for i in range(n):
        ts = tokens[i]
        if open_step <= arr[i] < close_step:
            count += 1
            if ts:
                ttft.append(ts[0] - due[i])
        if ts:
            delivered += 2 * inside(ts[0]) + sum(inside(t) for t in ts[1:])
            gaps.extend(b - a for a, b in zip(ts, ts[1:]) if inside(b))
    return Window(lo, hi, delivered, count, np.asarray(ttft, float),
                  np.asarray(gaps, float),
                  [k for k in timed.ticks if inside(k.t1)])
