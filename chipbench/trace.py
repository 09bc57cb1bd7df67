"""From a profiler trace to the device's busy time, its programs and its
idle gaps.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it. Each chip is a plane ``/device:TPU:<n>``; its ``XLA Ops`` line
holds one event per operation, its ``XLA Modules`` line one event per
program run (``jit_<name>(<fingerprint>)``, with a ``run_id``). The host
plane ``/host:CPU`` holds the harness's spans (``TraceAnnotation``), one
``PJRT_LoadedExecutable_Execute`` per program the host launched, on the
launching thread and in launch order, and a ``DoEnqueueProgram`` per run
with its ``run_id``.

The reduction:

- busy: the union of the operation intervals inside the window, per chip,
  averaged over the chips;
- each program run is put to the engine call that launched it: the k-th
  launch (in ``run_id`` order) is the k-th ``Execute`` on the host, inside
  one of the harness's spans;
- the device clock is put on the host's: no run starts before the host
  enqueued it, and the most prompt run starts when it was enqueued;
- idle gaps: the stretches of the window that no operation covers, each put
  to the host span that overlaps it most (``host`` where the host was in
  none: ``serve()``'s own Python between calls).
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
import tempfile
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
LAUNCH = "PJRT_LoadedExecutable_Execute"
ENQUEUE = "DoEnqueueProgram"
WINDOW_SPAN = "window"
CALL_SPANS = ("prefill", "insert", "generate_step")
HOST_SPANS = CALL_SPANS + ("sync",)
#: operations that hold others (their time is their children's)
CONTAINERS = re.compile(r"\b(while|conditional|call)\(")


@dataclass
class Events:
    """What the reduction reads of a trace; times in seconds."""
    ops: Dict[str, List[Tuple[str, float, float]]]      # chip -> operations
    runs: Dict[str, List[Tuple[str, float, float, int]]]  # chip -> programs
    spans: List[Tuple[str, float, float]]               # harness spans
    launches: List[float]                               # Execute, in order
    enqueued: Dict[int, float]                          # run_id -> time


@dataclass
class Summary:
    window_s: float
    busy_s: float
    programs: Dict[str, float]          # program -> device seconds
    ops: Dict[str, float]               # program/operation -> seconds
    idle_by_host: Dict[str, float]      # host activity -> idle seconds
    #: device seconds and runs per engine call that launched them; empty
    #: where launches and runs could not be matched one for one
    by_call: Dict[str, float] = field(default_factory=dict)
    runs_by_call: Dict[str, int] = field(default_factory=dict)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self) -> dict:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1])[:10]
        idle = sorted(self.idle_by_host.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in idle]}


def program_name(event_name: str) -> str:
    """``jit__step_impl(1234)`` -> ``jit__step_impl``."""
    return re.sub(r"\(\d+\)$", "", event_name).strip()


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...), kind=kLoop, ...`` ->
    ``fusion.12 kLoop``."""
    head = event_name.split(" = ")[0].lstrip("%")
    kind = re.search(r"kind=(k\w+)", event_name)
    return f"{head} {kind.group(1)}" if kind else head


def load(path: str) -> Events:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, runs, spans, launches, enqueued = {}, {}, [], [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = [
                        (e.name, e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events]
                elif line.name == MODULES_LINE:
                    runs[plane.name] = [
                        (program_name(e.name), e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9,
                         int(dict(e.stats).get("run_id", -1)))
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    t0 = e.start_ns * 1e-9
                    if e.name == WINDOW_SPAN or e.name in HOST_SPANS:
                        spans.append((e.name, t0, t0 + e.duration_ns * 1e-9))
                    elif e.name == LAUNCH:
                        launches.append(t0)
                    elif e.name == ENQUEUE:
                        rid = dict(e.stats).get("run_id")
                        if rid is not None:
                            enqueued[int(rid)] = t0
    return Events(ops, runs, spans, sorted(launches), enqueued)


def union(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _overlap(a, b, lo, hi) -> float:
    return max(0.0, min(b, hi) - max(a, lo))


class _Spans:
    """Host spans, one after another on the launching thread."""

    def __init__(self, spans):
        self.spans = sorted(spans, key=lambda s: s[1])
        self.starts = [s[1] for s in self.spans]

    def at(self, t: float) -> str:
        k = bisect.bisect_right(self.starts, t) - 1
        if k >= 0 and self.spans[k][2] >= t:
            return self.spans[k][0]
        return "host"

    def most(self, a: float, b: float) -> str:
        best, name = 0.0, "host"
        k = bisect.bisect_left(self.starts, b) - 1
        while k >= 0:
            n, s, e = self.spans[k]
            o = _overlap(a, b, s, e)
            if o > best:
                best, name = o, n
            if e <= a:
                break
            k -= 1
        return name


def clock_shift(runs, enqueued) -> float:
    """Seconds to add to device times to put them on the host's clock."""
    d = [enqueued[r] - s for _, s, _, r in runs if r in enqueued]
    return max(d) if d else 0.0


def reduce(ev: Events) -> Summary:
    win = [(s, e) for n, s, e in ev.spans if n == WINDOW_SPAN]
    if not win:
        raise ValueError("the trace holds no window span")
    lo, hi = win[0]
    chips = sorted(ev.ops) or sorted(ev.runs)
    if not chips:
        raise ValueError("the trace holds no device operations")
    calls = _Spans([s for s in ev.spans if s[0] in CALL_SPANS])
    host = _Spans([s for s in ev.spans if s[0] in HOST_SPANS])
    n = len(chips)
    busy, programs, ops = [], defaultdict(float), defaultdict(float)
    idle_by = defaultdict(float)
    by_call, runs_by_call = defaultdict(float), defaultdict(int)
    for chip in chips:
        runs = ev.runs.get(chip, [])
        shift = clock_shift(runs, ev.enqueued)
        op_ev = [(name, s + shift, e + shift)
                 for name, s, e in (ev.ops.get(chip) or
                                    [(r[0], r[1], r[2]) for r in runs])]
        cov = union([(max(s, lo), min(e, hi)) for _, s, e in op_ev
                     if e > lo and s < hi])
        busy.append(sum(b - a for a, b in cov))
        runs = sorted((s + shift, e + shift, name, r)
                      for name, s, e, r in runs)
        starts = [r[0] for r in runs]
        for s, e, name, _ in runs:
            programs[name] += _overlap(s, e, lo, hi) / n
        for name, s, e in op_ev:
            if e > lo and s < hi and not CONTAINERS.search(name):
                k = bisect.bisect_right(starts, s) - 1
                owner = runs[k][2] if k >= 0 and runs[k][1] >= s else "?"
                ops[f"{owner}/{op_name(name)}"] += _overlap(s, e, lo, hi) / n
        if chip == chips[0]:
            by_rid = sorted(runs, key=lambda r: r[3])
            if len(by_rid) == len(ev.launches):
                for (s, e, _, _), t in zip(by_rid, ev.launches):
                    if _overlap(s, e, lo, hi) > 0:
                        who = calls.at(t)
                        by_call[who] += _overlap(s, e, lo, hi)
                        runs_by_call[who] += 1
        edges = [lo] + [x for iv in cov for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                who = host.most(a, b)
                idle_by[who] += (b - a) / n
    return Summary(window_s=hi - lo, busy_s=sum(busy) / n,
                   programs=dict(programs), ops=dict(ops),
                   idle_by_host=dict(idle_by), by_call=dict(by_call),
                   runs_by_call=dict(runs_by_call))


def find(directory: str) -> str:
    found = glob.glob(os.path.join(directory, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[0]


class Recorder:
    """Profiles a stretch of the measured window: from the first decode step
    that ends ``start_s`` after the window opened to the first that ends
    ``stop_s`` after, inside a ``window`` span. Collecting a trace costs the
    host seconds for each second traced, and that pause falls after the
    stretch, in the traced run only. The Python tracer is off: the host's
    spans are the harness's."""

    def __init__(self, start_s: float, stop_s: float,
                 keep: Optional[str] = None):
        self.start_s, self.stop_s, self.keep = start_s, stop_s, keep
        self.dir = tempfile.mkdtemp(prefix="trace-")
        self.state = "before"
        self.ticks = self.prefills = (0, 0)   # index ranges traced
        self._span = None

    def tick(self, timed, now: float) -> None:
        """Called by the engine proxy at the end of every decode step."""
        import jax
        if timed.opened_at is None:
            return
        into = now - timed.opened_at
        if self.state == "before" and into >= self.start_s:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
            self._span.__enter__()
            self._lo = (len(timed.ticks), len(timed.prefills))
            self.state = "on"
        elif self.state == "on" and into >= self.stop_s:
            self.stop(timed)

    def stop(self, timed) -> None:
        import jax
        if self.state != "on":
            return
        self._span.__exit__(None, None, None)
        hi = (len(timed.ticks), len(timed.prefills))
        self.ticks, self.prefills = (self._lo[0], hi[0]), (self._lo[1], hi[1])
        jax.profiler.stop_trace()
        self.state = "done"

    def reduce(self) -> Optional[Summary]:
        if self.state != "done":
            return None
        path = find(self.dir)
        if self.keep:
            shutil.copy(path, self.keep)
        summary = reduce(load(path))
        shutil.rmtree(self.dir, ignore_errors=True)
        return summary
