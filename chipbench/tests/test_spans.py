"""The readers of ``serve()``'s own records, the split of the device's idle
time across the program's spans, on made-up runs and events whose answers
are known, and the split on a trace recorded on a TPU v5e
(``record_trace.py`` of a program that writes the ``serve.*`` spans)."""
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import harness, spans, trace

DATA = Path(__file__).resolve().parent / "data" / "serve_spans.xplane.pb"
NEW = ("queue_wait_p95_ms", "tick_host_ms")


def _records():
    # eight ticks, 100 ms apart; the host waits 80 ms of each for tokens;
    # ticks 0 and 4 admitted a request
    read = 0.1 * np.arange(1, 9)
    ticks = SimpleNamespace(read_s=read, wait_s=np.full(8, 0.08),
                            admitted=np.array([1, 0, 0, 0, 1, 0, 0, 0]))
    # ten requests, arriving at steps 0..9, each due 0.1 s apart and
    # admitted after 0.01 s per step of arrival; the last one was never
    # stepped (done at prefill)
    requests = SimpleNamespace(
        due_s=0.1 * np.arange(10), admit_s=0.1 * np.arange(10)
        + 0.01 * np.arange(10), first_tick=np.r_[np.arange(9), -1])
    return ticks, requests


def _run(ticks=None, requests=None, lo=0.15, hi=0.75):
    report = SimpleNamespace(queue_peak=0)
    if ticks is not None:
        report.ticks = ticks
    if requests is not None:
        report.requests = requests
    timed = SimpleNamespace(_arrivals=np.arange(10) + 0.5, open_step=2,
                            ticks=[], prefills=[])
    window = SimpleNamespace(t_open=lo, t_close=hi, requests=8, ticks=[])
    return SimpleNamespace(report=report, timed=timed, window=window,
                           trace=None)


def _new(run):
    return {k: v["value"] for k, v in harness.per_layer_all(run).items()
            if k in NEW}


def test_readers_on_made_up_records():
    got = _new(_run(*_records()))
    # in (0.15, 0.75]: ticks 2..6 end there after a tick there; tick 4
    # admitted: ticks 2, 3, 5, 6 leave 100 - 80 ms to the host
    assert got["tick_host_ms"] == pytest.approx(20.0)
    # due in the window: arrivals 2.5 .. 9.5, the eight from step 2; the
    # one never stepped is not in TTFT's set: waits of 20 .. 80 ms
    assert got["queue_wait_p95_ms"] == pytest.approx(
        np.percentile(10.0 * np.arange(2, 9), 95))


def test_readers_are_silent_without_records():
    # what the program reported before it kept records
    assert _new(_run()) == {}
    ticks, requests = _records()
    run = _run(ticks, requests, lo=5.0, hi=6.0)     # nothing in the window
    run.window.requests = 0
    assert _new(run) == {}


def _events(ops, spans_, lo=0.0, hi=10.0, chips=("/device:TPU:0",)):
    return trace.Events(
        ops={c: [("op", s, e) for s, e in ops] for c in chips},
        runs={c: [("jit_decode_step", 0.0, 0.0, 0)] for c in chips},
        spans=[(trace.WINDOW_SPAN, lo, hi)], launches=[],
        enqueued={0: 0.0}), spans_


def test_idle_is_split_by_overlap_and_innermost_span_wins():
    # device busy 0-2, 5-6, 9-10: idle 2-5 and 6-9
    ev, sp = _events([(0, 2), (5, 6), (9, 10)], [
        ("serve.tick", 1.0, 8.0), ("serve.step", 1.5, 2.5),
        ("serve.read", 2.5, 4.0), ("serve.book", 4.0, 4.5),
        ("serve.admit", 8.0, 8.5)])
    got = spans.idle_by_phase(ev, sp)
    assert got == pytest.approx({
        "serve.step": 0.5, "serve.read": 1.5, "serve.book": 0.5,
        "serve.tick": 0.5 + 2.0,     # 4.5-5 and 6-8 inside the tick alone
        "serve.admit": 0.5,          # an admission before the next tick
        "outside": 0.5})             # 8.5-9
    assert sum(got.values()) == pytest.approx(10.0 - 4.0)
    assert spans.host_idle_share(got, 10.0) == pytest.approx(45.0)


def test_idle_by_phase_averages_over_chips_and_sums_to_the_idle():
    ev, sp = _events([(1, 3)], [("serve.read", 0.0, 5.0)],
                     chips=("/device:TPU:0", "/device:TPU:1"))
    ev.ops["/device:TPU:1"] = [("op", 0.0, 10.0)]
    got = spans.idle_by_phase(ev, sp)
    assert got == pytest.approx({"serve.read": 1.5, "outside": 2.5})
    s = trace.reduce(ev)
    assert sum(got.values()) == pytest.approx(s.window_s - s.busy_s)


def test_innermost_pieces_of_nested_spans():
    pieces = spans.innermost([("a", 0, 10), ("b", 1, 3), ("c", 3, 4),
                              ("d", 12, 13)])
    assert pieces == [("a", 0, 1), ("b", 1, 3), ("c", 3, 4), ("a", 4, 10),
                      ("d", 12, 13)]


@pytest.fixture(scope="module")
def recorded():
    ev = trace.load(str(DATA))
    return ev, spans.load(str(DATA)), trace.reduce(ev)


def test_recorded_idle_by_phase_sums_to_the_idle(recorded):
    ev, sp, summary = recorded
    got = spans.idle_by_phase(ev, sp)
    assert abs(sum(got.values()) - (summary.window_s - summary.busy_s)) \
        < 1e-6
    assert {"serve.read", "serve.step", "serve.book"} <= set(got)
    # the existing split of the same gaps is unchanged beside it
    assert sum(summary.idle_by_host.values()) == pytest.approx(
        sum(got.values()))


def test_recorded_spans_nest_and_name_the_programs(recorded):
    ev, sp, summary = recorded
    lo, hi = [(s, e) for n, s, e in ev.spans if n == trace.WINDOW_SPAN][0]
    ticks = [(s, e) for n, s, e in sp if n == "serve.tick"
             and lo <= s and e <= hi]
    assert ticks
    for part in ("serve.step", "serve.read", "serve.book"):
        inner = [(s, e) for n, s, e in sp if n == part]
        assert all(sum(a <= s and e <= b for s, e in inner) == 1
                   for a, b in ticks)
    names = set(summary.programs)
    assert {"jit_prefill", "jit_insert", "jit_decode_step"} <= names
    assert not {"jit_f", "jit__step_impl"} & names
    # one decode step program run per traced generate_step, as before
    assert summary.runs_by_call["generate_step"] == sum(
        1 for n, s, e in ev.spans if n == "generate_step" and lo <= s <= hi)
