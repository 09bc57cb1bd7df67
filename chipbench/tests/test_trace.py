"""The trace reduction, on a trace recorded on a TPU v5e
(``record_trace.py``: stablelm-12b at published widths cut to 2 layers,
4 slots, eight short requests)."""
from pathlib import Path

import numpy as np
import pytest

from chipbench import trace

DATA = Path(__file__).resolve().parent / "data" / "serve.xplane.pb"


@pytest.fixture(scope="module")
def events():
    return trace.load(str(DATA))


@pytest.fixture(scope="module")
def summary(events):
    return trace.reduce(events)


def _window(ev):
    return [(s, e) for n, s, e in ev.spans if n == trace.WINDOW_SPAN][0]


def test_busy_matches_a_raster_of_the_operations(events, summary):
    lo, hi = _window(events)
    chip = sorted(events.ops)[0]
    shift = trace.clock_shift(events.runs[chip], events.enqueued)
    step = 1e-7
    covered = np.zeros(int((hi - lo) / step) + 1, bool)
    for _, s, e in events.ops[chip]:
        a, b = max(s + shift, lo), min(e + shift, hi)
        if b > a:
            covered[int((a - lo) / step):int(np.ceil((b - lo) / step))] = True
    raster = covered.sum() * step
    assert summary.busy_s == pytest.approx(raster, abs=2e-7 * len(
        events.ops[chip]))
    assert 0 < summary.busy_s < summary.window_s


def test_idle_gaps_add_up_to_the_idle_time(summary):
    idle = sum(summary.idle_by_host.values())
    assert idle == pytest.approx(summary.window_s - summary.busy_s, rel=1e-9)
    assert set(summary.idle_by_host) <= set(trace.HOST_SPANS) | {"host"}


def test_each_run_goes_to_the_call_that_launched_it(events, summary):
    lo, hi = _window(events)
    spans = {n: sum(1 for m, s, e in events.spans if m == n and lo <= s <= hi)
             for n in trace.CALL_SPANS}
    chip = sorted(events.runs)[0]
    names = {n for n, *_ in events.runs[chip]}
    # every decode step is one run of the step program; prefill and insert
    # launch their program and a few scalar conversions each
    assert summary.runs_by_call["generate_step"] == spans["generate_step"]
    step_s = sum(min(e, hi) - max(s, lo) for n, s, e, _ in [
        (n, s + trace.clock_shift(events.runs[chip], events.enqueued),
         e + trace.clock_shift(events.runs[chip], events.enqueued), r)
        for n, s, e, r in events.runs[chip]] if n == "jit__step_impl")
    assert summary.by_call["generate_step"] == pytest.approx(step_s)
    assert summary.runs_by_call["prefill"] >= spans["prefill"]
    assert summary.runs_by_call["insert"] >= spans["insert"]
    assert sum(summary.by_call.values()) == pytest.approx(
        sum(summary.programs.values()))
    assert "jit__step_impl" in names


def test_clock_shift_puts_no_run_before_its_enqueue(events):
    chip = sorted(events.runs)[0]
    runs = events.runs[chip]
    shift = trace.clock_shift(runs, events.enqueued)
    lags = [s + shift - events.enqueued[r] for _, s, _, r in runs
            if r in events.enqueued]
    assert min(lags) == pytest.approx(0.0, abs=1e-12)
    assert len(lags) == len(runs)


def test_breakdown_lists_operations_and_gaps(summary):
    b = summary.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert all(v > 0 for _, v in b["device_ops"] + b["idle_gaps"])
    # containers (the layer loop) are not operations of their own
    assert not any("while" in k for k, _ in b["device_ops"])


def test_union_and_spans_on_made_up_intervals():
    assert trace.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [
        (0, 2.5), (3, 4)]
    sp = trace._Spans([("a", 0.0, 1.0), ("b", 1.0, 3.0), ("c", 3.5, 4.0)])
    assert sp.at(0.5) == "a" and sp.at(3.2) == "host"
    assert sp.most(0.8, 2.0) == "b" and sp.most(3.1, 3.4) == "host"
