"""Each traffic mix, at its cell's window, holds the slot pool at its
steady load and keeps the queue bounded: checked in the scheduler's own
clock (decode steps) with an engine that runs no model, through the
program's own ``serve()``. The proxy times a step when ``serve()`` reads
its tokens, and reads none itself."""
import json
from unittest import mock

import numpy as np
import pytest

from chipbench import harness, timeline, traffic
from fake_engine import Clock, FakeEngine

BENCH = harness.load_json(harness.ROOT / "BENCHMARK.json")
CELLS = [w["name"] for w in BENCH["workloads"]]


def _serve(cell_name: str, seed: int = 1):
    from repro.serving import Request, serve
    cell = harness.cell(cell_name, seed, BENCH["run_seconds"], False)
    clock = Clock()
    eng = FakeEngine(clock, cell.conf["serving"]["slots"],
                     traffic.max_len(cell.mix))
    n = traffic.arriving_before(cell.mix, cell.window[1])
    tr = traffic.generate(cell.mix, n, seed, 1000)
    reqs = [Request(prompt=p, max_new_tokens=int(o))
            for p, o in zip(tr.prompts, tr.output_lens)]
    with mock.patch.object(timeline.time, "perf_counter", clock):
        timed = timeline.TimedEngine(eng, reqs, tr.arrivals,
                                     open_step=cell.window[0])
        timed.start()
        report = serve(timed, reqs, tr.arrivals)
    return cell, tr, report, timeline.window(timed, n, *cell.window)


@pytest.mark.parametrize("name", CELLS)
def test_the_window_holds_the_pool_at_its_steady_load(name):
    cell, tr, report, win = _serve(name)
    slots = cell.conf["serving"]["slots"]
    # requests arrive at 0.8 of the pool's capacity: over the window the
    # pool holds about 0.8 of its slots, and the queue forms and drains
    # again without ever holding as many requests as the pool has slots
    occupancy = np.mean([len(k.slots) for k in win.ticks])
    assert 0.7 * slots < occupancy < 0.9 * slots, occupancy
    assert report.queue_peak < slots, report.queue_peak
    # one clock unit a decode step: the window holds its steps, and every
    # request due in it has its time to first token
    assert len(win.ticks) == cell.window[1] - cell.window[0]
    assert win.seconds == pytest.approx(len(win.ticks))
    assert len(win.ttft_s) == win.requests
    # the pool drains once the arrivals stop, within the longest answer
    longest = int(cell.mix["output_tokens"]["max"])
    assert report.n_steps <= cell.window[1] + longest
    print(json.dumps({"cell": name, "requests": len(tr.arrivals),
                      "window_requests": win.requests,
                      "window_steps": len(win.ticks),
                      "queue_peak": report.queue_peak,
                      "occupancy": float(occupancy),
                      "decode_steps": report.n_steps,
                      "ttft_p95_steps": float(np.percentile(win.ttft_s, 95))}))


@pytest.mark.parametrize("name", CELLS)
def test_every_seed_serves_the_same_trace(name):
    cell = harness.cell(name, 1, 10, False)
    vocab = int(cell.conf["vocab_size"])
    a = traffic.generate(cell.mix, 40, 1, vocab)
    b = traffic.generate(cell.mix, 80, 2 ** 33 + 5, vocab)
    np.testing.assert_array_equal(a.prompt_lens, b.prompt_lens[:40])
    np.testing.assert_array_equal(a.output_lens, b.output_lens[:40])
    np.testing.assert_allclose(a.arrivals, b.arrivals[:40])
    assert any((x != y).any() for x, y in zip(a.prompts, b.prompts))
    lo, hi = cell.mix["prompt_tokens"]["min"], cell.mix["prompt_tokens"]["max"]
    assert lo <= a.prompt_lens.min() and a.prompt_lens.max() <= hi
    assert max(b.prompt_lens + b.output_lens) <= traffic.max_len(cell.mix)


def test_arrivals_keep_their_rate():
    mix = {"prompt_tokens": {"dist": "uniform", "min": 1, "max": 2},
           "output_tokens": {"dist": "uniform", "min": 1, "max": 2},
           "arrivals": {"process": "gamma", "shape": 0.25,
                        "rate_per_step": 0.1}}
    tr = traffic.generate(mix, 4000, 0, 10)
    gaps = np.diff(tr.arrivals)
    assert np.mean(gaps) == pytest.approx(10.0, rel=0.1)
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(2.0, rel=0.15)


def test_a_step_ends_when_serve_reads_its_tokens():
    clock = Clock()
    reads = []

    class Lazy:
        def __array__(self, dtype=None, copy=None):
            reads.append(clock.t)
            return np.zeros(2, np.int32)

    class Engine(FakeEngine):
        def generate_step(self, active=None):
            self.clock.t += 1.0
            return Lazy()

    with mock.patch.object(timeline.time, "perf_counter", clock):
        timed = timeline.TimedEngine(Engine(clock, 2, 64), [], [])
        timed.start()
        toks = timed.generate_step(np.array([True, False]))
        assert reads == [] and timed.ticks == []
        clock.t += 0.5                  # the host does other work first
        np.asarray(toks)
    assert reads == [1.5]
    assert [k.t1 for k in timed.ticks] == [1.5]
    assert timed.ticks[0].slots.tolist() == [0]
