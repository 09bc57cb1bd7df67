"""``serve()``'s tick and request records and its profiler spans.

The records are checked on a fake engine under a clock that moves only
where the engine works (a prefill, a dispatch, the device's step, an
``observe``), so every time is a hand-worked sum; against the benchmark's
timing proxy (``chipbench/timeline.py``) on the same runs; and the spans
and program names in a CPU profiler trace of a tiny model served."""
import glob
import re
import sys
import time
from pathlib import Path

import numpy as np
import pytest
from conftest import reduced_f32
from test_serving_engine import _expected_output, _FakeEngine

from repro.serving import Request, serve

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

PREFILL, DISPATCH, DEVICE, OBSERVE = 0.25, 0.125, 1.0, 0.0625


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


class _Pending:
    """A step's tokens on the device: reading them waits for the step."""

    def __init__(self, toks, clock):
        self._toks, self._clock = toks, clock

    def __array__(self, dtype=None, copy=None):
        self._clock.t += DEVICE
        return self._toks if dtype is None else self._toks.astype(dtype)


class _ClockedEngine(_FakeEngine):
    def __init__(self, clock, max_slots, max_len=64):
        super().__init__(max_slots, max_len)
        self.clock = clock

    def prefill(self, request, temperature=0.0):
        self.clock.t += PREFILL
        return super().prefill(request, temperature)

    def generate_step(self, active=None):
        toks = super().generate_step(active)
        self.clock.t += DISPATCH
        return _Pending(toks, self.clock)

    def observe(self, n_prefills, n_decode=1, wall_s=None):
        self.clock.t += OBSERVE


@pytest.fixture
def clock(monkeypatch):
    c = _Clock()
    monkeypatch.setattr(time, "perf_counter", c)
    return c


def _requests(lens, budgets):
    return [Request(np.full(n, i, np.int64), max_new_tokens=m)
            for i, (n, m) in enumerate(zip(lens, budgets))]


#: two slots; three requests at step 0 (the third queues for a slot), one
#: done at prefill, and one after the pool has stood idle (the counter
#: jumps from 2 to 8)
LENS, BUDGETS, ARRIVALS = [3, 5, 4, 2, 6], [3, 2, 2, 1, 2], [0, 0, 0, 1.5, 7.2]


@pytest.fixture
def worked(clock):
    eng = _ClockedEngine(clock, max_slots=2)
    return eng, serve(eng, _requests(LENS, BUDGETS), arrivals=ARRIVALS)


def test_tick_records_hand_worked(worked):
    _, rep = worked
    t = rep.ticks
    # tick 0: r0, r1 admitted at 0 and 0.25; dispatched at 0.5
    # tick 1: r2 into r1's slot at 1.6875 (after tick 0's observe)
    # tick 2: r3 (done at prefill) at 3.125, the jump, r4 at 3.4375
    np.testing.assert_array_equal(t.start_s, [0.0, 1.6875, 3.125])
    np.testing.assert_array_equal(t.read_s, [1.625, 3.0625, 4.8125])
    np.testing.assert_array_equal(t.admit_s, [0.5, 0.25, 0.5])
    np.testing.assert_array_equal(t.step_s, [DISPATCH] * 3)
    np.testing.assert_array_equal(t.wait_s, [DEVICE] * 3)
    np.testing.assert_array_equal(t.admitted, [2, 1, 2])
    np.testing.assert_array_equal(t.prompt_tokens, [3 + 5, 4, 2 + 6])
    np.testing.assert_array_equal(t.active, [2, 2, 1])
    # a slot at position p attends p + 1: (3+1)+(5+1), (4+1)+(4+1), 6+1
    np.testing.assert_array_equal(t.kv_positions, [10, 10, 7])
    assert t.admitted.dtype == np.int64 and t.read_s.dtype == np.float64


def test_request_records_hand_worked(worked):
    _, rep = worked
    r = rep.requests
    # the counter reached 0 at 0, 2 at 3.0625 and 8 (the jump) at r4's
    # admission
    np.testing.assert_array_equal(r.due_s, [0.0, 0.0, 0.0, 3.0625, 3.4375])
    np.testing.assert_array_equal(r.admit_s,
                                  [0.0, 0.25, 1.6875, 3.125, 3.4375])
    np.testing.assert_array_equal(r.first_tick, [0, 0, 1, -1, 2])
    np.testing.assert_array_equal(r.last_tick, [1, 0, 1, -1, 2])
    np.testing.assert_array_equal(r.prompt_len, LENS)


class _PagedEngine(_ClockedEngine):
    """Prefills each prompt at a power-of-two page; its prefill attention
    runs as the flash kernel at ``prefill_blocks`` where they divide the
    page."""
    prefill_blocks = (4, 4)

    def prefill(self, request, temperature=0.0):
        pf = super().prefill(request, temperature)
        pf.page = 1 << (pf.length - 1).bit_length()
        return pf


def test_request_records_count_prefill_attention_blocks(clock):
    """Each request records its page and the (q, kv) block pairs the
    kernel visited, the kernel's own host count (the causal triangle's
    blocks up to the length); 0 where the page is smaller than the block
    and the prefill ran as chunked_attention. The launcher's line gives
    their share of the pages' squares. An engine without pages or a
    block records 0 for both."""
    from repro.kernels.flash_attention import visited_blocks
    from repro.launch.serve import prefill_attention
    eng = _PagedEngine(clock, max_slots=2)
    r = serve(eng, _requests(LENS, BUDGETS), arrivals=ARRIVALS).requests
    np.testing.assert_array_equal(r.page, [4, 8, 4, 2, 8])
    # 5 of 8: q block 0 sees kv block 0; q block 1 (rows 4-7) kv 0 and 1
    np.testing.assert_array_equal(r.attn_blocks, [1, 3, 1, 0, 3])
    assert [visited_blocks(n, p, 4, 4) for n, p in
            [(3, 4), (5, 8), (4, 4), (6, 8)]] == [1, 3, 1, 3]
    assert prefill_attention(serve(eng, _requests(LENS, BUDGETS),
                                   arrivals=ARRIVALS), (4, 4)) == (
        "prefill attention: flash kernel at blocks 4 x 4 for 4 of 5 prompts, "
        "visited 8 of 10 block pairs (80.0% of their pages' squares)")
    plain = serve(_ClockedEngine(clock, max_slots=2),
                  _requests(LENS, BUDGETS), arrivals=ARRIVALS)
    np.testing.assert_array_equal(plain.requests.page, 0)
    np.testing.assert_array_equal(plain.requests.attn_blocks, 0)
    assert prefill_attention(plain, (0, 0)) == (
        "prefill attention: chunked_attention, every pair of each page")


def test_report_counts_from_records(worked):
    eng, rep = worked
    assert rep.n_steps == 3
    assert rep.occupancy_mean == pytest.approx(5 / 3)
    assert rep.queue_peak == 1
    assert rep.wall_s == 4.875
    for i, out in enumerate(rep.outputs):
        assert out.tolist() == _expected_output(i, LENS[i], BUDGETS[i],
                                                eng.max_len)


def test_no_decode_step_leaves_no_tick(clock):
    rep = serve(_ClockedEngine(clock, max_slots=2),
                _requests([3, 4, 5], [1, 1, 1]), arrivals=[0, 2, 9])
    assert rep.n_steps == 0 and rep.occupancy_mean == 0.0
    assert rep.ticks.read_s.shape == (0,)
    np.testing.assert_array_equal(rep.requests.first_tick, [-1, -1, -1])
    np.testing.assert_array_equal(rep.requests.admit_s, [0.0, 0.3125, 0.625])
    np.testing.assert_array_equal(rep.requests.due_s,
                                  rep.requests.admit_s)


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_ttft_and_gaps_match_the_proxy(clock, seed):
    """TTFT and the gaps between tokens, from the records, are the
    benchmark proxy's on the same run, for the window's requests."""
    from chipbench import timeline
    rng = np.random.default_rng(seed)
    n = 40
    lens = rng.integers(1, 30, n)
    budgets = rng.integers(1, 12, n)
    arrivals = np.cumsum(rng.exponential(1.2, n)) * (seed % 2 + 1)
    reqs = _requests(lens.tolist(), budgets.tolist())
    timed = timeline.TimedEngine(_ClockedEngine(clock, max_slots=4), reqs,
                                 arrivals)
    timed.start()
    rep = serve(timed, reqs, arrivals=arrivals)
    t, r = rep.ticks, rep.requests
    assert [k.t1 for k in timed.ticks] == t.read_s.tolist()
    assert [len(k.slots) for k in timed.ticks] == t.active.tolist()
    assert [int(k.keys.sum()) for k in timed.ticks] == \
        t.kv_positions.tolist()
    assert [k.admitted for k in timed.ticks] == t.admitted.tolist()
    for open_step, close_step in [(0, 10 ** 6), (5, 30), (12, 13)]:
        win = timeline.window(timed, n, open_step, close_step)
        due = (open_step <= arrivals) & (arrivals < close_step)
        mine = due & (r.first_tick >= 0)
        ttft = t.read_s[r.first_tick[mine]] - r.due_s[mine]
        np.testing.assert_array_equal(ttft, win.ttft_s)
        gaps = np.concatenate([np.zeros(0)] + [
            np.diff(t.read_s[a:b + 1]) for a, b in
            zip(r.first_tick, r.last_tick) if a >= 0])
        ends = np.concatenate([np.zeros(0)] + [
            t.read_s[a + 1:b + 1] for a, b in
            zip(r.first_tick, r.last_tick) if a >= 0])
        inside = (win.t_open < ends) & (ends <= win.t_close)
        np.testing.assert_array_equal(gaps[inside], win.gaps_s)
        # a request waits in the queue for part of its time to first token
        assert np.all(r.admit_s[mine] - r.due_s[mine] <= ttft)


@pytest.mark.parametrize("chunk,want", [
    # every position of both slots, whatever their lengths
    (0, [2 * 64] * 3),
    # each slot's extent (pos + 1) in chunks of 4: tick 0 at 3 and 5, tick
    # 1 at 4 and 4 (r2 in r1's slot), tick 2 the stale slot 0 at 5 and r4
    # at 6 (after r3, done at prefill, passed through slot 1 at 2)
    (4, [4 + 8, 8 + 8, 8 + 8]),
])
def test_kv_read_positions(clock, chunk, want):
    """What the step's attention read: with the engine's ``kv_chunk`` each
    slot's extent rounded up to it, inactive slots included (the pool
    kernel reads them too); without, ``slots x max_len``."""
    eng = _ClockedEngine(clock, max_slots=2)
    if chunk:
        eng.kv_chunk = chunk
    rep = serve(eng, _requests(LENS, BUDGETS), arrivals=ARRIVALS)
    np.testing.assert_array_equal(rep.ticks.kv_read_positions, want)
    np.testing.assert_array_equal(rep.ticks.kv_positions, [10, 10, 7])
    assert rep.ticks.kv_read_positions.dtype == np.int64


def test_engine_reads_live_chunks_with_the_pool_kernel(monkeypatch):
    """A real engine: attending over layer views its step reads every
    position; with the pool kernel (interpreted here) it reads whole
    chunks of 128 covering what each slot wrote, and serves the same
    tokens."""
    import dataclasses

    import jax

    from repro.models import attention as A
    from repro.models import model as M
    from repro.models.transformer import Runtime
    from repro.serving import ContinuousEngine
    cfg = dataclasses.replace(reduced_f32("stablelm-12b"), n_layers=1)
    rt = Runtime(tp=1)
    params, _ = M.init_params(cfg, rt, jax.random.PRNGKey(0))
    reqs = [Request(np.arange(1, n + 1, dtype=np.int32), max_new_tokens=m)
            for n, m in [(5, 3), (140, 4), (3, 1), (20, 3)]]
    reports = []
    for kernel in (False, True):
        monkeypatch.setattr(A, "_on_tpu", lambda: kernel)
        eng = ContinuousEngine(cfg, rt, params, max_slots=3, max_len=640)
        reports.append((eng.kv_chunk, serve(eng, reqs, arrivals=[0, 0, 1, 2])))
    (chunk_view, view), (chunk, kern) = reports
    assert chunk_view == 0 and chunk == 128
    np.testing.assert_array_equal(view.ticks.kv_read_positions, 3 * 640)
    read = kern.ticks.kv_read_positions
    assert np.all(read % chunk == 0)
    assert np.all(kern.ticks.kv_positions <= read)
    assert np.all(read < 3 * 640)
    for a, b in zip(view.outputs, kern.outputs):
        np.testing.assert_array_equal(a, b)


def test_launcher_latencies_from_records(worked):
    from repro.launch.serve import latencies_ms
    got = latencies_ms(worked[1])
    np.testing.assert_allclose(got["queue wait"],
                               [0, 250, 1687.5, 62.5, 0])
    np.testing.assert_allclose(got["TTFT"], [1625, 1625, 3062.5, 1375])
    np.testing.assert_allclose(got["ITL"], [1437.5])


def test_launcher_slowest_tick_adds_up(worked):
    from repro.launch.serve import slowest_tick
    # tick 2: tick 1's bookkeeping (its observe), r3's admission and its
    # observe while the pool stood idle, r4's, then the step
    assert slowest_tick(worked[1]) == (
        "slowest tick 2: 1750.0 ms = book 62.5 + admit 500.0 (2 requests, "
        "8 prompt tokens) + step 125.0 + wait 1000.0 (1 slots attending 7 "
        "positions) + other 62.5")


# ---------------------------------------------------------------------------
# Spans and program names in a CPU profiler trace
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    import dataclasses

    import jax
    from jax.profiler import ProfileData

    from repro.models import model as M
    from repro.models.transformer import Runtime
    from repro.serving import ContinuousEngine
    cfg = dataclasses.replace(reduced_f32("stablelm-12b"), n_layers=1)
    rt = Runtime(tp=1)
    params, _ = M.init_params(cfg, rt, jax.random.PRNGKey(0))
    eng = ContinuousEngine(cfg, rt, params, max_slots=2, max_len=48)
    # prompts on two pages (16 and 32 positions)
    reqs = [Request(np.arange(1, n + 1, dtype=np.int32), max_new_tokens=m)
            for n, m in [(5, 3), (9, 4), (3, 1), (20, 3)]]
    serve(eng, reqs, arrivals=[0, 0, 1, 1])          # compile every program
    out = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    rep = serve(eng, reqs, arrivals=[0, 0, 1, 6])
    jax.profiler.stop_trace()
    path = glob.glob(f"{out}/plugins/profile/*/*.xplane.pb")[0]
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events]
    return rep, events, eng


def test_each_tick_span_holds_its_step_read_and_book(traced):
    rep, events, _ = traced
    spans = {}
    for name, s, e in events:
        if name.startswith("serve."):
            spans.setdefault(name, []).append((s, e))
    ticks = sorted(spans["serve.tick"])
    assert len(ticks) == rep.n_steps
    assert len(spans["serve.admit"]) == 4
    for part in ("serve.step", "serve.read", "serve.book"):
        inner = sorted(spans[part])
        assert len(inner) == len(ticks)
        assert all(a <= s and e <= b for (a, b), (s, e) in zip(ticks, inner))
    # step, read and book follow one another inside each tick
    for k in range(len(ticks)):
        (_, step_end), (read_s, read_e), (book_s, _) = (
            sorted(spans[p])[k] for p in ("serve.step", "serve.read",
                                          "serve.book"))
        assert step_end <= read_s and read_e <= book_s


def _module(lowered) -> str:
    return re.match(r"module @(\S+)", lowered.as_text()).group(1)


def test_programs_have_stable_names(traced):
    import jax
    import jax.numpy as jnp
    _, events, eng = traced
    launched = {name[len("PjitFunction("):-1] for name, _, _ in events
                if name.startswith("PjitFunction(")}
    assert {"prefill", "insert", "decode_step"} <= launched
    # the XLA programs those calls run (a CPU trace does not name them),
    # one name per kind whatever the prompt's page
    sds = jax.ShapeDtypeStruct
    i32, f32 = sds((), jnp.int32), sds((), jnp.float32)
    names = {_module(eng._step_fn.lower(
        eng.params, eng._state, eng._pos, eng._tokens, eng._temps,
        eng._all_active, eng._key))}
    assert len(eng._prefill_fns) == 2
    for page, prefill in eng._prefill_fns.items():
        args = (eng.params, sds((1, page), jnp.int32), sds((1,), jnp.int32),
                f32, eng._key)
        tok, state = jax.eval_shape(prefill, *args)
        names.add(_module(prefill.lower(*args)))
        names.add(_module(eng._insert_fns[page].lower(
            eng._state, eng._pos, eng._tokens, eng._temps, state, tok, i32,
            i32, f32)))
    assert names == {"jit_prefill", "jit_insert", "jit_decode_step"}
