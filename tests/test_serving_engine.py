"""Continuous-batching serving engine: scheduler slot invariants (jax-free
fake engine + hypothesis), per-phase power-policy decisions, continuous vs
lock-step greedy parity, and the served-trace -> Study round trip."""
import numpy as np
import pytest
from conftest import given, reduced_f32, settings, st

from repro.power import EnergySession, StepProfile, Study, Workload
from repro.serving import (ContinuousEngine, Request, ServeEngine,
                           poisson_arrivals, scale_profile, serve,
                           serving_profiles)

NOMINAL_MHZ = 1700


# ---------------------------------------------------------------------------
# Jax-free scheduler core: a fake engine that enforces the slot protocol
# ---------------------------------------------------------------------------
class _FakePrefix:
    def __init__(self, rid, token, length, max_new, temperature):
        self.state = rid
        self.token = token
        self.length = length
        self.max_new = max_new
        self.temperature = temperature


class _FakeEngine:
    """Implements the engine protocol :func:`serve` drives, with assertions
    where the device state would be: insert into a busy slot or stepping a
    finished slot is exactly the slot-leak bug class. Tokens encode
    (request id, step index) so output routing is fully checkable."""

    def __init__(self, max_slots, max_len=64):
        self.max_slots, self.max_len = max_slots, max_len
        self.session = None
        self.n_prefills = 0
        self.n_steps = 0
        self.left = [0] * max_slots        # tokens still owed per slot
        self.occupant = [-1] * max_slots
        self.count = [0] * max_slots

    def prefill(self, request, temperature=0.0):
        self.n_prefills += 1
        rid = int(request.prompt[0])
        L = max(1, min(len(request.prompt), self.max_len - 1))
        max_new = max(1, min(request.max_new_tokens, self.max_len - L))
        return _FakePrefix(rid, rid * 1000, L, max_new, temperature)

    def insert(self, prefix, slot):
        assert self.left[slot] == 0, "slot leak: insert into occupied slot"
        self.occupant[slot] = prefix.state
        self.left[slot] = prefix.max_new - 1
        self.count[slot] = 0

    def generate_step(self, active=None):
        act = (np.ones(self.max_slots, bool) if active is None
               else np.asarray(active, bool))
        toks = np.zeros(self.max_slots, np.int64)
        for s in range(self.max_slots):
            if act[s]:
                assert self.left[s] > 0, "stepping a finished slot"
                self.count[s] += 1
                self.left[s] -= 1
                toks[s] = self.occupant[s] * 1000 + self.count[s]
        self.n_steps += 1
        return toks

    def observe(self, n_prefills, n_decode=1, wall_s=None):
        return None


def _expected_output(rid, length, max_new, max_len):
    L = max(1, min(length, max_len - 1))
    n = max(1, min(max_new, max_len - L))
    return [rid * 1000 + k for k in range(n)]


@settings(max_examples=40, deadline=None)
@given(data=st.data())
def test_scheduler_slot_invariants(data):
    """Randomized arrivals/budgets: every request completes with exactly its
    clamped budget, tokens route to the right request, no slot is ever
    double-booked or stepped past its budget, and the pool drains empty."""
    n = data.draw(st.integers(0, 25), label="n_requests")
    slots = data.draw(st.integers(1, 6), label="slots")
    lens = data.draw(st.lists(st.integers(1, 20), min_size=n, max_size=n))
    budgets = data.draw(st.lists(st.integers(1, 9), min_size=n, max_size=n))
    gaps = data.draw(st.lists(st.floats(0.0, 4.0), min_size=n, max_size=n))
    arrivals = np.cumsum(np.asarray(gaps)) if n else []
    reqs = [Request(np.full(l, i, np.int64), max_new_tokens=m)
            for i, (l, m) in enumerate(zip(lens, budgets))]
    eng = _FakeEngine(slots)
    rep = serve(eng, reqs, arrivals=arrivals)
    assert eng.n_prefills == n
    assert all(left == 0 for left in eng.left), "pool did not drain"
    assert len(rep.outputs) == n
    for i, out in enumerate(rep.outputs):
        assert out.tolist() == _expected_output(i, lens[i], budgets[i],
                                                eng.max_len)
    assert rep.tokens_out == sum(len(o) for o in rep.outputs)
    if n:
        assert 0 < rep.occupancy_mean <= slots


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_scheduler_slot_invariants_deterministic(seed):
    """Seeded version of the hypothesis property above — runs even where
    hypothesis is not installed."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 25))
    slots = int(rng.integers(1, 6))
    lens = rng.integers(1, 20, n)
    budgets = rng.integers(1, 9, n)
    arrivals = np.cumsum(rng.exponential(1.5, n))
    reqs = [Request(np.full(int(l), i, np.int64), max_new_tokens=int(m))
            for i, (l, m) in enumerate(zip(lens, budgets))]
    eng = _FakeEngine(slots)
    rep = serve(eng, reqs, arrivals=arrivals)
    assert eng.n_prefills == n
    assert all(left == 0 for left in eng.left)
    for i, out in enumerate(rep.outputs):
        assert out.tolist() == _expected_output(i, int(lens[i]),
                                                int(budgets[i]), eng.max_len)
    assert 0 < rep.occupancy_mean <= slots


def test_serve_rejects_mismatched_arrivals():
    with pytest.raises(ValueError, match="arrival times"):
        serve(_FakeEngine(2), [Request(np.array([0]), 2)], arrivals=[0, 1])


def test_poisson_arrivals_deterministic_and_monotone():
    a = poisson_arrivals(500, rate_per_step=2.0, seed=3)
    b = poisson_arrivals(500, rate_per_step=2.0, seed=3)
    np.testing.assert_array_equal(a, b)
    assert len(a) == 500 and np.all(np.diff(a) >= 0) and a[0] > 0
    # mean inter-arrival gap ~ 1/rate
    assert 0.3 < np.mean(np.diff(a)) < 0.8


# ---------------------------------------------------------------------------
# Profiles and phase accounting (no model needed)
# ---------------------------------------------------------------------------
def test_serving_profiles_phase_split():
    """At production shapes the derived profiles land on opposite sides of
    the roofline: prefill compute-bound, decode memory-bound."""
    from repro.configs import get_config
    pre, dec = serving_profiles(get_config("stablelm-12b"), batch=8,
                                prompt_len=512, context_len=2048)
    assert pre.compute_s > pre.memory_s
    assert dec.memory_s > dec.compute_s


def test_scale_profile_keeps_intensity():
    p = StepProfile(compute_s=0.2, memory_s=1.0)
    s = scale_profile(p, 0.005)
    assert s.total_s == pytest.approx(0.005)
    assert s.compute_s / s.memory_s == pytest.approx(0.2)


def test_session_phase_report_caps_decode_not_prefill():
    """Distinct prefill/decode profiles through one session: the policy caps
    the memory-bound phase deep and leaves the compute-bound phase at
    nominal, with per-phase savings/dT accounted."""
    sess = EnergySession(policy="energy-aware", slowdown_budget=0.0)
    pre = StepProfile(compute_s=1.0, memory_s=0.1)
    dec = StepProfile(compute_s=0.01, memory_s=1.0)
    sess.observe_many([pre, dec, dec, dec, pre, dec], wall_s=0.1)
    report = sess.phase_report()
    assert len(report) == 2
    modes = {idx: r for idx, r in report.items()}
    (ci_idx, ci), (mi_idx, mi) = sorted(
        modes.items(), key=lambda kv: kv[1]["freq_mhz_mean"], reverse=True)
    assert ci["steps"] == 2 and mi["steps"] == 4
    assert ci["freq_mhz_mean"] == NOMINAL_MHZ          # prefill stays nominal
    assert mi["freq_mhz_mean"] < NOMINAL_MHZ           # decode capped deep
    assert mi["savings_pct"] > 0
    assert sess.dt_pct() <= 1e-6                       # zero-slowdown budget
    assert mi["dt_pct"] <= 1e-6
    assert "dt_pct" in sess.summary()


def test_from_serving_requires_session():
    with pytest.raises(ValueError, match="EnergySession"):
        Workload.from_serving(object())


def test_continuous_engine_rejects_recurrent_families():
    cfg = reduced_f32("mamba2-2.7b")
    with pytest.raises(ValueError, match="continuous batching"):
        ContinuousEngine(cfg, None, None)


# ---------------------------------------------------------------------------
# Real-model tests (slow lane)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def served():
    import jax
    from repro.models import model as M
    from repro.models.transformer import Runtime
    cfg = reduced_f32("stablelm-12b")
    rt = Runtime(tp=1, moe_impl="local")
    params, _ = M.init_params(cfg, rt, jax.random.PRNGKey(0))
    return cfg, rt, params


@pytest.mark.slow
def test_continuous_matches_lockstep_greedy_same_length(served):
    cfg, rt, params = served
    engine = ServeEngine(cfg, rt, params, max_len=48)
    rng = np.random.default_rng(0)
    reqs = [Request(rng.integers(0, cfg.vocab_size, 9, dtype=np.int32),
                    max_new_tokens=6) for _ in range(3)]
    cont = engine.generate(reqs)          # greedy dense -> continuous route
    lock = engine.generate_blocking(reqs)
    for c, l in zip(cont, lock):
        np.testing.assert_array_equal(c, l)


@pytest.mark.slow
def test_slot_pool_outputs_independent_of_batch_composition(served):
    """The defining property of per-slot masking: a request's tokens don't
    depend on what shares the pool with it (randomized arrivals/budgets)."""
    cfg, rt, params = served
    eng = ContinuousEngine(cfg, rt, params, max_slots=3, max_len=48)
    rng = np.random.default_rng(2)
    reqs = [Request(rng.integers(0, cfg.vocab_size, int(l), dtype=np.int32),
                    max_new_tokens=int(m))
            for l, m in zip(rng.integers(2, 14, 8), rng.integers(1, 7, 8))]
    rep = serve(eng, reqs, arrivals=poisson_arrivals(8, 1.0, seed=4))
    solo_eng = ContinuousEngine(cfg, rt, params, max_slots=1, max_len=48)
    for i, r in enumerate(reqs):
        solo = serve(solo_eng, [r]).outputs[0]
        np.testing.assert_array_equal(rep.outputs[i], solo)


@pytest.mark.slow
def test_engine_session_per_phase_windows(served):
    """Under a real served trace the session records both phases: decode
    windows capped below nominal, prefill windows at nominal, dT within the
    policy's own budget."""
    cfg, rt, params = served
    from repro.configs import get_config
    pre, dec = serving_profiles(get_config("stablelm-12b"), batch=4,
                                prompt_len=512, context_len=2048)
    sess = EnergySession(policy="energy-aware", slowdown_budget=0.0)
    eng = ContinuousEngine(cfg, rt, params, max_slots=4, max_len=48,
                           session=sess, prefill_profile=pre,
                           decode_profile=dec)
    reqs = [Request(np.arange(1, 6, dtype=np.int32), max_new_tokens=4)
            for _ in range(6)]
    serve(eng, reqs, arrivals=poisson_arrivals(6, 2.0, seed=0))
    report = sess.phase_report()
    assert len(report) == 2                       # both phases decided
    freqs = sorted(r["freq_mhz_mean"] for r in report.values())
    assert freqs[0] < NOMINAL_MHZ and freqs[1] == NOMINAL_MHZ
    assert sess.savings_pct() > 0
    assert sess.dt_pct() <= 1e-6                  # dT <= the policy budget
    assert len(sess.mode_hours_pct()) >= 1


@pytest.mark.slow
def test_from_serving_study_roundtrip(served):
    """A served trace flows into a 2-axis Study grid like any fleet
    workload."""
    cfg, rt, params = served
    from repro.configs import get_config
    pre, dec = serving_profiles(get_config("stablelm-12b"), batch=4,
                                prompt_len=512, context_len=2048)
    sess = EnergySession(policy=None)             # nominal recording
    eng = ContinuousEngine(cfg, rt, params, max_slots=4, max_len=48,
                           session=sess, prefill_profile=pre,
                           decode_profile=dec)
    reqs = [Request(np.arange(1, 8, dtype=np.int32), max_new_tokens=5)
            for _ in range(5)]
    rep = serve(eng, reqs)
    w = Workload.from_serving(rep, name="served")
    assert w.name == "served"
    result = Study(workloads=[w], chips=["tpu-v5e", "mi250x-gcd"],
                   caps=[900.0, 1100.0]).run()
    assert len(result) == 4                       # 2 chips x 2 caps
    assert np.all(np.isfinite(result.savings_pct))
    # the snapshot is decoupled from the live session: more serving traffic
    # does not change the workload
    before = w._store.total_energy_j()
    serve(eng, reqs)
    assert w._store.total_energy_j() == before


@pytest.mark.slow
def test_launch_serve_run_answers_every_request():
    """The serving entry point as chip_smoke.py calls it, at a tiny size:
    seeded log-uniform prompts under Poisson arrivals, every request
    answered with its full budget."""
    import dataclasses
    from repro.configs import get_config
    from repro.launch import serve as serve_launch
    cfg = dataclasses.replace(get_config("stablelm-12b").reduced(),
                              n_layers=2)
    engine, reqs, rep = serve_launch.run(
        cfg, requests=5, slots=2, max_len=64, prompt_lens=(4, 40),
        new_tokens=4, seed=3)
    lens = [len(r.prompt) for r in reqs]
    assert all(4 <= n <= 40 for n in lens) and len(set(lens)) > 1
    assert [len(o) for o in rep.outputs] == [4] * 5
    assert engine.n_prefills == 5
    again = serve_launch.make_requests(cfg, 5, (4, 40), 4, seed=3)
    assert [len(r.prompt) for r in again] == lens


@pytest.mark.parametrize("arch", ["recurrentgemma-2b", "mamba2-2.7b",
                                  "seamless-m4t-large-v2",
                                  "llama-3.2-vision-11b"])
def test_launch_serve_refuses_non_slot_families(arch, monkeypatch, capsys):
    """The launcher drives the slot engine only and says so, before it
    builds anything, for the families it does not serve."""
    from repro.launch import serve as serve_launch
    monkeypatch.setattr("sys.argv", ["serve", "--arch", arch, "--reduced"])
    with pytest.raises(SystemExit) as e:
        serve_launch.main()
    assert e.value.code == 2
    assert "slot families" in capsys.readouterr().err
