"""A run with the timed path broken underneath comes out not correct.

Each test drives a whole tiny cell on the CPU (set-up, the window through
``serve()``, the comparison), skipping only the look for a chip, with one
fault planted in the engine that the window drives."""
import jax.numpy as jnp
import pytest

from conftest import tiny_cell


def _run(**kw):
    cell = tiny_cell(n_requests=12, **kw)
    return cell.driver.run(cell)


def test_a_sound_run_is_correct():
    out = _run()
    assert out.failed == 0 and all(c.ok for c in out.checks), out.checks


def test_a_decoded_token_altered_where_it_is_produced(monkeypatch):
    from repro.serving import engine as engine_mod
    real = engine_mod.ContinuousEngine.generate_step
    calls = {"n": 0}

    def altered(self, active=None):
        toks = real(self, active)
        calls["n"] += 1
        if calls["n"] == 7:             # one token of one slot, once
            slot = int(jnp.argmax(jnp.asarray(active, bool)))
            toks = toks.at[slot].set((toks[slot] + 1) % self.cfg.vocab_size)
        return toks

    monkeypatch.setattr(engine_mod.ContinuousEngine, "generate_step",
                        altered)
    out = _run()
    assert not out.checks[0].ok, out.checks


def test_a_first_token_altered_where_prefill_produces_it(monkeypatch):
    from repro.serving import engine as engine_mod
    real = engine_mod.ContinuousEngine.prefill
    calls = {"n": 0}

    def altered(self, request, temperature=0.0):
        pf = real(self, request, temperature)
        calls["n"] += 1
        if calls["n"] % 5 == 0:         # past the warm-up's prefills
            pf.token = (pf.token + 1) % self.cfg.vocab_size
        return pf

    monkeypatch.setattr(engine_mod.ContinuousEngine, "prefill", altered)
    out = _run()
    assert not out.checks[0].ok, out.checks


def test_a_request_left_unfinished(monkeypatch):
    from repro.serving import scheduler
    real = scheduler.serve

    def drops_one(engine, requests, *a, **k):
        report = real(engine, requests, *a, **k)
        report.outputs[2] = report.outputs[2][:-1]
        return report

    import repro.serving
    monkeypatch.setattr(repro.serving, "serve", drops_one)
    out = _run()
    assert out.failed == 1
