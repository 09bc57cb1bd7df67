"""The plain reference encodes the program's equations: at a small size on
the CPU it agrees with the program's own full forward
(``model.forward_logits``) on the benchmark's weights, and the comparison
fails a scratch engine whose decode position is off by one."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_cell


@pytest.fixture(scope="module")
def model():
    from repro.models.transformer import Runtime
    cell = tiny_cell()
    conf = cell.conf
    fam = cell.family
    return cell, conf, fam, fam.program_config(conf), Runtime(tp=1), \
        fam.make_weights(conf, 3)


def _reference(fam, conf, params, tokens, pos, seg, targets):
    ref = fam.reference
    x = ref.hidden(params, conf, jnp.asarray(tokens), jnp.asarray(pos),
                   jnp.asarray(seg))
    return [np.asarray(a) for a in ref.logits_at(params, conf, x,
                                                 jnp.asarray(targets))]


@pytest.mark.parametrize("config", ["mistral-nemo-12b-l8",
                                    "codestral-22b-l8"])
def test_reference_matches_the_program_forward(config):
    from repro.models import model as model_mod
    from repro.models.transformer import Runtime
    cell = tiny_cell(config)
    conf, fam = cell.conf, cell.family
    params = fam.make_weights(conf, 5)
    S = 96
    rng = np.random.default_rng(0)
    toks = rng.integers(0, conf["vocab_size"], S + 1).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(model_mod.forward_logits(
            fam.program_config(conf), Runtime(tp=1), params,
            {"tokens": jnp.asarray(toks[None])}))[0]
    targets = rng.integers(0, conf["vocab_size"], S).astype(np.int32)
    # pad the packed row to a multiple of the reference's chunk
    T = 128
    tokens = np.zeros(T, np.int32)
    tokens[:S] = toks[:S]
    pos = np.concatenate([np.arange(S), np.zeros(T - S)]).astype(np.int32)
    seg = np.concatenate([np.zeros(S), -np.ones(T - S)]).astype(np.int32)
    tg = np.zeros(T, np.int32)
    tg[:S] = targets
    best, at, arg = (a[:S] for a in _reference(fam, conf, params, tokens,
                                               pos, seg, tg))
    np.testing.assert_allclose(best, want.max(-1), atol=2e-4, rtol=1e-4)
    np.testing.assert_allclose(at, want[np.arange(S), targets], atol=2e-4,
                               rtol=1e-4)
    np.testing.assert_array_equal(arg, want.argmax(-1))


def test_packed_sequences_do_not_see_each_other(model):
    cell, conf, fam, _, _, params = model
    rng = np.random.default_rng(1)
    a = rng.integers(0, conf["vocab_size"], 40).astype(np.int32)
    b = rng.integers(0, conf["vocab_size"], 50).astype(np.int32)
    T = 128

    def row(parts):
        toks = np.concatenate(parts + [np.zeros(T - sum(map(len, parts)),
                                                np.int32)])
        pos = np.concatenate([np.arange(len(p)) for p in parts]
                             + [np.zeros(T - sum(map(len, parts)))])
        seg = np.concatenate([np.full(len(p), i) for i, p in enumerate(parts)]
                             + [-np.ones(T - sum(map(len, parts)))])
        return toks, pos.astype(np.int32), seg.astype(np.int32)

    tg = np.zeros(T, np.int32)
    both = _reference(fam, conf, params, *row([a, b]), tg)[0]
    alone = _reference(fam, conf, params, *row([b]), tg)[0]
    np.testing.assert_allclose(both[40:90], alone[:50], atol=1e-5)


def test_decode_position_off_by_one_fails_the_comparison(monkeypatch):
    from repro.models import decode as decode_mod
    cell = tiny_cell(n_requests=12)
    out = cell.driver.run(cell)
    assert all(c.ok for c in out.checks)

    real = decode_mod.decode_step

    def off_by_one(cfg, rt, p, token, pos, state):
        return real(cfg, rt, p, token, pos + 1, state)

    monkeypatch.setattr(decode_mod, "decode_step", off_by_one)
    bad = tiny_cell(n_requests=12)
    out = bad.driver.run(bad)
    gap = out.checks[0]
    assert gap.name == "max_logit_gap" and not gap.ok, gap


def test_a_long_row_reads_each_sequence_as_alone(model):
    """Past the longest sequence, a query chunk reads only the keys that
    end with it: six sequences in a row of 2048 read as each does alone."""
    cell, conf, fam, _, _, params = model
    rng = np.random.default_rng(2)
    lens = [300, 250, 400, 120, 350, 280]
    parts = [rng.integers(0, conf["vocab_size"], n).astype(np.int32)
             for n in lens]
    T = 2048

    def row(parts):
        used = sum(map(len, parts))
        toks = np.concatenate(parts + [np.zeros(T - used, np.int32)])
        pos = np.concatenate([np.arange(len(p)) for p in parts]
                             + [np.zeros(T - used)])
        seg = np.concatenate([np.full(len(p), i) for i, p in enumerate(parts)]
                             + [-np.ones(T - used)])
        return toks, pos.astype(np.int32), seg.astype(np.int32)

    tg = np.zeros(T, np.int32)
    both = _reference(fam, conf, params, *row(parts), tg)[0]
    at = 0
    for p in parts:
        alone = _reference(fam, conf, params, *row([p]), tg)[0]
        np.testing.assert_allclose(both[at:at + len(p)], alone[:len(p)],
                                   atol=1e-5)
        at += len(p)
