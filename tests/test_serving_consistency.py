"""The strongest end-to-end model test: prefill + step-by-step decode must
reproduce the teacher-forced forward logits for EVERY architecture family
(KV caches, MLA latent cache, SSM state, RG-LRU state, ring buffers,
cross-attention caches all exercised)."""
import functools

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import reduced_f32
from repro.configs import ARCH_IDS
from repro.models import attention as A
from repro.models import decode as D
from repro.models import model as M
from repro.models import transformer as T
from repro.models.common import apply_rope, rms_norm
from repro.models.transformer import Runtime

B, S = 2, 16


@pytest.fixture(autouse=True)
def _no_moe_capacity_drops(monkeypatch):
    """Teacher-forced forward and decode can only match bit-for-bit if no
    (token, expert) pair overflows the MoE capacity buffers: capacity is
    derived from the token count, which differs between the full forward and
    a 1-token decode step (same override as tests/test_distributed.py)."""
    from repro.models import moe as moe_mod
    monkeypatch.setattr(moe_mod, "CAPACITY_FACTOR", 8.0)


@pytest.mark.slow
@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_matches_forward(arch):
    cfg = reduced_f32(arch)
    rt = Runtime(tp=1, moe_impl="local")
    key = jax.random.PRNGKey(0)
    params, _ = M.init_params(cfg, rt, key)
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": tokens}
    if cfg.frontend_seq:
        batch["frontend"] = jax.random.normal(
            key, (B, cfg.frontend_seq, cfg.d_model), jnp.float32) * 0.02

    full = M.forward_logits(
        cfg, rt, params, {**batch, "tokens": jnp.pad(tokens, ((0, 0), (0, 1)))})
    P0 = S // 2
    pf_logits, state = D.prefill(cfg, rt, params,
                                 {**batch, "tokens": tokens[:, :P0]}, S)
    np.testing.assert_allclose(pf_logits[:, 0], full[:, P0 - 1],
                               rtol=2e-4, atol=2e-4)
    for t in range(P0, S):
        lg, state = D.decode_step(cfg, rt, params, tokens[:, t:t + 1],
                                  jnp.int32(t), state)
        np.testing.assert_allclose(lg[:, 0], full[:, t],
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("arch", ["stablelm-12b", "deepseek-v3-671b",
                                  "recurrentgemma-2b"])
def test_scalar_pos_is_the_pos_of_every_sequence(arch):
    """A scalar decode position is that position for every sequence: the
    step gives exactly the logits and state it gives with ``pos`` broadcast
    to ``[B]`` (dense, MLA, and the hybrid's ring buffer)."""
    cfg = reduced_f32(arch)
    rt = Runtime(tp=1, moe_impl="local")
    key = jax.random.PRNGKey(4)
    params, _ = M.init_params(cfg, rt, key)
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    _, state = D.prefill(cfg, rt, params, {"tokens": tokens[:, :S - 1]}, S)
    step = jax.jit(functools.partial(D.decode_step, cfg, rt))
    pos = jnp.int32(S - 1)
    scalar = step(params, tokens[:, S - 1:], pos, state)
    vector = step(params, tokens[:, S - 1:], jnp.full((B,), pos), state)
    for got, want in zip(jax.tree.leaves(scalar), jax.tree.leaves(vector)):
        np.testing.assert_array_equal(got, want)


def _reference_attention(p, cfg, z, pos, cache, layer):
    """One layer's decode attention, one sequence at a time: write the new
    row at ``pos[b]``, then attend over positions ``0..pos[b]`` in float64.
    GQA expands the kv heads; MLA decompresses per-head keys and values from
    the latent (the program absorbs the up-projections instead)."""
    posm = pos[:, None]
    if cfg.use_mla:
        q_nope, q_rope = A._mla_q(p, cfg, z, posm)
        c_new, kr_new = A._mla_latent(p, cfg, z, posm)
        q = np.concatenate([np.asarray(q_nope), np.asarray(q_rope)], -1)
        rows = {"c_kv": c_new, "k_rope": kr_new}
        scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    else:
        q, k, v = A._qkv(p, z)
        q = np.asarray(apply_rope(q, posm, cfg.rope_theta))
        rows = {"k": apply_rope(k, posm, cfg.rope_theta), "v": v}
        scale = cfg.resolved_head_dim ** -0.5
    for name, row in rows.items():
        for b in range(len(pos)):
            cache[name][layer, b, pos[b]] = np.asarray(row)[b, 0]
    outs = []
    for b in range(len(pos)):
        live = slice(0, pos[b] + 1)
        if cfg.use_mla:
            c = cache["c_kv"][layer, b, live].astype(np.float64)
            kr = cache["k_rope"][layer, b, live].astype(np.float64)
            k_nope = np.einsum("tr,rhk->thk", c, np.asarray(p["wk_b"]))
            keys = np.concatenate(
                [k_nope, np.broadcast_to(kr[:, None], k_nope.shape[:2]
                                         + kr.shape[-1:])], -1)
            vals = np.einsum("tr,rhk->thk", c, np.asarray(p["wv_b"]))
        else:
            G = q.shape[2] // cache["k"].shape[3]
            keys = np.repeat(cache["k"][layer, b, live], G, axis=1)
            vals = np.repeat(cache["v"][layer, b, live], G, axis=1)
        s = np.einsum("hd,thd->ht", q[b, 0].astype(np.float64), keys) * scale
        w = np.exp(s - s.max(-1, keepdims=True))
        w /= w.sum(-1, keepdims=True)
        outs.append(np.einsum("ht,thd->hd", w, vals))
    out = np.stack(outs)[:, None].astype(np.float32)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def _reference_decode(cfg, rt, params, tokens, pos, cache):
    """The decode step as a plain Python loop over the layers, writing into
    the numpy ``cache`` in place."""
    x = M.embed(params, cfg, tokens[:, None])
    for layer in range(cfg.n_layers):
        p = jax.tree.map(lambda a: a[layer], params["layers"])
        z = rms_norm(x, p["ln1"], cfg.norm_eps)
        x = x + _reference_attention(p["attn"], cfg, z, pos, cache, layer)
        y, _ = T._ffn(p, cfg, rt, x, decode=True)
        x = x + y
    return M.logits_fn(params, cfg, x)


@pytest.fixture
def pool_kernel(monkeypatch):
    """Select the pool kernel on the CPU, in the Pallas interpreter."""
    monkeypatch.setattr(A, "_on_tpu", lambda: True)


#: the pool kernel's cases read 640 positions in chunks of 128, and start
#: slots either side of a chunk edge and three steps short of the end
_KERNEL_CASE = dict(max_len=640, pos=[127, 637, 0, 255])


@pytest.mark.parametrize("arch,kernel", [
    pytest.param("stablelm-12b", False, id="stablelm-12b"),
    pytest.param("deepseek-v3-671b", False, id="deepseek-v3-671b"),
    pytest.param("stablelm-12b", True, id="stablelm-12b-pool-kernel"),
])
def test_slot_pool_decode_matches_a_loop_over_layers(arch, kernel, request):
    """Slot-pool decode (per-slot positions, one slot inactive, several
    steps) gives the logits and every cache row of a plain loop over the
    layers: the new rows land at ``(layer, slot, pos % max_len)``, every
    other row is left as it was, and stale rows past a slot's position
    are masked. With ``kernel``, attention is the pool kernel's, reading
    the stack after the rows are written."""
    cfg = reduced_f32(arch)
    rt = Runtime(tp=1, moe_impl="local")
    key = jax.random.PRNGKey(3)
    params, _ = M.init_params(cfg, rt, key)
    case = _KERNEL_CASE if kernel else dict(max_len=24, pos=[3, 17, 0, 9])
    if kernel:
        request.getfixturevalue("pool_kernel")
    slots, max_len = 4, case["max_len"]
    # stale rows everywhere: a slot may only ever see its own live rows
    state = jax.tree.map(
        lambda a: jax.random.normal(key, a.shape, a.dtype),
        D.init_decode_state(cfg, rt, slots, max_len))
    cache = jax.tree.map(np.array, state["layers"])
    pos = jnp.array(case["pos"], jnp.int32)
    active = jnp.array([True, True, False, True])
    tokens = jax.random.randint(key, (slots,), 0, cfg.vocab_size)
    step = jax.jit(functools.partial(D.decode_step, cfg, rt))
    for _ in range(3):
        written = np.zeros(cache[next(iter(cache))].shape[:3], bool)
        written[:, np.arange(slots), np.asarray(pos)] = True
        before = jax.tree.map(np.asarray, state["layers"])
        logits, state = step(params, tokens[:, None], pos, state)
        want = _reference_decode(cfg, rt, params, tokens, np.asarray(pos),
                                 cache)
        np.testing.assert_allclose(logits, want, rtol=2e-4, atol=2e-4)
        for name, got in state["layers"].items():
            got = np.asarray(got)
            np.testing.assert_array_equal(got[~written],
                                          before[name][~written])
            np.testing.assert_allclose(got[written], cache[name][written],
                                       rtol=2e-5, atol=2e-5)
        pos = pos + active.astype(jnp.int32)
        tokens = jnp.where(active, jnp.argmax(logits[:, 0], -1), tokens)


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of nested jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else [param]:
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    yield from _eqns(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _eqns(sub)


def _scans(jaxpr):
    """Every scan equation of a jaxpr, nested ones included."""
    return (eqn for eqn in _eqns(jaxpr) if eqn.primitive.name == "scan")


@pytest.mark.parametrize("arch", ["stablelm-12b", "dbrx-132b",
                                  "deepseek-v3-671b"])
def test_decode_step_carries_the_stacked_cache(arch):
    """The stacked cache goes into the layer scan as carry and comes out as
    carry, untouched on the way: no scan takes it as xs (a per-layer slice
    copied out) or gives it back as ys (a fresh stack written), the pattern
    that moved the whole cache several times a step."""
    cfg = reduced_f32(arch)
    rt = Runtime(tp=1, moe_impl="local")
    params = jax.eval_shape(
        lambda k: M.init_params(cfg, rt, k)[0], jax.random.PRNGKey(0))
    state = jax.eval_shape(lambda: D.init_decode_state(cfg, rt, 3, 40))
    tok = jax.ShapeDtypeStruct((3, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((3,), jnp.int32)
    closed = jax.make_jaxpr(functools.partial(D.decode_step, cfg, rt))(
        params, tok, pos, state)
    jaxpr = closed.jaxpr
    n_state = len(jax.tree.leaves(state))
    stacks = {leaf.shape for leaf in jax.tree.leaves(state)}
    state_in, state_out = jaxpr.invars[-n_state:], jaxpr.outvars[1:]

    layer_scans = []
    for eqn in _scans(jaxpr):
        n_const, n_carry = eqn.params["num_consts"], eqn.params["num_carry"]
        xs = eqn.invars[n_const + n_carry:]
        ys = eqn.outvars[n_carry:]
        assert not any(v.aval.shape in stacks for v in xs + ys), eqn
        carry_in = eqn.invars[n_const:n_const + n_carry]
        if set(state_in) <= set(carry_in):
            layer_scans.append(eqn)
            assert set(state_out) <= set(eqn.outvars[:n_carry])
    assert len(layer_scans) == 1


@pytest.mark.parametrize("arch", ["stablelm-12b", "dbrx-132b"])
def test_pool_kernel_step_matches_the_layer_view_step(arch, monkeypatch):
    """The decode step with the pool kernel gives the logits and the cache
    of the step that attends over each layer's view (the XLA path, which
    the loop over layers above checks), over three steps from stale rows
    with one slot inactive; the ``moe`` family takes the kernel too."""
    cfg = reduced_f32(arch)
    rt = Runtime(tp=1, moe_impl="local")
    key = jax.random.PRNGKey(5)
    params, _ = M.init_params(cfg, rt, key)
    state0 = jax.tree.map(
        lambda a: jax.random.normal(key, a.shape, a.dtype),
        D.init_decode_state(cfg, rt, 4, _KERNEL_CASE["max_len"]))
    active = jnp.array([True, True, False, True])
    runs = []
    for kernel in (False, True):                        # view, then kernel
        monkeypatch.setattr(A, "_on_tpu", lambda: kernel)
        step = jax.jit(functools.partial(D.decode_step, cfg, rt))
        state, pos = state0, jnp.array(_KERNEL_CASE["pos"], jnp.int32)
        tokens = jax.random.randint(key, (4,), 0, cfg.vocab_size)
        logits = []
        for _ in range(3):
            lg, state = step(params, tokens[:, None], pos, state)
            logits.append(lg)
            pos = pos + active.astype(jnp.int32)
            tokens = jnp.where(active, jnp.argmax(lg[:, 0], -1), tokens)
        runs.append((logits, state))
    (view_logits, view_state), (kern_logits, kern_state) = runs
    for got, want in zip(kern_logits, view_logits):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for name in view_state["layers"]:
        np.testing.assert_allclose(kern_state["layers"][name],
                                   view_state["layers"][name],
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("arch", ["stablelm-12b", "dbrx-132b"])
def test_pool_kernel_reads_the_stack_whole(arch, pool_kernel):
    """With the pool kernel, the stacked K and V enter its ``pallas_call``
    whole, after the row write, and no layer of the stack is sliced or
    gathered out of it anywhere in the step: the layer view that XLA copied
    before the attention's dot is gone."""
    cfg = reduced_f32(arch)
    rt = Runtime(tp=1, moe_impl="local")
    params = jax.eval_shape(
        lambda k: M.init_params(cfg, rt, k)[0], jax.random.PRNGKey(0))
    state = jax.eval_shape(lambda: D.init_decode_state(cfg, rt, 3, 640))
    tok = jax.ShapeDtypeStruct((3, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((3,), jnp.int32)
    closed = jax.make_jaxpr(functools.partial(D.decode_step, cfg, rt))(
        params, tok, pos, state)
    stack = state["layers"]["k"].shape
    eqns = list(_eqns(closed.jaxpr))
    kernels = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(kernels) == 1
    whole = [v for v in kernels[0].invars if v.aval.shape == stack]
    assert len(whole) == 2                          # K and V
    assert all(w.count > 0 for w in whole)          # written, not the input
    for eqn in eqns:
        if eqn.primitive.name in ("dynamic_slice", "gather", "slice",
                                  "squeeze"):
            assert not any(v.aval.shape == stack for v in eqn.invars), eqn


@pytest.mark.slow
def test_serve_engine_greedy_consistency():
    from repro.serving import Request, ServeEngine
    cfg = reduced_f32("stablelm-12b")
    rt = Runtime(tp=1, moe_impl="local")
    params, _ = M.init_params(cfg, rt, jax.random.PRNGKey(0))
    engine = ServeEngine(cfg, rt, params, max_len=48)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, 8, dtype=np.int32)
               for _ in range(2)]
    outs = engine.generate([Request(p, max_new_tokens=6) for p in prompts])
    assert len(outs) == 2 and outs[0].shape == (6,)
    # greedy decode is deterministic
    outs2 = engine.generate([Request(p, max_new_tokens=6) for p in prompts])
    np.testing.assert_array_equal(outs[0], outs2[0])


@pytest.mark.slow
def test_serve_engine_heterogeneous_prompts_not_truncated():
    """Prompts are right-padded to the batch max, not silently truncated to
    the first request's length: a long prompt decodes identically whether
    batched with a short one or with a copy of itself."""
    from repro.serving import Request, ServeEngine
    cfg = reduced_f32("stablelm-12b")
    rt = Runtime(tp=1, moe_impl="local")
    params, _ = M.init_params(cfg, rt, jax.random.PRNGKey(0))
    engine = ServeEngine(cfg, rt, params, max_len=48)
    rng = np.random.default_rng(1)
    short = rng.integers(0, cfg.vocab_size, 4, dtype=np.int32)
    long = rng.integers(0, cfg.vocab_size, 10, dtype=np.int32)
    mixed = engine.generate([Request(short, max_new_tokens=5),
                             Request(long, max_new_tokens=5)])
    ref = engine.generate([Request(long, max_new_tokens=5),
                           Request(long, max_new_tokens=5)])
    np.testing.assert_array_equal(mixed[1], ref[0])
    # pad-as-context bug closed: the short prompt's continuation is
    # independent of its batch-mates (per-sequence prefill masking + decode
    # positions), not just reproducible for one batch composition
    solo = engine.generate([Request(short, max_new_tokens=5),
                            Request(short, max_new_tokens=5)])
    np.testing.assert_array_equal(mixed[0], solo[0])
    # ... and serve() over the slot pool agrees (each slot at its own
    # position there)
    from repro.serving import ContinuousEngine, serve
    pool = serve(ContinuousEngine(cfg, rt, params, max_slots=2, max_len=48),
                 [Request(short, max_new_tokens=5),
                  Request(long, max_new_tokens=5)]).outputs
    np.testing.assert_array_equal(mixed[0], pool[0])
    np.testing.assert_array_equal(mixed[1], pool[1])


@pytest.mark.slow
def test_serve_engine_session_telemetry():
    """The engine records one telemetry sample per decode step through its
    EnergySession."""
    from repro.power import EnergySession, StepProfile
    from repro.serving import Request, ServeEngine
    cfg = reduced_f32("stablelm-12b")
    rt = Runtime(tp=1, moe_impl="local")
    params, _ = M.init_params(cfg, rt, jax.random.PRNGKey(0))
    session = EnergySession(policy="energy-aware",
                            slowdown_budget=0.0)
    engine = ServeEngine(cfg, rt, params, max_len=48, session=session,
                         profile=StepProfile(compute_s=0.1, memory_s=1.0))
    rng = np.random.default_rng(0)
    reqs = [Request(rng.integers(0, cfg.vocab_size, 8, dtype=np.int32),
                    max_new_tokens=6) for _ in range(2)]
    engine.generate(reqs)
    assert len(session.decisions) == 6          # one per decode step
    assert session.total_energy_j() > 0
    assert session.mode_hours_pct() == {2: 100.0}   # decode is mode 2 (M.I.)
