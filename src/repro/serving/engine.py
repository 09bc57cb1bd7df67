"""Serving engines: a slot-based continuous-batching engine plus a blocking
lock-step engine.

:class:`ContinuousEngine` is the JetStream-style core — ``prefill(request)
-> Prefix``, ``insert(prefix, slot)``, ``generate_step()`` — over a fixed
pool of decode slots. Each slot carries its own KV rows, position, last
token and sampling temperature inside donated jax buffers, so one jitted
decode step advances every occupied slot with per-sequence position/length
masking: no lock-step barrier, no right-padding beyond the prompt page, and
a short prompt's continuation never depends on its batch-mates.

The energy hook is the point (the paper's per-phase DVFS headroom): prefill
is compute-bound, decode is memory-bound, and the engine reports each as its
own roofline :class:`StepProfile` — derived from the model config through
the chip model, not guessed — so any :class:`~repro.power.PowerPolicy`
behind an :class:`~repro.power.EnergySession` caps the decode phase deep
while leaving prefill at nominal.

:class:`ServeEngine.generate` is one lock-step loop over a right-padded
batch, the only server of the families the slot pool cannot hold. For the
causal-cache families it reads logits and decodes at per-sequence positions
(closing the pad-as-context bug there too).
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core import roofline
from repro.core.hardware import ChipSpec, TPU_V5E
from repro.power import ChipModel, EnergySession, StepProfile
from repro.models import attention as attn
from repro.models import decode as decode_mod
from repro.models.transformer import Runtime

#: families the slot engine can serve: per-slot KV rows are scatter-written
#: at per-sequence positions (MLA included — its latent cache is
#: position-indexed too). vlm/encdec carry a shared frontend memory that is
#: not per-slot; ssm/hybrid state absorbs pads.
SLOT_FAMILIES = ("dense", "moe")


# ---------------------------------------------------------------------------
# Roofline profiles for the two serving phases
# ---------------------------------------------------------------------------
def serving_profiles(cfg: ModelConfig, chip=TPU_V5E, batch: int = 8,
                     prompt_len: int = 512, context_len: int = 2048,
                     chips: int = 1) -> Tuple[StepProfile, StepProfile]:
    """(prefill, decode) :class:`StepProfile` pair for this model on this
    chip, from the analytic rooflines: FLOPs-per-step over peak for the
    compute term, weights+cache bytes over HBM bandwidth for the memory
    term. At production shapes prefill lands compute-bound and decode
    memory-bound — the per-phase split every power policy feeds on."""
    spec: ChipSpec = ChipModel(chip).spec
    out = []
    for kind, seq in (("prefill", prompt_len), ("decode", context_len)):
        shape = ShapeConfig(f"serve_{kind}", seq, batch, kind)
        out.append(StepProfile(
            compute_s=roofline.model_flops(cfg, shape)
            / (chips * spec.peak_flops),
            memory_s=roofline.memory_floor_s(cfg, shape, chips, spec)))
    return out[0], out[1]


def scale_profile(profile: StepProfile, wall_s: float) -> StepProfile:
    """Rescale a derived profile so its nominal step time equals a measured
    wall-clock: the roofline *position* (arithmetic intensity) comes from
    the model config, the magnitude from the measurement."""
    r = wall_s / profile.total_s
    return StepProfile(compute_s=profile.compute_s * r,
                       memory_s=profile.memory_s * r,
                       collective_s=profile.collective_s * r)


def _sample_tokens(logits: jax.Array, temperature: jax.Array,
                   key: jax.Array) -> jax.Array:
    """Greedy/categorical per row: logits [B,V], temperature scalar or [B]
    (0 = greedy). Traced temperature, so one compiled graph serves any mix
    of per-slot sampling params."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    t = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32),
                         logits.shape[:-1])

    def _categorical(_):
        sampled = jax.random.categorical(
            key, logits / jnp.maximum(t, 1e-6)[..., None], axis=-1
        ).astype(jnp.int32)
        return jnp.where(t > 0.0, sampled, greedy)

    # all-greedy batches (the common serving default) skip the gumbel-noise
    # draw entirely — at decode batch sizes it costs as much as a layer
    return jax.lax.cond(jnp.any(t > 0.0), _categorical,
                        lambda _: greedy, None)


def decode_step(cfg: ModelConfig, rt: Runtime, params, state, pos, tokens,
                temps, active, key):
    """One token for every slot of the pool (the engine's ``decode_step``
    program): per-slot positions, per-slot sampling temperatures."""
    key, sub = jax.random.split(key)
    logits, state = decode_mod.decode_step(
        cfg, rt, params, tokens[:, None], pos, state)
    nxt = _sample_tokens(logits[:, 0, :cfg.vocab_size], temps, sub)
    # inactive slots hold position/token so an inserted prefix starts
    # clean; their cache writes land on dead rows (never attended)
    pos = pos + active.astype(jnp.int32)
    tokens = jnp.where(active, nxt, tokens)
    return state, pos, tokens, nxt, key


@dataclass
class Request:
    prompt: np.ndarray            # [S] int32
    max_new_tokens: int = 16


@dataclass
class Prefix:
    """A prefilled prompt, ready for :meth:`ContinuousEngine.insert`: the
    per-layer cache rows for one sequence (padded to the prompt page), the
    first sampled token, and the slot bookkeeping that travels with it."""
    state: Any                    # cache pytree, batch dim 1, seq dim = page
    token: jax.Array              # [] int32 — sampled from the prompt logits
    length: int                   # true prompt length
    max_new: int                  # decode budget (first token included)
    temperature: float = 0.0


class ContinuousEngine:
    """Fixed pool of ``max_slots`` decode slots over donated jax buffers.

    ``prefill`` runs one prompt (right-padded only to its power-of-two page)
    and samples the first token; ``insert`` scatter-writes the prefix rows
    into a free slot; ``generate_step`` advances every slot one token with
    per-slot positions, gathering per-slot sampling temperatures. A
    scheduler (see :func:`repro.serving.serve`) admits queued requests into
    freed slots between steps — continuous batching.

    With a ``session``, each scheduler tick reports its prefill count and
    decode step as distinct roofline profiles via ``observe_many`` — the
    per-phase power-policy hook.

    Its programs read ``jit_prefill``, ``jit_insert`` and
    ``jit_decode_step`` in a device trace, whatever the page."""

    def __init__(self, cfg: ModelConfig, rt: Runtime, params,
                 max_slots: int = 8, max_len: int = 256, page: int = 16,
                 session: Optional[EnergySession] = None,
                 prefill_profile: Optional[StepProfile] = None,
                 decode_profile: Optional[StepProfile] = None,
                 seed: int = 0):
        if cfg.family not in SLOT_FAMILIES:
            raise ValueError(
                f"continuous batching needs per-slot position-indexed KV "
                f"(families {SLOT_FAMILIES}); family {cfg.family!r} is "
                f"served by ServeEngine.generate")
        self.cfg, self.rt, self.params = cfg, rt, params
        self.max_slots, self.max_len, self.page = max_slots, max_len, page
        self.session = session
        if prefill_profile is None or decode_profile is None:
            chip = session.chip if session is not None else TPU_V5E
            pre, dec = serving_profiles(cfg, chip=chip, batch=max_slots,
                                        context_len=max_len)
            prefill_profile = prefill_profile or pre
            decode_profile = decode_profile or dec
        self.prefill_profile, self.decode_profile = (prefill_profile,
                                                     decode_profile)
        # per-slot device state (donated through every jitted update)
        self._state = decode_mod.init_decode_state(cfg, rt, max_slots,
                                                   max_len)
        self._pos = jnp.zeros((max_slots,), jnp.int32)
        self._tokens = jnp.zeros((max_slots,), jnp.int32)
        self._temps = jnp.zeros((max_slots,), jnp.float32)
        self._key = jax.random.PRNGKey(seed)
        self._all_active = jnp.ones((max_slots,), bool)
        self._prefill_fns: Dict[int, Any] = {}   # one per prompt page
        self._insert_fns: Dict[int, Any] = {}
        # donation halves cache residency on accelerators; on the CPU
        # backend it serializes the per-step cache copies (the runtime can't
        # double-buffer a donated input), costing ~30% per step
        donate = (1, 2, 3, 6) if jax.default_backend() != "cpu" else ()
        # named for the device trace: jit_decode_step
        step = functools.update_wrapper(
            functools.partial(decode_step, cfg, rt), decode_step)
        self._step_fn = jax.jit(step, donate_argnums=donate)
        self.n_prefills = 0
        # the chunk in which the step's attention reads each slot's written
        # positions (the pool kernel), or 0: it reads all max_len of them
        self.kv_chunk = attn.pool_kernel_chunk(cfg, rt.tp, max_len)

    # ------------------------------------------------------------- prefill
    def _bucket(self, length: int) -> int:
        """Prompt page: the smallest power-of-two >= length (floor =
        ``page``) — right-padding never exceeds the page size and each page
        compiles once."""
        b = max(self.page, 1)
        while b < length:
            b *= 2
        return min(b, self.max_len)

    def _make_prefill(self, page: int):
        cfg, rt = self.cfg, self.rt

        def prefill(params, tokens, length, temperature, key):
            logits, state = decode_mod.prefill(
                cfg, rt, params, {"tokens": tokens}, page, lengths=length)
            tok = _sample_tokens(logits[:, 0, :cfg.vocab_size],
                                 temperature, key)
            return tok[0], state

        return jax.jit(prefill)

    def prefill(self, request: Request, temperature: float = 0.0) -> Prefix:
        """Run one prompt through the trunk; returns the :class:`Prefix`
        (cache rows at its page size + first sampled token)."""
        prompt = np.asarray(request.prompt, np.int32)[: self.max_len - 1]
        L = max(len(prompt), 1)
        page = self._bucket(L)
        toks = np.zeros((1, page), np.int32)
        toks[0, :len(prompt)] = prompt
        fn = self._prefill_fns.get(page)
        if fn is None:
            fn = self._prefill_fns[page] = self._make_prefill(page)
        if temperature > 0.0:
            self._key, sub = jax.random.split(self._key)
        else:
            sub = self._key     # greedy consumes no randomness: skip the
            #                     host-side split dispatch per admission
        tok, state = fn(self.params, jnp.asarray(toks),
                        jnp.asarray([L], jnp.int32),
                        jnp.float32(temperature), sub)
        self.n_prefills += 1
        max_new = max(1, min(request.max_new_tokens, self.max_len - L))
        return Prefix(state=state, token=tok, length=L, max_new=max_new,
                      temperature=temperature)

    # -------------------------------------------------------------- insert
    def _make_insert(self, page: int):
        def insert(state, pos, tokens, temps, prefix_state, token, slot,
                   length, temperature):
            def put(c, u):
                # c: [..., slots, max_len, ...]; u: [..., 1, page, ...] —
                # the slot axis follows the (scanned) layer axis everywhere
                start = (0, slot) + (0,) * (c.ndim - 2)
                return jax.lax.dynamic_update_slice(c, u.astype(c.dtype),
                                                    start)

            state = jax.tree.map(put, state, prefix_state)
            return (state, pos.at[slot].set(length),
                    tokens.at[slot].set(token),
                    temps.at[slot].set(temperature))

        return jax.jit(insert, donate_argnums=(0, 1, 2, 3))

    def insert(self, prefix: Prefix, slot: int) -> None:
        """Scatter the prefix rows into ``slot`` and arm its position, last
        token and sampling temperature."""
        page = jax.tree.leaves(prefix.state)[0].shape[2]
        fn = self._insert_fns.get(page)
        if fn is None:
            fn = self._insert_fns[page] = self._make_insert(page)
        self._state, self._pos, self._tokens, self._temps = fn(
            self._state, self._pos, self._tokens, self._temps,
            prefix.state, prefix.token, jnp.int32(slot),
            jnp.int32(prefix.length), jnp.float32(prefix.temperature))

    # ------------------------------------------------------ generate_step
    def generate_step(self, active=None) -> jax.Array:
        """Advance every (active) slot one token; returns the [max_slots]
        int32 tokens sampled this step (inactive entries are meaningless)."""
        act = (self._all_active if active is None
               else jnp.asarray(active, bool))
        self._state, self._pos, self._tokens, toks, self._key = \
            self._step_fn(self.params, self._state, self._pos, self._tokens,
                          self._temps, act, self._key)
        return toks

    # ------------------------------------------------------------- energy
    def observe(self, n_prefills: int, n_decode: int = 1,
                wall_s: Optional[float] = None):
        """Report one scheduler tick to the session: ``n_prefills``
        compute-bound prefill profiles + ``n_decode`` memory-bound decode
        profiles, one vectorized policy pass."""
        if self.session is None:
            return None
        profiles = ([self.prefill_profile] * n_prefills
                    + [self.decode_profile] * n_decode)
        if not profiles:
            return None
        return self.session.observe_many(profiles, wall_s=wall_s)


class ServeEngine:
    """Blocking lock-step batch engine over the serving substrate: one
    right-padded prefill, then every sequence decodes in lock-step to the
    batch-max budget. The only server of the families the slot pool cannot
    hold (ssm, hybrid, vlm, encdec); :func:`repro.serving.serve` over a
    :class:`ContinuousEngine` serves dense and moe with per-request
    budgets."""

    def __init__(self, cfg: ModelConfig, rt: Runtime, params,
                 max_len: int = 256,
                 session: Optional[EnergySession] = None,
                 profile: Optional[StepProfile] = None):
        self.cfg, self.rt, self.params = cfg, rt, params
        self.max_len = max_len
        self.session = session
        self.profile = profile      # decode-step roofline profile (if known)
        self._prefill = jax.jit(
            lambda p, b, l: decode_mod.prefill(cfg, rt, p, b, max_len,
                                               lengths=l))
        self._decode = jax.jit(
            lambda p, tok, pos, st: decode_mod.decode_step(
                cfg, rt, p, tok, pos, st))
        self._derived_decode: Optional[StepProfile] = None

    def _decode_roofline(self) -> StepProfile:
        """Decode-phase profile derived from the model config via the chip
        roofline (replaces the old hardcoded 0.1*wall guess); scaled to the
        measured wall-clock per step at observe time."""
        if self._derived_decode is None:
            chip = self.session.chip if self.session is not None else TPU_V5E
            self._derived_decode = serving_profiles(
                self.cfg, chip=chip, batch=1, context_len=self.max_len)[1]
        return self._derived_decode

    def _sample(self, logits: jax.Array, temperature: float,
                key: jax.Array) -> jax.Array:
        logits = logits[:, 0, :self.cfg.vocab_size]
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, logits / temperature, axis=-1).astype(jnp.int32)

    def generate(self, requests: List[Request], temperature: float = 0.0,
                 seed: int = 0, extra_batch: Optional[Dict] = None
                 ) -> List[np.ndarray]:
        """Generate for a batch of requests, blocking until all are done
        (every output is ``max(r.max_new_tokens)`` long; per-request budgets
        need :func:`repro.serving.serve`). The session sees one observation
        per decode call.

        Short prompts' continuations are independent of the batch max for
        the causal-cache families (per-sequence prefill lengths and decode
        positions); only the recurrent families (ssm/hybrid) still fold pad
        tokens into their state — batch same-length requests there."""
        B = len(requests)
        plen = min(max(len(r.prompt) for r in requests), self.max_len - 1)
        prompts = np.zeros((B, plen), np.int32)
        lengths = np.zeros((B,), np.int32)
        for i, r in enumerate(requests):
            p = np.asarray(r.prompt[:plen])
            prompts[i, :len(p)] = p
            lengths[i] = len(p)
        batch = {"tokens": jnp.asarray(prompts, jnp.int32)}
        if extra_batch:
            batch.update(extra_batch)
        key = jax.random.PRNGKey(seed)

        if self.cfg.family in decode_mod.CAUSAL_CACHE_FAMILIES:
            logits, state = self._prefill(self.params, batch,
                                          jnp.asarray(lengths))
            base_pos = jnp.asarray(lengths)
        else:
            logits, state = self._prefill(self.params, batch, None)
            base_pos = jnp.full((B,), plen, jnp.int32)
        max_new = min(max(r.max_new_tokens for r in requests),
                      self.max_len - plen)
        outs = []
        walls: List[float] = []
        for i in range(max_new):
            key, sub = jax.random.split(key)
            tok = self._sample(logits, temperature, sub)
            outs.append(np.asarray(tok))
            t0 = time.perf_counter()
            logits, state = self._decode(self.params, tok[:, None],
                                         base_pos + i, state)
            jax.block_until_ready(logits)
            wall = time.perf_counter() - t0
            walls.append(wall)
            if self.session is not None and self.profile is None:
                # profile scaled to this step's wall-clock: must record
                # online, one step at a time
                self.session.observe(
                    i, scale_profile(self._decode_roofline(), wall), wall)
        if self.session is not None and self.profile is not None:
            # known decode profile: one vectorized policy pass for the whole
            # decode loop instead of max_new scalar sweeps
            self.session.observe_many([self.profile] * max_new,
                                      wall_s=walls, start_step=0)
        gen = np.stack(outs, axis=1)                     # [B, max_new]
        return [gen[i] for i in range(B)]
