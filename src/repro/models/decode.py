"""Serving substrate: decode-state construction, prefill, single-token decode
for every architecture family.

State layout mirrors the trunk structure (stacked over layers / pattern
groups). The dense and MoE decode step carries the stacked cache through its
layer scan and writes each step's new rows in place, so the step moves B
rows of it, not the whole stack; the other families scan over (params,
cache) jointly. The same builder runs in "spec mode" to produce the
PartitionSpec tree used by the dry-run and the serving launcher.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import model as model_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models import transformer as tfm
from repro.models.common import default_rules, gated_mlp, rms_norm, shard
from repro.models.transformer import Runtime


# ---------------------------------------------------------------------------
# Cache construction (array mode / spec mode share one builder)
# ---------------------------------------------------------------------------
class CacheMaker:
    def __init__(self, spec_mode: bool, rules=None):
        self.spec_mode = spec_mode
        self.rules = rules or default_rules()

    def __call__(self, shape, axes, dtype=jnp.bfloat16):
        if self.spec_mode:
            return self.rules.mesh_axes(axes)
        return jnp.zeros(shape, dtype)


def _kv_axes(cfg: ModelConfig, rt: Runtime):
    nkv = cfg.padded_kv_heads(rt.tp)
    kv_ax = "kv_heads" if (rt.tp > 1 and nkv % rt.tp == 0) else None
    return nkv, kv_ax


def _seq_ax(rt: Runtime, kv_ax):
    """Cache sequence dim -> model axis when heads can't shard (§Perf:
    replicated 32k caches blow HBM; sequence-sharded caches + SPMD softmax
    partition cleanly with the dense decode attention)."""
    if rt.decode_cache_shard == "seq" and kv_ax is None and rt.tp > 1:
        return "kv_seq"
    return None


def _build_state(mk: CacheMaker, cfg: ModelConfig, rt: Runtime, B: int,
                 M: int) -> Dict:
    """M = max cache length (tokens)."""
    hd = cfg.resolved_head_dim
    L = cfg.n_layers
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32

    if cfg.family in ("dense", "moe"):
        if cfg.use_mla:
            sq = _seq_ax(rt, None)
            return {"layers": {
                "c_kv": mk((L, B, M, cfg.kv_lora_rank),
                           (None, "batch", sq, None), dt),
                "k_rope": mk((L, B, M, cfg.qk_rope_dim),
                             (None, "batch", sq, None), dt)}}
        nkv, kv_ax = _kv_axes(cfg, rt)
        sq = _seq_ax(rt, kv_ax)
        return {"layers": {
            "k": mk((L, B, M, nkv, hd), (None, "batch", sq, kv_ax, None), dt),
            "v": mk((L, B, M, nkv, hd), (None, "batch", sq, kv_ax, None), dt)}}

    if cfg.family == "ssm":
        d_in, H, shd, ds = ssm_mod.ssm_dims(cfg)
        C = d_in + 2 * cfg.ssm_n_groups * ds
        K = cfg.ssm_conv_kernel
        return {"layers": {
            "h": mk((L, B, H, shd, ds), (None, "batch", "heads", None, None),
                    jnp.float32),
            "conv": mk((L, B, K - 1, C), (None, "batch", None, "lru"), dt)}}

    if cfg.family == "hybrid":
        G, n_rest = tfm.hybrid_group_counts(cfg)
        w = cfg.lru_width or cfg.d_model
        win = min(cfg.local_window, M)
        nkv, kv_ax = _kv_axes(cfg, rt)
        K = rglru_mod.CONV_K

        def rec_cache(n):
            return {"h": mk((n, B, w), (None, "batch", "lru"), jnp.float32),
                    "conv": mk((n, B, K - 1, w), (None, "batch", None, "lru"),
                               dt)}

        def attn_cache(n):
            return {"k": mk((n, B, win, nkv, hd),
                            (None, "batch", None, kv_ax, None), dt),
                    "v": mk((n, B, win, nkv, hd),
                            (None, "batch", None, kv_ax, None), dt)}

        groups = {}
        for i, kind in enumerate(cfg.block_pattern):
            groups[f"pos{i}"] = (attn_cache(G) if kind == "attn"
                                 else rec_cache(G))
        rest = []
        for i in range(n_rest):
            kind = cfg.block_pattern[i % len(cfg.block_pattern)]
            rest.append(attn_cache(1) if kind == "attn" else rec_cache(1))
        return {"groups": groups, "rest": rest}

    if cfg.family == "vlm":
        k_in = cfg.cross_attn_every
        G = cfg.n_layers // k_in
        nkv, kv_ax = _kv_axes(cfg, rt)
        sq = _seq_ax(rt, kv_ax)
        F = cfg.frontend_seq
        return {
            "self": {"k": mk((G, k_in, B, M, nkv, hd),
                             (None, None, "batch", sq, kv_ax, None), dt),
                     "v": mk((G, k_in, B, M, nkv, hd),
                             (None, None, "batch", sq, kv_ax, None), dt)},
            "cross": {"k": mk((G, B, F, nkv, hd),
                              (None, "batch", None, kv_ax, None), dt),
                      "v": mk((G, B, F, nkv, hd),
                              (None, "batch", None, kv_ax, None), dt)}}

    if cfg.family == "encdec":
        nkv, kv_ax = _kv_axes(cfg, rt)
        sq = _seq_ax(rt, kv_ax)
        F = cfg.frontend_seq
        return {
            "self": {"k": mk((L, B, M, nkv, hd),
                             (None, "batch", sq, kv_ax, None), dt),
                     "v": mk((L, B, M, nkv, hd),
                             (None, "batch", sq, kv_ax, None), dt)},
            "cross": {"k": mk((L, B, F, nkv, hd),
                              (None, "batch", None, kv_ax, None), dt),
                      "v": mk((L, B, F, nkv, hd),
                              (None, "batch", None, kv_ax, None), dt)}}

    raise ValueError(cfg.family)


def init_decode_state(cfg: ModelConfig, rt: Runtime, batch: int,
                      max_len: int) -> Dict:
    return _build_state(CacheMaker(False), cfg, rt, batch, max_len)


def decode_state_specs(cfg: ModelConfig, rt: Runtime, batch: int,
                       max_len: int, rules=None) -> Dict:
    return _build_state(CacheMaker(True, rules), cfg, rt, batch, max_len)


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------
def _pad_to(x: jax.Array, M: int, axis: int) -> jax.Array:
    S = x.shape[axis]
    if S == M:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, M - S)
    return jnp.pad(x, pad)


def _ring_from_kv(k: jax.Array, win: int) -> jax.Array:
    """Arrange the last ``win`` entries of k [B,S,...] into ring-buffer order
    (slot = pos % win)."""
    S = k.shape[1]
    if S <= win:
        return _pad_to(k, win, 1)
    base = S - win
    slots = jnp.arange(win)
    pos = base + ((slots - base) % win)
    return jnp.take(k, pos, axis=1)


#: families whose decode state is a position-indexed cache, so padding past a
#: sequence's true length is recoverable (masked at read time). The recurrent
#: families (ssm / hybrid) fold every prefill token into their state and
#: cannot un-see pads.
CAUSAL_CACHE_FAMILIES = ("dense", "moe", "vlm", "encdec")


def prefill(cfg: ModelConfig, rt: Runtime, p: Dict, batch: Dict,
            max_len: int, lengths: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, Dict]:
    """Run the prompt through the trunk, building the decode state.
    Returns (last-token logits [B,1,V], state).

    ``lengths`` ([B] int32) gives each sequence's true prompt length within
    the right-padded ``tokens``: logits are then read at position
    ``lengths[b]-1`` per sequence instead of the batch max, so a short
    prompt's first sampled token is independent of its batch-mates (causal
    attention already keeps positions < length clean; the pad KV entries the
    cache still holds are masked later by per-sequence decode positions).
    Only meaningful for :data:`CAUSAL_CACHE_FAMILIES`."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    M = max_len
    x = model_mod.embed(p, cfg, tokens)
    pos = jnp.arange(S, dtype=jnp.int32)[None]

    if cfg.family in ("dense", "moe"):
        def body(carry, p_layer):
            h = carry
            z = rms_norm(h, p_layer["ln1"], cfg.norm_eps)
            if cfg.use_mla:
                y, (a, b) = attn.mla_attention(p_layer["attn"], cfg, z, pos,
                                               return_cache=True)
            else:
                y, (a, b) = attn.self_attention(p_layer["attn"], cfg, z, pos,
                                                return_cache=True)
            h = h + y
            y2, _ = tfm._ffn(p_layer, cfg, rt, h)
            return h + y2, (a, b)

        x, (ka, kb) = jax.lax.scan(body, x, p["layers"])
        if cfg.use_mla:
            state = {"layers": {"c_kv": _pad_to(ka, M, 2),
                                "k_rope": _pad_to(kb, M, 2)}}
        else:
            state = {"layers": {"k": _pad_to(ka, M, 2),
                                "v": _pad_to(kb, M, 2)}}

    elif cfg.family == "ssm":
        def body(carry, p_layer):
            h = carry
            z = rms_norm(h, p_layer["ln1"], cfg.norm_eps)
            y, (hs, conv) = ssm_mod.ssd_forward(p_layer["ssm"], cfg, z,
                                                return_state=True)
            return h + y, (hs, conv)

        x, (hs, conv) = jax.lax.scan(body, x, p["layers"])
        state = {"layers": {"h": hs, "conv": conv}}

    elif cfg.family == "hybrid":
        pat = cfg.block_pattern
        win = min(cfg.local_window, M)

        def block_prefill(p_blk, h, kind):
            z = rms_norm(h, p_blk["ln1"], cfg.norm_eps)
            if kind == "attn":
                y, (k, v) = attn.self_attention(
                    p_blk["attn"], cfg, z, pos, window=cfg.local_window,
                    return_cache=True)
                cache = {"k": _ring_from_kv(k, win),
                         "v": _ring_from_kv(v, win)}
            else:
                y, (hf, tail) = rglru_mod.rglru_forward(
                    p_blk["rglru"], cfg, z, return_state=True)
                cache = {"h": hf, "conv": tail}
            h = h + y
            z = rms_norm(h, p_blk["ln2"], cfg.norm_eps)
            return h + gated_mlp(p_blk["mlp"], z, cfg.act), cache

        def group_body(carry, p_group):
            h = carry
            caches = []
            for i, kind in enumerate(pat):
                h, c = block_prefill(p_group[f"pos{i}"], h, kind)
                caches.append(c)
            return h, tuple(caches)

        x, group_caches = jax.lax.scan(group_body, x, p["layers"]["groups"])
        rest_caches = []
        for i, p_blk in enumerate(p["layers"]["rest"]):
            x, c = block_prefill(p_blk, x, pat[i % len(pat)])
            rest_caches.append(jax.tree.map(lambda a: a[None], c))
        state = {"groups": {f"pos{i}": group_caches[i]
                            for i in range(len(pat))},
                 "rest": rest_caches}

    elif cfg.family == "vlm":
        memory = batch["frontend"]

        def group_body(carry, p_group):
            h = carry
            p_self, p_cross = p_group

            def inner(c, pl):
                z = rms_norm(c, pl["ln1"], cfg.norm_eps)
                y, (k, v) = attn.self_attention(pl["attn"], cfg, z, pos,
                                                return_cache=True)
                c = c + y
                y2, _ = tfm._ffn(pl, cfg, rt, c)
                return c + y2, (k, v)

            h, (ks, vs) = jax.lax.scan(inner, h, p_self)
            z = rms_norm(h, p_cross["ln_x"], cfg.norm_eps)
            qx, kx, vx = attn._qkv(p_cross["xattn"], z, kv_src=memory)
            o = attn.chunked_attention(qx, kx, vx, causal=False)
            ca = jnp.einsum("bshk,hkd->bsd", o, p_cross["xattn"]["wo"])
            h = h + jnp.tanh(p_cross["gate_a"]) * ca
            z = rms_norm(h, p_cross["ln_m"], cfg.norm_eps)
            h = h + jnp.tanh(p_cross["gate_m"]) * gated_mlp(
                p_cross["mlp"], z, cfg.act)
            return h, ((ks, vs), (kx, vx))

        x, ((ks, vs), (kx, vx)) = jax.lax.scan(
            group_body, x, (p["layers"]["self"], p["layers"]["cross"]))
        state = {"self": {"k": _pad_to(ks, M, 3), "v": _pad_to(vs, M, 3)},
                 "cross": {"k": kx, "v": vx}}

    elif cfg.family == "encdec":
        memory = tfm.encoder_forward(p["encoder"], cfg, rt, batch["frontend"])

        def body(carry, p_layer):
            h = carry
            z = rms_norm(h, p_layer["ln1"], cfg.norm_eps)
            y, (k, v) = attn.self_attention(p_layer["attn"], cfg, z, pos,
                                            return_cache=True)
            h = h + y
            z = rms_norm(h, p_layer["ln_x"], cfg.norm_eps)
            qx, kx, vx = attn._qkv(p_layer["xattn"], z, kv_src=memory)
            o = attn.chunked_attention(qx, kx, vx, causal=False)
            h = h + jnp.einsum("bshk,hkd->bsd", o, p_layer["xattn"]["wo"])
            y2, _ = tfm._ffn(p_layer, cfg, rt, h)
            return h + y2, ((k, v), (kx, vx))

        x, ((ks, vs), (kx, vx)) = jax.lax.scan(body, x, p["layers"])
        state = {"self": {"k": _pad_to(ks, M, 2), "v": _pad_to(vs, M, 2)},
                 "cross": {"k": kx, "v": vx}}
    else:
        raise ValueError(cfg.family)

    if lengths is None:
        x_last = x[:, -1:]
    else:
        if cfg.family not in CAUSAL_CACHE_FAMILIES:
            raise ValueError(
                f"per-sequence prefill lengths need a position-indexed "
                f"cache; the recurrent state of family {cfg.family!r} "
                f"absorbs pad tokens")
        idx = (jnp.asarray(lengths, jnp.int32) - 1)[:, None, None]
        x_last = jnp.take_along_axis(x, idx, axis=1)
    logits = model_mod.logits_fn(p, cfg, x_last)
    return logits, state


# ---------------------------------------------------------------------------
# Single-token decode
# ---------------------------------------------------------------------------
def decode_step(cfg: ModelConfig, rt: Runtime, p: Dict, token: jax.Array,
                pos: jax.Array, state: Dict) -> Tuple[jax.Array, Dict]:
    """token: [B, 1] int32; pos: next position to write — scalar int32 for
    lock-step batches, or per-sequence [B] int32 for slot-pool decode
    (:data:`CAUSAL_CACHE_FAMILIES` only: the recurrent families have no
    position to index). Returns (logits [B,1,V], new state)."""
    x = model_mod.embed(p, cfg, token)
    pos = pos.astype(jnp.int32)

    if cfg.family in ("dense", "moe"):
        # the stacked cache rides in the carry: each layer writes its B new
        # rows in place and attends over its own view of the stack
        def body(carry, inp):
            h, cache = carry
            layer, p_layer = inp
            z = rms_norm(h, p_layer["ln1"], cfg.norm_eps)
            if cfg.use_mla:
                y, cache = attn.mla_decode(p_layer["attn"], cfg, z, cache,
                                           pos, layer=layer)
            else:
                y, cache = attn.decode_self_attention(
                    p_layer["attn"], cfg, z, cache, pos,
                    impl=rt.decode_impl, layer=layer)
            h = h + y
            y2, _ = tfm._ffn(p_layer, cfg, rt, h, decode=True)
            return (h + y2, cache), None

        (x, layers), _ = jax.lax.scan(
            body, (x, state["layers"]),
            (jnp.arange(cfg.n_layers, dtype=jnp.int32), p["layers"]))
        state = {"layers": layers}

    elif cfg.family == "ssm":
        def body(carry, inp):
            h = carry
            p_layer, cache = inp
            z = rms_norm(h, p_layer["ln1"], cfg.norm_eps)
            y, new = ssm_mod.ssd_decode_step(p_layer["ssm"], cfg, z, cache)
            return h + y, new

        x, new_layers = jax.lax.scan(body, x, (p["layers"], state["layers"]))
        state = {"layers": new_layers}

    elif cfg.family == "hybrid":
        pat = cfg.block_pattern

        def block_decode(p_blk, cache, h, kind):
            z = rms_norm(h, p_blk["ln1"], cfg.norm_eps)
            if kind == "attn":
                y, new = attn.decode_self_attention(p_blk["attn"], cfg, z,
                                                    cache, pos,
                                                    impl=rt.decode_impl)
            else:
                y, new = rglru_mod.rglru_decode_step(p_blk["rglru"], cfg, z,
                                                     cache)
            h = h + y
            z = rms_norm(h, p_blk["ln2"], cfg.norm_eps)
            return h + gated_mlp(p_blk["mlp"], z, cfg.act), new

        def group_body(carry, inp):
            h = carry
            p_group, caches = inp
            new = {}
            for i, kind in enumerate(pat):
                h, c = block_decode(p_group[f"pos{i}"], caches[f"pos{i}"],
                                    h, kind)
                new[f"pos{i}"] = c
            return h, new

        x, new_groups = jax.lax.scan(
            group_body, x, (p["layers"]["groups"], state["groups"]))
        new_rest = []
        for i, (p_blk, cache) in enumerate(
                zip(p["layers"]["rest"], state["rest"])):
            cache0 = jax.tree.map(lambda a: a[0], cache)
            x, c = block_decode(p_blk, cache0, x, pat[i % len(pat)])
            new_rest.append(jax.tree.map(lambda a: a[None], c))
        state = {"groups": new_groups, "rest": new_rest}

    elif cfg.family == "vlm":
        def group_body(carry, inp):
            h = carry
            (p_self, p_cross), cache = inp

            def inner(c, pl_and_cache):
                pl, kv = pl_and_cache
                z = rms_norm(c, pl["ln1"], cfg.norm_eps)
                y, new = attn.decode_self_attention(pl["attn"], cfg, z, kv,
                                                    pos,
                                                    impl=rt.decode_impl)
                c = c + y
                y2, _ = tfm._ffn(pl, cfg, rt, c)
                return c + y2, new

            h, new_self = jax.lax.scan(inner, h, (p_self, cache["self_kv"]))
            z = rms_norm(h, p_cross["ln_x"], cfg.norm_eps)
            q = jnp.einsum("bsd,dhk->bshk", z, p_cross["xattn"]["wq"])
            o = attn.chunked_attention(q, cache["cross_kv"]["k"],
                                       cache["cross_kv"]["v"], causal=False)
            ca = jnp.einsum("bshk,hkd->bsd", o, p_cross["xattn"]["wo"])
            h = h + jnp.tanh(p_cross["gate_a"]) * ca
            z = rms_norm(h, p_cross["ln_m"], cfg.norm_eps)
            h = h + jnp.tanh(p_cross["gate_m"]) * gated_mlp(
                p_cross["mlp"], z, cfg.act)
            return h, new_self

        cache_in = {"self_kv": state["self"],
                    "cross_kv": state["cross"]}
        x, new_self = jax.lax.scan(
            group_body, x,
            ((p["layers"]["self"], p["layers"]["cross"]), cache_in))
        state = {"self": new_self, "cross": state["cross"]}

    elif cfg.family == "encdec":
        def body(carry, inp):
            h = carry
            p_layer, cache = inp
            z = rms_norm(h, p_layer["ln1"], cfg.norm_eps)
            y, new = attn.decode_self_attention(p_layer["attn"], cfg, z,
                                                cache["self_kv"], pos,
                                                impl=rt.decode_impl)
            h = h + y
            z = rms_norm(h, p_layer["ln_x"], cfg.norm_eps)
            q = jnp.einsum("bsd,dhk->bshk", z, p_layer["xattn"]["wq"])
            o = attn.chunked_attention(q, cache["cross_kv"]["k"],
                                       cache["cross_kv"]["v"], causal=False)
            h = h + jnp.einsum("bshk,hkd->bsd", o, p_layer["xattn"]["wo"])
            y2, _ = tfm._ffn(p_layer, cfg, rt, h, decode=True)
            return h + y2, new

        cache_in = {"self_kv": state["self"], "cross_kv": state["cross"]}
        x, new_self = jax.lax.scan(body, x, (p["layers"], cache_in))
        state = {"self": new_self, "cross": state["cross"]}
    else:
        raise ValueError(cfg.family)

    logits = model_mod.logits_fn(p, cfg, x)
    return logits, state
