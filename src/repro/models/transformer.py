"""Trunk assembly for every architecture family.

Homogeneous stacks (dense / moe / ssm / encdec) scan over stacked layer
params to keep the HLO compact at 61+ layers; heterogeneous stacks (hybrid
rg-lru pattern, vlm cross-attn groups) scan over *pattern groups*.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.common import (ParamMaker, gated_mlp, gated_mlp_params,
                                 rms_norm, shard)


@dataclasses.dataclass(frozen=True)
class Runtime:
    """Runtime knobs orthogonal to the architecture config."""
    tp: int = 1
    mesh: Optional[Any] = None
    batch_axes: Tuple[str, ...] = ("data",)
    moe_impl: str = "local"       # dense | local | ep
    remat: str = "none"           # none | full | dots
    mtp_coef: float = 0.1
    # §Perf knobs (baseline values first)
    decode_cache_shard: str = "none"  # none | seq (cache seq dim -> model)
    moe_dispatch_dtype: str = "bfloat16"  # bfloat16 | f8 (DSv3 fp8 dispatch)
    moe_capacity_factor: float = 1.25
    moe_ep2d_decode: bool = False  # 2D expert sharding (serving weights fit)


class StackedMaker:
    """ParamMaker view that prepends a layer-stack dimension."""

    def __init__(self, mk: ParamMaker, n: int):
        self._mk, self._n = mk, n

    def __call__(self, name, shape, axes, scale=None, init="normal"):
        if scale is None and len(shape) > 1 and init == "normal":
            scale = shape[0] ** -0.5
        return self._mk(name, (self._n,) + tuple(shape), (None,) + tuple(axes),
                        scale=scale, init=init)


def _maybe_remat(fn, rt: Runtime):
    if rt.remat == "full":
        return jax.checkpoint(fn)
    if rt.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return fn


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------
def decoder_layer_params(mk, cfg: ModelConfig, rt: Runtime,
                         cross: bool = False) -> Dict:
    p = {"ln1": mk("ln1", (cfg.d_model,), (None,), init="ones"),
         "ln2": mk("ln2", (cfg.d_model,), (None,), init="ones")}
    if cfg.use_mla:
        p["attn"] = attn.mla_params(mk, "attn", cfg, rt.tp)
    else:
        p["attn"] = attn.attention_params(mk, "attn", cfg, rt.tp)
    if cfg.family == "moe":
        p["mlp"] = moe_mod.moe_params(mk, "moe", cfg, rt.tp)
    else:
        p["mlp"] = gated_mlp_params(mk, "mlp", cfg.d_model, cfg.d_ff)
    if cross:
        p["ln_x"] = mk("ln_x", (cfg.d_model,), (None,), init="ones")
        p["xattn"] = attn.attention_params(mk, "xattn", cfg, rt.tp,
                                           cross=True)
    return p


def _mixer(p, cfg: ModelConfig, rt: Runtime, x, positions, window=0):
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if cfg.use_mla:
        return attn.mla_attention(p["attn"], cfg, h, positions)
    return attn.self_attention(p["attn"], cfg, h, positions, window=window)


def _ffn(p, cfg: ModelConfig, rt: Runtime, x, decode=False):
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.family == "moe":
        if rt.moe_impl == "ep":
            h = shard(h, "batch", None if decode else "seq_moe", None)
        y, aux = moe_mod.moe_block(p["mlp"], cfg, h, impl=rt.moe_impl,
                                   mesh=rt.mesh, batch_axes=rt.batch_axes,
                                   decode=decode,
                                   dispatch_dtype=rt.moe_dispatch_dtype,
                                   capacity_factor=rt.moe_capacity_factor,
                                   ep2d=rt.moe_ep2d_decode)
        if rt.moe_impl == "ep":
            y = shard(y, "batch", None, None)
        return y, aux
    return gated_mlp(p["mlp"], h, cfg.act), jnp.float32(0.0)


def decoder_layer(p, cfg: ModelConfig, rt: Runtime, x, positions,
                  window: int = 0, memory=None) -> Tuple[jax.Array, jax.Array]:
    x = x + _mixer(p, cfg, rt, x, positions, window)
    if memory is not None and "xattn" in p:
        h = rms_norm(x, p["ln_x"], cfg.norm_eps)
        x = x + attn.cross_attention(p["xattn"], cfg, h, memory)
    y, aux = _ffn(p, cfg, rt, x)
    return x + y, aux


# ---------------------------------------------------------------------------
# Homogeneous trunks (dense / moe / ssm / encoder)
# ---------------------------------------------------------------------------
def trunk_params(mk, cfg: ModelConfig, rt: Runtime, n_layers: int,
                 kind: str) -> Dict:
    sm = StackedMaker(mk, n_layers)
    if kind == "ssm":
        return {"ln1": sm("ln1", (cfg.d_model,), (None,), init="ones"),
                "ssm": ssm_mod.ssm_params(sm, "ssm", cfg, rt.tp)}
    return decoder_layer_params(sm, cfg, rt)


def trunk_forward(params: Dict, cfg: ModelConfig, rt: Runtime, x, positions,
                  kind: str, causal: bool = True) -> Tuple[jax.Array, jax.Array]:
    def body(carry, p_layer):
        h, aux = carry
        # re-anchor the scan carry's sharding: GSPMD assigns ONE sharding to
        # the while-loop carry, and without this constraint propagation can
        # settle on replicated (a silent 16x flop/byte blowup in backward)
        h = shard(h, "batch", "seq", None)
        if kind == "ssm":
            z = rms_norm(h, p_layer["ln1"], cfg.norm_eps)
            h = h + ssm_mod.ssd_forward(p_layer["ssm"], cfg, z)
            a = jnp.float32(0.0)
        elif kind == "encoder":
            z = rms_norm(h, p_layer["ln1"], cfg.norm_eps)
            z = attn.self_attention(p_layer["attn"], cfg, z, positions)
            h = h + z
            y, a = _ffn(p_layer, cfg, rt, h)
            h = h + y
        else:
            h, a = decoder_layer(p_layer, cfg, rt, h, positions)
        return (shard(h, "batch", "seq", None), aux + a), None

    body = _maybe_remat(body, rt)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)), params)
    return x, aux


# Encoder layers attend bidirectionally: reuse decoder_layer machinery with
# causal disabled via a dedicated path in self-attention.
def encoder_layer_params(mk, cfg: ModelConfig, rt: Runtime) -> Dict:
    return decoder_layer_params(mk, cfg, rt)


def encoder_forward(params, cfg: ModelConfig, rt: Runtime, x) -> jax.Array:
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)[None]

    def body(carry, p_layer):
        h = shard(carry, "batch", "seq", None)
        z = rms_norm(h, p_layer["ln1"], cfg.norm_eps)
        q, k, v = attn._qkv(p_layer["attn"], z)
        q = attn.apply_rope(q, positions, cfg.rope_theta)
        k = attn.apply_rope(k, positions, cfg.rope_theta)
        o = attn.chunked_attention(q, k, v, causal=False)
        h = h + jnp.einsum("bshk,hkd->bsd", o, p_layer["attn"]["wo"])
        y, _ = _ffn(p_layer, cfg, rt, h)
        return shard(h + y, "batch", "seq", None), None

    body = _maybe_remat(body, rt)
    x, _ = jax.lax.scan(body, x, params)
    return x


# ---------------------------------------------------------------------------
# Hybrid trunk (recurrentgemma): scan over (rglru, rglru, attn) groups
# ---------------------------------------------------------------------------
def hybrid_group_counts(cfg: ModelConfig) -> Tuple[int, int]:
    pat = cfg.block_pattern
    n_groups = cfg.n_layers // len(pat)
    n_rest = cfg.n_layers - n_groups * len(pat)
    return n_groups, n_rest


def _rg_block_params(mk, cfg: ModelConfig, rt: Runtime, kind: str) -> Dict:
    p = {"ln1": mk("ln1", (cfg.d_model,), (None,), init="ones"),
         "ln2": mk("ln2", (cfg.d_model,), (None,), init="ones"),
         "mlp": gated_mlp_params(mk, "mlp", cfg.d_model, cfg.d_ff)}
    if kind == "attn":
        p["attn"] = attn.attention_params(mk, "attn", cfg, rt.tp)
    else:
        p["rglru"] = rglru_mod.rglru_params(mk, "rglru", cfg, rt.tp)
    return p


def hybrid_params(mk, cfg: ModelConfig, rt: Runtime) -> Dict:
    n_groups, n_rest = hybrid_group_counts(cfg)
    pat = cfg.block_pattern
    groups = {}
    for i, kind in enumerate(pat):
        groups[f"pos{i}"] = _rg_block_params(
            StackedMaker(mk, n_groups), cfg, rt, kind)
    rest = [
        _rg_block_params(mk, cfg, rt, pat[i % len(pat)])
        for i in range(n_rest)
    ]
    return {"groups": groups, "rest": rest}


def _rg_block(p, cfg: ModelConfig, rt: Runtime, x, positions, kind: str):
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if kind == "attn":
        x = x + attn.self_attention(p["attn"], cfg, h, positions,
                                    window=cfg.local_window)
    else:
        x = x + rglru_mod.rglru_forward(p["rglru"], cfg, h)
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    return x + gated_mlp(p["mlp"], h, cfg.act)


def hybrid_forward(params, cfg: ModelConfig, rt: Runtime, x, positions):
    pat = cfg.block_pattern

    def body(carry, p_group):
        h = shard(carry, "batch", "seq", None)
        for i, kind in enumerate(pat):
            h = _rg_block(p_group[f"pos{i}"], cfg, rt, h, positions, kind)
        return shard(h, "batch", "seq", None), None

    body = _maybe_remat(body, rt)
    x, _ = jax.lax.scan(body, x, params["groups"])
    for i, p in enumerate(params["rest"]):
        x = _rg_block(p, cfg, rt, x, positions, pat[i % len(pat)])
    return x


# ---------------------------------------------------------------------------
# VLM trunk: scan over groups of (cross_attn_every self layers + 1 cross)
# ---------------------------------------------------------------------------
def vlm_params(mk, cfg: ModelConfig, rt: Runtime) -> Dict:
    k = cfg.cross_attn_every
    n_groups = cfg.n_layers // k
    gm = StackedMaker(mk, n_groups)
    inner = StackedMaker(gm, k)  # [n_groups, k, ...]
    self_p = decoder_layer_params(inner, cfg, rt)
    cross_p = {
        "ln_x": gm("ln_x", (cfg.d_model,), (None,), init="ones"),
        "ln_m": gm("ln_m", (cfg.d_model,), (None,), init="ones"),
        "xattn": attn.attention_params(gm, "xattn", cfg, rt.tp, cross=True),
        "gate_a": gm("gate_a", (1,), (None,), init="zeros"),
        "gate_m": gm("gate_m", (1,), (None,), init="zeros"),
        "mlp": gated_mlp_params(gm, "xmlp", cfg.d_model, cfg.d_ff),
    }
    return {"self": self_p, "cross": cross_p}


def vlm_forward(params, cfg: ModelConfig, rt: Runtime, x, positions, memory):
    def group(carry, p_group):
        h = shard(carry, "batch", "seq", None)
        p_self, p_cross = p_group

        def inner(c, pl):
            y, _ = decoder_layer(pl, cfg, rt, shard(c, "batch", "seq", None),
                                 positions)
            return shard(y, "batch", "seq", None), None
        h, _ = jax.lax.scan(inner, h, p_self)
        # gated cross-attention block (tanh gates, zero-init)
        z = rms_norm(h, p_cross["ln_x"], cfg.norm_eps)
        ca = attn.cross_attention(p_cross["xattn"], cfg, z, memory)
        h = h + jnp.tanh(p_cross["gate_a"]) * ca
        z = rms_norm(h, p_cross["ln_m"], cfg.norm_eps)
        h = h + jnp.tanh(p_cross["gate_m"]) * gated_mlp(
            p_cross["mlp"], z, cfg.act)
        return h, None

    group = _maybe_remat(group, rt)
    x, _ = jax.lax.scan(group, x, (params["self"], params["cross"]))
    return x
