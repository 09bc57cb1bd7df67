"""Mixture-of-Experts with real expert parallelism.

Three dispatch paths:

* ``dense``  — every expert applied to every token, mask-weighted. O(E/k)
  flop waste; used only as the numerical *oracle* for tiny configs and tests.
* ``ep_a2a`` — production training path: tokens are sharded over
  (batch x model) before dispatch, each device sort-scatters its local tokens
  into per-expert capacity buffers, a ragged-free ``all_to_all`` over the
  ``model`` axis exchanges expert shards, local experts run as one batched
  matmul, and the inverse all_to_all + weighted unsort combines. Runs inside
  ``shard_map`` so the collective schedule is explicit (and shows up
  verbatim in the §Roofline collective-bytes accounting).
* ``ep_gather`` — decode path (few tokens): all-gather tokens over ``model``,
  compute local experts, ``psum_scatter`` the combine. Flop-exact, tiny
  collectives at decode batch sizes.

Token-choice top-k routing with optional shared experts and the standard
load-balancing auxiliary loss (switch-style), matching DeepSeek-V3 / DBRX
semantics at the fidelity the paper's power analysis needs.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.common import ParamMaker, gated_mlp, gated_mlp_params, shard

CAPACITY_FACTOR = 1.25


def moe_params(mk: ParamMaker, prefix: str, cfg: ModelConfig,
               tp: int = 1) -> Dict:
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    p = {
        "router": mk(f"{prefix}.router", (d, E), ("dmodel", None),
                     scale=0.02),
        "experts": {
            "wi": mk(f"{prefix}.e_wi", (E, d, ff),
                     ("experts", "dmodel", "expert_ff")),
            "wg": mk(f"{prefix}.e_wg", (E, d, ff),
                     ("experts", "dmodel", "expert_ff")),
            "wo": mk(f"{prefix}.e_wo", (E, ff, d),
                     ("experts", "expert_ff", "dmodel")),
        },
    }
    if cfg.n_shared_experts:
        p["shared"] = gated_mlp_params(
            mk, f"{prefix}.shared", d, ff * cfg.n_shared_experts)
    return p


def _route(router_w: jax.Array, x: jax.Array, k: int
           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k token-choice routing. Returns (weights [T,k], idx [T,k],
    aux_loss scalar). Router math in f32."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, k)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    # switch-style load balance loss: E * sum_e f_e * p_e
    E = probs.shape[-1]
    hard = jnp.zeros_like(probs).at[
        jnp.arange(idx.shape[0])[:, None], idx].set(1.0)
    f = jnp.mean(hard, axis=0)
    pbar = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(f * pbar)
    return w.astype(x.dtype), idx.astype(jnp.int32), aux


def _expert_ffn(experts: Dict, xs: jax.Array, act: str) -> jax.Array:
    """xs: [E_loc, C, d] -> [E_loc, C, d], one batched matmul per weight."""
    a = jnp.einsum("ecd,edf->ecf", xs, experts["wi"])
    g = jnp.einsum("ecd,edf->ecf", xs, experts["wg"])
    g = jax.nn.silu(g) if act == "silu" else jax.nn.gelu(g)
    return jnp.einsum("ecf,efd->ecd", a * g, experts["wo"])


def _dispatch_indices(idx: jax.Array):
    """Sort (token, expert) pairs by expert; compute within-expert positions.
    Returns (order [T*k], sorted_e, pos_in_expert) — pairs whose position
    exceeds capacity are dropped by the scatter."""
    flat_e = idx.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    first = jnp.searchsorted(sorted_e, sorted_e, side="left")
    pos = jnp.arange(sorted_e.shape[0], dtype=jnp.int32) - first.astype(jnp.int32)
    return order, sorted_e, pos


def _local_moe(x: jax.Array, router_w: jax.Array, experts: Dict,
               cfg: ModelConfig, capacity: int) -> Tuple[jax.Array, jax.Array]:
    """Single-device MoE via sort-scatter dispatch (no collectives).
    x: [T, d]."""
    T, d = x.shape
    k, E = cfg.experts_per_token, cfg.n_experts
    w, idx, aux = _route(router_w, x, k)
    order, sorted_e, pos = _dispatch_indices(idx)
    tok = order // k
    buf = jnp.zeros((E, capacity, d), x.dtype)
    buf = buf.at[sorted_e, pos].set(x[tok], mode="drop")
    out_buf = _expert_ffn(experts, buf, cfg.act)
    y_sorted = out_buf.at[sorted_e, pos].get(
        mode="fill", fill_value=0.0)
    # pairs that exceeded capacity must contribute zero, not a wrong slot
    y_sorted = jnp.where((pos < capacity)[:, None], y_sorted, 0.0)
    y_pairs = jnp.zeros((T * k, d), x.dtype).at[order].set(y_sorted)
    y = jnp.sum(y_pairs.reshape(T, k, d) * w[..., None], axis=1)
    return y, aux


def moe_block_dense(p: Dict, cfg: ModelConfig, x: jax.Array
                    ) -> Tuple[jax.Array, jax.Array]:
    """Oracle: every expert on every token (tests / tiny configs only)."""
    B, S, d = x.shape
    xt = x.reshape(-1, d)
    w, idx, aux = _route(p["router"], xt, cfg.experts_per_token)
    dense_w = jnp.zeros((xt.shape[0], cfg.n_experts), x.dtype)
    dense_w = dense_w.at[jnp.arange(idx.shape[0])[:, None], idx].add(w)
    ys = _expert_ffn(p["experts"], jnp.broadcast_to(
        xt[None], (cfg.n_experts,) + xt.shape), cfg.act)     # [E, T, d]
    y = jnp.einsum("etd,te->td", ys, dense_w)
    if cfg.n_shared_experts:
        y = y + gated_mlp(p["shared"], xt, cfg.act)
    return y.reshape(B, S, d), aux


def _capacity(tokens: int, cfg: ModelConfig,
              factor: Optional[float] = None) -> int:
    c = int(tokens * cfg.experts_per_token / max(cfg.n_experts, 1)
            * (factor if factor is not None else CAPACITY_FACTOR))
    return max(8, ((c + 7) // 8) * 8)


def moe_block_local(p: Dict, cfg: ModelConfig, x: jax.Array
                    ) -> Tuple[jax.Array, jax.Array]:
    """Sort-scatter MoE without expert parallelism (single device / smoke)."""
    B, S, d = x.shape
    xt = x.reshape(-1, d)
    y, aux = _local_moe(xt, p["router"], p["experts"], cfg,
                        _capacity(B * S, cfg))
    if cfg.n_shared_experts:
        y = y + gated_mlp(p["shared"], xt, cfg.act)
    return y.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# Expert-parallel paths (shard_map over the mesh)
# ---------------------------------------------------------------------------
def moe_block_ep(p: Dict, cfg: ModelConfig, x: jax.Array, *,
                 mesh: jax.sharding.Mesh, batch_axes: Tuple[str, ...],
                 model_axis: str = "model",
                 decode: bool = False,
                 dispatch_dtype: str = "bfloat16",
                 capacity_factor: float = 1.25,
                 ep2d: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE. x: [B, S, d] sharded batch->batch_axes and (for
    train) seq->model. Expert weights sharded over ``model``; with ``ep2d``
    (decode) the expert FFN dim additionally shards over the data axes —
    a 256-way weight layout that fits 100B+ MoEs for serving."""
    E = cfg.n_experts
    tp = mesh.shape[model_axis]
    assert E % tp == 0, (E, tp)

    xs = P(batch_axes, None if decode else model_axis, None)
    ff_axes = batch_axes if (decode and ep2d) else ()
    ffs = ff_axes if ff_axes else None
    wspec = {"router": P(None, None),
             "experts": {"wi": P(model_axis, None, ffs),
                         "wg": P(model_axis, None, ffs),
                         "wo": P(model_axis, ffs, None)}}
    pp = {"router": p["router"], "experts": p["experts"]}

    all_axes = tuple(mesh.axis_names)
    if decode:
        fn = functools.partial(_ep_gather_fn, cfg=cfg, tp=tp,
                               model_axis=model_axis, all_axes=all_axes,
                               capacity_factor=capacity_factor,
                               ff_axes=ff_axes)
    else:
        fn = functools.partial(_ep_a2a_fn, cfg=cfg, tp=tp,
                               model_axis=model_axis, all_axes=all_axes,
                               dispatch_dtype=dispatch_dtype,
                               capacity_factor=capacity_factor)
    y, aux = jax.shard_map(
        fn, mesh=mesh, in_specs=(xs, wspec), out_specs=(xs, P()),
        check_vma=False)(x, pp)
    if cfg.n_shared_experts:
        y = y + gated_mlp(p["shared"], x, cfg.act)
    return y, aux


def _ep_a2a_fn(x_loc: jax.Array, p: Dict, *, cfg: ModelConfig, tp: int,
               model_axis: str, all_axes: Tuple[str, ...],
               dispatch_dtype: str = "bfloat16",
               capacity_factor: float = 1.25
               ) -> Tuple[jax.Array, jax.Array]:
    """Per-device body, training path. x_loc: [B_loc, S_loc, d]."""
    Bl, Sl, d = x_loc.shape
    T = Bl * Sl
    k, E = cfg.experts_per_token, cfg.n_experts
    E_loc = E // tp
    C = _capacity(T, cfg, capacity_factor)
    xt = x_loc.reshape(T, d)
    w, idx, aux = _route(p["router"], xt, k)
    aux = jax.lax.pmean(aux, all_axes)
    order, sorted_e, pos = _dispatch_indices(idx)
    tok = order // k
    buf = jnp.zeros((E, C, d), x_loc.dtype)
    buf = buf.at[sorted_e, pos].set(xt[tok], mode="drop")
    # exchange expert shards within the model axis:
    # [E, C, d] -> [tp, E_loc, C, d] -> a2a -> [tp, E_loc, C, d] (peers' tokens)
    buf = buf.reshape(tp, E_loc, C, d)
    if dispatch_dtype == "f8":
        # DSv3-style low-precision dispatch: halve the a2a wire bytes; the
        # combine path stays bf16 (as in the DeepSeek-V3 recipe)
        buf = buf.astype(jnp.float8_e4m3fn)
    buf = jax.lax.all_to_all(buf, model_axis, split_axis=0, concat_axis=0,
                             tiled=False)
    if dispatch_dtype == "f8":
        buf = buf.astype(x_loc.dtype)
    # local experts over all peers' capacity slots
    buf = buf.transpose(1, 0, 2, 3).reshape(E_loc, tp * C, d)
    out = _expert_ffn(p["experts"], buf, cfg.act)
    out = out.reshape(E_loc, tp, C, d).transpose(1, 0, 2, 3)
    out = jax.lax.all_to_all(out, model_axis, split_axis=0, concat_axis=0,
                             tiled=False)
    out = out.reshape(E, C, d)
    y_sorted = out.at[sorted_e, pos].get(mode="fill", fill_value=0.0)
    y_sorted = jnp.where((pos < C)[:, None], y_sorted, 0.0)
    y_pairs = jnp.zeros((T * k, d), x_loc.dtype).at[order].set(y_sorted)
    y = jnp.sum(y_pairs.reshape(T, k, d) * w[..., None], axis=1)
    return y.reshape(Bl, Sl, d), aux


def _ep_gather_fn(x_loc: jax.Array, p: Dict, *, cfg: ModelConfig, tp: int,
                  model_axis: str, all_axes: Tuple[str, ...],
                  capacity_factor: float = 1.25,
                  ff_axes: Tuple[str, ...] = ()
                  ) -> Tuple[jax.Array, jax.Array]:
    """Per-device body, decode path. x replicated over model axis. With
    ``ff_axes`` (2D expert sharding) every device holds an (expert-block x
    ffn-slice); tokens are gathered over ``ff_axes`` (tiny at decode), the
    wo matmul produces ffn-partial sums, and the combine psums over both
    axes before re-slicing the local batch rows."""
    B_loc_in = x_loc.shape[0]
    if ff_axes:
        x_loc = jax.lax.all_gather(x_loc, ff_axes, axis=0, tiled=True)
    Bl, Sl, d = x_loc.shape
    T = Bl * Sl
    k, E = cfg.experts_per_token, cfg.n_experts
    E_loc = E // tp
    C = _capacity(T, cfg, capacity_factor)
    my = jax.lax.axis_index(model_axis)
    xt = x_loc.reshape(T, d)
    w, idx, aux = _route(p["router"], xt, k)
    aux = jax.lax.pmean(aux, all_axes)
    # keep only pairs routed to my local experts; scatter into [E_loc, C]
    local = (idx >= my * E_loc) & (idx < (my + 1) * E_loc)
    idx_l = jnp.where(local, idx - my * E_loc, E_loc)  # E_loc = drop bucket
    order, sorted_e, pos = _dispatch_indices(idx_l)
    tok = order // k
    buf = jnp.zeros((E_loc, C, d), x_loc.dtype)
    buf = buf.at[sorted_e, pos].set(xt[tok], mode="drop")
    out = _expert_ffn(p["experts"], buf, cfg.act)
    y_sorted = out.at[sorted_e, pos].get(mode="fill", fill_value=0.0)
    valid = (pos < C)[:, None] & (sorted_e < E_loc)[:, None]
    y_sorted = jnp.where(valid, y_sorted, 0.0)
    y_pairs = jnp.zeros((T * k, d), x_loc.dtype).at[order].set(y_sorted)
    y = jnp.sum(y_pairs.reshape(T, k, d) * w[..., None], axis=1)
    # combine expert-group (model) and, in 2D, ffn-slice (data) partials
    y = jax.lax.psum(y, (model_axis,) + tuple(ff_axes))
    y = y.reshape(Bl, Sl, d)
    if ff_axes:
        row = jax.lax.axis_index(ff_axes)
        y = jax.lax.dynamic_slice_in_dim(y, row * B_loc_in, B_loc_in, 0)
    return y, aux


def moe_block(p: Dict, cfg: ModelConfig, x: jax.Array, *,
              impl: str = "local", mesh=None,
              batch_axes: Tuple[str, ...] = ("data",),
              decode: bool = False,
              dispatch_dtype: str = "bfloat16",
              capacity_factor: float = 1.25,
              ep2d: bool = False) -> Tuple[jax.Array, jax.Array]:
    if impl == "dense":
        return moe_block_dense(p, cfg, x)
    if impl == "local":
        y, aux = moe_block_local(p, cfg, x)
        return y, aux
    if impl == "ep":
        return moe_block_ep(p, cfg, x, mesh=mesh, batch_axes=batch_axes,
                            decode=decode, dispatch_dtype=dispatch_dtype,
                            capacity_factor=capacity_factor, ep2d=ep2d)
    raise ValueError(impl)
