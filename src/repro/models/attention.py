"""Attention: chunked online-softmax (memory-safe at 32k+), GQA with
replicate-or-pad head policy, MLA (DeepSeek-V3) with absorbed decode, local
(windowed) attention with ring-buffer caches, and cross-attention.

The chunked implementation is the XLA-native path for training, prefill and
cross-attention; the Pallas flash kernel (``repro.kernels.flash_attention``)
is the TPU fast path validated against the same reference. One-token decode
takes per-sequence positions ``[B]`` and one of two attentions: the Pallas
pool kernel (``repro.kernels.decode_attention``) where
:func:`pool_kernel_chunk` gives a chunk, else the single-einsum
:func:`_dense_decode_attend` over the layer's cache.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import ParamMaker, apply_rope, shard

NEG_INF = -1e30


def _pick_chunk(n: int, pref: int) -> int:
    c = min(pref, n)
    while c > 1 and n % c:
        c //= 2
    if n % c:  # odd sizes: fall back to divisor search
        for c in range(min(pref, n), 0, -1):
            if n % c == 0:
                return c
    return max(c, 1)


def _block_mask(qpos: jax.Array, kpos: jax.Array, causal: bool,
                window: int) -> jax.Array:
    mask = jnp.ones((qpos.shape[0], kpos.shape[0]), dtype=bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window:
        mask &= kpos[None, :] > qpos[:, None] - window
    return mask


def _fwd_impl(q, k, v, causal, window, scale, qc, kc):
    """Online-softmax forward. Returns (out [B,Sq,Hq,Dv], lse [B,Hkv,G,Sq])."""
    B, Sq, Hq, Dk = q.shape
    _, Skv, Hkv, Dv = v.shape
    G = Hq // Hkv
    nq, nk = Sq // qc, Skv // kc

    qg = q.reshape(B, Sq, Hkv, G, Dk)
    k_ch = k.reshape(B, nk, kc, Hkv, Dk).transpose(1, 0, 2, 3, 4)
    v_ch = v.reshape(B, nk, kc, Hkv, Dv).transpose(1, 0, 2, 3, 4)
    kv_pos = jnp.arange(Skv, dtype=jnp.int32).reshape(nk, kc)

    def q_chunk_fn(qi: jax.Array):
        qch = jax.lax.dynamic_slice_in_dim(qg, qi * qc, qc, axis=1)
        qpos = qi * qc + jnp.arange(qc, dtype=jnp.int32)

        def kv_step(carry, inp):
            m, l, acc = carry
            kch, vch, kpos = inp
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qch, kch,
                           preferred_element_type=jnp.float32) * scale
            mask = _block_mask(qpos, kpos, causal, window)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bhgqk,bkhd->bhgqd", p, vch.astype(jnp.float32))
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Hkv, G, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, qc), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, qc, Dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (k_ch, v_ch, kv_pos))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        # out: [B, Hkv, G, qc, Dv] -> [B, qc, Hq, Dv]
        return (out.transpose(0, 3, 1, 2, 4).reshape(B, qc, Hq, Dv), lse)

    if nq == 1:
        out, lse = q_chunk_fn(jnp.int32(0))
    else:
        outs, lses = jax.lax.map(q_chunk_fn, jnp.arange(nq, dtype=jnp.int32))
        out = outs.transpose(1, 0, 2, 3, 4).reshape(B, Sq, Hq, Dv)
        # lses: [nq, B, Hkv, G, qc] -> [B, Hkv, G, Sq]
        lse = lses.transpose(1, 2, 3, 0, 4).reshape(B, Hkv, G, Sq)
    if nq == 1:
        lse = lse.reshape(B, Hkv, G, Sq)
    return out.astype(v.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, window, scale, qc, kc):
    out, _ = _fwd_impl(q, k, v, causal, window, scale, qc, kc)
    return out


def _flash_fwd(q, k, v, causal, window, scale, qc, kc):
    out, lse = _fwd_impl(q, k, v, causal, window, scale, qc, kc)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, window, scale, qc, kc, res, dout):
    """Flash-attention backward: recompute P per block from (q, k, lse);
    never materializes [Sq, Skv] for the whole sequence."""
    q, k, v, out, lse = res
    B, Sq, Hq, Dk = q.shape
    _, Skv, Hkv, Dv = v.shape
    G = Hq // Hkv
    nq, nk = Sq // qc, Skv // kc

    qg = q.reshape(B, Sq, Hkv, G, Dk)
    dog = dout.reshape(B, Sq, Hkv, G, Dv)
    og = out.reshape(B, Sq, Hkv, G, Dv)
    # D_i = rowsum(dO * O): [B, Hkv, G, Sq]
    Dterm = jnp.einsum("bshgd,bshgd->bhgs", dog.astype(jnp.float32),
                       og.astype(jnp.float32))

    kv_pos_all = jnp.arange(Skv, dtype=jnp.int32)
    q_pos_all = jnp.arange(Sq, dtype=jnp.int32)

    def _p_ds(qi_start, qch, kch, qpos, kpos, lse_i, D_i, do_i, vch):
        """Recompute p and ds for one (q, kv) block pair (all f32)."""
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qch, kch,
                       preferred_element_type=jnp.float32) * scale
        mask = _block_mask(qpos, kpos, causal, window)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        p = jnp.exp(s - lse_i[..., None])            # [B,h,g,qc,kc]
        dp = jnp.einsum("bqhgd,bkhd->bhgqk", do_i.astype(jnp.float32),
                        vch.astype(jnp.float32))
        ds = p * (dp - D_i[..., None]) * scale
        return p, ds

    # ---- pass 1: dQ (outer over q chunks, scan over kv chunks) ----
    k_ch = k.reshape(B, nk, kc, Hkv, Dk).transpose(1, 0, 2, 3, 4)
    v_ch = v.reshape(B, nk, kc, Hkv, Dv).transpose(1, 0, 2, 3, 4)
    kv_pos = kv_pos_all.reshape(nk, kc)

    def dq_chunk(qi):
        qch = jax.lax.dynamic_slice_in_dim(qg, qi * qc, qc, axis=1)
        do_i = jax.lax.dynamic_slice_in_dim(dog, qi * qc, qc, axis=1)
        lse_i = jax.lax.dynamic_slice_in_dim(lse, qi * qc, qc, axis=3)
        D_i = jax.lax.dynamic_slice_in_dim(Dterm, qi * qc, qc, axis=3)
        qpos = qi * qc + jnp.arange(qc, dtype=jnp.int32)

        def kv_step(dq_acc, inp):
            kch, vch, kpos = inp
            p, ds = _p_ds(qi, qch, kch, qpos, kpos, lse_i, D_i, do_i, vch)
            dq_acc = dq_acc + jnp.einsum("bhgqk,bkhd->bqhgd", ds,
                                         kch.astype(jnp.float32))
            return dq_acc, None

        dq0 = jnp.zeros((B, qc, Hkv, G, Dk), jnp.float32)
        dq_i, _ = jax.lax.scan(kv_step, dq0, (k_ch, v_ch, kv_pos))
        return dq_i

    if nq == 1:
        dq = dq_chunk(jnp.int32(0))
    else:
        dq = jax.lax.map(dq_chunk, jnp.arange(nq, dtype=jnp.int32))
        dq = dq.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, Hkv, G, Dk)
    dq = dq.reshape(B, Sq, Hq, Dk).astype(q.dtype)

    # ---- pass 2: dK, dV (outer over kv chunks, scan over q chunks) ----
    q_chs = qg.reshape(B, nq, qc, Hkv, G, Dk).transpose(1, 0, 2, 3, 4, 5)
    do_chs = dog.reshape(B, nq, qc, Hkv, G, Dv).transpose(1, 0, 2, 3, 4, 5)
    lse_chs = lse.reshape(B, Hkv, G, nq, qc).transpose(3, 0, 1, 2, 4)
    D_chs = Dterm.reshape(B, Hkv, G, nq, qc).transpose(3, 0, 1, 2, 4)
    q_pos_ch = q_pos_all.reshape(nq, qc)

    def dkv_chunk(kj):
        kch = jax.lax.dynamic_slice_in_dim(k, kj * kc, kc, axis=1)
        vch = jax.lax.dynamic_slice_in_dim(v, kj * kc, kc, axis=1)
        kpos = kj * kc + jnp.arange(kc, dtype=jnp.int32)

        def q_step(carry, inp):
            dk_acc, dv_acc = carry
            qch, do_i, lse_i, D_i, qpos = inp
            p, ds = _p_ds(None, qch, kch, qpos, kpos, lse_i, D_i, do_i, vch)
            dk_acc = dk_acc + jnp.einsum("bhgqk,bqhgd->bkhd", ds,
                                         qch.astype(jnp.float32))
            dv_acc = dv_acc + jnp.einsum("bhgqk,bqhgd->bkhd", p,
                                         do_i.astype(jnp.float32))
            return (dk_acc, dv_acc), None

        dk0 = jnp.zeros((B, kc, Hkv, Dk), jnp.float32)
        dv0 = jnp.zeros((B, kc, Hkv, Dv), jnp.float32)
        (dk_j, dv_j), _ = jax.lax.scan(
            q_step, (dk0, dv0), (q_chs, do_chs, lse_chs, D_chs, q_pos_ch))
        return dk_j, dv_j

    if nk == 1:
        dk, dv = dkv_chunk(jnp.int32(0))
    else:
        dk, dv = jax.lax.map(dkv_chunk, jnp.arange(nk, dtype=jnp.int32))
        dk = dk.transpose(1, 0, 2, 3, 4).reshape(B, Skv, Hkv, Dk)
        dv = dv.transpose(1, 0, 2, 3, 4).reshape(B, Skv, Hkv, Dv)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_fwd, _flash_bwd)


def chunked_attention(
    q: jax.Array,                 # [B, Sq, Hq, Dk]
    k: jax.Array,                 # [B, Skv, Hkv, Dk]
    v: jax.Array,                 # [B, Skv, Hkv, Dv]
    *,
    causal: bool = True,
    window: int = 0,              # 0 = global; >0 = local attention width
    softmax_scale: Optional[float] = None,
    q_chunk: int = 2048,
    kv_chunk: int = 1024,
) -> jax.Array:
    """Doubly-chunked online-softmax attention; f32 accumulation. ``q[0]``
    sits at position 0 of the keys. A flash-style ``custom_vjp`` recomputes
    probabilities in the backward pass — per-block residuals are never
    stacked across scan steps."""
    B, Sq, Hq, Dk = q.shape
    _, Skv, Hkv, Dv = v.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    scale = softmax_scale if softmax_scale is not None else Dk ** -0.5
    qc = _pick_chunk(Sq, q_chunk)
    kc = _pick_chunk(Skv, kv_chunk)
    return _flash(q, k, v, causal, window, scale, qc, kc)


# ---------------------------------------------------------------------------
# Standard GQA attention block (dense / hybrid / vlm / encdec trunks)
# ---------------------------------------------------------------------------
def attention_params(mk: ParamMaker, prefix: str, cfg: ModelConfig,
                     tp: int = 1, cross: bool = False) -> Dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    nh, nkv = cfg.padded_heads(tp), cfg.padded_kv_heads(tp)
    h_ax = "heads" if nh % max(tp, 1) == 0 and tp > 1 else None
    kv_ax = "kv_heads" if (tp > 1 and nkv % tp == 0) else None
    p = {
        "wq": mk(f"{prefix}.wq", (d, nh, hd), ("dmodel", h_ax, None)),
        "wk": mk(f"{prefix}.wk", (d, nkv, hd), ("dmodel", kv_ax, None)),
        "wv": mk(f"{prefix}.wv", (d, nkv, hd), ("dmodel", kv_ax, None)),
        "wo": mk(f"{prefix}.wo", (nh, hd, d), (h_ax, None, "dmodel")),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = mk(f"{prefix}.bq", (nh, hd), (h_ax, None), init="zeros")
        p["bk"] = mk(f"{prefix}.bk", (nkv, hd), (kv_ax, None), init="zeros")
        p["bv"] = mk(f"{prefix}.bv", (nkv, hd), (kv_ax, None), init="zeros")
    return p


def _qkv(p: Dict, x: jax.Array, kv_src: Optional[jax.Array] = None):
    src = x if kv_src is None else kv_src
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", src, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", src, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    return shard(q, "batch", None, "heads"), k, v


def self_attention(p: Dict, cfg: ModelConfig, x: jax.Array,
                   positions: jax.Array, window: int = 0,
                   use_rope: bool = True, return_cache: bool = False):
    """Training / prefill self-attention over a full sequence.
    ``return_cache`` additionally returns the (roped) K and V for caching."""
    q, k, v = _qkv(p, x)
    if use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    out = chunked_attention(q, k, v, causal=True, window=window)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    if return_cache:
        return y, (k, v)
    return y


def cross_attention(p: Dict, cfg: ModelConfig, x: jax.Array,
                    memory: jax.Array) -> jax.Array:
    q, k, v = _qkv(p, x, kv_src=memory)
    out = chunked_attention(q, k, v, causal=False)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


# --- KV caches --------------------------------------------------------------
def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, tp: int = 1,
                  window: int = 0, dtype=jnp.bfloat16) -> Dict:
    nkv, hd = cfg.padded_kv_heads(tp), cfg.resolved_head_dim
    slots = min(window, max_len) if window else max_len
    return {
        "k": jnp.zeros((batch, slots, nkv, hd), dtype),
        "v": jnp.zeros((batch, slots, nkv, hd), dtype),
    }


def _dense_decode_attend(q: jax.Array, k: jax.Array, v: jax.Array,
                         valid: jax.Array, scale: float) -> jax.Array:
    """Single-einsum decode attention: no kv-chunk scan, so a cache whose
    sequence dim is sharded over the model axis partitions cleanly (the
    softmax reductions over the sharded axis become psums — SPMD-friendly).
    q: [B,1,Hq,Dk]; k/v: [B,S,Hkv,D*]; valid: each sequence's length [B]."""
    B, S, Hkv, Dk = k.shape
    G = q.shape[2] // Hkv
    qg = q.reshape(B, 1, Hkv, G, Dk)
    s = jnp.einsum("bqhgd,bshd->bhgqs", qg, k,
                   preferred_element_type=jnp.float32) * scale
    kv_pos = jnp.arange(S, dtype=jnp.int32)
    mask = (kv_pos[None, :] < valid[:, None])[:, None, None, None, :]
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqs,bshd->bqhgd", p.astype(v.dtype), v)
    return out.reshape(B, 1, q.shape[2], v.shape[-1])


def _write_rows(cache: jax.Array, new: jax.Array, slot: jax.Array,
                layer: Optional[jax.Array]) -> jax.Array:
    """Write each sequence's new row in place. ``cache`` is one layer's
    ``[B, S, ...]``, or with ``layer`` the stack ``[L, B, S, ...]``; ``new``
    is ``[B, 1, ...]``; ``slot`` each sequence's row ``[B]``. One scatter
    of B rows, whatever the cache's size."""
    at = (jnp.arange(new.shape[0]), slot)
    if layer is not None:
        at = (layer,) + at
    return cache.at[at].set(new[:, 0].astype(cache.dtype))


def _layer_view(cache: jax.Array, layer: Optional[jax.Array]) -> jax.Array:
    """The ``[B, S, ...]`` cache that attention reads."""
    return cache if layer is None else cache[layer]


def _on_tpu() -> bool:
    """Whether the program runs on a TPU, the backend the pool kernel is
    compiled for (elsewhere the Pallas interpreter runs it)."""
    return jax.default_backend() == "tpu"


def pool_kernel_chunk(cfg: ModelConfig, tp: int, max_len: int) -> int:
    """The kv chunk in which slot-pool decode attention over the stacked
    cache of ``max_len`` positions reads each slot's written positions
    through the pool kernel, or 0 where it reads every position of the
    layer's view instead: MLA, a sequence or head axis sharded over
    ``tp > 1``, a backend other than a TPU, or heads no block of the kernel
    tiles within its VMEM."""
    if cfg.use_mla or tp != 1 or not _on_tpu():
        return 0
    from repro.kernels.decode_attention import kv_chunk
    return kv_chunk(max_len, cfg.n_heads, cfg.n_kv_heads,
                    cfg.resolved_head_dim, jnp.dtype(cfg.dtype).itemsize)


def decode_self_attention(p: Dict, cfg: ModelConfig, x: jax.Array,
                          cache: Dict, pos: jax.Array,
                          layer: Optional[jax.Array] = None, tp: int = 1
                          ) -> Tuple[jax.Array, Dict]:
    """One-token decode: rope the new token's K/V, write them into the cache
    in place, attend over the cache. ``pos`` ``[B]`` is each sequence's
    absolute position: it ropes, writes and masks at its own position, so
    batch-mates of different lengths never see each other's padding.
    ``cache`` holds one layer's ``k``/``v`` ``[B, S, Hkv, hd]``, or with
    ``layer`` the whole stack ``[L, B, S, Hkv, hd]``, of which only that
    layer's rows are written and read (the stack stays in the layer scan's
    carry). Keys are roped at write time; a cache shorter than the
    sequence is a ring buffer (the hybrid's local attention). Attention
    over the stack reads each sequence's live chunks of that layer straight
    from the stack through the pool kernel where :func:`pool_kernel_chunk`
    gives a chunk; elsewhere :func:`_dense_decode_attend` reads the layer's
    view."""
    q, k, v = _qkv(p, x)                      # [B, 1, H(kv), hd]
    posm = pos[:, None]
    q = apply_rope(q, posm, cfg.rope_theta)
    k = apply_rope(k, posm, cfg.rope_theta)
    slots = cache["k"].shape[-3]
    slot = (pos % slots).astype(jnp.int32)
    new = {"k": _write_rows(cache["k"], k, slot, layer),
           "v": _write_rows(cache["v"], v, slot, layer)}
    # Ring semantics: every written slot is within the window by construction,
    # so masking only needs "slot has been written": slot_idx <= pos.
    valid = jnp.minimum(pos + 1, slots)
    scale = cfg.resolved_head_dim ** -0.5
    chunk = pool_kernel_chunk(cfg, tp, slots) if layer is not None else 0
    if chunk:
        from repro.kernels.decode_attention import pool_decode_attention
        out = pool_decode_attention(
            q, new["k"], new["v"], layer, valid, scale=scale, chunk=chunk,
            interpret=jax.default_backend() != "tpu")
    else:
        out = _dense_decode_attend(q, _layer_view(new["k"], layer),
                                   _layer_view(new["v"], layer), valid, scale)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, new


# ---------------------------------------------------------------------------
# MLA — multi-head latent attention (DeepSeek-V3)
# ---------------------------------------------------------------------------
def mla_params(mk: ParamMaker, prefix: str, cfg: ModelConfig,
               tp: int = 1) -> Dict:
    d = cfg.d_model
    nh = cfg.padded_heads(tp)
    h_ax = "heads" if tp > 1 else None
    qk = cfg.qk_nope_dim + cfg.qk_rope_dim
    return {
        # query low-rank path
        "wq_a": mk(f"{prefix}.wq_a", (d, cfg.q_lora_rank), ("dmodel", None)),
        "q_norm": mk(f"{prefix}.q_norm", (cfg.q_lora_rank,), (None,), init="ones"),
        "wq_b": mk(f"{prefix}.wq_b", (cfg.q_lora_rank, nh, qk),
                   (None, h_ax, None)),
        # kv latent path (+ shared rope key)
        "wkv_a": mk(f"{prefix}.wkv_a", (d, cfg.kv_lora_rank + cfg.qk_rope_dim),
                    ("dmodel", None)),
        "kv_norm": mk(f"{prefix}.kv_norm", (cfg.kv_lora_rank,), (None,), init="ones"),
        "wk_b": mk(f"{prefix}.wk_b", (cfg.kv_lora_rank, nh, cfg.qk_nope_dim),
                   (None, h_ax, None)),
        "wv_b": mk(f"{prefix}.wv_b", (cfg.kv_lora_rank, nh, cfg.v_head_dim),
                   (None, h_ax, None)),
        "wo": mk(f"{prefix}.wo", (nh, cfg.v_head_dim, d),
                 (h_ax, None, "dmodel")),
    }


def _mla_q(p: Dict, cfg: ModelConfig, x: jax.Array, positions: jax.Array):
    from repro.models.common import rms_norm
    qa = rms_norm(jnp.einsum("bsd,dr->bsr", x, p["wq_a"]), p["q_norm"])
    q = jnp.einsum("bsr,rhk->bshk", qa, p["wq_b"])
    q_nope = q[..., :cfg.qk_nope_dim]
    q_rope = apply_rope(q[..., cfg.qk_nope_dim:], positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_latent(p: Dict, cfg: ModelConfig, x: jax.Array,
                positions: jax.Array):
    from repro.models.common import rms_norm
    kv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])
    c_kv = rms_norm(kv[..., :cfg.kv_lora_rank], p["kv_norm"])
    k_rope = apply_rope(kv[..., None, cfg.kv_lora_rank:], positions,
                        cfg.rope_theta)[..., 0, :]   # shared across heads
    return c_kv, k_rope


def mla_attention(p: Dict, cfg: ModelConfig, x: jax.Array,
                  positions: jax.Array, return_cache: bool = False):
    """Train/prefill MLA: decompress per-head K/V from the latent."""
    q_nope, q_rope = _mla_q(p, cfg, x, positions)
    c_kv, k_rope = _mla_latent(p, cfg, x, positions)
    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, p["wk_b"])
    v = jnp.einsum("bsr,rhk->bshk", c_kv, p["wv_b"])
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None],
                                  k_nope.shape[:3] + (cfg.qk_rope_dim,))],
        axis=-1)
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    out = chunked_attention(shard(q, "batch", None, "heads"), k, v,
                            causal=True, softmax_scale=scale)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    if return_cache:
        return y, (c_kv, k_rope)
    return y


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16) -> Dict:
    return {
        "c_kv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_dim), dtype),
    }


def mla_decode(p: Dict, cfg: ModelConfig, x: jax.Array, cache: Dict,
               pos: jax.Array, layer: Optional[jax.Array] = None
               ) -> Tuple[jax.Array, Dict]:
    """Absorbed-matrix MLA decode: attention runs entirely in the latent
    space — the cache stores only (c_kv, k_rope) per token (the paper-scale
    memory win of MLA). ``pos`` ``[B]`` is each sequence's position.
    ``cache`` holds one layer's ``[B, S, *]`` latents, or with ``layer`` the
    stack ``[L, B, S, *]``, written in place at that layer's rows as in
    :func:`decode_self_attention`."""
    posm = pos[:, None]
    q_nope, q_rope = _mla_q(p, cfg, x, posm)         # [B,1,H,*]
    c_new, kr_new = _mla_latent(p, cfg, x, posm)     # [B,1,r], [B,1,rope]
    slot = (pos % cache["c_kv"].shape[-2]).astype(jnp.int32)
    new = {"c_kv": _write_rows(cache["c_kv"], c_new, slot, layer),
           "k_rope": _write_rows(cache["k_rope"], kr_new, slot, layer)}
    ck = _layer_view(new["c_kv"], layer)
    kr = _layer_view(new["k_rope"], layer)
    # absorb W_uk into q: q_tilde = q_nope @ W_uk^T  -> latent space
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, p["wk_b"])
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    valid = pos + 1
    kv_pos = jnp.arange(ck.shape[1], dtype=jnp.int32)
    s = (jnp.einsum("bshr,btr->bhst", q_lat, ck.astype(q_lat.dtype))
         + jnp.einsum("bshk,btk->bhst", q_rope, kr.astype(q_rope.dtype)))
    s = (s.astype(jnp.float32) * scale)
    s = jnp.where((kv_pos[None, :] < valid[:, None])[:, None, None, :],
                  s, NEG_INF)
    a = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhst,btr->bshr", a.astype(ck.dtype), ck)
    out = jnp.einsum("bshr,rhk->bshk", o_lat, p["wv_b"])
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, new
