"""Jit'd public wrappers around the Pallas kernels."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as fa
from repro.kernels import membw as mb
from repro.kernels import vai as vai_mod


@functools.partial(jax.jit, static_argnames=("loopsize", "block_rows",
                                             "interpret"))
def vai_op(a, b, c, *, loopsize: int, block_rows: int = 256,
           interpret: bool = False):
    return vai_mod.vai(a, b, c, loopsize=loopsize, block_rows=block_rows,
                       interpret=interpret)


@functools.partial(jax.jit, static_argnames=("n_chunks", "n_iters",
                                             "interpret"))
def membw_op(x, *, n_chunks: int, n_iters: int,
             interpret: bool = False):
    return mb.membw(x, n_chunks=n_chunks, n_iters=n_iters,
                    interpret=interpret)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention_op(q, k, v, *, causal: bool = True, block_q: int = 256,
                       block_k: int = 256,
                       interpret: bool = False):
    """q: [B, Sq, Hq, D]; k/v: [B, Skv, Hkv, D/Dv]. GQA-expands KV, folds
    (batch, heads) into the kernel's leading grid dim."""
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, Dv = v.shape
    G = Hq // Hkv
    kk = jnp.repeat(k, G, axis=2) if G > 1 else k
    vv = jnp.repeat(v, G, axis=2) if G > 1 else v
    qf = q.transpose(0, 2, 1, 3).reshape(B * Hq, Sq, D)
    kf = kk.transpose(0, 2, 1, 3).reshape(B * Hq, Skv, D)
    vf = vv.transpose(0, 2, 1, 3).reshape(B * Hq, Skv, Dv)
    out = fa.flash_attention(qf, kf, vf, causal=causal, block_q=block_q,
                             block_k=block_k, interpret=interpret)
    return out.reshape(B, Hq, Sq, Dv).transpose(0, 2, 1, 3)
