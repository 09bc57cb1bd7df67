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
def flash_attention_op(q, k, v, lengths=None, *, causal: bool = True,
                       block_q: int = 256, block_k: int = 256,
                       interpret: bool = False):
    """q: [B, S, Hq, D]; k/v: [B, S, Hkv, D/Dv]; ``lengths`` [B] or None.
    Each kv head's G = Hq / Hkv query heads go to the kernel as one block
    against that head's K and V, which are not copied per query head."""
    B, S, Hq, D = q.shape
    Hkv, Dv = v.shape[2], v.shape[3]
    G = Hq // Hkv
    out = fa.flash_attention(
        q.reshape(B, S, Hkv, G, D).transpose(0, 2, 3, 1, 4),
        k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), lengths,
        causal=causal, block_q=block_q, block_k=block_k, interpret=interpret)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, Hq, Dv)
