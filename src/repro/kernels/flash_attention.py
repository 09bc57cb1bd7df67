"""Pallas TPU flash attention (fwd) — the MXU fast path for prefill/train.

Blocked online-softmax with VMEM scratch accumulators, grid =
(batch*heads, q_blocks, kv_blocks); the kv dimension is the innermost
(sequential) axis so the (m, l, acc) scratch carries across kv steps.
Block shapes are MXU-aligned (multiples of 128 on the lane dim). Validated
in interpret mode against :mod:`repro.kernels.ref` (see tests); the XLA
fallback used by the dry-run is ``repro.models.attention.chunked_attention``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tpu import compiler_params

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, bq: int, bk: int, nk: int):
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                                    # [bq, d]
    k = k_ref[0]                                    # [bk, d]
    v = v_ref[0]                                    # [bk, dv]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # [bq, bk]
    if causal:
        qi = pl.program_id(1)
        qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(kpos <= qpos, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1)
    m_scr[...] = m_new
    acc_scr[...] = (acc_scr[...] * corr[:, None]
                    + jax.lax.dot_general(
                        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))

    @pl.when(kj == nk - 1)
    def _finalize():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)[:, None]
        o_ref[0] = out.astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, scale: float | None = None,
                    block_q: int = 256, block_k: int = 256,
                    interpret: bool = False) -> jax.Array:
    """q: [BH, Sq, D], k/v: [BH, Skv, D/Dv] -> [BH, Sq, Dv].

    Batch and heads are folded into the leading dim (GQA expansion happens in
    the ops wrapper).
    """
    BH, Sq, D = q.shape
    _, Skv, Dv = v.shape
    scale = D ** -0.5 if scale is None else scale
    bq = min(block_q, Sq)
    bk = min(block_k, Skv)
    assert Sq % bq == 0 and Skv % bk == 0, (Sq, bq, Skv, bk)
    nq, nk = Sq // bq, Skv // bk
    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda h, qi, kj: (h, qi, 0)),
            pl.BlockSpec((1, bk, D), lambda h, qi, kj: (h, kj, 0)),
            pl.BlockSpec((1, bk, Dv), lambda h, qi, kj: (h, kj, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, Dv), lambda h, qi, kj: (h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
        compiler_params=compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
