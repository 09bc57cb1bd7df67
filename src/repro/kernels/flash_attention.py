"""Pallas TPU flash attention (forward): causal self-attention of a prefill.

Blocked online softmax with VMEM accumulators; the grid is (batch, kv head,
q block, kv block), and the kv axis is innermost and sequential, so the
(m, l, acc) scratch carries across it. The G query heads of one kv head
share a block: q is laid out ``[B, Hkv, G, S, D]``, and a block's G x bq
query rows are one ``[G * bq, D]`` matrix against that kv head's
``[bk, D]`` K and V blocks. K and V are read once per (kv head, q block),
never copied per query head; the score and probability tiles live in VMEM
only.

Blocks that hold no live pair are skipped. For q block ``qi`` the kv blocks
past its last row (causal) or at or past the sequence's length (``lengths``,
a scalar-prefetch argument) are neither computed (``pl.when``) nor fetched:
their index map clamps to the last needed block, which is already in VMEM.
A q block wholly past the length computes nothing and writes zeros. Only a
block that crosses the diagonal or the length masks. Scores, the max, the
sum and the accumulator are f32; P enters the PV matmul in V's dtype.
Validated in interpret mode against :mod:`repro.kernels.ref`; the XLA path
is ``repro.models.attention.chunked_attention``.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tpu import LANE, VMEM_LIMIT_BYTES, compiler_params

NEG_INF = -1e30
#: block edges (positions) the prefill route takes on each axis, largest
#: first
BLOCKS = (512, 256)


def vmem_bytes(block_q: int, block_k: int, groups: int, head_dim: int,
               itemsize: int) -> int:
    """VMEM one grid step holds, counted high: the q, K, V and output
    blocks double buffered, the f32 accumulator and the lane-padded (m, l),
    and two f32 ``[groups * block_q, block_k]`` tiles (scores,
    probabilities); the head is padded to whole lanes. The v5e compiler
    needs 15.4 MiB at 6 x 512 rows by 512 against this count's 20 MiB,
    10.9 at 4 x 512 by 512 against 13.5."""
    rows = groups * block_q
    hd = -(-head_dim // LANE) * LANE
    return (2 * (2 * rows + 2 * block_k) * hd * itemsize
            + 4 * rows * (hd + 2 * LANE)
            + 2 * 4 * rows * block_k)


def block_sizes(groups: int, head_dim: int, itemsize: int
                ) -> Tuple[int, int]:
    """The (q, kv) block edges the kernel tiles a self-attention with
    ``groups`` query heads per kv head in: of :data:`BLOCKS`, the widest kv
    block, then the tallest q block, whose grid step fits the VMEM limit by
    :func:`vmem_bytes`; (0, 0) where none does. The kv edge goes first: it
    halves the sequential steps, the larger gain on a TPU v5e (one call at
    48 query and 8 kv heads of 128 over 4096 positions: 2.71 ms at
    (512, 512), 3.25 at (256, 512), 4.71 at (512, 256), 4.95 at
    (256, 256))."""
    for bk in BLOCKS:
        for bq in BLOCKS:
            if vmem_bytes(bq, bk, groups, head_dim,
                          itemsize) <= VMEM_LIMIT_BYTES:
                return bq, bk
    return 0, 0


def _last_kv_block(qi, length, bq: int, bk: int, causal: bool, xp=jnp):
    """The last kv block q block ``qi`` attends, or -1 where its rows all
    lie at or past ``length``; ``xp`` is numpy on the host."""
    lim = length - 1
    if causal:
        lim = xp.minimum(lim, qi * bq + bq - 1)
    return xp.where(qi * bq < length, lim // bk, -1)


def visited_blocks(length: int, seq: int, block_q: int, block_k: int,
                   causal: bool = True) -> int:
    """(q block, kv block) pairs the kernel computes for one sequence of
    ``length`` live positions in ``seq``: the host's count of its work."""
    qi = np.arange(seq // block_q)
    return int((_last_kv_block(qi, length, block_q, block_k, causal,
                               np) + 1).sum())


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool):
    b, qi, kj = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    G, bq, D = q_ref.shape
    bk = k_ref.shape[0]
    length = len_ref[b]
    last = _last_kv_block(qi, length, bq, bk, causal)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def attend(masked: bool):
        q = q_ref[...].reshape(G * bq, D)
        v = v_ref[...]
        s = jax.lax.dot_general(
            q, k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [G * bq, bk]
        if masked:
            col = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            keep = col < length
            if causal:
                row = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk),
                                                         0)
                keep &= col <= row
            s = jnp.where(keep[None], s.reshape(G, bq, bk),
                          NEG_INF).reshape(G * bq, bk)
            kpos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
            v = jnp.where(kpos < length, v, jnp.zeros_like(v))
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    edge = kj * bk + bk > length
    if causal:
        edge |= kj * bk + bk - 1 > qi * bq
    pl.when((kj <= last) & ~edge)(functools.partial(attend, False))
    pl.when((kj <= last) & edge)(functools.partial(attend, True))

    @pl.when(kj == pl.num_programs(3) - 1)
    def _finalize():
        # a q block past the length has l == acc == 0: its rows are zeros
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = out.reshape(G, bq, -1).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    lengths: Optional[jax.Array] = None, *,
                    causal: bool = True, scale: Optional[float] = None,
                    block_q: int = 256, block_k: int = 256,
                    interpret: bool = False) -> jax.Array:
    """Self-attention of q ``[B, Hkv, G, S, D]`` over k ``[B, Hkv, S, D]``
    and v ``[B, Hkv, S, Dv]``, query and key positions both from 0; returns
    ``[B, Hkv, G, S, Dv]`` in q's dtype. ``lengths`` ``[B]`` (default S)
    bounds both axes: rows at or past a sequence's length attend its first
    ``length`` keys, and those of a q block wholly past it are zeros."""
    B, Hkv, G, S, D = q.shape
    Dv = v.shape[-1]
    assert k.shape == (B, Hkv, S, D) and v.shape[:3] == (B, Hkv, S), (
        q.shape, k.shape, v.shape)
    scale = D ** -0.5 if scale is None else scale
    bq, bk = min(block_q, S), min(block_k, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    lengths = (jnp.full((B,), S, jnp.int32) if lengths is None
               else jnp.clip(jnp.asarray(lengths, jnp.int32), 1, S))

    def q_map(b, h, qi, kj, len_ref):
        return (b, h, 0, jnp.minimum(qi, (len_ref[b] - 1) // bq), 0)

    def kv_map(b, h, qi, kj, len_ref):
        # a q block past the length keeps the last block its predecessor
        # read, so nothing is fetched for it
        length = len_ref[b]
        live_q = jnp.minimum(qi, (length - 1) // bq)
        last = _last_kv_block(live_q, length, bq, bk, causal)
        return (b, h, jnp.where(qi == live_q, jnp.minimum(kj, last), last), 0)

    def o_map(b, h, qi, kj, len_ref):
        return (b, h, 0, qi, 0)

    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal),
        name="flash_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Hkv, S // bq, S // bk),
            in_specs=[
                pl.BlockSpec((None, None, G, bq, D), q_map),
                pl.BlockSpec((None, None, bk, D), kv_map),
                pl.BlockSpec((None, None, bk, Dv), kv_map),
            ],
            out_specs=pl.BlockSpec((None, None, G, bq, Dv), o_map),
            scratch_shapes=[
                pltpu.VMEM((G * bq, 1), jnp.float32),
                pltpu.VMEM((G * bq, 1), jnp.float32),
                pltpu.VMEM((G * bq, Dv), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, S, Dv), q.dtype),
        compiler_params=compiler_params(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(lengths, q, k, v)
