"""Pallas TPU decode attention over the slot pool's stacked KV cache.

One query token per slot against that slot's live cache rows, read straight
out of the stack ``[L, B, M, Hkv, hd]`` that the decode step's layer scan
carries: no layer view is sliced or copied out of it first. The layer index
and each slot's extent (the positions it has written, ``min(pos + 1, M)``)
are scalar-prefetch arguments; the grid is (slot, kv-head block, kv chunk),
with up to :data:`KV_HEAD_BLOCK` kv heads and their G query heads in one
block. A chunk past a slot's last live chunk maps to the last live one,
whose block is already in VMEM, so it is never fetched again, and its
compute is skipped; the last live chunk masks the positions at or past the
extent. Online softmax in f32 with VMEM accumulators, as in
:mod:`repro.kernels.flash_attention`.

The block's rows are taken as one ``[kc * hb, hd]`` matrix, in the order
they lie in (a position's kv heads side by side), and every query head
scores all of them; a bias keeps only its own kv head's rows. That costs
hb times the scores of a per-head loop, cheap beside the block's read at
hb <= 8, where slicing one kv head out of the block would regather it in
VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tpu import LANE, SUBLANE, VMEM_LIMIT_BYTES, compiler_params

NEG_INF = -1e30
#: preferred kv chunk (positions); the chunk is the largest multiple of
#: LANE up to this that divides the cache length and fits VMEM
KV_CHUNK = 512
#: kv heads in a block at most: each query head scores every kv head of its
#: block, and a block's kv heads must fill whole sublane tiles
KV_HEAD_BLOCK = SUBLANE


def kv_head_block(kv_heads: int) -> int:
    """The kv heads one block holds: all of them up to
    :data:`KV_HEAD_BLOCK`, else that many where they divide the heads, else
    0 (no block tiles them)."""
    if kv_heads <= KV_HEAD_BLOCK:
        return kv_heads
    return KV_HEAD_BLOCK if kv_heads % KV_HEAD_BLOCK == 0 else 0


def vmem_bytes(chunk: int, kv_block: int, groups: int, head_dim: int,
               itemsize: int) -> int:
    """VMEM one grid step holds, counted high: K and V blocks double
    buffered and once more each as values, the query and output blocks
    double buffered, and four f32 ``[query heads, chunk * kv_block]``
    matrices (bias, scores, probabilities, mask) beside the accumulators."""
    heads = -(-kv_block * groups // SUBLANE) * SUBLANE
    rows = chunk * kv_block
    return (6 * rows * head_dim * itemsize
            + 4 * heads * head_dim * itemsize
            + 4 * 4 * heads * rows
            + 4 * heads * (head_dim + 2 * LANE))


def kv_chunk(max_len: int, q_heads: int, kv_heads: int, head_dim: int,
             itemsize: int) -> int:
    """The kv chunk the kernel reads a cache of ``max_len`` positions in:
    the largest multiple of LANE up to :data:`KV_CHUNK` that divides
    ``max_len`` (or ``max_len`` itself where none does) whose grid step
    fits the VMEM limit; 0 where no chunk fits or no kv-head block tiles
    the heads, and the kernel cannot run."""
    hb = kv_head_block(kv_heads)
    if not hb:
        return 0
    chunks = [c for c in range(min(KV_CHUNK, max_len) // LANE * LANE, 0,
                               -LANE) if max_len % c == 0] or [max_len]
    for c in chunks:
        if vmem_bytes(c, hb, q_heads // kv_heads, head_dim,
                      itemsize) <= VMEM_LIMIT_BYTES:
            return c
    return 0


def _kernel(layer_ref, ext_ref, q_ref, k_ref, v_ref, o_ref, bias_scr, m_scr,
            l_scr, acc_scr, *, scale: float, groups: int, n_chunks: int):
    del layer_ref                                   # read by the index maps
    b, j = pl.program_id(0), pl.program_id(2)
    kc, n_kv, hd = k_ref.shape
    ext = ext_ref[b]
    last = (ext - 1) // kc

    @pl.when(j == 0)
    def _init():
        # query head r = h * groups + g of the block scores every row
        # t * n_kv + h' of the chunk; it keeps those of its own kv head
        row = jax.lax.broadcasted_iota(jnp.int32, bias_scr.shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, bias_scr.shape, 1)
        bias_scr[...] = jnp.where(col % n_kv == row // groups, 0.0, NEG_INF)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def attend(last_chunk: bool):
        # [kc, n_kv, hd] -> [kc * n_kv, hd]: with 8 kv heads a position is
        # one (8, 128) tile of the block, so no data moves
        k = k_ref[...].reshape(kc * n_kv, hd)
        v = v_ref[...].reshape(kc * n_kv, hd)
        s = jax.lax.dot_general(
            q_ref[...], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale + bias_scr[...]
        if last_chunk:                              # positions >= ext
            col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(j * kc + col // n_kv < ext, s, NEG_INF)
            row = jax.lax.broadcasted_iota(jnp.int32, (kc * n_kv, 1), 0)
            v = jnp.where(j * kc + row // n_kv < ext, v, jnp.zeros_like(v))
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    pl.when(j < last)(functools.partial(attend, False))
    pl.when(j == last)(functools.partial(attend, True))

    @pl.when(j == n_chunks - 1)
    def _finalize():
        o_ref[...] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def pool_decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                          layer: jax.Array, extent: jax.Array, *,
                          scale: float, chunk: int, interpret: bool = False
                          ) -> jax.Array:
    """q: ``[B, 1, Hq, hd]``; k/v: the stack ``[L, B, M, Hkv, hd]``;
    ``layer`` a scalar; ``extent`` ``[B]`` positions written per slot (at
    least 1); ``chunk`` the kv chunk, :func:`kv_chunk` of these widths.
    Returns ``[B, 1, Hq, hd]`` in ``q``'s dtype, attending each slot's
    positions ``< extent`` of ``layer``."""
    L, B, M, Hkv, hd = k.shape
    Hq = q.shape[2]
    G = Hq // Hkv
    hb = kv_head_block(Hkv)
    assert chunk and M % chunk == 0 and hb, (chunk, M, Hkv)
    nc = M // chunk

    def kv_map(b, h, j, layer_ref, ext_ref):
        last = (ext_ref[b] - 1) // chunk
        return (layer_ref[0], b, jnp.minimum(j, last), h, 0)

    def q_map(b, h, j, layer_ref, ext_ref):
        return (b, h, 0)

    kv_spec = pl.BlockSpec((None, None, chunk, hb, hd), kv_map)
    q_spec = pl.BlockSpec((None, hb * G, hd), q_map)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, groups=G, n_chunks=nc),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, Hkv // hb, nc),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((hb * G, chunk * hb), jnp.float32),
                pltpu.VMEM((hb * G, 1), jnp.float32),
                pltpu.VMEM((hb * G, 1), jnp.float32),
                pltpu.VMEM((hb * G, hd), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, Hq, hd), q.dtype),
        compiler_params=compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      extent.astype(jnp.int32), q.reshape(B, Hq, hd), k, v)
    return out.reshape(B, 1, Hq, hd)
