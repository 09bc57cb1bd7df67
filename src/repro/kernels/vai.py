"""VAI — Variable Arithmetic Intensity kernel (paper Algorithm 1, TPU-native).

The paper's OpenMP/HIP kernel walks the roofline by tuning ``LOOPSIZE``:
3 reads + 1 write per element with ``2*LOOPSIZE`` FMA flops. On TPU the
``globalWIs`` work-items become a Pallas grid over VMEM tiles; the unrolled
FMA loop runs on the VPU over the resident tile, so arithmetic intensity is
exactly ``2*LOOPSIZE / 16`` flops/byte in f32 (AI=0 degenerates to the
stream-copy c = b, as in the paper).

Used by :mod:`repro.core.vai` to trace the power/performance roofline under
frequency and power caps.
"""
from __future__ import annotations

import functools
import operator

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tpu import LANE, compiler_params

DEFAULT_BLOCK_ROWS = 256


def _vai_kernel(a_ref, b_ref, c_ref, o_ref, *, loopsize: int):
    x = a_ref[...]
    y = b_ref[...]
    if loopsize == 0:
        # arithmetic intensity 0: pure stream copy (paper: c[i] <- b[i])
        o_ref[...] = y
        return
    z = c_ref[...]

    def body(_, acc):
        return x * y + acc          # 2 flops/element per iteration

    z = jax.lax.fori_loop(0, loopsize, body, z)
    o_ref[...] = z


def vai(a: jax.Array, b: jax.Array, c: jax.Array, *, loopsize: int,
        block_rows: int = DEFAULT_BLOCK_ROWS,
        interpret: bool = False) -> jax.Array:
    """a, b, c: [rows, 128] f32; returns updated c.

    ``loopsize`` must be a non-negative int (0 = the stream-copy c <- b);
    ``block_rows`` must be positive and, after clamping to ``rows``,
    divide the row count — rejected with a ``ValueError`` here rather
    than a grid assert deep inside ``pallas_call``."""
    assert a.shape == b.shape == c.shape and a.shape[1] == LANE, a.shape
    try:
        loopsize = operator.index(loopsize)
        block_rows = operator.index(block_rows)
    except TypeError:
        raise ValueError(
            f"loopsize and block_rows must be ints, got "
            f"loopsize={loopsize!r}, block_rows={block_rows!r}") from None
    if loopsize < 0:
        raise ValueError(
            f"loopsize must be non-negative (0 = stream copy), "
            f"got {loopsize}")
    if block_rows <= 0:
        raise ValueError(
            f"block_rows must be positive, got {block_rows}")
    rows = a.shape[0]
    br = min(block_rows, rows)
    if rows % br:
        raise ValueError(
            f"block_rows={block_rows} does not tile the {rows}-row input: "
            f"rows % {br} == {rows % br} (pick a divisor of {rows})")
    grid = (rows // br,)
    spec = pl.BlockSpec((br, LANE), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_vai_kernel, loopsize=loopsize),
        grid=grid,
        in_specs=[spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(c.shape, c.dtype),
        compiler_params=compiler_params(),
        interpret=interpret,
    )(a, b, c)


def vai_flops_bytes(n_elems: int, loopsize: int, itemsize: int = 4):
    """(flops, bytes) of one VAI pass — the roofline coordinates."""
    if loopsize == 0:
        return 0, 2 * n_elems * itemsize          # read b + write c
    return 2 * loopsize * n_elems, 4 * n_elems * itemsize
