"""Memory-subsystem bandwidth probe (paper §III-B-b, GPU-benches L2 kernel,
TPU-adapted).

The paper's kernel loads the same memory chunk from many blocks to measure
L2-vs-HBM bandwidth as a function of the chunk size. Here iteration ``i``
reads chunk ``i % n_chunks`` through the BlockSpec index map and reduces
it to one lane row (bandwidth-bound by construction).

A chunk is read as ``n_tiles`` tiles of at most :data:`MAX_TILE_BYTES`, one
per step of the inner grid axis, so a chunk of any size compiles under the
scoped VMEM limit (the input block is double-buffered). Each iteration
accumulates its tiles into an ``(8, 128)`` output block, the f32 tile the
output must respect; the wrapper folds the 8 sublanes into the row.

On a TPU a BlockSpec fetches its block from HBM whenever the block index
changes, so only a chunk that is one tile (``n_chunks == 1``) is re-read
from VMEM; every other configuration streams from HBM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tpu import LANE, SUBLANE, VMEM_LIMIT_BYTES, compiler_params

#: largest input tile (bytes): double-buffered it takes half the VMEM limit
MAX_TILE_BYTES = VMEM_LIMIT_BYTES // 4


def tile_rows(chunk_rows: int, itemsize: int = 4) -> int:
    """Rows per tile: the largest multiple of 8 that divides ``chunk_rows``
    and fits :data:`MAX_TILE_BYTES`."""
    if chunk_rows <= 0 or chunk_rows % SUBLANE:
        raise ValueError(f"chunk_rows={chunk_rows} is not a positive "
                         f"multiple of the {SUBLANE}-row sublane tile")
    cap = min(chunk_rows, MAX_TILE_BYTES // (LANE * itemsize))
    for t in range(cap - cap % SUBLANE, 0, -SUBLANE):
        if chunk_rows % t == 0:
            return t
    raise AssertionError("unreachable: SUBLANE divides chunk_rows")


def _membw_kernel(x_ref, o_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]
    o_ref[...] += jnp.sum(x.reshape(-1, SUBLANE, LANE), axis=0)[None]


def membw(x: jax.Array, *, n_chunks: int, n_iters: int,
          interpret: bool = False) -> jax.Array:
    """x: [n_chunks * chunk_rows, 128] f32. Returns per-iteration chunk sums
    [n_iters, 128]; iteration i reads chunk (i % n_chunks)."""
    rows = x.shape[0]
    if x.ndim != 2 or x.shape[1] != LANE or rows % n_chunks:
        raise ValueError(f"x {x.shape} is not [n_chunks * chunk_rows, "
                         f"{LANE}] for n_chunks={n_chunks}")
    chunk_rows = rows // n_chunks
    tr = tile_rows(chunk_rows, x.dtype.itemsize)
    n_tiles = chunk_rows // tr
    out = pl.pallas_call(
        _membw_kernel,
        grid=(n_iters, n_tiles),
        in_specs=[pl.BlockSpec(
            (tr, LANE), lambda i, t: ((i % n_chunks) * n_tiles + t, 0))],
        out_specs=pl.BlockSpec((1, SUBLANE, LANE), lambda i, t: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_iters, SUBLANE, LANE), x.dtype),
        compiler_params=compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x)
    return jnp.sum(out, axis=1)


def membw_bytes(chunk_bytes: int, n_iters: int) -> int:
    return chunk_bytes * n_iters
