"""What every Pallas kernel here shares about the TPU it compiles for.

``VMEM_LIMIT_BYTES`` is the scoped VMEM limit each kernel is compiled
with, passed explicitly through :func:`compiler_params` so the limit the
Mosaic compiler enforces is the one :mod:`repro.tuning.space` prunes
against. It is the compiler's default on a TPU v5e, well under the chip's
128 MiB of physical VMEM (``ChipSpec.vmem_bytes``): a block that needs
more fails to compile ("Ran out of memory in memory space vmem").
"""
from __future__ import annotations

from jax.experimental.pallas import tpu as pltpu

#: lane width: the last dim of every block is a multiple of this
LANE = 128
#: f32 sublane tile: the second-to-last dim of an f32 block is a multiple
SUBLANE = 8
#: scoped VMEM limit (bytes) every kernel is compiled with
VMEM_LIMIT_BYTES = 16 * 1024 * 1024


def compiler_params(**kw) -> pltpu.CompilerParams:
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES, **kw)
