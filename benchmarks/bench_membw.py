"""Paper Fig. 6 — memory-subsystem probe: bandwidth/runtime vs chunk size
under frequency caps. Chunks below the VMEM boundary are clock-sensitive;
chunks streaming from HBM are not (the paper's central mechanism)."""
import time
from typing import List, Tuple

import jax
import jax.numpy as jnp

from repro.power import ChipModel, StepProfile, TPU_V5E

CHIP = ChipModel(TPU_V5E)
from repro.kernels import ops


def run(verbose: bool = False) -> List[Tuple[str, float, str]]:
    rows: List[Tuple[str, float, str]] = []
    # validated kernel execution (small, CPU-interpret)
    x = jnp.ones((8 * 64, 128), jnp.float32)
    t0 = time.perf_counter()
    out = ops.membw_op(x, n_chunks=8, n_iters=16, interpret=True)
    out.block_until_ready()
    us = (time.perf_counter() - t0) * 1e6
    rows.append(("membw_kernel_16iters", us, f"checksum={float(out.sum()):.1f}"))

    if verbose:
        print("\n# Fig. 6 analogue (TPU v5e): chunk size vs freq sensitivity")
        print("chunk_bytes,regime,runtime_ratio_700MHz")
    for chunk_bytes in [384 << 10, 3 << 20, 24 << 20, 96 << 20, 384 << 20,
                        1536 << 20]:
        vmem_resident = chunk_bytes <= TPU_V5E.vmem_bytes
        # VMEM-resident: effective bandwidth scales with clock (compute-fed);
        # HBM-resident: bandwidth pinned by HBM.
        reads_s = chunk_bytes / TPU_V5E.hbm_bw
        prof = (StepProfile(compute_s=reads_s, memory_s=reads_s * 0.05)
                if vmem_resident
                else StepProfile(compute_s=reads_s * 0.1,
                                 memory_s=reads_s))
        ratio = CHIP.step_time(prof, 700 / 1700) / CHIP.step_time(prof, 1.0)
        regime = "vmem" if vmem_resident else "hbm"
        if verbose:
            print(f"{chunk_bytes},{regime},{ratio:.2f}")
        rows.append((f"membw_chunk_{chunk_bytes >> 20}mb", 0.0,
                     f"regime={regime};slowdown_700mhz={ratio:.2f}"))
    return rows


if __name__ == "__main__":
    for r in run(verbose=True):
        print(",".join(str(x) for x in r))
