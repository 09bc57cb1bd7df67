"""Per-kernel configuration spaces — enumerate, prune, validate.

The AttentionEngine shape (template + roller policy): each in-tree Pallas
kernel gets a :class:`KernelSpace` that (1) enumerates its tiling knobs,
(2) prunes candidates that can't run well on the target chip *before*
anything is timed — TPU tiling alignment (f32 sublane tile is ``(8, 128)``,
the MXU systolic array is ``128 x 128``), grid divisibility, and the
per-grid-step VMEM footprint (pipelined blocks counted twice, as Pallas
double-buffers them, plus the kernel body's block-sized values) against
the scoped VMEM limit the kernels compile with,
:data:`repro.kernels.tpu.VMEM_LIMIT_BYTES` — and (3) validates every
surviving candidate against the pure-jnp oracles in
:mod:`repro.kernels.ref` in interpret mode before it is allowed into the
measurement harness.

Validation contract: the VAI and membw spaces draw small *integer-valued*
float32 inputs, so every product and partial sum is exactly representable
and the Pallas output must equal the oracle **bit-for-bit**
(``max_abs_err == 0.0``). Flash attention's blocked online softmax
reassociates the reduction, so bit-equality across block shapes is
unattainable by construction; its parity gate is a pinned tight tolerance
instead (the same contract `tests/test_kernels.py` holds the kernel to).

Each space also carries the *analytic* cost of a candidate —
:class:`Candidate` records the pass's flops, its modeled HBM traffic
(config-dependent: e.g. flash attention re-reads K/V once per q-block),
the per-grid-step VMEM footprint and the grid size — and renders it as a
roofline :class:`~repro.core.power_model.StepProfile` under a
:class:`PerfParams` efficiency model. :class:`PerfParams.ideal` makes the
rendering collapse to the bare roofline (bit-for-bit
``ChipModel.vai_profile`` for the VAI space), which is how
``repro.core.vai.run_sweep`` re-seats on this layer without moving a float.
"""
from __future__ import annotations

import functools
import operator
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.hardware import ChipSpec, TPU_V5E
from repro.core.power_model import ChipModel, StepProfile
from repro.kernels.membw import tile_rows
from repro.kernels.tpu import LANE, SUBLANE, VMEM_LIMIT_BYTES

#: MXU systolic-array edge — matmul block shapes should be multiples
MXU = 128

Config = Tuple[Tuple[str, int], ...]


@dataclass(frozen=True)
class PerfParams:
    """Config-dependent efficiency knobs of the simulated timer.

    ``launch_overhead_s`` is added to the compute roofline term once per
    grid step (small blocks pay more steps); ``pipeline_rows`` models the
    compute-unit ramp — a block of ``r`` rows runs at efficiency
    ``r / (r + pipeline_rows)``, so tiny tiles never reach peak.
    :meth:`ideal` zeroes both, collapsing :meth:`KernelSpace.profile` to
    the bare roofline.
    """

    launch_overhead_s: float = 2e-6
    pipeline_rows: int = 32

    @classmethod
    def ideal(cls) -> "PerfParams":
        return cls(launch_overhead_s=0.0, pipeline_rows=0)

    def efficiency(self, *block_rows: int) -> float:
        eff = 1.0
        for r in block_rows:
            if self.pipeline_rows:
                eff *= r / (r + self.pipeline_rows)
        return eff


@dataclass(frozen=True)
class Candidate:
    """One enumerated kernel configuration plus its analytic cost."""

    kernel: str
    config: Config                 # sorted (knob, value) pairs — hashable
    flops: float                   # useful flops of one pass
    hbm_bytes: float               # modeled HBM traffic of one pass
    vmem_bytes: int                # per-grid-step resident footprint
    grid_steps: int

    def get(self, knob: str) -> int:
        for k, v in self.config:
            if k == knob:
                return v
        raise KeyError(f"{self.kernel} candidate has no knob {knob!r}; "
                       f"knobs: {[k for k, _ in self.config]}")

    @property
    def config_dict(self) -> Dict[str, int]:
        return dict(self.config)

    @property
    def label(self) -> str:
        return ",".join(f"{k}={v}" for k, v in self.config)


class ValidationError(AssertionError):
    """A candidate's interpret-mode output diverged from the oracle."""


def _check_positive_int(name: str, value) -> int:
    try:
        value = operator.index(value)
    except TypeError:
        raise ValueError(f"{name} must be an int, got {value!r}") from None
    if value <= 0:
        raise ValueError(f"{name} must be positive, got {value}")
    return value


class KernelSpace:
    """Base class: enumerate -> prune -> validate for one kernel.

    Subclasses define ``kernel``, ``_raw_configs()`` (the unpruned knob
    lattice, in enumeration order), ``_prune(config) -> Optional[str]``
    (a rejection reason, or None to keep), ``_candidate(config)`` (attach
    the analytic cost), ``program(candidate, interpret)`` (the jitted
    kernel and its inputs), ``_reference(candidate)`` (the jnp oracle) and
    ``profile(candidate, model, perf)`` (the roofline rendering).
    """

    kernel: str = ""
    #: bit-for-bit oracle parity (integer-valued inputs); False = the
    #: space's pinned ``tol`` applies instead
    exact: bool = True
    tol: float = 0.0

    def __init__(self, chip: ChipSpec = TPU_V5E,
                 vmem_limit_bytes: Optional[int] = None):
        self.chip = ChipModel(chip).spec
        self.vmem_limit_bytes = int(
            VMEM_LIMIT_BYTES if vmem_limit_bytes is None
            else vmem_limit_bytes)
        self._kept: Optional[List[Candidate]] = None
        self._pruned: Optional[List[Tuple[Config, str]]] = None

    # ------------------------------------------------------------ enumerate
    def enumerate_all(self) -> Tuple[List[Candidate],
                                     List[Tuple[Config, str]]]:
        """(kept candidates, pruned ``(config, reason)`` pairs), cached."""
        if self._kept is None:
            kept, pruned = [], []
            for config in self._raw_configs():
                reason = self._prune(config)
                if reason is None:
                    kept.append(self._candidate(config))
                else:
                    pruned.append((config, reason))
            self._kept, self._pruned = kept, pruned
        return list(self._kept), list(self._pruned)

    def candidates(self) -> List[Candidate]:
        return self.enumerate_all()[0]

    # ------------------------------------------------------------- validate
    def validate(self, candidate: Candidate) -> float:
        """Run the candidate in interpret mode against the jnp oracle.

        Returns the max abs error (0.0 for the exact spaces); raises
        :class:`ValidationError` on divergence."""
        fn, args = self.program(candidate, interpret=True)
        out = np.asarray(fn(*args))
        want = np.asarray(self._reference(candidate))
        err = float(np.max(np.abs(out.astype(np.float64)
                                  - want.astype(np.float64)))) \
            if out.size else 0.0
        if self.exact:
            if not np.array_equal(out, want):
                raise ValidationError(
                    f"{self.kernel}[{candidate.label}] diverged bit-for-bit "
                    f"from kernels.ref (max abs err {err:.3g})")
        elif err > self.tol or not np.all(np.isfinite(out)):
            raise ValidationError(
                f"{self.kernel}[{candidate.label}] exceeded the oracle "
                f"tolerance {self.tol:g} (max abs err {err:.3g})")
        return err

    def validate_all(self) -> Dict[Config, float]:
        return {c.config: self.validate(c) for c in self.candidates()}

    # ------------------------------------------------- subclass obligations
    def _raw_configs(self) -> Sequence[Config]:
        raise NotImplementedError

    def _prune(self, config: Config) -> Optional[str]:
        raise NotImplementedError

    def _candidate(self, config: Config) -> Candidate:
        raise NotImplementedError

    def program(self, candidate: Candidate, interpret: bool
                ) -> Tuple[Callable, Tuple]:
        """``(fn, args)``: the candidate's kernel as a jitted function and
        the space's inputs. ``interpret=True`` runs the Pallas interpreter
        (validation, on any backend); ``False`` compiles the kernel for the
        TPU, as :class:`~repro.tuning.harness.WallClockBackend` times it."""
        raise NotImplementedError

    def _reference(self, candidate: Candidate):
        raise NotImplementedError

    def profile(self, candidate: Candidate, model: ChipModel,
                perf: PerfParams) -> StepProfile:
        raise NotImplementedError

    def __repr__(self) -> str:
        kept, pruned = self.enumerate_all()
        return (f"{type(self).__name__}(chip={self.chip.name!r}, "
                f"{len(kept)} candidates, {len(pruned)} pruned)")


# ---------------------------------------------------------------------------
# VAI — block_rows x loopsize over the [rows, 128] VPU tile walk
# ---------------------------------------------------------------------------
class VaiSpace(KernelSpace):
    """:func:`repro.kernels.vai.vai` — knobs ``block_rows`` (VMEM tile
    height) and ``loopsize`` (the paper's arithmetic-intensity dial;
    ``AI = 2 * loopsize / 16`` flops/byte in f32).

    ``loopsizes`` is part of the lattice on purpose: the VAI benchmark's
    whole point is walking the roofline, so the joint tuner can ask where
    on the (AI, tile, frequency) grid each objective's optimum sits.
    Duplicate loopsizes are preserved in enumeration order so drivers
    sweeping a fixed intensity list (``repro.core.vai.run_sweep``) can zip
    candidates back to their sweep points.
    """

    kernel = "vai"
    exact = True

    def __init__(self, n_elems: int = 1 << 18,
                 loopsizes: Sequence[int] = (8,),
                 block_rows_options: Sequence[int] = (128, 256, 512, 1024),
                 chip: ChipSpec = TPU_V5E,
                 vmem_limit_bytes: Optional[int] = None, seed: int = 0):
        super().__init__(chip, vmem_limit_bytes)
        self.n_elems = _check_positive_int("n_elems", n_elems)
        self.rows = max(self.n_elems // LANE, LANE)
        self.loopsizes = tuple(int(x) for x in loopsizes)
        self.block_rows_options = tuple(int(b) for b in block_rows_options)
        self.seed = seed
        self._inputs = None

    def _raw_configs(self):
        return [(("block_rows", br), ("loopsize", L))
                for L in self.loopsizes for br in self.block_rows_options]

    def _prune(self, config: Config) -> Optional[str]:
        cfg = dict(config)
        br, L = cfg["block_rows"], cfg["loopsize"]
        if L < 0:
            return "negative-loopsize"
        if br <= 0 or br % SUBLANE:
            return f"sublane-misaligned (block_rows % {SUBLANE} != 0)"
        if self.rows % min(br, self.rows):
            return f"indivisible ({self.rows} rows % {br})"
        footprint = self._footprint(br)
        if footprint > self.vmem_limit_bytes:
            return (f"vmem-overflow ({footprint} B > "
                    f"{self.vmem_limit_bytes} B)")
        return None

    def _footprint(self, block_rows: int) -> int:
        # a, b, c blocks in + the written block out, each double-buffered,
        # and the body's x, y, z and accumulator values
        return (2 * 4 + 4) * min(block_rows, self.rows) * LANE * 4

    def _candidate(self, config: Config) -> Candidate:
        from repro.kernels.vai import vai_flops_bytes
        cfg = dict(config)
        br = min(cfg["block_rows"], self.rows)
        flops, byts = vai_flops_bytes(self.n_elems, cfg["loopsize"])
        return Candidate(kernel=self.kernel, config=config,
                         flops=float(flops), hbm_bytes=float(byts),
                         vmem_bytes=self._footprint(br),
                         grid_steps=self.rows // br)

    # integer-valued f32 inputs: every x*y + acc is exact, so the kernel
    # must match the oracle bit-for-bit at any loopsize <= ~2^19
    def _get_inputs(self):
        if self._inputs is None:
            rng = np.random.default_rng(self.seed)
            shape = (self.rows, LANE)
            self._inputs = tuple(
                rng.integers(0, 5, size=shape).astype(np.float32)
                for _ in range(3))
        return self._inputs

    def program(self, candidate: Candidate, interpret: bool):
        from repro.kernels import ops
        return (functools.partial(ops.vai_op,
                                  loopsize=candidate.get("loopsize"),
                                  block_rows=candidate.get("block_rows"),
                                  interpret=interpret),
                self._get_inputs())

    def _reference(self, candidate: Candidate):
        from repro.kernels import ref
        a, b, c = self._get_inputs()
        return ref.vai_ref(a, b, c, candidate.get("loopsize"))

    def profile(self, candidate: Candidate, model: ChipModel,
                perf: PerfParams) -> StepProfile:
        # VAI runs on the VPU: vector peak ~ MXU peak / 8 (the same unit
        # ChipModel.vai_profile uses — PerfParams.ideal() reproduces it
        # bit-for-bit)
        vector_peak = model.spec.peak_flops / 8.0
        eff = perf.efficiency(min(candidate.get("block_rows"), self.rows))
        compute_s = (candidate.flops / vector_peak / eff
                     + candidate.grid_steps * perf.launch_overhead_s)
        return StepProfile(compute_s=compute_s,
                           memory_s=candidate.hbm_bytes / model.spec.hbm_bw)


# ---------------------------------------------------------------------------
# membw — n_chunks over the VMEM-vs-HBM re-read probe
# ---------------------------------------------------------------------------
class MembwSpace(KernelSpace):
    """:func:`repro.kernels.membw.membw` — knob ``n_chunks`` (the working
    set is ``n_chunks * chunk_rows`` rows; iteration ``i`` re-reads chunk
    ``i % n_chunks``, so a working set under the VMEM boundary streams
    from fast memory after the cold pass while a larger one re-streams
    every iteration from HBM — the paper's Fig. 6 boundary)."""

    kernel = "membw"
    exact = True

    def __init__(self, total_rows: int = 1 << 14, n_iters: int = 64,
                 n_chunks_options: Sequence[int] = (1, 2, 4, 8, 16, 32),
                 chip: ChipSpec = TPU_V5E,
                 vmem_limit_bytes: Optional[int] = None, seed: int = 0):
        super().__init__(chip, vmem_limit_bytes)
        self.total_rows = _check_positive_int("total_rows", total_rows)
        self.n_iters = _check_positive_int("n_iters", n_iters)
        self.n_chunks_options = tuple(int(n) for n in n_chunks_options)
        self.seed = seed
        self._x = None

    def _raw_configs(self):
        return [(("n_chunks", n),) for n in self.n_chunks_options]

    def _prune(self, config: Config) -> Optional[str]:
        n = dict(config)["n_chunks"]
        if n <= 0:
            return "non-positive n_chunks"
        if self.total_rows % n:
            return f"indivisible ({self.total_rows} rows % {n} chunks)"
        chunk_rows = self.total_rows // n
        if chunk_rows % SUBLANE:
            return f"sublane-misaligned (chunk_rows % {SUBLANE} != 0)"
        footprint = self._footprint(chunk_rows)
        if footprint > self.vmem_limit_bytes:
            return (f"vmem-overflow ({footprint} B > "
                    f"{self.vmem_limit_bytes} B)")
        return None

    @staticmethod
    def _footprint(chunk_rows: int) -> int:
        # the kernel reads a chunk tile by tile: one tile in and the
        # (8, 128) accumulator out, each double-buffered, and the loaded tile
        return (3 * tile_rows(chunk_rows) + 2 * SUBLANE) * LANE * 4

    def _candidate(self, config: Config) -> Candidate:
        n = dict(config)["n_chunks"]
        chunk_rows = self.total_rows // n
        chunk_bytes = chunk_rows * LANE * 4
        working_set = n * chunk_bytes
        # cold pass reads the working set once; re-reads hit VMEM/cache
        # only if the whole rotation fits under the chip's physical VMEM.
        # (On a TPU a block is re-fetched whenever its index changes, so
        # this cache model is an open question for the chip benchmark.)
        if working_set <= self.chip.vmem_bytes:
            traffic = float(working_set)
        else:
            traffic = float(chunk_bytes) * self.n_iters
        return Candidate(kernel=self.kernel, config=config,
                         flops=float(chunk_rows * LANE * self.n_iters),
                         hbm_bytes=traffic,
                         vmem_bytes=self._footprint(chunk_rows),
                         grid_steps=self.n_iters)

    def _get_x(self):
        if self._x is None:
            rng = np.random.default_rng(self.seed)
            self._x = rng.integers(0, 4, size=(self.total_rows, LANE)
                                   ).astype(np.float32)
        return self._x

    def program(self, candidate: Candidate, interpret: bool):
        from repro.kernels import ops
        return (functools.partial(ops.membw_op,
                                  n_chunks=candidate.get("n_chunks"),
                                  n_iters=self.n_iters, interpret=interpret),
                (self._get_x(),))

    def _reference(self, candidate: Candidate):
        from repro.kernels import ref
        return ref.membw_ref(self._get_x(), candidate.get("n_chunks"),
                             self.n_iters)

    def profile(self, candidate: Candidate, model: ChipModel,
                perf: PerfParams) -> StepProfile:
        vector_peak = model.spec.peak_flops / 8.0
        chunk_rows = self.total_rows // candidate.get("n_chunks")
        eff = perf.efficiency(chunk_rows)
        compute_s = (candidate.flops / vector_peak / eff
                     + candidate.grid_steps * perf.launch_overhead_s)
        return StepProfile(compute_s=compute_s,
                           memory_s=candidate.hbm_bytes / model.spec.hbm_bw)


# ---------------------------------------------------------------------------
# flash attention — block_q x block_k over the MXU online-softmax kernel
# ---------------------------------------------------------------------------
class FlashAttentionSpace(KernelSpace):
    """:func:`repro.kernels.flash_attention.flash_attention` — knobs
    ``block_q`` / ``block_k``. MXU alignment prunes blocks that aren't
    multiples of the 128-wide systolic array; the VMEM check covers the
    q/k/v/o blocks plus the (m, l, acc) scratch accumulators.

    The modeled HBM traffic is config-dependent: the q block is resident
    across the (sequential, innermost) kv axis, so K and V are re-fetched
    once per *q block* — larger ``block_q`` means fewer K/V re-reads,
    which is exactly the traffic/occupancy trade the tuner explores.
    """

    kernel = "flash_attention"
    exact = False
    tol = 2e-5                     # f32 contract of tests/test_kernels.py

    def __init__(self, batch_heads: int = 4, seq_q: int = 1024,
                 seq_kv: Optional[int] = None, head_dim: int = 128,
                 value_dim: Optional[int] = None, causal: bool = True,
                 block_q_options: Sequence[int] = (128, 256, 512),
                 block_k_options: Sequence[int] = (128, 256, 512),
                 chip: ChipSpec = TPU_V5E,
                 vmem_limit_bytes: Optional[int] = None, seed: int = 0):
        super().__init__(chip, vmem_limit_bytes)
        self.batch_heads = _check_positive_int("batch_heads", batch_heads)
        self.seq_q = _check_positive_int("seq_q", seq_q)
        self.seq_kv = self.seq_q if seq_kv is None \
            else _check_positive_int("seq_kv", seq_kv)
        self.head_dim = _check_positive_int("head_dim", head_dim)
        self.value_dim = self.head_dim if value_dim is None \
            else _check_positive_int("value_dim", value_dim)
        self.causal = bool(causal)
        self.block_q_options = tuple(int(b) for b in block_q_options)
        self.block_k_options = tuple(int(b) for b in block_k_options)
        self.seed = seed
        self._qkv = None

    def _raw_configs(self):
        return [(("block_k", bk), ("block_q", bq))
                for bq in self.block_q_options
                for bk in self.block_k_options]

    def _footprint(self, bq: int, bk: int) -> int:
        d, dv = self.head_dim, self.value_dim
        blocks = bq * d + bk * d + bk * dv + bq * dv    # q, k, v, o
        scratch = bq + bq + bq * dv                     # m, l, acc
        body = 2 * bq * bk + 2 * bq * dv                # s, p; p @ v, acc
        return 4 * (2 * blocks + scratch + body)

    def _prune(self, config: Config) -> Optional[str]:
        cfg = dict(config)
        bq, bk = cfg["block_q"], cfg["block_k"]
        if bq <= 0 or bq % MXU or bk <= 0 or bk % MXU:
            return f"mxu-misaligned (blocks must be multiples of {MXU})"
        if self.seq_q % bq:
            return f"indivisible (seq_q {self.seq_q} % block_q {bq})"
        if self.seq_kv % bk:
            return f"indivisible (seq_kv {self.seq_kv} % block_k {bk})"
        footprint = self._footprint(bq, bk)
        if footprint > self.vmem_limit_bytes:
            return (f"vmem-overflow ({footprint} B > "
                    f"{self.vmem_limit_bytes} B)")
        return None

    def _candidate(self, config: Config) -> Candidate:
        cfg = dict(config)
        bq, bk = cfg["block_q"], cfg["block_k"]
        bh, sq, skv = self.batch_heads, self.seq_q, self.seq_kv
        d, dv = self.head_dim, self.value_dim
        nq, nk = sq // bq, skv // bk
        # the kernel evaluates every (qi, kj) block even under the causal
        # mask (masked, not skipped), so flops are the full rectangle
        flops = 2.0 * bh * sq * skv * (d + dv)
        # q + o move once; k/v are re-fetched once per q block
        traffic = 4.0 * bh * (sq * d + sq * dv + nq * skv * (d + dv))
        return Candidate(kernel=self.kernel, config=config, flops=flops,
                         hbm_bytes=traffic,
                         vmem_bytes=self._footprint(bq, bk),
                         grid_steps=bh * nq * nk)

    def _get_qkv(self):
        if self._qkv is None:
            import jax
            import jax.numpy as jnp
            key = jax.random.PRNGKey(self.seed)
            self._qkv = (
                jax.random.normal(jax.random.fold_in(key, 0),
                                  (self.batch_heads, self.seq_q,
                                   self.head_dim), jnp.float32),
                jax.random.normal(jax.random.fold_in(key, 1),
                                  (self.batch_heads, self.seq_kv,
                                   self.head_dim), jnp.float32),
                jax.random.normal(jax.random.fold_in(key, 2),
                                  (self.batch_heads, self.seq_kv,
                                   self.value_dim), jnp.float32))
        return self._qkv

    def program(self, candidate: Candidate, interpret: bool):
        import jax
        from repro.kernels.flash_attention import flash_attention
        return (jax.jit(functools.partial(
                    flash_attention, causal=self.causal,
                    block_q=candidate.get("block_q"),
                    block_k=candidate.get("block_k"),
                    interpret=interpret)),
                self._get_qkv())

    def _reference(self, candidate: Candidate):
        from repro.kernels import ref
        q, k, v = self._get_qkv()
        return ref.attention_ref(q, k, v, causal=self.causal)

    def profile(self, candidate: Candidate, model: ChipModel,
                perf: PerfParams) -> StepProfile:
        cfg = candidate.config_dict
        eff = perf.efficiency(cfg["block_q"], cfg["block_k"])
        compute_s = (candidate.flops / model.spec.peak_flops / eff
                     + candidate.grid_steps * perf.launch_overhead_s)
        return StepProfile(compute_s=compute_s,
                           memory_s=candidate.hbm_bytes / model.spec.hbm_bw)
