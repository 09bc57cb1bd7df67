"""Measurement harness — time surviving candidates across a frequency sweep.

Two interchangeable backends produce the same :class:`Measurement` record
(a ``(candidates, freqs)`` grid of step time and power):

* :class:`SimulatedBackend` — a deterministic timer backed by
  :class:`~repro.power.surface.TransferSurface`: each candidate's analytic
  :class:`~repro.core.power_model.StepProfile` (from
  :meth:`KernelSpace.profile`) is pushed through the chip's transfer
  functions in ONE batched ``(profiles, freqs)`` pass. Hermetic — no
  hardware, no clocks, no RNG — so CI can pin exact outputs, and
  bit-for-bit with the scalar :meth:`measure_one` path per the surface
  parity contract.
* :class:`WallClockBackend` — times the kernel compiled for the TPU (best
  of ``repeats``, after a warmup compile+run; never the Pallas
  interpreter, so it needs a TPU) and anchors the analytic
  profile to the observed wall clock: the roofline terms are rescaled so
  ``step_time(profile, 1.0)`` equals the measured time, then the
  frequency/power response comes from the same transfer surface. On a
  machine with a DVFS actuator, pass ``actuator``/``power_sensor``
  callables to measure the response directly instead of modeling it.

Both stamp ``Measurement.source`` so downstream calibration artifacts
(:mod:`repro.tuning.calibrate`) record their provenance.
"""
from __future__ import annotations

import time as _time
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.hardware import ChipSpec, TPU_V5E
from repro.core.power_model import ChipModel, StepProfile
from repro.tuning.space import Candidate, Config, KernelSpace, PerfParams

#: Default frequency sweep: the chip's 11-point DVFS grid (matches the
#: paper's governor sweep in ``repro.core.governor``).
DEFAULT_N_FREQS = 11


def default_freq_fracs(chip: ChipModel, n_freqs: int = DEFAULT_N_FREQS
                       ) -> np.ndarray:
    return np.asarray(chip.freq_grid(n_freqs), dtype=np.float64)


@dataclass(eq=False)
class Measurement:
    """A ``(candidates, freqs)`` grid of measured/simulated step behavior.

    ``time_s`` and ``power_w`` are ``(N, F)`` float64 arrays over the
    ``candidates`` batch and the ``freq_fracs`` sweep; ``energy_j`` is
    their product. ``source`` records which backend produced the grid
    (``"simulated:<chip>"`` / ``"wallclock:<chip>"``).
    """

    kernel: str
    chip: ChipSpec
    source: str
    candidates: Tuple[Candidate, ...]
    freq_fracs: np.ndarray              # (F,)
    time_s: np.ndarray                  # (N, F)
    power_w: np.ndarray                 # (N, F)
    validation_err: Optional[Tuple[float, ...]] = None

    def __post_init__(self):
        self.freq_fracs = np.asarray(self.freq_fracs, dtype=np.float64)
        self.time_s = np.asarray(self.time_s, dtype=np.float64)
        self.power_w = np.asarray(self.power_w, dtype=np.float64)
        n, f = len(self.candidates), self.freq_fracs.shape[0]
        if self.time_s.shape != (n, f) or self.power_w.shape != (n, f):
            raise ValueError(
                f"measurement grids must be ({n}, {f}); got time_s "
                f"{self.time_s.shape}, power_w {self.power_w.shape}")

    @property
    def configs(self) -> Tuple[Config, ...]:
        return tuple(c.config for c in self.candidates)

    @property
    def energy_j(self) -> np.ndarray:
        return self.time_s * self.power_w

    @property
    def shape(self) -> Tuple[int, int]:
        return (len(self.candidates), int(self.freq_fracs.shape[0]))

    def nominal_column(self) -> int:
        """Index of the sweep column closest to nominal frequency."""
        return int(np.argmin(np.abs(self.freq_fracs - 1.0)))

    def __repr__(self) -> str:
        n, f = self.shape
        return (f"Measurement({self.kernel!r}, {n} candidates x {f} freqs, "
                f"source={self.source!r})")


class SimulatedBackend:
    """Deterministic transfer-surface timer (the hermetic CI backend).

    The whole ``(candidates, freqs)`` grid is one batched surface pass
    over the candidates' analytic profiles. Bit-for-bit with the scalar
    path: ``measure_one(space, c, f)`` equals the grid cell because the
    surface's scalar fast path and array path share their formulas.
    """

    name = "simulated"

    def __init__(self, chip: "ChipSpec | str | ChipModel" = TPU_V5E,
                 perf: Optional[PerfParams] = None):
        self.chip = ChipModel(chip)
        self.perf = perf if perf is not None else PerfParams()

    def __repr__(self) -> str:
        return f"SimulatedBackend({self.chip.spec.name!r}, perf={self.perf})"

    def profiles(self, space: KernelSpace,
                 candidates: Sequence[Candidate]) -> List[StepProfile]:
        return [space.profile(c, self.chip, self.perf) for c in candidates]

    def measure(self, space: KernelSpace,
                candidates: Optional[Sequence[Candidate]] = None,
                freq_fracs: Optional[Sequence[float]] = None,
                validate: bool = False) -> Measurement:
        from repro.power.surface import ProfileArray
        if candidates is None:
            candidates = space.candidates()
        candidates = tuple(candidates)
        if not candidates:
            raise ValueError(
                f"no candidates to measure for {space.kernel!r} "
                f"(all pruned?)")
        fr = (default_freq_fracs(self.chip) if freq_fracs is None
              else np.asarray(freq_fracs, dtype=np.float64))
        errs = tuple(space.validate(c) for c in candidates) \
            if validate else None
        surf = self.chip.surface()
        pa = ProfileArray.from_profiles(
            self.profiles(space, candidates)).expand()      # (N, 1)
        t = np.asarray(surf.step_time(pa, fr))              # (N, F)
        p = np.asarray(surf.power_w(pa, fr))
        return Measurement(kernel=space.kernel, chip=self.chip.spec,
                           source=f"{self.name}:{self.chip.spec.name}",
                           candidates=candidates, freq_fracs=fr,
                           time_s=t, power_w=p, validation_err=errs)

    def measure_one(self, space: KernelSpace, candidate: Candidate,
                    freq_frac: float = 1.0) -> Tuple[float, float]:
        """Scalar ``(time_s, power_w)`` of one cell — bit-for-bit the
        corresponding :meth:`measure` grid entry."""
        prof = space.profile(candidate, self.chip, self.perf)
        return (self.chip.step_time(prof, freq_frac),
                self.chip.power_w(prof, freq_frac))


class WallClockBackend(SimulatedBackend):
    """Times the real kernel and anchors the model to the wall clock.

    Each candidate runs ``repeats`` times after a warmup (compile +
    execute) and the minimum wall time is kept. The candidate's analytic
    profile is then rescaled uniformly so ``step_time(profile, 1.0)``
    reproduces the measurement, and the frequency/power response is read
    off the transfer surface — the model supplies what this machine
    cannot actuate. To measure the response directly on hardware with
    DVFS control, pass ``actuator(freq_frac)`` (called before each
    column's timings) and ``power_sensor()`` (sampled around each run).
    """

    name = "wallclock"

    def __init__(self, chip: "ChipSpec | str | ChipModel" = TPU_V5E,
                 perf: Optional[PerfParams] = None, repeats: int = 3,
                 actuator: Optional[Callable[[float], None]] = None,
                 power_sensor: Optional[Callable[[], float]] = None,
                 timer: Callable[[], float] = _time.perf_counter):
        super().__init__(chip, perf)
        if repeats < 1:
            raise ValueError(f"repeats must be >= 1, got {repeats}")
        self.repeats = int(repeats)
        self.actuator = actuator
        self.power_sensor = power_sensor
        self.timer = timer

    def _time_candidate(self, space: KernelSpace,
                        candidate: Candidate) -> float:
        import jax
        fn, args = space.program(candidate, interpret=False)
        args = jax.device_put(args)                 # time the kernel alone
        jax.block_until_ready(fn(*args))            # warmup: compile + run
        best = float("inf")
        for _ in range(self.repeats):
            t0 = self.timer()
            jax.block_until_ready(fn(*args))
            best = min(best, self.timer() - t0)
        return best

    def anchored_profile(self, space: KernelSpace, candidate: Candidate,
                         wall_s: float) -> StepProfile:
        """The analytic profile scaled uniformly so its nominal step time
        equals the wall-clock measurement (shape from the model, scale
        from the machine)."""
        model_prof = space.profile(candidate, self.chip, self.perf)
        scale = wall_s / max(model_prof.total_s, 1e-12)
        return StepProfile(compute_s=model_prof.compute_s * scale,
                           memory_s=model_prof.memory_s * scale,
                           collective_s=model_prof.collective_s * scale)

    def measure(self, space: KernelSpace,
                candidates: Optional[Sequence[Candidate]] = None,
                freq_fracs: Optional[Sequence[float]] = None,
                validate: bool = False) -> Measurement:
        from repro.power.surface import ProfileArray
        if candidates is None:
            candidates = space.candidates()
        candidates = tuple(candidates)
        if not candidates:
            raise ValueError(
                f"no candidates to measure for {space.kernel!r} "
                f"(all pruned?)")
        fr = (default_freq_fracs(self.chip) if freq_fracs is None
              else np.asarray(freq_fracs, dtype=np.float64))
        errs = tuple(space.validate(c) for c in candidates) \
            if validate else None
        surf = self.chip.surface()
        if self.actuator is not None and self.power_sensor is not None:
            # direct hardware response: actuate each frequency column
            t = np.empty((len(candidates), fr.shape[0]))
            p = np.empty_like(t)
            for j, f in enumerate(fr):
                self.actuator(float(f))
                for i, c in enumerate(candidates):
                    t[i, j] = self._time_candidate(space, c)
                    p[i, j] = float(self.power_sensor())
        else:
            profs = [self.anchored_profile(space, c,
                                           self._time_candidate(space, c))
                     for c in candidates]
            pa = ProfileArray.from_profiles(profs).expand()
            t = np.asarray(surf.step_time(pa, fr))
            p = np.asarray(surf.power_w(pa, fr))
        return Measurement(kernel=space.kernel, chip=self.chip.spec,
                           source=f"{self.name}:{self.chip.spec.name}",
                           candidates=candidates, freq_fracs=fr,
                           time_s=t, power_w=p, validation_err=errs)
