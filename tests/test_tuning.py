"""repro.tuning — config spaces, harness determinism, calibration, tuner.

The contracts pinned here are the ISSUE-10 acceptance criteria: pruning
rules (alignment/divisibility/VMEM), interpret-mode parity of every
enumerated flash-attention config vs kernels.ref, simulated-timer
determinism, the ``"calibrated:*"`` resolver round-trip through a Study
cell, the bit-for-bit JSON cache, and objective-aware selection where
energy picks a different cell than pure step time.
"""
import numpy as np
import pytest

from repro.core.hardware import TPU_V5E
from repro.core.power_model import ChipModel
from repro.tuning import (FlashAttentionSpace, MembwSpace, PerfParams,
                          SimulatedBackend, VaiSpace, calibrate,
                          calibrated_tables, load_calibration,
                          register_calibration, save_calibration, tune)


# --------------------------------------------------------------- enumeration
class TestPruning:
    def test_vai_prunes_misaligned_and_indivisible(self):
        space = VaiSpace(n_elems=1 << 16,            # 512 rows
                         loopsizes=(8,),
                         block_rows_options=(4, 100, 200, 128, 512))
        kept, pruned = space.enumerate_all()
        kept_br = {c.get("block_rows") for c in kept}
        reasons = {dict(cfg)["block_rows"]: why for cfg, why in pruned}
        assert kept_br == {128, 512}
        assert "sublane-misaligned" in reasons[4]
        assert "sublane-misaligned" in reasons[100]   # 100 % 8 != 0
        assert "indivisible" in reasons[200]

    def test_vai_clamped_block_rows_kept(self):
        space = VaiSpace(n_elems=1 << 16, loopsizes=(8,),
                         block_rows_options=(1024,))
        kept, pruned = space.enumerate_all()
        assert len(kept) == 1 and not pruned
        assert kept[0].grid_steps == 1

    def test_vai_vmem_overflow_pruned(self):
        space = VaiSpace(n_elems=1 << 20, loopsizes=(8,),
                         block_rows_options=(8192,),
                         vmem_limit_bytes=1 << 20)
        kept, pruned = space.enumerate_all()
        assert not kept
        assert "vmem-overflow" in pruned[0][1]

    def test_flash_attention_mxu_alignment_and_vmem(self):
        space = FlashAttentionSpace(
            batch_heads=1, seq_q=512, head_dim=128,
            block_q_options=(64, 128, 256, 384),
            block_k_options=(128,))
        kept, pruned = space.enumerate_all()
        assert {c.get("block_q") for c in kept} == {128, 256}
        reasons = {dict(cfg)["block_q"]: why for cfg, why in pruned}
        assert "mxu-misaligned" in reasons[64]
        assert "indivisible" in reasons[384]    # 512 % 384

        tight = FlashAttentionSpace(
            batch_heads=1, seq_q=512, head_dim=128,
            block_q_options=(256,), block_k_options=(256,),
            vmem_limit_bytes=256 * 1024)
        kept, pruned = tight.enumerate_all()
        assert not kept and "vmem-overflow" in pruned[0][1]

    def test_flash_attention_counts_visited_blocks(self):
        """Under ``causal`` the candidate's FLOPs and K/V traffic are those
        of the block pairs the kernel visits (on or below the diagonal:
        10 of 16 at 512 / 128), not the full square it used to mask."""
        kw = dict(batch_heads=2, seq_q=512, head_dim=64,
                  block_q_options=(128,), block_k_options=(128,))
        causal = FlashAttentionSpace(**kw).candidates()[0]
        full = FlashAttentionSpace(causal=False, **kw).candidates()[0]
        assert full.flops == 2.0 * 2 * 512 * 512 * 128
        assert causal.flops == full.flops * 10 / 16
        qo = 4.0 * 2 * 2 * 512 * 64
        assert causal.hbm_bytes - qo == (full.hbm_bytes - qo) * 10 / 16
        assert causal.grid_steps == full.grid_steps == 2 * 16

    def test_membw_chunk_rules(self):
        space = MembwSpace(total_rows=2048, n_iters=4,
                           n_chunks_options=(1, 3, 8, 2048))
        kept, pruned = space.enumerate_all()
        assert {c.get("n_chunks") for c in kept} == {1, 8}
        reasons = {dict(cfg)["n_chunks"]: why for cfg, why in pruned}
        assert "indivisible" in reasons[3]
        assert "sublane-misaligned" in reasons[2048]  # chunk_rows == 1

    def test_candidate_config_access(self):
        space = VaiSpace(n_elems=1 << 16, loopsizes=(8,),
                         block_rows_options=(128,))
        c = space.candidates()[0]
        assert c.get("block_rows") == 128 and c.get("loopsize") == 8
        assert c.config_dict == {"block_rows": 128, "loopsize": 8}
        with pytest.raises(KeyError):
            c.get("nope")


# ---------------------------------------------------------------- validation
class TestValidation:
    def test_vai_membw_bit_for_bit(self):
        vs = VaiSpace(n_elems=1 << 14, loopsizes=(0, 1, 8, 64),
                      block_rows_options=(64, 128))
        assert all(err == 0.0 for err in vs.validate_all().values())
        ms = MembwSpace(total_rows=1 << 11, n_iters=8,
                        n_chunks_options=(1, 2, 4, 8))
        assert all(err == 0.0 for err in ms.validate_all().values())

    def test_flash_attention_every_config_parity(self):
        """Interpret-mode parity of EVERY enumerated flash-attention
        config against kernels.ref (the online softmax reassociates, so
        the gate is the pinned f32 tolerance, not bit equality)."""
        space = FlashAttentionSpace(
            batch_heads=2, seq_q=512, head_dim=64,
            block_q_options=(128, 256, 512),
            block_k_options=(128, 256, 512))
        kept, pruned = space.enumerate_all()
        assert len(kept) == 9 and not pruned
        errs = space.validate_all()
        assert set(errs) == {c.config for c in kept}
        assert all(e <= space.tol for e in errs.values())

    def test_validation_error_raises(self):
        from repro.tuning import ValidationError
        space = VaiSpace(n_elems=1 << 14, loopsizes=(8,),
                         block_rows_options=(128,))
        cand = space.candidates()[0]
        orig = space._reference
        space._reference = lambda c: np.asarray(orig(c)) + 1.0
        with pytest.raises(ValidationError, match="bit-for-bit"):
            space.validate(cand)


# ------------------------------------------------------------------- harness
class TestHarness:
    def test_simulated_backend_deterministic(self):
        space = VaiSpace(n_elems=1 << 16, loopsizes=(0, 8, 256),
                         block_rows_options=(128, 256))
        m1 = SimulatedBackend(TPU_V5E).measure(space)
        m2 = SimulatedBackend(TPU_V5E).measure(
            VaiSpace(n_elems=1 << 16, loopsizes=(0, 8, 256),
                     block_rows_options=(128, 256)))
        assert np.array_equal(m1.time_s, m2.time_s)
        assert np.array_equal(m1.power_w, m2.power_w)
        assert m1.source == "simulated:tpu-v5e"

    def test_grid_matches_scalar_path_bit_for_bit(self):
        space = VaiSpace(n_elems=1 << 16, loopsizes=(0, 8, 256),
                         block_rows_options=(128, 512))
        backend = SimulatedBackend(TPU_V5E)
        meas = backend.measure(space)
        for i, cand in enumerate(meas.candidates):
            for j, f in enumerate(meas.freq_fracs):
                t, p = backend.measure_one(space, cand, float(f))
                assert meas.time_s[i, j] == t
                assert meas.power_w[i, j] == p

    def test_wallclock_times_the_compiled_kernel(self):
        """WallClockBackend asks the space for the compiled program
        (``interpret=False``); only validation asks for the interpreter.
        The CPU cannot compile a Mosaic kernel, so the spy hands back the
        interpreted program to let the timing loop run here."""
        from repro.tuning import WallClockBackend
        space = VaiSpace(n_elems=1 << 14, loopsizes=(8,),
                         block_rows_options=(128,))
        real, asked = space.program, []

        def spy(cand, interpret):
            asked.append(interpret)
            return real(cand, interpret=True)

        space.program = spy
        meas = WallClockBackend(TPU_V5E, repeats=2).measure(
            space, freq_fracs=[1.0], validate=True)
        assert asked == [True, False]
        assert meas.source == "wallclock:tpu-v5e"
        assert np.all(meas.time_s > 0)

    def test_ideal_perf_reproduces_vai_profile(self):
        """PerfParams.ideal() collapses the space's profile to
        ChipModel.vai_profile bit-for-bit — the run_sweep re-seat
        contract."""
        model = ChipModel(TPU_V5E)
        space = VaiSpace(n_elems=1 << 18, loopsizes=(0, 8, 64, 1024),
                         block_rows_options=(256,))
        for cand in space.candidates():
            got = space.profile(cand, model, PerfParams.ideal())
            want = model.vai_profile(space.n_elems, cand.get("loopsize"))
            assert got == want

    def test_nominal_column_and_energy(self):
        space = VaiSpace(n_elems=1 << 16, loopsizes=(8,),
                         block_rows_options=(128,))
        meas = SimulatedBackend(TPU_V5E).measure(space)
        j0 = meas.nominal_column()
        assert meas.freq_fracs[j0] == 1.0
        assert np.array_equal(meas.energy_j, meas.time_s * meas.power_w)


# --------------------------------------------------------------------- tuner
class TestTuner:
    def test_energy_differs_from_time(self):
        """A compute-heavy sweep: the energy-optimal (config, freq) cell
        must differ from the step-time-optimal one (lower clock wins on
        energy for compute-bound kernels)."""
        res = tune(VaiSpace(n_elems=1 << 16, loopsizes=(1024,),
                            block_rows_options=(128, 256, 512)),
                   validate=False)
        fast = res.best("time")
        green = res.best("energy")
        assert fast.index != green.index
        assert green.energy_j < fast.energy_j
        assert fast.time_s <= green.time_s

    def test_slowdown_budget_constrains(self):
        res = tune(VaiSpace(n_elems=1 << 16, loopsizes=(1024,),
                            block_rows_options=(256,)), validate=False)
        t_best = float(res.measurement.time_s.min())
        bounded = res.best("energy", slowdown_budget=0.1)
        assert bounded.time_s <= t_best * 1.1 * (1 + 1e-9)
        free = res.best("energy")
        assert free.energy_j <= bounded.energy_j

    def test_registry_objectives_and_errors(self):
        res = tune(VaiSpace(n_elems=1 << 16, loopsizes=(64,),
                            block_rows_options=(256,)), validate=False)
        for obj in ("edp", "ed2p", "perf_per_watt"):
            cell = res.best(obj)
            assert cell.objective == obj
        with pytest.raises(ValueError, match="tuning objective"):
            res.best("not-a-metric")

    def test_grid_argbest_mask_exhaustion(self):
        from repro.power.objectives import grid_argbest
        e = np.ones((2, 3))
        t = np.ones((2, 3))
        with pytest.raises(ValueError, match="admissible"):
            grid_argbest("energy", e, t, mask=np.zeros((2, 3), dtype=bool))
        i, j = grid_argbest("energy", e, t)
        assert (i, j) == (0, 0)


# --------------------------------------------------------------- calibration
class TestCalibration:
    def _measurement(self):
        space = VaiSpace(n_elems=1 << 16,
                         loopsizes=(0, 2, 8, 32, 128, 512),
                         block_rows_options=(128, 256))
        return SimulatedBackend(TPU_V5E).measure(space)

    def test_inversion_pins_nominal_time(self):
        meas = self._measurement()
        cal = calibrate(meas)
        surf = ChipModel(TPU_V5E).surface()
        j0 = meas.nominal_column()
        t_hat = np.asarray(surf.step_time(cal.profile_array(),
                                          float(meas.freq_fracs[j0])))
        np.testing.assert_allclose(t_hat, meas.time_s[:, j0], rtol=1e-12)
        assert cal.fit_rms_pct < 25.0           # whole-grid fit diagnostic

    def test_cache_round_trip_bit_for_bit(self, tmp_path):
        cal = calibrate(self._measurement())
        path = str(tmp_path / "cal.json")
        save_calibration(cal, path)
        cal2 = load_calibration(path)
        assert cal2.tables == cal.tables
        assert cal2.configs == cal.configs
        assert cal2.freq_fracs == cal.freq_fracs
        assert cal2.chip == cal.chip
        assert np.array_equal(cal2.profiles, cal.profiles)
        first = open(path, "rb").read()
        save_calibration(cal2, path)
        assert open(path, "rb").read() == first

    def test_schema_guard(self, tmp_path):
        path = tmp_path / "bad.json"
        path.write_text('{"schema": 99}')
        with pytest.raises(ValueError, match="schema"):
            load_calibration(str(path))

    def test_calibrated_tables_default_pipeline(self):
        for kernel in ("vai", "membw", "flash_attention"):
            tables = calibrated_tables(kernel)
            assert tables.kind == "freq"
            assert tables.source == f"calibrated:{kernel}:tpu-v5e"
            base = max(tables.vai)
            # base column normalises to ~100%; inferred profiles round-trip
            # through the surface so allow 1-ulp wobble
            assert tables.vai[base] == pytest.approx((100.0, 100.0, 100.0))
        with pytest.raises(ValueError, match="unknown kernel"):
            calibrated_tables("nope")

    def test_registered_calibration_wins(self, tmp_path):
        cal = calibrate(self._measurement(), kind="power")
        register_calibration(cal)
        assert calibrated_tables("vai", kind="power") is cal.tables

    def test_resolver_round_trip_through_study_cell(self):
        """resolve_tables("calibrated:vai") returns tuner-derived tables
        usable in a Study cell; the cell is bit-for-bit the same Study
        run with the tables passed explicitly."""
        from repro.power import Study, Workload
        from repro.power.scenarios import resolve_tables

        tables = resolve_tables("calibrated:vai")
        assert tables.source == "calibrated:vai:tpu-v5e"
        assert tables is calibrated_tables("vai")     # cached, not rebuilt

        wl = Workload.synthetic(50_000, seed=0)
        res = Study(workloads=[wl], chips=["tpu-v5e"], caps=[1300, 900],
                    tables="calibrated:vai").run()
        ref = Study(workloads=[wl], chips=["tpu-v5e"], caps=[1300, 900],
                    tables=tables).run()
        assert np.array_equal(res.savings_pct, ref.savings_pct)
        assert np.array_equal(res.dt_pct, ref.dt_pct)
        assert np.all(np.isfinite(res.savings_pct))

    def test_resolver_kind_and_other_spellings_unchanged(self):
        from repro.core.projection import ResponseTables
        from repro.power.scenarios import resolve_tables
        t = resolve_tables("calibrated:vai", kind="power")
        assert t.kind == "power"
        assert resolve_tables(None) is None
        assert resolve_tables("measured") is None
        assert isinstance(resolve_tables("tpu-v5e"), ResponseTables)


# ------------------------------------------------------- kernel arg checking
class TestVaiArgValidation:
    def _abc(self, rows=256):
        x = np.ones((rows, 128), dtype=np.float32)
        return x, x, x

    def test_rejects_bad_args_with_value_error(self):
        from repro.kernels.vai import vai
        a, b, c = self._abc()
        with pytest.raises(ValueError, match="loopsize"):
            vai(a, b, c, loopsize=-1)
        with pytest.raises(ValueError, match="ints"):
            vai(a, b, c, loopsize=2.5)
        with pytest.raises(ValueError, match="block_rows"):
            vai(a, b, c, loopsize=1, block_rows=0)
        with pytest.raises(ValueError, match="does not tile"):
            vai(a, b, c, loopsize=1, block_rows=100)

    def test_flops_bytes_exported_from_package(self):
        from repro.kernels import membw_bytes, vai_flops_bytes
        assert vai_flops_bytes(1024, 0) == (0, 2 * 1024 * 4)
        assert vai_flops_bytes(1024, 8) == (2 * 8 * 1024, 4 * 1024 * 4)
        assert membw_bytes(512, 4) == 2048
        # the package must NOT shadow its submodules (ops.py imports them)
        import repro.kernels as pkg
        import types
        assert isinstance(pkg.vai, types.ModuleType)
        assert isinstance(pkg.membw, types.ModuleType)


# ---------------------------------------------------------- run_sweep re-seat
class TestRunSweepReseat:
    def test_run_sweep_bit_for_bit_with_model_path(self):
        """The harness-seated run_sweep must reproduce the direct
        ChipModel evaluation exactly (the pre-tuning implementation)."""
        from repro.configs.paper_vai import VAISuiteConfig
        from repro.core.vai import _loopsize_for, run_sweep
        from repro.kernels.vai import vai_flops_bytes

        cfg = VAISuiteConfig(elements=1 << 16,
                             intensities=(0.0, 0.5, 4.0, 64.0))
        pts = run_sweep(cfg, execute_kernel=False)
        model = ChipModel(TPU_V5E)
        chip = TPU_V5E
        k = 0
        for ai in cfg.intensities:
            L = _loopsize_for(ai)
            profile = model.vai_profile(cfg.elements, L)
            t0 = model.step_time(profile, 1.0)
            e0 = model.energy_j(profile, 1.0)
            flops, byts = vai_flops_bytes(cfg.elements, L)
            for f_mhz in cfg.frequencies_mhz:
                frac = min(max(f_mhz / 1700, model.f_min_frac), 1.0)
                t = model.step_time(profile, frac)
                p = model.power_w(profile, frac)
                pt = pts[k]; k += 1
                assert (pt.ai, pt.loopsize, pt.freq_mhz) == (ai, L, f_mhz)
                assert pt.power_w == p and pt.time_rel == t / t0
                assert pt.energy_rel == p * t / e0
                assert pt.tflops == flops / t / 1e12
            for cap_frac in (1.0, 0.9, 0.72, 0.54, 0.36, 0.25, 0.18):
                cap_w = cap_frac * chip.tdp_w
                frac = model.freq_for_power_cap(profile, cap_w)
                t = model.step_time(profile, frac)
                p = model.power_w(profile, frac)
                pt = pts[k]; k += 1
                assert pt.power_cap_w == cap_w
                assert pt.power_w == p and pt.time_rel == t / t0
        assert k == len(pts)

    def test_run_sweep_rejects_untileable_elements(self):
        from repro.configs.paper_vai import VAISuiteConfig
        from repro.core.vai import run_sweep
        cfg = VAISuiteConfig(elements=384 * 128,     # 384 rows % 256 != 0
                             intensities=(0.5,))
        with pytest.raises(ValueError, match="does not tile"):
            run_sweep(cfg, execute_kernel=False)
