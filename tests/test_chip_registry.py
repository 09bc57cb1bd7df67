"""Registry pins for the newly registered chips (`h100-sxm`, `mi300x`):
spec sanity, transfer-surface monotonicity in frequency, cap-enforcement
monotonicity in the cap, and model-derived response tables that the
projection engine accepts."""
import numpy as np
import pytest

from repro.core.hardware import CHIPS, H100_SXM, MI300X
from repro.core.projection import project
from repro.power import (ChipModel, ProfileArray, StepProfile,
                         response_table)

NEW_CHIPS = ("h100-sxm", "mi300x")
PROFILES = [
    StepProfile(compute_s=1.0, memory_s=0.1),            # compute-bound
    StepProfile(compute_s=0.1, memory_s=1.0),            # memory-bound
    StepProfile(compute_s=0.7, memory_s=0.6,
                collective_s=0.2),                       # mixed
]


def test_registry_contains_new_chips():
    assert CHIPS["h100-sxm"] is H100_SXM
    assert CHIPS["mi300x"] is MI300X
    for spec in (H100_SXM, MI300X):
        assert 0 < spec.f_min_mhz < spec.f_nominal_mhz
        assert 0 < spec.idle_w < spec.tdp_w
        assert spec.peak_flops > 0 and spec.hbm_bw > 0
        # resolvable through every chip-spelling entry point
        assert ChipModel(spec.name).spec is spec


@pytest.mark.parametrize("name", NEW_CHIPS)
def test_surface_monotone_in_frequency(name):
    """Lower clocks never speed a step up and never raise power draw."""
    m = ChipModel(name)
    surf = m.surface()
    fr = np.linspace(m.f_min_frac, 1.0, 17)
    pa = ProfileArray.from_profiles(PROFILES)
    t = surf.step_time(pa.expand(), fr)              # (profiles, freqs)
    p = surf.power_w(pa.expand(), fr)
    assert (np.diff(t, axis=1) <= 1e-12).all()       # time nonincreasing
    assert (np.diff(p, axis=1) >= -1e-9).all()       # power nondecreasing
    assert (p <= m.spec.tdp_w + 1e-9).all()
    assert (p >= m.spec.idle_w - 1e-9).all()


@pytest.mark.parametrize("name", NEW_CHIPS)
def test_cap_enforcement_monotone_in_cap(name):
    """A tighter power cap never picks a higher clock, and the chosen
    clock's draw honors the cap whenever any grid point can."""
    m = ChipModel(name)
    surf = m.surface()
    caps = np.linspace(m.spec.idle_w * 1.1, m.spec.tdp_w, 12)
    for prof in PROFILES:
        prev = None
        for cap in caps:
            f = float(np.asarray(surf.freq_for_power_cap(prof, cap)))
            if prev is not None:
                assert f >= prev - 1e-12, (prof, cap)
            prev = f
            floor = abs(f - m.f_min_frac) < 1e-12
            assert floor or m.power_w(prof, f) <= cap + 1e-9, (prof, cap)


@pytest.mark.parametrize("name", NEW_CHIPS)
@pytest.mark.parametrize("kind", ("freq", "power"))
def test_response_tables_monotone_and_projectable(name, kind):
    """The model-derived Table-III analogue behaves physically: deeper
    caps draw less power and run compute-bound work longer — and it feeds
    the projection engine."""
    rt = response_table(CHIPS[name], kind=kind)
    caps = sorted(rt.vai, reverse=True)              # nominal first
    power = [rt.vai[c][0] for c in caps]
    runtime = [rt.vai[c][1] for c in caps]
    assert power[0] == pytest.approx(100.0)
    assert runtime[0] == pytest.approx(100.0)
    assert all(a >= b - 1e-9 for a, b in zip(power, power[1:]))
    assert all(a <= b + 1e-9 for a, b in zip(runtime, runtime[1:]))
    # the memory-family column must be less frequency-sensitive than the
    # compute family at the deepest cap (the paper's core asymmetry)
    assert rt.mb[caps[-1]][1] <= rt.vai[caps[-1]][1]
    rows = project(list(caps), kind, tables=rt)
    assert len(rows) == len(caps)
    assert all(np.isfinite(r.savings_pct) for r in rows)


def test_device_kind_table_maps_v5e_and_rejects_unknown():
    from repro.core.hardware import TPU_V5E, chip_for_device_kind
    assert chip_for_device_kind("TPU v5 lite") is TPU_V5E
    with pytest.raises(ValueError, match="no ChipSpec"):
        chip_for_device_kind("TPU v6 lite")
    with pytest.raises(ValueError, match="no ChipSpec"):
        chip_for_device_kind("cpu")
