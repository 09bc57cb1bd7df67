"""End-to-end behaviour: the energy-aware training loop (governor +
telemetry + watchdog), data determinism, telemetry aggregation, VAI driver,
and the sharding/optimizer substrate."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import reduced_f32
from repro.configs import SHAPES_BY_NAME
from repro.core import power_model as pm
from repro.core.telemetry import JobLog, JobRecord, StepSample, TelemetryStore
from repro.data import make_batch
from repro.launch.train import StragglerWatchdog, TrainConfig, Trainer
from repro.models.transformer import Runtime

# long-running model/serving tests: fast lane skips these
pytestmark = pytest.mark.slow


# ------------------------------------------------------------------ training
def test_governor_reduces_energy_vs_baseline(tmp_path):
    cfg = reduced_f32("qwen2.5-14b")
    shape = SHAPES_BY_NAME["train_4k"].reduced()
    rt = Runtime(tp=1, moe_impl="local")

    t_base = Trainer(cfg, shape, rt, tcfg=TrainConfig(
        steps=6, governor=False, log_every=100))
    t_gov = Trainer(cfg, shape, rt, tcfg=TrainConfig(
        steps=6, governor=True, log_every=100))
    out_b = t_base.run()
    out_g = t_gov.run()
    assert out_g["energy_j"] <= out_b["energy_j"] + 1e-9
    # loss trajectories identical: the governor never changes numerics
    np.testing.assert_allclose(out_b["losses"], out_g["losses"], rtol=1e-6)


def test_straggler_watchdog_flags_slow_host():
    w = StragglerWatchdog(threshold=2.0)
    for _ in range(10):
        w.record(0, 0.1)
        w.record(1, 0.1)
        w.record(2, 0.5)   # straggler
    assert w.stragglers() == [2]


# ------------------------------------------------------------------ data
def test_pipeline_deterministic_across_restarts():
    cfg = reduced_f32("stablelm-12b")
    shape = SHAPES_BY_NAME["train_4k"].reduced()
    b1 = make_batch(cfg, shape, step=7, seed=3)
    b2 = make_batch(cfg, shape, step=7, seed=3)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = make_batch(cfg, shape, step=8, seed=3)
    assert not np.array_equal(b1["tokens"], b3["tokens"])


def test_pipeline_markov_structure_learnable():
    cfg = reduced_f32("stablelm-12b")
    shape = SHAPES_BY_NAME["train_4k"].reduced()
    b = make_batch(cfg, shape, step=0)
    # noise band is vocab//16 -> consecutive-token relation is predictable
    t = b["tokens"]
    diffs = (t[:, 1:] - (t[:, :-1] * 31) % cfg.vocab_size) % cfg.vocab_size
    assert int(np.max(diffs)) < max(cfg.vocab_size // 16, 2)


# ------------------------------------------------------------------ telemetry
def test_telemetry_window_aggregation():
    st = TelemetryStore(window_s=15.0)
    for i in range(100):
        st.record(StepSample(step=i, t=i * 1.0, duration_s=1.0,
                             power_w=100.0 + i, energy_j=100.0 + i,
                             mode=2, freq_mhz=1700))
    st.flush()
    assert 5 <= len(st.windows) <= 8           # ~100s / 15s windows
    assert st.total_energy_j() == pytest.approx(sum(100.0 + i
                                                    for i in range(100)))
    assert st.mode_hours_pct() == {2: 100.0}


def test_telemetry_json_roundtrip():
    st = TelemetryStore()
    st.record(StepSample(0, 0.0, 1.0, 200.0, 200.0, 2, 1700))
    text = st.to_json()
    st2 = TelemetryStore.from_json(text)
    assert st2.total_energy_j() == pytest.approx(200.0)


def test_job_log_domains_and_size_classes():
    log = JobLog()
    log.start(JobRecord("j1", "chm_123", num_nodes=6000, begin_time=0.0))
    log.start(JobRecord("j2", "chm_456", num_nodes=50, begin_time=0.0))
    log.start(JobRecord("j3", "phy_1", num_nodes=200, begin_time=0.0))
    doms = log.by_domain()
    assert set(doms) == {"chm", "phy"}
    assert log.jobs["j1"].size_class() == "A"
    assert log.jobs["j2"].size_class() == "E"
    assert log.jobs["j3"].size_class() == "C"


# ------------------------------------------------------------------ VAI driver
def test_vai_sweep_reproduces_paper_shape():
    from repro.configs.paper_vai import VAISuiteConfig
    from repro.core.vai import response_table, run_sweep
    cfg = dataclasses.replace(VAISuiteConfig(), elements=1 << 16,
                              intensities=(0.0, 0.0625, 0.5, 4.0, 64.0))
    pts = run_sweep(cfg, execute_kernel=True)
    tab = response_table(pts, by="freq")
    caps = sorted(tab, reverse=True)
    # downclocking monotonically reduces average power (paper Table III)
    powers = [tab[c]["power_pct"] for c in caps]
    assert all(a >= b - 1e-6 for a, b in zip(powers, powers[1:]))
    # and some capped point saves energy on average
    assert min(tab[c]["energy_pct"] for c in caps) < 100.0


# ------------------------------------------------------------------ sharding
def test_zero1_specs_upgrade():
    import numpy as onp
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.parallel.sharding import spec_bytes_per_device, zero1_specs
    devs = onp.array(jax.devices()[:1]).reshape(1, 1)
    mesh = Mesh(devs, ("data", "model"))
    specs = {"w": P(None, None), "v": P("model", None)}
    shapes = {"w": jax.ShapeDtypeStruct((16, 8), jnp.float32),
              "v": jax.ShapeDtypeStruct((4, 16), jnp.float32)}
    up = zero1_specs(specs, shapes, mesh, ("data",))
    assert up["w"] == P("data", None)       # first unsharded divisible dim
    assert up["v"] == P("model", "data")


def test_opt_state_structure():
    from repro.optim import OptConfig, apply_updates, init_opt_state
    params = {"a": jnp.ones((4, 4)), "b": jnp.zeros((3,))}
    opt = init_opt_state(params)
    grads = jax.tree.map(jnp.ones_like, params)
    new_p, new_opt, m = apply_updates(params, grads, opt, OptConfig(lr=0.1))
    assert int(new_opt["step"]) == 1
    assert m["grad_norm"] > 0
    assert float(jnp.max(jnp.abs(new_p["a"] - params["a"]))) > 0


def test_compile_cache_placement(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code; without it
    the cache goes to the fixed .jax_cache/ at the checkout root."""
    import jax
    from repro.launch import compile_cache
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.use_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.use_compile_cache()
        assert path == str(compile_cache.DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == path
        root = compile_cache.DEFAULT_DIR.parent
        assert compile_cache.DEFAULT_DIR.name == ".jax_cache"
        assert (root / "chip_smoke.py").exists()
        assert ".jax_cache/" in (root / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
