import dataclasses
import os
import subprocess
import sys

import jax
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs import ARCH_IDS, get_config  # noqa: E402
from repro.models.transformer import Runtime  # noqa: E402

# ---------------------------------------------------------------------------
# Optional hypothesis (declared in requirements-dev.txt / pyproject [dev]).
# When absent, the suite must degrade to skips, not collection errors: the
# stubs below turn every @given test into a skip while the deterministic
# tests in the same module keep running.
# ---------------------------------------------------------------------------
try:
    from hypothesis import given, settings, strategies as st  # noqa: F401
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

    class _StrategyStub:
        """stands in for `strategies`: any strategy call returns None, which
        is fine because the stubbed @given never runs the test body."""

        def __getattr__(self, name):
            return lambda *a, **k: None

    st = _StrategyStub()

    def settings(*a, **k):
        return lambda fn: fn

    def given(*a, **k):
        return pytest.mark.skip(reason="hypothesis not installed")


@pytest.fixture(scope="session")
def rt():
    return Runtime(tp=1, moe_impl="local")


def reduced_f32(arch: str):
    return dataclasses.replace(get_config(arch).reduced(), dtype="float32")


def run_subprocess(code: str, devices: int = 8, timeout: int = 420) -> str:
    """Run a snippet in a fresh process with N host devices. The child is
    pinned to the CPU: on a machine with a chip, the parent (which has
    imported JAX) already holds it."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=timeout)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}"
    return out.stdout
