"""Tiny cells on the CPU: every step of a run but the look for a chip.

    PYTHONPATH=src python -m pytest chipbench/tests -q

(from the checkout's root; these tests sit outside the repository's own
``testpaths``).
"""
import copy
import json
import os
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from chipbench import harness  # noqa: E402
from chipbench import traffic as traffic_mod  # noqa: E402

TINY = {"hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 2, "vocab_size": 512,
        "torch_dtype": "float32"}
TINY_MIX = {"prompt_tokens": {"dist": "log_uniform", "min": 8, "max": 96},
            "output_tokens": {"dist": "log_uniform", "min": 4, "max": 24},
            "check": {"packed_tokens": 1024}}
#: a configuration that states its head size gets one that is not d / heads
TINY_HEAD_DIM = 24


class Counter:
    count = 0


def tiny_cell(config="mistral-nemo-12b-l8", traffic="chat", seed=7,
              n_requests=12, slots=4, trace=False):
    """A cell of the benchmark at CPU size: the configuration and mix files
    with the widths, lengths and depth cut (same keys, same code paths).
    Requests arrive at the mix's rate; the window opens at the third of
    them and closes after the ``n_requests``-th."""
    conf = copy.deepcopy(harness.load_json(
        harness.HERE / "configs" / f"{config}.json"))
    conf.update(TINY, name=f"tiny-{config}")
    if "head_dim" in conf:
        conf["head_dim"] = TINY_HEAD_DIM
    conf["serving"] = dict(conf["serving"], slots=slots)
    mix = copy.deepcopy(harness.load_json(
        harness.HERE / "traffic" / f"{traffic}.json"))
    mix.update(copy.deepcopy(TINY_MIX))
    arrivals = traffic_mod.generate(mix, n_requests + 1, 0, 2).arrivals
    window = (int(arrivals[2]), int(arrivals[n_requests - 1]) + 1)
    family = harness.load_module(
        harness.HERE / "families" / f"{conf['family']}.py",
        f"chipbench.families.{conf['family']}")
    driver = harness.load_module(harness.HERE / "drivers" /
                                 f"{mix['driver']}.py",
                                 f"chipbench.drivers.{mix['driver']}")
    return SimpleNamespace(
        conf=conf, mix=mix, family=family, driver=driver, seed=seed,
        seed32=harness.seed32(seed), seconds=1, trace=trace,
        window=window, t0=time.perf_counter(), compiles=Counter(),
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})


@pytest.fixture
def tiny():
    return tiny_cell
