"""Each per-layer reader's arithmetic, on a made-up run whose answers are
known, and its silence where there is nothing to read."""
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import harness, timeline, trace
from conftest import tiny_cell

PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def _run(conf, family, *, device_step=0.02, device_prefill=0.05):
    ticks = [timeline.Tick(t1=0.1 * i + 0.04,
                           slots=np.arange(3), keys=np.array([10, 20, 30]),
                           admitted=int(i % 4 == 0))
             for i in range(8)]
    prefills = [100 + i for i in range(4)]
    timed = SimpleNamespace(ticks=ticks, prefills=prefills)
    summary = trace.Summary(
        window_s=2.0, busy_s=1.5, programs={}, ops={}, idle_by_host={},
        by_call={"generate_step": 6 * device_step,
                 "prefill": 2 * device_prefill},
        runs_by_call={"generate_step": 6, "prefill": 2})
    window = SimpleNamespace(ticks=ticks)
    return SimpleNamespace(conf=conf, family=family, peaks=PEAKS,
                           timed=timed, window=window, trace=summary,
                           traced_ticks=(1, 7), traced_prefills=(1, 3))


@pytest.fixture(scope="module")
def cell():
    return tiny_cell()


def test_readers_on_a_made_up_run(cell):
    run = _run(cell.conf, cell.family)
    got = {k: v["value"] for k, v in harness.per_layer_all(run).items()}
    fam, conf = cell.family, cell.conf
    least = fam.decode_step_least(conf, np.array([10, 20, 30]),
                                  PEAKS["bf16_flops"],
                                  PEAKS["hbm_bytes_per_s"])
    assert got["decode_roofline"] == pytest.approx(100 * least / 0.02)
    assert got["prefill_ms_per_ktok"] == pytest.approx(
        1e3 * 0.1 / ((101 + 102) / 1e3))
    flops = (fam.prompt_flops(conf, [101, 102])
             + 6 * fam.token_flops(conf, np.array([10, 20, 30])))
    assert got["serve_mfu"] == pytest.approx(100 * flops / (2.0 * 197e12))
    assert got["device_idle_share"] == pytest.approx(25.0)
    assert got["slot_occupancy"] == 3.0
    # ticks 1, 2, 3 and 5, 6, 7 admitted none; each follows its
    # predecessor's end by 0.1 s
    assert got["decode_tick_ms"] == pytest.approx(100.0)


def test_readers_are_silent_without_a_trace(cell):
    run = _run(cell.conf, cell.family)
    run.trace = None
    got = harness.per_layer_all(run)
    assert set(got) == {"slot_occupancy", "decode_tick_ms"}


def test_roofline_is_silent_where_steps_and_runs_disagree(cell):
    run = _run(cell.conf, cell.family)
    run.trace.runs_by_call["generate_step"] = 5
    assert "decode_roofline" not in harness.per_layer_all(run)


def test_counts_of_the_dense_family(cell):
    fam, conf = cell.family, cell.conf
    k = fam.dims(conf)
    per_layer = (2 * k["d"] * k["H"] * k["hd"] + 2 * k["d"] * k["KV"] * k["hd"]
                 + 3 * k["d"] * k["ff"])
    assert fam.matmul_params(conf) == k["L"] * per_layer + k["d"] * k["V"]
    # one token at one position: 2 FLOPs a weight, 4 per head dim attended
    assert fam.token_flops(conf, np.array([1])) == pytest.approx(
        2 * fam.matmul_params(conf) + 4 * k["L"] * k["H"] * k["hd"])
    assert fam.prompt_flops(conf, [3]) == pytest.approx(
        fam.token_flops(conf, np.array([1, 2, 3])))
    weights = fam.make_weights(conf, 1)
    import jax
    leaves = jax.tree.leaves(weights)
    embedding = weights["emb"].size * weights["emb"].dtype.itemsize
    assert fam.weight_bytes(conf) == sum(
        a.size * a.dtype.itemsize for a in leaves) - embedding
