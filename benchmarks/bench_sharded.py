"""Sharded jitted replay vs the numpy single stream (CI-gated).

Counterfactual energy-aware replay of a 1M-sample quantized trace (0.1 W
sensor steps, 100 jobs) through :class:`repro.parallel.ShardedExecutor`
on 8 CPU-emulated devices must (a) return the **bit-for-bit identical**
report to the numpy path — exact equality, no tolerance — and (b) run
>=4x faster end to end (``speedup_vs_single``, gated in baselines.json).

The measurement runs in a worker subprocess because
``--xla_force_host_platform_device_count`` only takes effect before the
first jax import, and sibling benchmarks in the same harness process may
already have imported jax with the default single device
(docs/BACKENDS.md).
"""
import json
import os
import subprocess
import sys
import time
from typing import List, Tuple

N = 1_000_000
CHUNK = 65_536
N_JOBS = 100
N_DEVICES = 8


def _worker() -> None:
    import numpy as np

    from repro.core.modal import synth_fleet_powers
    from repro.parallel.executor import ShardedExecutor
    from repro.power.stream import SampleShard, replay

    powers = np.round(synth_fleet_powers(N, seed=0) * 10.0) / 10.0
    jobs = np.repeat([f"job{i:04d}" for i in range(N_JOBS)], N // N_JOBS)

    def stream():
        for a in range(0, N, CHUNK):
            b = min(a + CHUNK, N)
            yield SampleShard.from_arrays(powers[a:b], job_id=jobs[a:b])

    ex = ShardedExecutor(devices=N_DEVICES)
    kw = dict(chip="mi250x-gcd", slowdown_budget=0.05)
    replay(stream(), "energy-aware", executor=ex, **kw)   # compile warmup

    best = {}
    for label, extra in (("np", {}), ("ex", {"executor": ex})):
        best[label] = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            rep = replay(stream(), "energy-aware", **kw, **extra)
            best[label] = min(best[label], time.perf_counter() - t0)
        best[f"rep_{label}"] = rep

    r_np, r_ex = best["rep_np"], best["rep_ex"]
    exact = (
        r_np.energy_new_j == r_ex.energy_new_j
        and r_np.energy_base_j == r_ex.energy_base_j
        and r_np.time_new_s == r_ex.time_new_s
        and r_np.recorded.energy_mwh == r_ex.recorded.energy_mwh
        and r_np.replayed.energy_mwh == r_ex.replayed.energy_mwh
        and r_np.replayed.hours_pct == r_ex.replayed.hours_pct
        and all(a.energy_new_j == b.energy_new_j
                and a.time_new_s == b.time_new_s
                for a, b in zip(r_np.jobs, r_ex.jobs)))
    print(json.dumps({
        "t_np": best["np"], "t_ex": best["ex"], "exact": bool(exact),
        "ndev": ex.ndev, "savings_pct": r_ex.savings_pct,
        "kernel_calls": ex.stats["kernel_calls"]}))


def run(verbose: bool = False) -> List[Tuple[str, float, str]]:
    env = dict(os.environ)
    # the worker forces host devices; the parent may hold the chip
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count="
                        f"{N_DEVICES}").strip()
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in ("src", env.get("PYTHONPATH", "")) if p)
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_sharded", "--worker"],
        env=env, capture_output=True, text=True, check=True)
    res = json.loads(out.stdout.strip().splitlines()[-1])
    if not res["exact"]:
        raise AssertionError(
            "sharded replay is not bit-for-bit equal to the numpy path")
    speedup = res["t_np"] / res["t_ex"]
    if verbose:
        print(f"\n# sharded replay, {N} samples x chunk {CHUNK} "
              f"({res['ndev']} devices, energy-aware, 0.1 W quantized)")
        print(f"  numpy single-stream : {res['t_np'] * 1e3:8.1f} ms")
        print(f"  sharded executor    : {res['t_ex'] * 1e3:8.1f} ms  "
              f"({res['kernel_calls']} kernel launches)")
        print(f"  speedup             : {speedup:8.2f}x   "
              f"(bit-for-bit exact, savings {res['savings_pct']:.4f}%)")
    return [("sharded_replay_1m", res["t_ex"] * 1e6,
             f"speedup_vs_single={speedup:.2f};ndev={res['ndev']};"
             f"exact=1")]


if __name__ == "__main__":
    if "--worker" in sys.argv:
        _worker()
    else:
        for row in run(verbose=True):
            print(",".join(str(x) for x in row))
