"""Run one cell of BENCHMARK.json on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device`` and, traced, ``breakdown``; ``compared`` comes
last, each number compared beside its limit. The same numbers are the last
lines of standard error.

Exits non-zero, and prints no result, where JAX finds no TPU, fewer chips
than the cell asks for, or a ``device_kind`` that ``peaks.json`` lacks.
JAX's persistent compilation cache is ``$JAX_COMPILATION_CACHE_DIR`` where
that is set, otherwise ``.jax_cache/`` at the checkout's root.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))     # the program under test
sys.path.insert(0, str(ROOT))

from chipbench import harness  # noqa: E402


class CompileCounter:
    """Counts XLA backend compiles (persistent-cache reads do not compile)."""

    _one = None

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    @classmethod
    def shared(cls) -> "CompileCounter":
        """One listener per process, however many runs it makes."""
        if cls._one is None:
            cls._one = cls()
        return cls._one

    def _listen(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration


def gate(chips: int):
    """The chips this cell needs, and their published peaks; or no result."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"device gate: JAX found {devs[0].platform!r}, not a TPU")
    if len(devs) < chips:
        sys.exit(f"device gate: the cell needs {chips} chips, JAX found "
                 f"{len(devs)}")
    peaks = harness.load_json(harness.HERE / "peaks.json")
    kind = devs[0].device_kind
    if kind not in peaks:
        sys.exit(f"device gate: no peaks for device_kind {kind!r} in "
                 f"peaks.json")
    return devs[:chips], peaks[kind]


def use_cache() -> None:
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    # every program, however quick to compile, is read back on later runs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    cell = harness.cell(args.workload, args.seed, args.seconds,
                        bool(args.trace))
    devices, peaks = gate(int(cell.workload["chips"]))
    use_cache()
    cell.t0, cell.peaks, cell.compiles = T0, peaks, CompileCounter.shared()
    out = cell.driver.run(cell)

    if args.trace:
        metrics = harness.per_layer(cell.bench, args.workload, out.per_layer)
    else:
        metrics = {m["name"]: {"value": float(out.end_to_end[m["name"]]),
                               "unit": m["unit"]}
                   for m in harness.metrics_for(cell.bench, args.workload,
                                                "end_to_end")}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": out.memory_peak_bytes}
    if args.trace and out.busy_window:
        device["busy_s"], device["window_s"] = out.busy_window
    compared = {c.name: {"value": c.value, "limit": c.limit}
                for c in out.checks}
    compared["failed_requests"] = {"value": out.failed, "limit": 0}
    result = {"correct": all(c.ok for c in out.checks) and out.failed == 0,
              "attempted": out.attempted, "failed": out.failed,
              "metrics": metrics, "device": device}
    if args.trace:
        result["breakdown"] = out.breakdown
    result["info"] = out.info
    result["compared"] = compared
    for c in out.checks:
        print(f"{c.name} {c.value!r} limit {c.limit!r}", file=sys.stderr)
    print(f"failed_requests {out.failed} limit 0", file=sys.stderr)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
