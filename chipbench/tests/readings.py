"""The two readings a cell's limit is set from, at the cell's own size.

    python3 chipbench/tests/readings.py --workload <cell> --seconds 10 \\
        --seeds 11 12 13 ...                  # on a TPU, from the root

For each seed, in one process: the cell's set-up, a short window at the
cell's own load, and the comparison, which also runs the control (the
reference in fp8 in the program's place) over the same prompts and served
tokens. Prints one JSON line per seed: the program's widest gap and the
control's.
"""
import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from chipbench import harness  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=int, default=10)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--no-control", action="store_true")
    args = ap.parse_args()
    sys.path.insert(0, str(harness.HERE))
    import run as bench_run
    bench_run.use_cache()
    for seed in args.seeds:
        t0 = time.perf_counter()
        cell = harness.cell(args.workload, seed, args.seconds, False)
        cell.t0 = t0
        cell.compiles = bench_run.CompileCounter.shared()
        cell.peaks = bench_run.gate(1)[1]
        cell.control = not args.no_control
        out = cell.driver.run(cell)
        print(json.dumps({"seed": seed, "correct": all(
            c.ok for c in out.checks), **{c.name: c.value for c in out.checks},
            **out.info, **out.end_to_end}), flush=True)
        del out
        gc.collect()


if __name__ == "__main__":
    main()
