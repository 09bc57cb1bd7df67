"""Record the small chip trace that ``test_trace.py`` reads.

    python3 chipbench/tests/record_trace.py      # on a TPU, from the root

mistral-nemo-12b-l8 at its published widths cut to 2 layers, 4 slots,
eight short requests through the harness's own traced window.
Writes ``chipbench/tests/data/serve.xplane.pb`` and, beside it,
``serve.calls.json``: the harness's record of the window (decode steps,
prefill lengths, the traced ranges) and the reduction's numbers at
recording time. Prints the trace's planes and
lines, with a few events of each.
"""
import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))
sys.path.insert(0, str(HERE.parents[1]))

import jax  # noqa: E402

from conftest import tiny_cell  # noqa: E402
from chipbench import harness  # noqa: E402


def main():
    if jax.devices()[0].platform != "tpu":
        sys.exit("records a chip trace: needs a TPU")
    data = Path(sys.argv[1]) if len(sys.argv) > 1 else HERE / "data"
    data.mkdir(parents=True, exist_ok=True)
    cell = tiny_cell(n_requests=8, slots=4, trace=True)
    cell.conf.update({k: v for k, v in harness_conf().items()
                      if k in ("hidden_size", "head_dim", "intermediate_size",
                               "num_attention_heads", "num_key_value_heads",
                               "vocab_size", "torch_dtype")})
    cell.conf["num_hidden_layers"] = 2
    cell.mix["output_tokens"] = {"dist": "uniform", "min": 4, "max": 6}
    cell.mix["prompt_tokens"] = {"dist": "uniform", "min": 20, "max": 60}
    cell.keep_trace = str(data / "serve.xplane.pb")
    cell.trace_stretch = (0.0, float("inf"))     # the whole window
    cell.t0 = time.perf_counter()
    try:
        out = cell.driver.run(cell)
    finally:
        dump(data / "serve.xplane.pb")
    run = out.per_layer
    s, timed = run.trace, run.timed
    with open(data / "serve.calls.json", "w") as f:
        json.dump({"busy_s": s.busy_s,
                   "window_s": s.window_s, "programs": s.programs,
                   "idle_by_host": s.idle_by_host, "by_call": s.by_call,
                   "runs_by_call": s.runs_by_call,
                   "conf": cell.conf,
                   "ticks": [[k.slots.tolist(), k.keys.tolist(), k.admitted]
                             for k in timed.ticks],
                   "prefills": timed.prefills,
                   "traced_ticks": run.traced_ticks,
                   "traced_prefills": run.traced_prefills,
                   "per_layer": {k: v["value"] for k, v in
                                 harness.per_layer_all(run).items()}},
                  f, indent=1)
    print("calls", len(timed.ticks), "busy", s.busy_s, "window", s.window_s,
          "programs", s.programs, "by_call", s.by_call, "runs_by_call",
          s.runs_by_call, "idle", s.idle_by_host)


def dump(path):
    print("size", os.path.getsize(path))
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    for plane in pd.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            ev = list(line.events)
            print("  LINE", repr(line.name), len(ev))
            for e in ev[:4]:
                print("     ", repr(e.name), e.start_ns, e.duration_ns,
                      [(k, v) for k, v in e.stats][:8])


def harness_conf():
    return harness.load_json(harness.HERE / "configs" /
                             "mistral-nemo-12b-l8.json")


if __name__ == "__main__":
    main()
