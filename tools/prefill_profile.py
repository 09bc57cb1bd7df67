"""Where one prefill's device time goes, page by page, on the chip at hand.

Builds the engine of a benchmark configuration (its widths and depth,
weights drawn from ``--seed``), runs ``ContinuousEngine.prefill`` at each
page under the JAX profiler, and writes, per page and prompt length, the
device time of one ``jit_prefill`` run and its longest operations; with
``--hlo``, each page's compiled prefill, so that an operation's name can be
traced to the jax code it came from (``metadata={op_name=... source_file=
... source_line=...}``). ``--kernel`` also times the flash attention kernel
alone at the configuration's widths, at every pair of block edges.

    PYTHONPATH=src:. python tools/prefill_profile.py \\
        --config chipbench/configs/codestral-22b-l8.json \\
        --pages 1024 2048 4096 --out build/prefill_profile.json

Needs the accelerator: a device time comes only from a device trace.
"""
from __future__ import annotations

import argparse
import collections
import functools
import glob
import json
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np


def _trace(fn, reps: int):
    """Device seconds of each program run and of each operation, summed
    over ``reps`` calls of ``fn`` in one profiler trace."""
    from chipbench import trace
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for _ in range(reps):
            jax.block_until_ready(fn())
        jax.profiler.stop_trace()
        ev = trace.load(glob.glob(f"{d}/plugins/profile/*/*.xplane.pb")[0])
    runs = collections.defaultdict(list)
    for chip_runs in ev.runs.values():
        for name, t0, t1, _ in chip_runs:
            runs[name].append(t1 - t0)
    ops = collections.Counter()
    for chip_ops in ev.ops.values():
        for name, t0, t1 in chip_ops:
            # a Pallas kernel is a custom-call, which CONTAINERS also
            # matches: count it as the operation it is
            if "custom-call(" in name or not trace.CONTAINERS.search(name):
                ops[trace.op_name(name)] += t1 - t0
    return runs, ops


def profile_prefill(engine, pages, reps: int, hlo_prefix: str):
    from repro.serving import Request
    rng = np.random.default_rng(0)
    rows = []
    for page in pages:
        # the full page, and the median of prompts log-uniform over
        # (page / 2, page]
        for length in (page, int(round(page * 2 ** -0.5))):
            req = Request(rng.integers(0, engine.cfg.vocab_size, length,
                                       dtype=np.int32), max_new_tokens=1)
            warm = engine.prefill(req)
            assert getattr(warm, "page", page) == page, (warm.page, page)
            runs, ops = _trace(lambda: engine.prefill(req).state, reps)
            run = runs.get("jit_prefill", [])
            top = [[k, v / reps] for k, v in ops.most_common(16)]
            rows.append({"page": page, "length": length,
                         "prefill_ms": 1e3 * float(np.median(run)),
                         "runs": len(run), "ops_ms_per_run": [
                             [k, 1e3 * v] for k, v in top],
                         "ops_total_ms_per_run": 1e3 * sum(ops.values())
                         / reps})
            print(json.dumps(rows[-1]), flush=True)
        if hlo_prefix:
            toks = jnp.zeros((1, page), jnp.int32)
            text = engine._prefill_fns[page].lower(
                engine.params, toks, jnp.asarray([page], jnp.int32),
                jnp.float32(0.0), jax.random.PRNGKey(0)).compile().as_text()
            with open(f"{hlo_prefix}_hlo_{page}.txt", "w") as f:
                f.write(text)
    return rows


def time_kernel(cfg, page: int, reps: int):
    """Seconds of one flash kernel call (one layer) at ``cfg``'s widths,
    every pair of block edges, at the full page and a 0.71 length: the
    device time of its programs per call."""
    from repro.kernels.flash_attention import BLOCKS, flash_attention
    hkv, g = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    hd = cfg.resolved_head_dim
    key = jax.random.PRNGKey(1)
    q = jax.random.normal(key, (1, hkv, g, page, hd), jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, hkv, page, hd),
                          jnp.bfloat16)
    rows = []
    for bq in BLOCKS:
        for bk in BLOCKS:
            fn = jax.jit(functools.partial(flash_attention, block_q=bq,
                                           block_k=bk))
            for length in (page, int(round(page * 2 ** -0.5))):
                lens = jnp.asarray([length], jnp.int32)
                try:
                    jax.block_until_ready(fn(q, k, k, lens))
                except Exception as e:          # a block over VMEM
                    rows.append({"block_q": bq, "block_k": bk,
                                 "error": str(e).splitlines()[0][:200]})
                    break
                runs, _ = _trace(lambda: fn(q, k, k, lens), reps)
                ms = 1e3 * sum(map(sum, runs.values())) / reps
                flops = 4.0 * g * hkv * hd * length * (length + 1) / 2
                rows.append({"block_q": bq, "block_k": bk, "length": length,
                             "kernel_ms": ms,
                             "causal_tflops": flops / ms / 1e9})
                print(json.dumps(rows[-1]), flush=True)
    return rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--pages", type=int, nargs="+",
                    default=[1024, 2048, 4096])
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=3141592661)
    ap.add_argument("--out", required=True, help="a JSON file to write")
    ap.add_argument("--hlo", action="store_true")
    ap.add_argument("--kernel", action="store_true")
    args = ap.parse_args()
    if jax.default_backend() == "cpu":
        print("a device time needs an accelerator", file=sys.stderr)
        return 2
    from chipbench.families import dense
    from repro.models.transformer import Runtime
    from repro.serving import ContinuousEngine
    with open(args.config) as f:
        conf = json.load(f)
    cfg = dense.program_config(conf)
    t0 = time.perf_counter()
    params = dense.make_weights(conf, args.seed)
    engine = ContinuousEngine(cfg, Runtime(tp=1), params, max_slots=1,
                              max_len=max(args.pages) + 128)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    hlo_prefix = os.path.splitext(args.out)[0] if args.hlo else None
    result = {"device": jax.devices()[0].device_kind,
              "config": conf["name"],
              "prefill_blocks": getattr(engine, "prefill_blocks", None),
              "prefill": profile_prefill(engine, args.pages, args.reps,
                                         hlo_prefix)}
    if args.kernel:
        result["kernel"] = time_kernel(cfg, max(args.pages), args.reps)
    result["seconds"] = time.perf_counter() - t0
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
