import os
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_XLA_EXTRA", "") +
                           " --xla_force_host_platform_device_count="
                           + os.environ.get("REPRO_DRYRUN_DEVICES", "512")
                           ).strip()

"""Multi-pod dry-run: prove every (architecture x input shape x mesh)
combination lowers, SPMD-partitions, and compiles on the production meshes,
and extract the roofline terms from the compiled artifact.

MUST be a fresh process (device count is locked at first jax init):

    PYTHONPATH=src python -m repro.launch.dryrun --arch stablelm-12b \
        --shape train_4k --mesh single --out experiments/dryrun
"""
import argparse
import json
import pathlib
import time
import traceback
from typing import Dict, Optional

import jax

from repro.configs import (SHAPES_BY_NAME, applicable_shapes, get_config,
                           ARCH_IDS)
from repro.core import roofline as rl
from repro.core.hardware import TPU_V5E
from repro.launch import steps as steps_mod
from repro.launch.mesh import batch_axes_for, make_production_mesh
from repro.models.common import sharding_ctx
from repro.models.transformer import Runtime
from repro.optim import OptConfig


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             overrides: Optional[Dict] = None) -> Dict:
    """Lower + compile one cell; return the analysis record."""
    overrides = overrides or {}
    cfg = get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = steps_mod.rules_for_shape(shape, multi_pod, mesh)
    if overrides.get("seq_shard"):
        from repro.models.common import ShardingRules
        d = dict(rules.rules)
        d["seq"] = "model"        # Megatron-style sequence parallelism
        rules = ShardingRules(rules=d)
    if overrides.get("moe_ep2d_decode"):
        from repro.models.common import ShardingRules
        d = dict(rules.rules)
        d["expert_ff"] = "data"   # 2D expert-weight layout for serving
        rules = ShardingRules(rules=d)
    if overrides.get("rules"):
        rules = overrides["rules"]
    rt = Runtime(
        tp=mesh.shape["model"],
        mesh=mesh,
        batch_axes=batch_axes_for(mesh),
        moe_impl=overrides.get("moe_impl", "ep"),
        remat=overrides.get(
            "remat", "full" if shape.kind == "train" else "none"),
        decode_cache_shard=overrides.get("decode_cache_shard", "none"),
        moe_dispatch_dtype=overrides.get("moe_dispatch_dtype", "bfloat16"),
        moe_capacity_factor=overrides.get("moe_capacity_factor", 1.25),
        moe_ep2d_decode=overrides.get("moe_ep2d_decode", False),
    )
    opt_cfg = OptConfig()
    rec: Dict = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "chips": mesh.devices.size, "kind": shape.kind,
        "overrides": {k: v for k, v in overrides.items() if k != "rules"},
    }

    t0 = time.time()
    with mesh, sharding_ctx(rules, mesh):
        if shape.kind == "train":
            fn = steps_mod.make_train_step(cfg, rt, opt_cfg, rules)
            (state, batch), _ = steps_mod.input_specs(
                cfg, shape, rt, mesh, rules,
                zero1=overrides.get("zero1", True),
                moment_dtype=overrides.get("moment_dtype", "float32"))
            jitted = jax.jit(fn, donate_argnums=(0,))
            lowered = jitted.lower(state, batch)
        elif shape.kind == "prefill":
            fn = steps_mod.make_prefill_step(cfg, rt, shape.seq_len, rules)
            (params, batch), _ = steps_mod.input_specs(
                cfg, shape, rt, mesh, rules)
            lowered = jax.jit(fn).lower(params, batch)
        else:  # decode
            fn = steps_mod.make_decode_step(cfg, rt, rules)
            (params, token, pos, dstate), _ = steps_mod.input_specs(
                cfg, shape, rt, mesh, rules)
            lowered = jax.jit(fn, donate_argnums=(3,)).lower(
                params, token, pos, dstate)
        rec["lower_s"] = round(time.time() - t0, 2)

        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)

        cost = compiled.cost_analysis()
        rec["cost"] = {k: cost[k] for k in ("flops", "bytes accessed")
                       if k in cost}
        try:
            mem = compiled.memory_analysis()
            rec["memory"] = {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "generated_code_bytes": getattr(
                    mem, "generated_code_size_in_bytes", None),
            }
        except Exception as e:  # pragma: no cover - backend-specific
            rec["memory"] = {"error": str(e)}

        hlo = compiled.as_text()
        rec["hlo_lines"] = hlo.count("\n")
        if os.environ.get("REPRO_SAVE_HLO"):
            pathlib.Path(os.environ["REPRO_SAVE_HLO"]).write_text(hlo)
        # trip-count-aware per-device cost (XLA's cost_analysis counts while
        # bodies once — see repro.core.hlo_cost)
        from repro.core import hlo_cost
        parsed = hlo_cost.analyze_hlo(hlo)
        rec["parsed_cost"] = parsed.to_dict()
        rec["collectives"] = {**{k: v for k, v in
                                 parsed.collective_bytes.items()},
                              "total": parsed.collective_total,
                              "__counts__": parsed.collective_counts}

        report = rl.roofline_from_artifacts(
            {"flops": parsed.flops, "bytes accessed": parsed.bytes_accessed},
            {"total": parsed.collective_total}, mesh.devices.size,
            rl.model_flops(cfg, shape), TPU_V5E)
        rec["roofline"] = report.to_dict()
        # analytic memory floor: the parsed bytes are an upper bound (CPU
        # fusion granularity); this is the idealized-TPU-fusion lower bound
        rec["roofline"]["memory_s_floor"] = rl.memory_floor_s(
            cfg, shape, mesh.devices.size, TPU_V5E)

        # static per-device footprint of the step inputs (weights + state)
        from repro.parallel.sharding import spec_bytes_per_device
        if shape.kind == "train":
            args = (state, batch)
        elif shape.kind == "prefill":
            args = (params, batch)
        else:
            args = (params, token, pos, dstate)
        shardings = jax.tree.map(
            lambda s: s.sharding.spec, args,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        rec["input_bytes_per_device"] = spec_bytes_per_device(
            args, shardings, mesh)
        rec["fits_hbm"] = bool(
            rec["input_bytes_per_device"] < TPU_V5E.hbm_bytes)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--moe-impl", default="ep")
    ap.add_argument("--remat", default=None)
    ap.add_argument("--no-zero1", action="store_true")
    ap.add_argument("--decode-cache-shard", default=None)
    ap.add_argument("--moe-dispatch", default=None,
                    help="f8 = DSv3-style low-precision dispatch a2a")
    ap.add_argument("--moe-cf", type=float, default=None,
                    help="MoE capacity factor (baseline 1.25)")
    ap.add_argument("--moe-ep2d", action="store_true",
                    help="2D expert sharding for decode (weights fit)")
    ap.add_argument("--moments", default=None,
                    help="optimizer moment dtype (bfloat16 halves opt HBM)")
    ap.add_argument("--seq-shard", action="store_true",
                    help="sequence-parallel residual stream")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    archs = list(ARCH_IDS) if args.arch == "all" else args.arch.split(",")
    meshes = (["single", "multi"] if args.mesh == "both"
              else [args.mesh])
    outdir = pathlib.Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)

    failures = []
    for arch in archs:
        cfg = get_config(arch)
        shapes = ([s.name for s in applicable_shapes(cfg)]
                  if args.shape == "all" else args.shape.split(","))
        for shape_name in shapes:
            for mesh_kind in meshes:
                tag = f"{arch}__{shape_name}__{mesh_kind}" + (
                    f"__{args.tag}" if args.tag else "")
                path = outdir / f"{tag}.json"
                overrides = {"moe_impl": args.moe_impl,
                             "zero1": not args.no_zero1}
                if args.remat:
                    overrides["remat"] = args.remat
                if args.decode_cache_shard:
                    overrides["decode_cache_shard"] = args.decode_cache_shard
                if args.moments:
                    overrides["moment_dtype"] = args.moments
                if args.moe_dispatch:
                    overrides["moe_dispatch_dtype"] = args.moe_dispatch
                if args.moe_cf is not None:
                    overrides["moe_capacity_factor"] = args.moe_cf
                if args.moe_ep2d:
                    overrides["moe_ep2d_decode"] = True
                if args.seq_shard:
                    overrides["seq_shard"] = True
                try:
                    rec = run_cell(arch, shape_name, mesh_kind == "multi",
                                   overrides)
                    path.write_text(json.dumps(rec, indent=1))
                    r = rec["roofline"]
                    print(f"OK   {tag}: dominant={r['dominant']} "
                          f"step={r['step_time_s']:.4f}s mfu={r['mfu']:.3f} "
                          f"compile={rec['compile_s']}s "
                          f"fits={rec['fits_hbm']}", flush=True)
                except Exception as e:
                    failures.append(tag)
                    path.with_suffix(".error").write_text(
                        traceback.format_exc())
                    print(f"FAIL {tag}: {e}", flush=True)
    if failures:
        raise SystemExit(f"{len(failures)} dry-run failures: {failures}")


if __name__ == "__main__":
    main()
