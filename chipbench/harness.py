"""One run of one cell: everything here is found by name.

- the cell: an entry of ``workloads`` in ``BENCHMARK.json``;
- its configuration: ``configs/<config>.json`` (the entry of ``configs``
  names the file), whose ``family`` names ``families/<family>.py`` (the
  program's config, the weights, the counts) and, beside it,
  ``families/<family>_reference.py`` (the plain reference);
- its traffic: ``traffic/<traffic>.json``, whose ``driver`` names
  ``drivers/<driver>.py``, which runs set-up, window and comparison, and
  whose ``window`` sizes the run (``traffic.window``);
- each per-layer metric: ``metrics/<name>.py``, whose ``read(run)`` returns
  the number, or None where the run has nothing to read.

A later cell, configuration, traffic mix or metric adds files and entries;
nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType, SimpleNamespace

import numpy as np

from chipbench import traffic

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def load_module(path: Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def seed32(seed: int) -> int:
    """A 32-bit key for JAX from a seed of any size."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0])


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def cell(workload: str, seed: int, seconds: int, trace: bool,
         bench_path: Path = ROOT / "BENCHMARK.json") -> SimpleNamespace:
    """Everything a run of ``workload`` reads, before any device is touched."""
    bench = load_json(bench_path)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; cells: "
                         f"{sorted(cells)}")
    w = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    conf = load_json(ROOT / entry["file"])
    mix = load_json(HERE / "traffic" / f"{w['traffic']}.json")
    family = load_module(HERE / "families" / f"{conf['family']}.py",
                         f"chipbench.families.{conf['family']}")
    driver = load_module(HERE / "drivers" / f"{mix['driver']}.py",
                         f"chipbench.drivers.{mix['driver']}")
    return SimpleNamespace(
        bench=bench, workload=w, conf=conf, mix=mix,
        family=family, driver=driver, seed=int(seed), seed32=seed32(seed),
        seconds=int(seconds), trace=bool(trace),
        window=traffic.window(mix, int(seconds)))


def metrics_for(bench: dict, workload: str, kind: str):
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


def per_layer(bench: dict, workload: str, run) -> dict:
    out = {}
    for m in metrics_for(bench, workload, "per_layer"):
        reader = load_module(HERE / "metrics" / f"{m['name']}.py",
                             f"chipbench.metrics.{m['name']}")
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def per_layer_all(run) -> dict:
    """Every reader in ``metrics/`` on ``run``, whatever the cell."""
    bench = {"per_layer": [{"name": p.stem, "unit": ""} for p in
                           sorted((HERE / "metrics").glob("*.py"))
                           if p.stem != "__init__"]}
    return per_layer(bench, "", run)
