"""BENCHMARK.json names only what the harness can find, within the
limits the benchmark format sets on names, sizes and keys."""
import json
import re

import pytest

from chipbench import harness

BENCH = harness.load_json(harness.ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys_and_size():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert len(json.dumps(BENCH)) < 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51


def test_every_name_resolves_to_files():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        conf = harness.load_json(harness.ROOT / c["file"])
        assert conf["name"] == c["name"]
        assert set(c["reduced"]) == set(conf["reduced"])
        assert (harness.HERE / "families" / f"{conf['family']}.py").exists()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        mix = harness.load_json(harness.HERE / "traffic" /
                                f"{w['traffic']}.json")
        assert (harness.HERE / "drivers" / f"{mix['driver']}.py").exists()
        assert w["chips"] in (1, 4)
    for m in BENCH["per_layer"]:
        assert (harness.HERE / "metrics" / f"{m['name']}.py").exists()


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end",
                                  "per_layer"])
def test_names_units_and_texts(kind):
    names = [e["name"] for e in BENCH[kind]]
    assert len(names) == len(set(names))
    for e in BENCH[kind]:
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]) and e["better"] in ("lower",
                                                             "higher")
        for k in ("why", "layer", "source"):
            if k in e:
                assert 1 <= len(e[k]) <= 200 and "\n" not in e[k]
        if kind == "end_to_end":
            assert 0.01 <= e["bound"] <= 0.25
        if kind == "per_layer":
            e2e = {m["name"] for m in BENCH["end_to_end"]}
            assert e["moves"] in e2e
            cells = {w["name"] for w in BENCH["workloads"]}
            assert set(e.get("workloads", cells)) <= cells
