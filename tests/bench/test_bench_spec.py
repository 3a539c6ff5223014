"""BENCHMARK.json: names, units and keys as the contract has them, and
every configuration, mix and metric a cell names found by its name."""
import json
import re

import pytest

import _paths  # noqa: F401
from bench import spec as S

BM = S.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["command"][1].startswith(BM["paths"][0] + "/")
    assert 1 <= BM["run_seconds"] <= 51


@pytest.mark.parametrize("group", ["configs", "workloads", "end_to_end",
                                   "per_layer"])
def test_names_are_plain_and_unique(group):
    names = [e["name"] for e in BM[group]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n


@pytest.mark.parametrize("group", ["end_to_end", "per_layer"])
def test_metric_entries(group):
    keys = {"name", "unit", "better", "source", "workloads"}
    keys |= {"bound"} if group == "end_to_end" else {"layer", "moves"}
    cells = {c["name"] for c in BM["workloads"]}
    e2e = {m["name"] for m in BM["end_to_end"]}
    for m in BM[group]:
        assert set(m) <= keys and set(m) >= keys - {"workloads"}, m
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
        if group == "end_to_end":
            assert m["source"] in ("host_clock", "device_trace")
            assert 0.01 <= m["bound"] <= 0.25
        else:
            assert m["moves"] in e2e
            assert m["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")


@pytest.mark.parametrize("cell", BM["workloads"], ids=lambda c: c["name"])
def test_cell_resolves(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
    cfg = S.load_json("configs", cell["config"])
    ref = S.load_module("configs", cfg["reference"])
    for fn in ("init", "program_params", "forward", "sites"):
        assert callable(getattr(ref, fn))
    S.load_json("traffic", cell["traffic"])
    entry = next(c for c in BM["configs"] if c["name"] == cell["config"])
    assert entry["file"] == f"bench/configs/{cell['config']}.json"
    assert entry["reduced"] == cfg["reduced"]
    e2e = S.metrics_for(BM, cell["name"], trace=False)
    names = {m["name"] for m in e2e}
    assert "setup_s" in names and len(names) >= 2
    assert S.metrics_for(BM, cell["name"], trace=True)


@pytest.mark.parametrize("metric", BM["end_to_end"] + BM["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_reader_found(metric):
    assert callable(S.load_module("metrics", metric["name"]).read)


def test_config_sources_and_files():
    for c in BM["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["source"].startswith("https://")
        with open(S.ROOT / c["file"]) as f:
            assert json.load(f)["source"] == c["source"]


def test_command_refuses_a_machine_without_a_tpu():
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = BM["workloads"][0]["name"]
    out = subprocess.run(
        [sys.executable, *BM["command"][1:], "--workload", cell, "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=S.ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 2
    assert out.stdout == ""
    assert "TPU" in out.stderr


@pytest.mark.parametrize("path", sorted((S.BENCH / "metrics").glob("[!_]*.py")),
                         ids=lambda p: p.stem)
def test_every_reader_file_loads(path):
    assert callable(S.load_module("metrics", path.stem).read)
