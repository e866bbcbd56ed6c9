"""Every data file loads, and every name in BENCHMARK.json resolves to
the files the harness finds by it."""
import glob
import importlib
import json
import os

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


def names(kind):
    return sorted(os.path.basename(p)[:-5]
                  for p in glob.glob(os.path.join(HERE, kind, "*.json")))


def load(kind, name):
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", names("configs"))
def test_config_file(name):
    cfg = load("configs", name)
    assert cfg["name"] == name
    for key in ("source", "generator", "sizes", "params", "reduced",
                "assumed"):
        assert key in cfg, key
    assert cfg["params"]["tpu_autotune"] == "off"
    assert "tpu_autotune" in cfg["assumed"]
    gen = importlib.import_module(f"benchmarks.generators.{cfg['generator']}")
    assert callable(gen.make)
    importlib.import_module(
        f"benchmarks.reference.{cfg['params']['objective']}")


@pytest.mark.parametrize("name", names("workloads"))
def test_workload_file(name):
    cell = load("workloads", name)
    assert cell["name"] == name
    assert cell["config"] in names("configs")
    assert cell["chips"] in (1, 4)
    assert 0 < len(cell["why"]) <= 200
    assert cell["limits"]
    traffic = importlib.import_module(f"benchmarks.traffic.{cell['traffic']}")
    assert callable(traffic.run)


@pytest.mark.parametrize("name", names("metrics"))
def test_metric_file(name):
    spec = load("metrics", name)
    assert spec["name"] == name
    assert spec["better"] in ("lower", "higher")
    assert spec["source"] in ("device_trace", "program_span",
                              "program_counter", "host_clock")
    reducer = importlib.import_module(f"benchmarks.reducers.{spec['reducer']}")
    assert callable(reducer.reduce)
    assert spec["moves"] in [m["name"] for m in BENCH["end_to_end"]]


def test_benchmark_json_resolves():
    assert sorted(c["name"] for c in BENCH["configs"]) == sorted(
        {w["config"] for w in BENCH["workloads"]})
    for c in BENCH["configs"]:
        cfg = load("configs", c["name"])
        assert c["file"] == f"benchmarks/configs/{c['name']}.json"
        assert c["source"] == cfg["source"]
        assert c["reduced"] == cfg["reduced"]
    cells = [w["name"] for w in BENCH["workloads"]]
    for w in BENCH["workloads"]:
        cell = load("workloads", w["name"])
        for key in ("config", "traffic", "chips", "why"):
            assert w[key] == cell[key], (w["name"], key)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in BENCH["per_layer"]:
        spec = load("metrics", m["name"])
        for key in ("unit", "better", "source", "layer", "moves"):
            assert m[key] == spec[key], (m["name"], key)
        assert set(m["workloads"]) <= set(cells)
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
    with open(os.path.join(HERE, "peaks.json")) as f:
        assert "TPU v5 lite" in json.load(f)
