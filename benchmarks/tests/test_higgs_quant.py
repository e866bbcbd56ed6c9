"""The ``higgs_quant`` configuration and its cell ``higgs_quant_train``:
the files load and say what the cell needs, a rehearsal of the cell at a
toy size is ``correct`` under the cell's own limits and is not with the
leaf renewal off (the cell's program control), and the two ``quant``
metrics each through the harness's own lookup on a hand-made record."""
import copy
import json

import pytest

from benchmarks import correct, run as bench_run

with open(bench_run.ROOT + "/BENCHMARK.json") as f:
    BENCH = json.load(f)
CELL, TWIN = "higgs_quant_train", "higgs_train"
SEED = 2**31 + 3737
NEW = ["quant.int8_hist", "quant.renew_leaf"]
QUANT = {"use_quantized_grad": True, "num_grad_quant_bins": 4,
         "quant_train_renew_leaf": True, "stochastic_rounding": True}


# ------------------------------------------------------------ the files
def test_the_files_say_what_the_cell_needs():
    cell, config = bench_run.load_cell(CELL)
    _, twin = bench_run.load_cell(TWIN)
    assert cell["chips"] == 1 and cell["traffic"] == "train_window"
    assert cell["traffic_params"] == {"warmup_updates": 1,
                                      "trace_max_iterations": 3}
    assert config["generator"] == twin["generator"]
    assert config["sizes"] == twin["sizes"] == {"rows": 10_500_000,
                                                "features": 28}
    params = config["params"]
    assert not [k for k in params if k.startswith("tpu_")]
    # higgs with the quantized keys, and nothing else changed
    assert {k: v for k, v in params.items() if k not in QUANT} == {
        k: v for k, v in twin["params"].items() if not k.startswith("tpu_")}
    assert {k: params[k] for k in QUANT} == QUANT
    assert config["reduced"] == ["num_iterations"]
    for key in ("source", "source_part", "precision", "guarantees",
                "assumed", "cut"):
        assert config[key], key
    for key in ("data", "quant_train_renew_leaf", "num_grad_quant_bins",
                "stochastic_rounding"):
        assert key in config["assumed"], key
    assert len(config["source"]) <= 200
    assert set(cell["limits"]) == {
        "trees_missing", "leaf_count_wrong", "score_gap", "leaf_value_gap",
        "leaf_hessian_gap", "split_gain_gap"}
    assert cell["limits"]["leaf_count_wrong"] == 0
    assert cell["limits"]["trees_missing"] == 0
    assert cell["control_params"] == {"quant_train_renew_leaf": False}
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1


def test_the_cell_reports_what_its_twin_reports_and_the_two_counters():
    def listed(name):
        return {m["name"] for m in BENCH["per_layer"]
                if name in m.get("workloads", [name])}

    assert listed(CELL) == listed(TWIN) | set(NEW)
    assert {"fused_split_roofline", "step_mfu",
            "kernel.fused_split_ns_per_hist_row"} <= listed(CELL)
    for name in NEW:
        entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["source"] == "program_counter"
        assert entry["better"] == "higher"
        assert entry["moves"] == "train_s_per_iter"
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert CELL in e2e["train_s_per_iter"]["workloads"]


# --------------------------------------------------------- the rehearsal
def toy(**params):
    cell, config = bench_run.load_cell(CELL)
    cell, config = copy.deepcopy(cell), copy.deepcopy(config)
    config["sizes"]["rows"] = 20_000
    config["params"].update(num_leaves=15, min_data_in_leaf=20,
                            verbosity=-1, **params)
    cell["traffic_params"]["max_iterations"] = correct.CHECKED_TREES
    return cell, config


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """The cell at 20,000 rows through ``run.drive``, as it is and with
    the leaf renewal off; every reading against the cell's own
    configuration, as ``control.py`` reads its program control."""
    out = {}
    for renew in (True, False):
        cell, config = toy(quant_train_renew_leaf=renew)
        _, got, _, _ = bench_run.drive(
            CELL, SEED, 1e9, False, files=(cell, config),
            scratch=str(tmp_path_factory.mktemp("scratch")))
        readings = correct.reference_readings(got["produced"], got["data"],
                                              toy()[1])
        out[renew] = (got, readings, cell["limits"])
    return out


def test_a_rehearsal_of_the_cell_is_correct_under_its_limits(rehearsal):
    got, readings, limits = rehearsal[True]
    assert got["failed"] == 0
    assert got["iterations"] == correct.CHECKED_TREES
    ok, rows = correct.judge(readings, limits)
    assert ok, rows
    assert readings["leaf_count_wrong"] == 0


def test_with_the_renewal_off_it_is_not_correct(rehearsal):
    _, readings, limits = rehearsal[False]
    ok, rows = correct.judge(readings, limits)
    assert not ok, rows
    failed = [name for name, value, limit in rows if value > limit]
    # the leaves hold the 4-level sums' values: nothing else moved
    assert failed == ["leaf_value_gap"], rows
    assert readings["leaf_count_wrong"] == 0


# ----------------------------------------------------------- the metrics
def hand_made_run(**quant):
    tick = dict({"dispatches": 1, "host_syncs": 1}, **quant)
    return {
        "iterations": 2,
        "spans": [("update", 0.0, 1.0), ("update", 1.0, 2.0)],
        "records": {"spans": [("iteration", 0.0, 1.0, None, 5)],
                    "compiles": [],
                    "iterations": [dict(tick, t1=0.9), dict(tick, t1=1.9)]},
    }


@pytest.mark.parametrize("hist,renew", [(1, 1), (0, 1), (1, 0)])
def test_the_two_metrics_through_the_harness(hist, renew):
    got = bench_run.per_layer_metrics(NEW, hand_made_run(
        quant_hist=hist, quant_bins=4, quant_renew=renew))
    assert {name: got[name]["value"] for name in NEW} == {
        "quant.int8_hist": hist, "quant.renew_leaf": renew}
    for name in NEW:
        assert got[name]["unit"] == "count"


def test_a_program_without_the_counters_leaves_the_metrics_out():
    assert bench_run.per_layer_metrics(NEW, hand_made_run()) == {}
