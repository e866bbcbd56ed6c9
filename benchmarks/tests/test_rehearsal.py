"""``train_window`` at toy size on the CPU, past the harness's look for a
chip: a sound run ends in a well-formed, correct last line; the control
and each fault a one-chip training cell can have come out not correct."""
import copy
import json

import pytest

from benchmarks import control, correct, run as bench_run

with open(bench_run.ROOT + "/BENCHMARK.json") as f:
    BENCH = json.load(f)
SEED = 2**31 + 4321
TOY = {"higgs_train": {"rows": 6000}, "higgs_b63_train": {"rows": 6000},
       "msltr_train": {"rows": 6000}}


def toy(name):
    """The cell's own files at a size the CPU holds: rows and leaves cut,
    everything else as the cell runs it."""
    cell, config = bench_run.load_cell(name)
    config = copy.deepcopy(config)
    config["sizes"].update(TOY[name])
    config["params"].update(num_leaves=7, min_data_in_leaf=20, verbosity=-1)
    return cell, config


def listed(name):
    """BENCHMARK.json with the cell on every metric's list, as the PR that
    lists a cell whose files are already here would leave it."""
    bench = copy.deepcopy(BENCH)
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in metric and name not in metric["workloads"]:
            metric["workloads"].append(name)
    return bench


def run_toy(name, tmp_path, **kw):
    return bench_run.run_cell(name, SEED, 0.3, False, listed(name),
                              scratch=str(tmp_path), files=toy(name), **kw)


@pytest.mark.parametrize("name", sorted(TOY))
def test_sound_run_ends_in_a_well_formed_line(name, tmp_path):
    result = json.loads(json.dumps(run_toy(name, tmp_path)))
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert list(result)[-1] == "compared"
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_s_per_iter", "setup_s"}
    for metric in result["metrics"].values():
        assert metric["value"] > 0 and metric["unit"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        result["device"])
    for name_, row in result["compared"].items():
        assert row["value"] <= row["limit"], name_


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    """One sound toy run of each cell, kept for the controls."""
    out = {}
    for name in sorted(TOY):
        files = toy(name)
        _, got, cell, config = bench_run.drive(
            name, SEED, 0.3, False, files=files,
            scratch=str(tmp_path_factory.mktemp("scratch")))
        out[name] = (got, cell, config)
    return out


@pytest.mark.parametrize("name", sorted(TOY))
def test_control_in_bfloat16_is_not_correct(name, sound):
    import jax.numpy as jnp
    got, cell, config = sound[name]
    ok, _ = correct.judge(correct.reference_readings(
        got["produced"], got["data"], config), cell["limits"])
    assert ok
    ok, rows = correct.judge(correct.reference_readings(
        got["produced"], got["data"], config, precision=jnp.bfloat16),
        cell["limits"])
    assert not ok, rows


def test_program_control_quantized_gradients_is_not_correct(tmp_path):
    cell, config = toy("higgs_train")
    config["params"].update(cell["control_params"])
    result = bench_run.run_cell("higgs_train", SEED, 0.3, False, BENCH,
                                scratch=str(tmp_path), files=(cell, config))
    assert result["correct"] is False


def test_fault_state_unchanged(monkeypatch, tmp_path):
    import lightgbm_tpu as lgb
    calls = []
    real = lgb.Booster.update

    def update(self, *a, **kw):
        calls.append(1)
        if len(calls) <= 1:              # the warm-up update is sound
            return real(self, *a, **kw)
        return False                     # the window's: state as it was

    monkeypatch.setattr(lgb.Booster, "update", update)
    result = run_toy("higgs_train", tmp_path)
    assert result["correct"] is False
    assert result["compared"]["trees_missing"]["value"] >= 1


def test_fault_half_of_the_rows_left_out(monkeypatch, tmp_path):
    import lightgbm_tpu as lgb
    import numpy as np
    real = lgb.Dataset

    def dataset(data, label=None, **kw):
        weight = (np.arange(len(label)) % 2 == 0).astype(np.float32)
        return real(data, label=label, weight=weight, **kw)

    monkeypatch.setattr(lgb, "Dataset", dataset)
    result = run_toy("higgs_train", tmp_path)
    assert result["correct"] is False
    assert (result["compared"]["leaf_value_gap"]["value"]
            > result["compared"]["leaf_value_gap"]["limit"])


def test_fault_an_answer_altered_where_it_is_produced(monkeypatch, tmp_path):
    import lightgbm_tpu as lgb
    real = lgb.Booster.model_to_string

    def model_to_string(self, *a, **kw):
        text = real(self, *a, **kw)
        return control.altered_leaf(
            {"model_text": text, "first_window_tree": 2})["model_text"]

    monkeypatch.setattr(lgb.Booster, "model_to_string", model_to_string)
    result = run_toy("higgs_train", tmp_path)
    assert result["correct"] is False


def test_the_command_refuses_without_a_chip(capsys):
    rc = bench_run.main(["--workload", "higgs_train", "--seed", "1",
                         "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out.strip() == ""
