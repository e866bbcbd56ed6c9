"""``hist_levels`` rides every ``iteration`` event: how many levels the
one-hot of the fused kernel's histogram flush has in the step that ran (2:
bin = 64 hi + lo, more than 64 bins a feature; 1: the whole stride; 0: the
fused kernel is off), and the benchmark's ``kernel.hist_levels`` reads it
from the flight ring through the ``update_loop`` reducer that is there. So
does ``carry_bytes``, the bytes of VMEM a carried row's byte takes in the
kernel's ring carries (1: packed bytes; 0: the kernel is off), which
``kernel.carry_bytes`` reads."""
import json

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import flight
from lightgbm_tpu.ops.fused_split import (_carry_shape, carry_bytes,
                                          hist_levels)

from benchmarks import run as bench_run
from utils import OwnThreadRing

METRIC = "kernel.hist_levels"
CELLS = ["higgs_train", "higgs_b63_train", "istella_train",
         "criteo_dp4_train", "higgs_quant_train"]


@pytest.mark.parametrize("features,bins,want", [
    (28, 256, 2), (28, 255, 2), (220, 256, 2), (67, 256, 2),   # stride 256
    (28, 100, 2), (28, 65, 2), (5, 128, 2),                    # stride 128
    (28, 63, 2),      # an awkward count under 64 pads to a stride of 128
    (28, 64, 1), (28, 32, 1), (28, 16, 1), (28, 48, 1), (28, 8, 2)])
def test_levels_are_read_off_the_bin_stride(features, bins, want):
    assert hist_levels(features, bins) == want


def ticks(monkeypatch, max_bin, **more):
    rng = np.random.RandomState(11)
    x = rng.randn(1500, 6).astype(np.float32)
    y = (x[:, 0] - 0.5 * x[:, 3] + 0.4 * rng.randn(1500) > 0).astype(float)
    params = dict({"objective": "binary", "num_leaves": 7,
                   "max_bin": max_bin, "min_data_in_leaf": 20,
                   "verbosity": -1, "tpu_grower": "compact",
                   "tpu_fused_interpret": True, "tpu_fused_block": 128},
                  **more)
    monkeypatch.setattr(flight, "_RECORDER", OwnThreadRing())
    lgb.train(params, lgb.Dataset(x, label=y, params=params), 2)
    return [e for e in flight.recorder().events()
            if e["event"] == "iteration"]


@pytest.mark.parametrize("max_bin,more,want", [
    (255, {}, 2),
    (255, {"use_quantized_grad": True}, 2),
    (63, {}, 1),
    (255, {"tpu_fused": "off"}, 0),
    (63, {"tpu_fused": "off"}, 0),
    (255, {"tpu_grower": "masked"}, 0),
])
def test_hist_levels_rides_every_iteration_event(monkeypatch, max_bin, more,
                                                 want):
    got = ticks(monkeypatch, max_bin, **more)
    assert len(got) == 2
    assert [e["hist_levels"] for e in got] == [want, want]


# ------------------------------------------------- the benchmark's metric
def hand_made_run(**counters):
    tick = dict({"dispatches": 1, "host_syncs": 1}, **counters)
    return {
        "iterations": 2,
        "spans": [("update", 0.0, 1.0), ("update", 1.0, 2.0)],
        "records": {"spans": [("iteration", 0.0, 1.0, None, 5)],
                    "compiles": [],
                    "iterations": [dict(tick, t1=0.9), dict(tick, t1=1.9)]},
    }


@pytest.mark.parametrize("levels", [2, 1, 0])
def test_the_metric_reads_the_counter_through_the_harness(levels):
    got = bench_run.per_layer_metrics([METRIC],
                                      hand_made_run(hist_levels=levels))
    assert got[METRIC]["value"] == levels
    assert got[METRIC]["unit"] == "count"


def test_a_program_without_the_counter_leaves_the_metric_out():
    # the parent of PR 38 has no such counter: its side reads nothing
    assert bench_run.per_layer_metrics([METRIC], hand_made_run()) == {}


def test_the_benchmark_lists_the_metric_for_all_five_cells():
    with open(bench_run.ROOT + "/BENCHMARK.json") as f:
        bench = json.load(f)
    entry, = [m for m in bench["per_layer"] if m["name"] == METRIC]
    assert entry == {
        "name": METRIC, "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "train_s_per_iter", "workloads": CELLS}
    with open(bench_run.ROOT + "/benchmarks/metrics/" + METRIC + ".json") as f:
        spec = json.load(f)
    assert spec["reducer"] == "update_loop"
    assert spec["args"] == {"what": "hist_levels"}
    for key in ("name", "unit", "better", "layer", "source", "moves"):
        assert spec[key] == entry[key], key


# ------------------------------------------- carry_bytes rides along too
CARRY_METRIC = "kernel.carry_bytes"


@pytest.mark.parametrize("block,cols", [(384, 128), (256, 256), (32, 128),
                                        (128, 384)])
def test_carry_bytes_is_read_off_the_carry_scratch(block, cols):
    # the ring carries hold packed bytes: four rows a 32-bit word
    assert _carry_shape(block, cols) == (block // 4, cols)
    assert carry_bytes(block, cols) == 1


@pytest.mark.parametrize("more,want", [
    ({}, 1),
    ({"use_quantized_grad": True}, 1),
    ({"tpu_fused": "off"}, 0),
    ({"tpu_grower": "masked"}, 0),
])
def test_carry_bytes_rides_every_iteration_event(monkeypatch, more, want):
    got = ticks(monkeypatch, 255, **more)
    assert [e["carry_bytes"] for e in got] == [want, want]


@pytest.mark.parametrize("value", [1, 4, 0])
def test_the_carry_metric_reads_the_counter_through_the_harness(value):
    got = bench_run.per_layer_metrics([CARRY_METRIC],
                                      hand_made_run(carry_bytes=value))
    assert got[CARRY_METRIC]["value"] == value
    assert got[CARRY_METRIC]["unit"] == "count"


def test_a_program_without_carry_bytes_leaves_the_metric_out():
    # a program from before the counter: its side reads nothing
    assert bench_run.per_layer_metrics([CARRY_METRIC],
                                       hand_made_run(hist_levels=2)) == {}


def test_the_benchmark_lists_the_carry_metric_for_all_five_cells():
    with open(bench_run.ROOT + "/BENCHMARK.json") as f:
        bench = json.load(f)
    entry, = [m for m in bench["per_layer"] if m["name"] == CARRY_METRIC]
    assert entry == {
        "name": CARRY_METRIC, "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "kernels",
        "moves": "train_s_per_iter", "workloads": CELLS}
    with open(bench_run.ROOT + "/benchmarks/metrics/" + CARRY_METRIC
              + ".json") as f:
        spec = json.load(f)
    assert spec["reducer"] == "update_loop"
    assert spec["args"] == {"what": "carry_bytes"}
    for key in ("name", "unit", "better", "layer", "source", "moves"):
        assert spec[key] == entry[key], key
