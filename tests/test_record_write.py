"""``record_write`` (ops/record_write.py): the compact step's per-row columns
written into the row records by a streamed Pallas kernel, byte for byte what
XLA's lane-slice update writes; ``record_write`` rides every ``iteration``
event (1 where the kernel writes, 0 where the lane-slice update does), and
the benchmark's ``grower.record_write`` and
``grower.record_write_s_per_iter`` read it and the kernel's device seconds
through the reducers that are there."""
import json

import numpy as np
import pytest

import jax.numpy as jnp
from jax import lax

import lightgbm_tpu as lgb
from lightgbm_tpu.boosting import gbdt as gbdt_mod
from lightgbm_tpu.obs import flight
from lightgbm_tpu.ops.record_write import lane_window, record_write

from benchmarks import run as bench_run
from utils import OwnThreadRing

CELLS = ["higgs_train", "higgs_b63_train", "istella_train",
         "criteo_dp4_train", "higgs_quant_train"]


def lane_slice_write(work, cols, grad_off, **_):
    """The update the kernel replaces (and where the step keeps it)."""
    u8 = lax.bitcast_convert_type(cols.T, jnp.uint8).reshape(
        cols.shape[1], 4 * cols.shape[0])
    return work.at[:, grad_off:grad_off + u8.shape[1]].set(u8)


# ----------------------------------------------------------- the kernel
@pytest.mark.parametrize("num_cols,ncols,grad_off,rows,pad,block", [
    (128, 4, 28, 5000, 0, 1024),       # higgs's record; rows % block != 0
    (128, 4, 28, 3000, 416, 1024),     # pad rows after the real ones
    (256, 4, 220, 2900, 100, 1024),    # istella's: one lane tile of two
    (128, 12, 28, 2500, 64, 512),      # multiclass K = 3 at k = 0
    (256, 12, 100, 1111, 0, 256),      # lanes over two tiles: whole record
    (384, 4, 250, 700, 33, 128),       # tiles 1-2 of three: whole record
    (128, 4, 0, 100, 0, 4096),         # one block, smaller than its size
])
def test_kernel_writes_the_bytes_the_lane_slice_update_writes(
        num_cols, ncols, grad_off, rows, pad, block):
    rng = np.random.RandomState(rows)
    work = rng.randint(0, 256, (rows + pad, num_cols)).astype(np.uint8)
    cols = rng.randn(ncols, rows + pad).astype(np.float32)
    cols[:, rows:] = 0.0                 # the step pads its columns with 0
    cols[0, :7] = [0.0, -0.0, np.inf, -np.inf, 1e-45, np.nan, 3.4e38]
    want = np.asarray(lane_slice_write(jnp.asarray(work), jnp.asarray(cols),
                                       grad_off))
    got = np.asarray(record_write(jnp.asarray(work), jnp.asarray(cols),
                                  grad_off, block_rows=block,
                                  interpret=True))
    assert np.array_equal(got, want)
    lanes = np.zeros(num_cols, bool)
    lanes[grad_off:grad_off + 4 * ncols] = True
    assert np.array_equal(got[:, ~lanes], work[:, ~lanes])
    assert np.array_equal(np.ascontiguousarray(got[:, lanes]).view(np.uint32),
                          np.ascontiguousarray(cols.T).view(np.uint32))


@pytest.mark.parametrize("num_cols,lo,hi,want", [
    (128, 28, 44, (0, 128)), (256, 220, 236, (128, 128)),
    (256, 100, 148, (0, 256)), (384, 250, 266, (0, 384)),
    (384, 260, 300, (256, 128)), (512, 250, 266, (0, 512)),
    (512, 300, 500, (256, 256))])
def test_the_block_spans_the_lane_tiles_of_the_written_lanes(num_cols, lo,
                                                             hi, want):
    assert lane_window(num_cols, lo, hi) == want


# --------------------------------------------------- the step and its counter
def train(monkeypatch, rounds=2, **more):
    rng = np.random.RandomState(5)
    x = rng.randn(1200, 6).astype(np.float32)
    signal = x[:, 0] - 0.5 * x[:, 3] + 0.4 * rng.randn(1200)
    params = dict({"objective": "binary", "num_leaves": 7, "max_bin": 63,
                   "min_data_in_leaf": 20, "verbosity": -1,
                   "tpu_grower": "compact", "tpu_fused_interpret": True,
                   "tpu_fused_block": 128}, **more)
    if params["objective"] == "multiclass":
        y = np.digitize(signal, [-0.5, 0.5]).astype(float)
    elif params["objective"] == "lambdarank":
        y = np.clip(np.round(signal + 1.5), 0, 3)
    else:
        y = (signal > 0).astype(float)
    group = [100] * 12 if params["objective"] == "lambdarank" else None
    monkeypatch.setattr(flight, "_RECORDER", OwnThreadRing())
    bst = lgb.train(params, lgb.Dataset(x, label=y, group=group,
                                        params=params), rounds)
    ticks = [e for e in flight.recorder().events()
             if e["event"] == "iteration"]
    return bst, ticks


@pytest.mark.parametrize("more", [
    {},
    {"objective": "multiclass", "num_class": 3},
    {"objective": "lambdarank"},
    {"use_quantized_grad": True},
    {"tree_learner": "data", "tpu_mesh_shape": "4"},
], ids=["binary", "multiclass", "lambdarank", "quantized", "data-parallel"])
def test_the_kernel_trains_the_model_the_lane_slice_update_trains(
        monkeypatch, more):
    calls = []

    def counted(*args, **kwargs):
        calls.append(args[1].shape)
        return record_write(*args, **kwargs)

    monkeypatch.setattr(gbdt_mod, "record_write", counted)
    bst, ticks = train(monkeypatch, **more)
    assert calls, "the step did not call the kernel"
    assert [e["record_write"] for e in ticks] == [1, 1]
    # the kernel steered off in the test: the step's lane-slice update
    monkeypatch.setattr(gbdt_mod, "record_write", lane_slice_write)
    off, _ = train(monkeypatch, **more)
    assert bst.model_to_string() == off.model_to_string()


@pytest.mark.parametrize("more", [{"tpu_fused": "off"},
                                  {"tpu_grower": "masked"}])
def test_without_the_fused_kernel_the_counter_reads_0(monkeypatch, more):
    def refused(*args, **kwargs):
        raise AssertionError("record_write runs only beside the fused kernel")

    monkeypatch.setattr(gbdt_mod, "record_write", refused)
    _, ticks = train(monkeypatch, **more)
    assert [e["record_write"] for e in ticks] == [0, 0]


# ------------------------------------------------- the benchmark's metrics
def hand_made_run(profile=None, **counters):
    tick = dict({"dispatches": 1, "host_syncs": 1}, **counters)
    return {
        "iterations": 2,
        "profile": profile,
        "spans": [("update", 0.0, 1.0), ("update", 1.0, 2.0)],
        "records": {"spans": [("iteration", 0.0, 1.0, None, 5)],
                    "compiles": [],
                    "iterations": [dict(tick, t1=0.9), dict(tick, t1=1.9)]},
    }


@pytest.mark.parametrize("value", [1, 0])
def test_the_counter_metric_reads_the_iteration_events(value):
    got = bench_run.per_layer_metrics(["grower.record_write"],
                                      hand_made_run(record_write=value))
    assert got["grower.record_write"]["value"] == value


def test_the_seconds_metric_reads_the_kernel_events():
    events = [("fused_split_step.3", 0.10, 0.30),
              ("record_write.1", 0.40, 0.404),
              ("record_write.1", 1.40, 1.406), ("fusion.7", 1.5, 1.6)]
    profile = {"devices": {0: events}, "window": (0.0, 2.0)}
    got = bench_run.per_layer_metrics(
        ["grower.record_write_s_per_iter", "grower.other_device_s_per_iter"],
        hand_made_run(profile))
    assert got["grower.record_write_s_per_iter"]["value"] == \
        pytest.approx(0.005)
    # inside the grower's time outside the fused kernel, not the kernel's
    assert got["grower.other_device_s_per_iter"]["value"] == \
        pytest.approx(0.055)


def test_a_program_without_the_kernel_leaves_both_metrics_out():
    # the parent of PR 40 has no such counter and no such kernel
    profile = {"devices": {0: [("dynamic-update-slice.178", 0.1, 0.2)]},
               "window": (0.0, 2.0)}
    assert bench_run.per_layer_metrics(
        ["grower.record_write", "grower.record_write_s_per_iter"],
        hand_made_run(profile)) == {}


@pytest.mark.parametrize("name,unit,better,reducer,args", [
    ("grower.record_write", "count", "higher", "update_loop",
     {"what": "record_write"}),
    ("grower.record_write_s_per_iter", "s/iter", "lower",
     "device_self_time", {"pattern": "^record_write"}),
])
def test_the_benchmark_lists_the_metrics_for_all_five_cells(
        name, unit, better, reducer, args):
    with open(bench_run.ROOT + "/BENCHMARK.json") as f:
        bench = json.load(f)
    entry, = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": ("program_counter" if reducer == "update_loop"
                                else "device_trace"),
                     "layer": "grower", "moves": "train_s_per_iter",
                     "workloads": CELLS}
    # appended together after the metrics that were there before them
    # (later entries, such as kernel.carry_bytes, follow them)
    names = [m["name"] for m in bench["per_layer"]]
    i = names.index("grower.record_write")
    assert names[i:i + 2] == [
        "grower.record_write", "grower.record_write_s_per_iter"]
    assert i > names.index("device.idle_unnamed_s_per_iter")
    with open(bench_run.ROOT + "/benchmarks/metrics/" + name + ".json") as f:
        spec = json.load(f)
    assert spec["reducer"] == reducer and spec["args"] == args
    for key in ("name", "unit", "better", "layer", "source", "moves"):
        assert spec[key] == entry[key], key
