"""The ``criteo_dp`` configuration and its four-chip cell: the files load
and say what the cell needs, the generator keeps its contract (one
population in blocks, the seed orders the blocks), and the four
``collectives`` metrics each through the harness's own lookup, on
hand-made records and a hand-made profile. The cell's toy run over four
devices is in tier-1 (``tests/test_data_parallel_rows.py``: this directory's
``conftest.py`` asks for no virtual devices)."""
import json

import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.generators import criteo_like
from benchmarks.reducers import chip_spread

with open(bench_run.ROOT + "/BENCHMARK.json") as f:
    BENCH = json.load(f)
CELL = "criteo_dp4_train"
SEEDS = (7, 2**31 + 4321)
TOY = {"rows": 3 * criteo_like.BLOCK + 123, "features": 67}
NEW = ["collective.device_s_per_iter", "collective.bytes_in_step",
       "collective.count_in_step", "mesh.kernel_skew_pct"]


# ------------------------------------------------------------ the files
def test_the_files_say_what_the_cell_needs():
    cell, config = bench_run.load_cell(CELL)
    assert cell["chips"] == 4 and cell["traffic"] == "train_window"
    assert config["sizes"] == {"rows": 40_000_000, "features": 67}
    assert config["sizes"]["rows"] % (4 * criteo_like.BLOCK) == 0
    params = config["params"]
    assert params["tree_learner"] == "data" and params["num_leaves"] == 255
    assert not [k for k in params if k.startswith("tpu_")]
    assert config["reduced"] == ["rows", "num_iterations"]
    assert set(cell["limits"]) == {
        "trees_missing", "leaf_count_wrong", "score_gap", "leaf_value_gap",
        "leaf_hessian_gap", "split_gain_gap"}
    assert cell["limits"]["leaf_count_wrong"] == 0
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 4
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)


def test_the_cell_joins_the_metrics_that_read_rightly_over_chips():
    listed = {m["name"] for m in BENCH["per_layer"]
              if CELL in m["workloads"]}
    assert set(NEW) <= listed
    assert {"step_mfu", "device.idle_pct", "kernel.fused_split_s_per_iter",
            "grower.other_device_s_per_iter"} <= listed
    # one chip's peak against the whole forest's work, the first device's
    # calls against global rows: not for a cell on four chips
    assert not {"fused_split_roofline", "kernel.fused_split_us_per_call",
                "kernel.fused_split_ns_per_part_row",
                "kernel.fused_split_ns_per_hist_row"} & listed
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert CELL in e2e["train_s_per_iter"]["workloads"]


# -------------------------------------------------------- the generator
@pytest.fixture(scope="module")
def two_seeds():
    return [criteo_like.make(seed, **TOY) for seed in SEEDS]


def test_shapes_kinds_and_the_label(two_seeds):
    a, _ = two_seeds
    XT, y = a["XT"], a["label"]
    assert XT.shape == (67, TOY["rows"]) and XT.dtype == np.float32
    assert y.shape == (TOY["rows"],) and a["group"] is None
    assert np.isfinite(XT).all() and XT.min() >= 0.0
    integer, rates = criteo_like.INTEGER, criteo_like.RATES
    assert (XT[:integer] == np.floor(XT[:integer])).all()
    assert 0.2 < (XT[:integer] == 0).mean() < 0.45          # many ties
    assert XT[integer:integer + rates].max() < 1.0
    assert XT[integer:integer + rates].min() > 0.0
    counts = XT[integer + rates:]
    assert (counts == np.floor(counts)).all() and counts.max() > 1e4
    assert set(np.unique(y)) == {0.0, 1.0} and 0.02 < y.mean() < 0.05


def test_two_seeds_are_the_same_blocks_in_another_order(two_seeds):
    a, b = two_seeds

    def rows_of(d):
        """Each row as a record, its features and its label."""
        return np.concatenate([d["XT"], d["label"][None]]).T

    ra, rb = rows_of(a), rows_of(b)
    assert not np.array_equal(ra, rb)
    assert sorted(map(bytes, ra)) == sorted(map(bytes, rb))
    # inside a block the rows keep their order: the first block of one
    # seed is a contiguous run of the other
    first = ra[:criteo_like.BLOCK].tobytes()
    assert first in rb.tobytes()


def test_one_seed_twice_is_the_same_data():
    a = criteo_like.make(SEEDS[1], **TOY)
    b = criteo_like.make(SEEDS[1], **TOY)
    assert np.array_equal(a["XT"], b["XT"])
    assert np.array_equal(a["label"], b["label"])


# ----------------------------------------------------------- the metrics
def hand_made_run():
    """Two chips, one window of two iterations: each chip runs the fused
    kernel and three collectives; chip 1's kernel takes a tenth longer."""
    def chip(scale):
        return [("fused_split_root.1", 0.0, 1.0 * scale),
                ("all-reduce.7", 1.0 * scale, 1.0 * scale + 0.02),
                ("fused_split_step.2", 1.2, 1.2 + 2.0 * scale),
                ("all-gather-start.3", 3.4, 3.41),
                ("all-gather-done.3", 3.41, 3.45),
                ("fusion.9", 3.5, 3.9)]
    tick = {"dispatches": 1, "host_syncs": 1, "shards": 4,
            "rows_per_shard": 10_000_000, "collectives": 12,
            "collective_bytes": 345_678}
    return {
        "iterations": 2,
        "spans": [("update", 0.0, 2.0), ("update", 2.0, 4.0)],
        "profile": {"window": (0.0, 4.0), "host_spans": [],
                    "devices": {"/device:TPU:0": chip(1.0),
                                "/device:TPU:1": chip(1.1)}},
        "records": {"spans": [("iteration", 0.0, 2.0, None, 5)],
                    "compiles": [],
                    "iterations": [dict(tick, t1=1.9), dict(tick, t1=3.9)]},
    }


def test_the_new_metrics_through_the_harness():
    got = bench_run.per_layer_metrics(NEW, hand_made_run())
    assert set(got) == set(NEW)
    # (0.02 + 0.01 + 0.04) a chip, two iterations
    assert got["collective.device_s_per_iter"]["value"] == pytest.approx(
        0.035)
    assert got["collective.bytes_in_step"]["value"] == 345_678
    assert got["collective.count_in_step"]["value"] == 12
    # kernels 3.0 and 3.3 s: 0.3 over their mean 3.15
    assert got["mesh.kernel_skew_pct"]["value"] == pytest.approx(
        100 * 0.3 / 3.15)
    for name in NEW:
        assert got[name]["unit"]


def test_a_program_or_a_run_without_them_leaves_the_metrics_out():
    run = hand_made_run()
    for tick in run["records"]["iterations"]:
        for key in ("shards", "rows_per_shard", "collectives",
                    "collective_bytes"):
            del tick[key]
    one_chip = dict(run["profile"], devices={
        "/device:TPU:0": [("fused_split_step.2", 0.5, 1.5)]})
    run["profile"] = one_chip
    assert bench_run.per_layer_metrics(NEW, run) == {}
    assert chip_spread.reduce({"profile": None}, "^fused_split") is None
    two_idle = {"profile": {"window": (0.0, 1.0), "host_spans": [],
                            "devices": {"a": [], "b": []}}}
    assert chip_spread.reduce(two_idle, "^fused_split") is None
