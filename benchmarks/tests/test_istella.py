"""The ``istella`` configuration and its cell: the generator's contract
(one population, the seed orders the queries), the toy rehearsal of
``istella_train`` past the harness's look for a chip, and the three
``objective.*`` metrics, each through the harness's own lookup, on
hand-made records and a hand-made profile."""
import copy
import json

import numpy as np
import pytest

from benchmarks import correct, records, run as bench_run
from benchmarks.generators import istella_like
from benchmarks.reducers import before_first_kernel

with open(bench_run.ROOT + "/BENCHMARK.json") as f:
    BENCH = json.load(f)
SEEDS = (7, 2**31 + 4321)
TOY = {"rows": 6000, "features": 12, "queries": 40,
       "docs_per_query_log_sd": 0.6, "docs_per_query_max": 400}
NEW = ["objective.rank_slots_per_doc", "objective.rank_layout_s",
       "objective.rank_grads_s_per_iter"]


# -------------------------------------------------------- the generator
@pytest.fixture(scope="module")
def two_seeds():
    return [istella_like.make(seed, **TOY) for seed in SEEDS]


def test_lengths_sum_to_rows_and_keep_to_the_cap():
    sizes = bench_run.load_json("configs", "istella.json")["sizes"]
    lengths = istella_like.query_lengths(
        sizes["rows"], sizes["queries"], sizes["docs_per_query_log_sd"],
        sizes["docs_per_query_max"])
    assert len(lengths) == sizes["queries"] == 23219
    assert lengths.sum() == sizes["rows"] == 7325625
    assert lengths.min() >= 1 and lengths.max() == sizes["docs_per_query_max"]
    assert 0.005 < np.mean(lengths == sizes["docs_per_query_max"]) < 0.02
    assert abs(np.std(np.log(lengths)) - 0.6) < 0.05


@pytest.mark.parametrize("rows, queries, cap", [(40, 40, 9), (360, 40, 9),
                                                (100, 7, 64)])
def test_lengths_at_the_edges(rows, queries, cap):
    lengths = istella_like.query_lengths(rows, queries, 0.6, cap)
    assert lengths.sum() == rows and len(lengths) == queries
    assert lengths.min() >= 1 and lengths.max() <= cap


def test_two_seeds_are_the_same_rows_in_another_query_order(two_seeds):
    a, b = two_seeds
    assert a["XT"].shape == b["XT"].shape == (TOY["features"], TOY["rows"])
    assert a["XT"].dtype == np.float32 and a["XT"].min() >= 0.0
    for d in (a, b):
        assert d["group"].sum() == TOY["rows"]
        assert len(d["group"]) == TOY["queries"]
        assert d["group"].max() <= TOY["docs_per_query_max"]
    assert sorted(a["group"]) == sorted(b["group"])
    assert not np.array_equal(a["group"], b["group"])

    def queries(d):
        """Each query as its documents' bytes, features and label."""
        rows = np.concatenate([d["XT"], d["label"][None]]).T
        ends = np.cumsum(d["group"])
        return [rows[e - n:e].tobytes() for n, e in zip(d["group"], ends)]

    # contiguous queries: the same documents in the same order inside a
    # query, the queries in another order
    qa, qb = queries(a), queries(b)
    assert sorted(qa) == sorted(qb) and qa != qb
    assert set(np.unique(a["label"])) <= {0.0, 1.0, 2.0, 3.0, 4.0}


def test_one_seed_twice_is_the_same_data():
    a = istella_like.make(SEEDS[1], **TOY)
    b = istella_like.make(SEEDS[1], **TOY)
    assert np.array_equal(a["XT"], b["XT"])
    assert np.array_equal(a["label"], b["label"])
    assert np.array_equal(a["group"], b["group"])


# ------------------------------------------------------- the rehearsal
def toy():
    cell, config = bench_run.load_cell("istella_train")
    config = copy.deepcopy(config)
    config["sizes"].update(TOY)
    config["params"].update(num_leaves=7, min_data_in_leaf=20, verbosity=-1)
    return cell, config


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    """One sound toy run, with the records the program's ring held when it
    ended (the ring is the process's, and other tests write to it)."""
    from lightgbm_tpu.obs import flight
    flight.recorder().clear()
    _, got, cell, config = bench_run.drive(
        "istella_train", SEEDS[1], 0.3, False, files=toy(),
        scratch=str(tmp_path_factory.mktemp("scratch")))
    got["records"] = records.load(got)
    return got, cell, config


def test_sound_run_ends_in_a_well_formed_line(tmp_path):
    result = json.loads(json.dumps(bench_run.run_cell(
        "istella_train", SEEDS[1], 0.3, False, BENCH, scratch=str(tmp_path),
        files=toy())))
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert list(result)[-1] == "compared"
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_s_per_iter", "setup_s"}
    for name, row in result["compared"].items():
        assert row["value"] <= row["limit"], name


def test_control_in_bfloat16_is_not_correct(sound):
    import jax.numpy as jnp
    got, cell, config = sound
    ok, rows = correct.judge(correct.reference_readings(
        got["produced"], got["data"], config), cell["limits"])
    assert ok, rows
    ok, rows = correct.judge(correct.reference_readings(
        got["produced"], got["data"], config, precision=jnp.bfloat16),
        cell["limits"])
    assert not ok, rows


def test_the_program_wrote_the_layouts_records(sound):
    """The reducers on the ring the toy run left behind: the counters and
    the span are the program's, read as the harness reads them."""
    got, _, _ = sound
    out = bench_run.per_layer_metrics(NEW[:2], got)
    assert set(out) == set(NEW[:2])
    assert 1.0 <= out["objective.rank_slots_per_doc"]["value"] < 1.5
    assert out["objective.rank_layout_s"]["value"] > 0


# ------------------------------------- the metrics on hand-made records
# one warm-up update 40-50, then a window of two updates
SPANS = [("first_iter", 10.0, 40.0), ("warmup_updates", 40.0, 50.0),
         ("update", 50.0, 60.0), ("update", 60.1, 70.0)]
RANK = {"rank_slots": 3000, "rank_docs": 2000, "rank_slots_per_doc": 1.5,
        "rank_classes": 3}
RECORDS = {
    "spans": [
        ("rank_layout", 11.0, 11.25, "booster_init", None),
        ("rank_layout", 90.0, 95.0, None, None),     # a bare objective.init
        ("booster_init", 10.5, 14.0, None, None),
        ("rank_grads", 50.2, 50.3, "iteration", 2),
        ("iteration", 50.1, 59.9, None, 2),
        ("iteration", 60.2, 69.9, None, 3),
    ],
    "compiles": [],
    "iterations": [
        dict(RANK, t1=49.8, dispatches=2, rank_slots_per_doc=9.0),
        dict(RANK, t1=59.8, dispatches=2),
        dict(RANK, t1=69.8, dispatches=2),
    ],
}
# two updates, 0-10 and 10-20. In each the gradient program's sort and
# fusion and its enclosing while, then the step: a copy ahead of the
# first kernel call, the root kernel, a while around the other calls
PROFILE = {
    "window": (0.0, 20.0),
    "devices": {"/device:TPU:0": [
        ("while.1", 0.5, 2.5), ("sort.2", 0.5, 1.5), ("fusion.3", 1.5, 2.5),
        ("copy.4", 2.5, 3.0),
        ("fused_split_root.5", 3.0, 4.0),
        ("while.6", 4.0, 9.0), ("fused_split_step.7", 4.0, 8.0),
        ("fusion.8", 9.0, 9.5),
        ("sort.2", 10.5, 12.5), ("copy.4", 12.5, 13.0),
        ("fused_split_root.5", 13.0, 14.0),
        ("fused_split_step.7", 14.0, 18.0), ("fusion.8", 18.0, 19.0)]},
    "host_spans": [("update", 0.0, 10.0), ("update", 10.0, 20.0)],
}
RUN = {"spans": SPANS, "records": RECORDS, "profile": PROFILE,
       "iterations": 2, "counters": {}}


def test_the_new_metrics_through_the_harness():
    out = bench_run.per_layer_metrics(NEW, RUN)
    # the window's two events, not the warm-up's 9.0
    assert out["objective.rank_slots_per_doc"] == {"value": 1.5,
                                                   "unit": "count"}
    # the one inside booster_init
    assert out["objective.rank_layout_s"]["value"] == pytest.approx(0.25)
    # 2 + 0.5 and 2 + 0.5 s ahead of the root kernel, over two iterations
    assert out["objective.rank_grads_s_per_iter"] == {
        "value": pytest.approx(2.5), "unit": "s/iter"}


def test_before_first_kernel_counts_what_ends_ahead_of_the_match():
    pattern = "^fused_split_root"
    assert before_first_kernel.reduce(RUN, pattern) == pytest.approx(2.5)
    # an operation that encloses the match is not ahead of it
    wrapped = copy.deepcopy(PROFILE)
    wrapped["devices"]["/device:TPU:0"].append(("while.0", 0.2, 9.8))
    assert before_first_kernel.reduce(
        dict(RUN, profile=wrapped), pattern) == pytest.approx(2.5)
    # a span with no match gives nothing; with none in any span, silence
    one = copy.deepcopy(PROFILE)
    one["devices"]["/device:TPU:0"] = [
        ev for ev in one["devices"]["/device:TPU:0"]
        if not (ev[0].startswith("fused_split_root") and ev[1] > 10)]
    assert before_first_kernel.reduce(
        dict(RUN, profile=one), pattern) == pytest.approx(1.25)
    assert before_first_kernel.reduce(RUN, "^no_such_kernel") is None
    assert before_first_kernel.reduce(dict(RUN, profile=None),
                                      pattern) is None


def test_a_program_without_the_records_leaves_the_metrics_out():
    """The parent commit: no such span, no such counter, and a profile
    with no kernel of that name."""
    records = {"spans": [("booster_init", 10.5, 14.0, None, None)],
               "compiles": [],
               "iterations": [{"t1": 59.8, "dispatches": 2}]}
    profile = dict(PROFILE, devices={"/device:TPU:0": [
        ("fusion.1", 1.0, 2.0)]})
    run = dict(RUN, records=records, profile=profile)
    assert bench_run.per_layer_metrics(NEW, run) == {}
