"""The reducers on the program's own records (records.py), each on a
hand-made ``run``; the kernel fit on synthetic events with planted
coefficients; the ring that dropped records."""
import numpy as np
import pytest

from benchmarks import records
from benchmarks.reducers import (kernel_fit, program_span, setup_split,
                                 update_loop)

# the benchmark's own spans: construct 0-6, the first round 10-40, one
# warm-up update 40-50, then a window of two updates
SPANS = [("construct", 0.0, 6.0), ("first_iter", 10.0, 40.0),
         ("warmup_updates", 40.0, 50.0),
         ("update", 50.0, 60.0), ("update", 60.1, 70.0)]
RECORDS = {
    "spans": [
        ("import", -9.0, -5.0, None, None),
        ("find_bins", 0.5, 1.5, "construct", None),
        ("binning", 1.5, 5.5, "construct", None),
        ("construct", 0.1, 5.9, None, None),
        ("binning", 200.0, 201.0, None, None),        # a serve-time call
        ("to_device", 11.0, 12.0, "booster_init", None),
        ("booster_init", 10.5, 14.0, None, None),
        ("compact_setup", 15.0, 17.0, "iteration", 0),
        ("build_step", 17.0, 17.5, "iteration", 0),
        ("step_dispatch", 18.0, 28.0, "iteration", 0),
        ("flush_trees", 28.0, 39.0, "iteration", 0),
        ("iteration", 14.5, 39.5, None, 0),
        ("iteration", 40.0, 49.9, None, 1),
        ("flush_trees", 40.2, 49.8, "iteration", 1),
        ("iteration", 50.1, 59.9, None, 2),
        ("flush_trees", 50.2, 59.8, "iteration", 2),
        ("iteration", 60.2, 69.9, None, 3),
        ("step_dispatch", 60.3, 60.4, "iteration", 3),
        ("flush_trees", 60.4, 69.7, "iteration", 3),
    ],
    "compiles": [
        ("traces", 5.0, 5.5),                 # before the round: not taken
        ("traces", 13.0, 13.5),               # inside booster_init
        ("traces", 18.0, 20.0), ("lowerings", 20.0, 23.0),
        ("backend_compiles", 23.0, 27.0), ("cache_retrievals", 23.5, 26.5),
        ("traces", 41.0, 41.2),               # in the warm-up update
        ("lowerings", 61.0, 62.0),            # in the window: not set-up
    ],
    "iterations": [
        {"t1": 39.4, "dispatches": 1, "host_syncs": 1, "d2h_bytes": 9},
        {"t1": 49.8, "dispatches": 1, "host_syncs": 1, "d2h_bytes": 9},
        {"t1": 59.8, "dispatches": 1, "host_syncs": 1, "d2h_bytes": 9},
        {"t1": 69.8, "dispatches": 3, "host_syncs": 0, "d2h_bytes": 0},
    ],
}
RUN = {"spans": SPANS, "records": RECORDS, "iterations": 2}


@pytest.mark.parametrize("args, value", [
    (dict(span="import"), 4.0),
    (dict(span="find_bins", parent="construct"), 1.0),
    (dict(span="binning", parent="construct"), 4.0),  # not the serve-time one
    (dict(span="to_device"), 1.0),
    (dict(span="no_such_span"), None),
])
def test_program_span(args, value):
    got = program_span.reduce(RUN, **args)
    assert got == (value if value is None else pytest.approx(value))


@pytest.mark.parametrize("part, value", [
    # booster_init 3.5 + compact_setup 2 + build_step 0.5, less the 0.5 s
    # trace inside booster_init
    ("init", 5.5),
    # 13-13.5, 18-23, 41-41.2: from the round's start to the window's
    ("trace_lower", 5.7),
    ("cache_retrieval", 3.0),
    ("backend", 1.0),                                 # 4 less the 3 inside
    # the round's 30 s less booster_init 3.5, compact_setup 2, build_step
    # 0.5, step_dispatch 10 with its compiles, flush 11; `iteration`
    # (14.5-39.5) covers nothing itself
    ("unattributed", 30.0 - 27.0),
])
def test_setup_split(part, value):
    assert setup_split.reduce(RUN, part=part) == pytest.approx(value)


def test_setup_split_parts_do_not_overlap():
    parts = [setup_split.reduce(RUN, part=p) for p in
             ("init", "trace_lower", "cache_retrieval", "backend")]
    # with the first execution (flush 11 s) and the unattributed rest they
    # cannot exceed the round and the warm-up together
    assert sum(parts) + 11.0 + setup_split.reduce(
        RUN, part="unattributed") <= 40.0 + 1e-9
    with pytest.raises(ValueError):
        setup_split.reduce(RUN, part="no_such_part")


@pytest.mark.parametrize("what, value", [
    # the window's two iteration spans: 9.8 - 9.6 and 9.7 - 9.3, over two
    ("host_s", 0.3),
    ("host_syncs", 0.5), ("dispatches", 2.0), ("d2h_bytes", 4.5),
    ("no_such_counter", None),
])
def test_update_loop_takes_the_window_only(what, value):
    got = update_loop.reduce(RUN, what=what)
    assert got == (value if value is None else pytest.approx(value))


def test_window_filter_takes_only_records_inside_update_spans():
    updates = records.intervals(RUN, "update")
    inside = records.within(RECORDS["spans"], updates)
    assert {(s[0], s[4]) for s in inside} == {
        ("iteration", 2), ("flush_trees", 2), ("iteration", 3),
        ("step_dispatch", 3), ("flush_trees", 3)}
    # a record that straddles a span's edge is outside
    assert records.within([("x", 59.0, 61.0)], updates) == []
    assert records.seconds([("a", 0, 4), ("b", 1, 2), ("c", 3, 6)]) == 6
    assert records.seconds_outside([("a", 0, 4)], [("b", 1, 2)]) == 3


def test_no_records_no_numbers():
    run = dict(RUN, records=None)
    assert program_span.reduce(run, span="import") is None
    assert setup_split.reduce(run, part="init") is None
    assert update_loop.reduce(run, what="host_s") is None
    assert setup_split.reduce(dict(RUN, spans=[]), part="init") is None
    assert update_loop.reduce(dict(RUN, spans=[]), what="host_s") is None


# ------------------------------------------------------ the program's ring
def test_the_ring_is_read_and_a_ring_that_dropped_gives_none(monkeypatch):
    from lightgbm_tpu.obs import flight, spans
    run = {"spans": SPANS}
    monkeypatch.setattr(flight, "_RECORDER", flight.FlightRecorder(64))
    assert records.load(run) is None             # no span records yet
    with spans.span("construct"):
        with spans.span("find_bins"):
            pass
    flight.note("compile", kind="lowerings", phase="other", seconds=0.0)
    rec = records.load(run)                      # old-format compile: left out
    assert [s[0] for s in rec["spans"]] == ["find_bins", "construct"]
    assert rec["spans"][0][3] == "construct" and rec["compiles"] == []
    assert program_span.reduce(run, span="find_bins",
                               parent="construct") >= 0.0
    monkeypatch.setattr(flight, "_RECORDER", flight.FlightRecorder(8))
    for _ in range(20):
        with spans.span("bag"):
            pass
    assert flight.recorder().dropped() == 12
    assert records.load(run) is None
    assert program_span.reduce(run, span="bag") is None
    assert update_loop.reduce(run, what="host_s") is None


# ------------------------------------------------------------ the kernel fit
def tree_text(index, rng, rows, leaves=255):
    """One tree's model text: split the largest leaf at a random
    fraction, ``leaves - 1`` times (node i is the i-th split)."""
    counts, where = [rows], [None]          # per leaf: rows, (node, side)
    left, right, internal = [], [], []
    for i in range(leaves - 1):
        leaf = int(np.argmax(counts))
        n = counts[leaf]
        n_left = int(n * rng.uniform(0.1, 0.9))
        if where[leaf] is not None:
            node, side = where[leaf]
            (left if side == 0 else right)[node] = i
        internal.append(n)
        left.append(~leaf)
        right.append(~len(counts))
        counts[leaf] = n_left
        where[leaf] = (i, 0)
        counts.append(n - n_left)
        where.append((i, 1))
    n_int = leaves - 1
    line = lambda key, vals: f"{key}=" + " ".join(str(v) for v in vals)
    return "\n".join([
        f"Tree={index}", f"num_leaves={leaves}", "num_cat=0",
        line("split_feature", [0] * n_int), line("split_gain", [1] * n_int),
        line("threshold", [0.5] * n_int), line("decision_type", [0] * n_int),
        line("left_child", left), line("right_child", right),
        line("leaf_value", [0.0] * leaves), line("leaf_weight", counts),
        line("leaf_count", counts), line("internal_value", [0] * n_int),
        line("internal_weight", internal), line("internal_count", internal),
        "is_linear=0", "shrinkage=0.1", ""])


def fit_run(noise, a=2e-3, b=76e-9, c=65e-9, rows=10_500_000, seed=5):
    from benchmarks.modeltext import parse_trees
    rng = np.random.RandomState(seed)
    text = "tree\nversion=v4\n\n" + "\n".join(
        tree_text(i, rng, rows) for i in range(4)) + "\nend of trees\n"
    events, t = [("before.1", -1.0, -0.5)], 0.0
    for tree in parse_trees(text)[1:]:            # the window's three
        took = c * rows
        events.append(("fused_split_root.16", t, t + took))
        t += took + 1e-4
        for part, hist in kernel_fit.split_rows(tree):
            took = (a + b * part + c * hist) * (1 + noise * rng.randn())
            events.append(("fused_split_step.17", t, t + took))
            events.append(("fusion.3", t + took, t + took + 1e-5))
            t += took + 2e-5
    return {"iterations": 3,
            "produced": {"model_text": text, "first_window_tree": 1},
            "profile": {"window": (0.0, t), "host_spans": [],
                        "devices": {"/device:TPU:0": events}}}


def test_fit_recovers_planted_coefficients_from_noisy_events():
    run = fit_run(noise=0.01)
    out = kernel_fit.fit(run)
    assert out["calls"] == 3 * 254
    assert out["call"] == pytest.approx(2e-3, rel=0.01)
    assert out["part_row"] == pytest.approx(76e-9, rel=0.01)
    assert out["hist_row"] == pytest.approx(65e-9, rel=0.01)
    assert out["r2"] > 0.99
    # the held-out root calls check the histogram coefficient
    assert out["root_predicted_s"][0] == pytest.approx(
        out["root_s"][0] + out["call"], rel=0.02)
    assert kernel_fit.reduce(run, coefficient="call") == pytest.approx(
        2e3, rel=0.01)
    assert kernel_fit.reduce(run, coefficient="part_row") == pytest.approx(
        76.0, rel=0.01)
    assert kernel_fit.reduce(run, coefficient="hist_row") == pytest.approx(
        65.0, rel=0.01)


def test_fit_is_silent_without_named_calls():
    run = fit_run(noise=0.0)
    unnamed = [(n.replace("fused_split_step", "fused_split")
                .replace("fused_split_root", "fused_split"), s, e)
               for n, s, e in run["profile"]["devices"]["/device:TPU:0"]]
    old = dict(run, profile=dict(run["profile"],
                                 devices={"/device:TPU:0": unnamed}))
    assert kernel_fit.reduce(old, coefficient="call") is None
    assert kernel_fit.reduce(dict(run, profile=None),
                             coefficient="call") is None
    assert kernel_fit.reduce(dict(run, iterations=2),
                             coefficient="call") is None
