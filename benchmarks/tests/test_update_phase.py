"""The two reducers of PR 39 on hand-made runs, and the closure of the
update's spans on a toy run of ``higgs_train`` on the CPU:
``update_phase`` (seconds of a phase of ``Booster.update()``, per
iteration) and ``idle_by_span`` (the chip's idle gaps by the program span
the host was in, the ring's spans moved onto the trace's clock)."""
import copy
import json

import pytest

from benchmarks import run as bench_run
from benchmarks.reducers import idle_by_span, update_phase

NEW = ["entry.step_args_s_per_iter", "entry.step_dispatch_s_per_iter",
       "entry.tree_d2h_s_per_iter", "entry.decode_trees_s_per_iter",
       "entry.unnamed_host_s_per_iter",
       "device.idle_before_step_s_per_iter", "device.idle_in_wait_s_per_iter",
       "device.idle_after_step_s_per_iter", "device.idle_unnamed_s_per_iter"]
OFFSET = 1234.5          # trace clock = host clock + OFFSET


def update_records(t, it):
    """The program's spans of one update that starts at host time ``t``
    and takes 1.0 s: (name, t0, t1, parent, iteration)."""
    return [
        ("step_args", t + 0.010, t + 0.030, "iteration", it),
        ("step_dispatch", t + 0.030, t + 0.040, "iteration", it),
        ("step_wait", t + 0.045, t + 0.900, "flush_trees", it),
        ("flush_trees", t + 0.040, t + 0.920, "iteration", it),
        ("decode_trees", t + 0.930, t + 0.980, "iteration", it),
        ("iteration", t + 0.001, t + 0.999, None, it),
    ]


def hand_made_run(updates=2, offset=OFFSET, traced_updates=None):
    """``updates`` one-second updates from host time 100.0. The chip is
    busy from 60 ms into each update (20 ms into ``step_dispatch``...
    no: 20 ms after it ends, inside ``step_wait``) to 910 ms (inside
    ``flush_trees``, past ``step_wait``), with one 10 ms gap of its own
    at 500 ms."""
    spans, recs, ops, host = [], [], [], []
    for i in range(updates):
        t = 100.0 + i
        spans.append(("update", t, t + 1.0))
        recs += update_records(t, 7 + i)
        ops += [("fused_split_root.1", t + offset + 0.060, t + offset + 0.500),
                ("fusion.2", t + offset + 0.510, t + offset + 0.910)]
    for i in range(updates if traced_updates is None else traced_updates):
        host.append(("update", 100.0 + i + offset, 101.0 + i + offset))
    window = (host[0][1], host[-1][2]) if host else (0.0, 0.0)
    return {
        "iterations": updates, "spans": [("data", 1.0, 2.0)] + spans,
        "records": {"spans": recs, "compiles": [], "iterations": []},
        "profile": {"window": window, "devices": {"/device:TPU:0": ops},
                    "host_spans": host},
    }


def metrics(run, names=NEW):
    return {n: m["value"]
            for n, m in bench_run.per_layer_metrics(names, run).items()}


# ------------------------------------------------------------- update_phase
def test_phase_seconds_per_iteration():
    got = metrics(hand_made_run())
    assert got["entry.step_args_s_per_iter"] == pytest.approx(0.020)
    assert got["entry.step_dispatch_s_per_iter"] == pytest.approx(0.010)
    # flush_trees 0.880 less step_wait 0.855
    assert got["entry.tree_d2h_s_per_iter"] == pytest.approx(0.025)
    assert got["entry.decode_trees_s_per_iter"] == pytest.approx(0.050)
    # iteration 0.998 less its children 0.020 + 0.010 + 0.880 + 0.050
    assert got["entry.unnamed_host_s_per_iter"] == pytest.approx(0.038)


def test_the_phases_close_on_host_s_per_iter():
    run = hand_made_run(3)
    got = metrics(run, NEW + ["entry.host_s_per_iter"])
    parts = sum(got[f"entry.{n}_s_per_iter"] for n in
                ("step_args", "step_dispatch", "decode_trees",
                 "unnamed_host"))
    assert parts == pytest.approx(got["entry.host_s_per_iter"], abs=1e-9)


def test_records_outside_the_window_do_not_count():
    run = hand_made_run()
    run["records"]["spans"] += update_records(50.0, 1)    # a warm-up update
    assert metrics(run)["entry.step_args_s_per_iter"] == pytest.approx(0.020)


@pytest.mark.parametrize("case", ["no_marker", "no_records", "no_iteration"])
def test_a_program_without_the_spans_reads_nothing(case):
    run = hand_made_run()
    if case == "no_marker":
        # the parent of PR 39: iteration, bag, step_dispatch, flush_trees
        run["records"]["spans"] = [
            r for r in run["records"]["spans"]
            if r[0] in ("iteration", "step_dispatch", "flush_trees")]
    elif case == "no_records":
        run["records"] = None
    else:
        run["records"]["spans"] = [r for r in run["records"]["spans"]
                                   if r[0] != "iteration"]
    assert metrics(run) == {}


# ------------------------------------------------------------- idle_by_span
def test_a_planted_offset_between_the_clocks_is_recovered():
    for offset in (OFFSET, -3.25, 0.0):
        clocks = idle_by_span.ring_to_trace(hand_made_run(offset=offset))
        assert clocks["offset_s"] == pytest.approx(offset, abs=1e-9)
        assert clocks["spread_s"] < 1e-9 and clocks["updates"] == 2


def test_gaps_cut_at_span_edges_sum_to_the_windows_idle():
    run = hand_made_run(3)
    got = metrics(run, NEW + ["device.idle_pct"])
    # before the chip starts, 60 ms an update: 10 ms unnamed (1 of it the
    # harness's), 20 step_args, 10 step_dispatch, 5 flush_trees ahead of
    # step_wait, 15 step_wait
    assert got["device.idle_before_step_s_per_iter"] == pytest.approx(0.030)
    # and the step's own gap of 10 ms at 500 ms
    assert got["device.idle_in_wait_s_per_iter"] == pytest.approx(0.025)
    # after the chip's last operation at 910 ms: 10 ms flush_trees, 50
    # decode_trees; and the 5 ms of flush_trees ahead of step_wait
    assert got["device.idle_after_step_s_per_iter"] == pytest.approx(0.065)
    # 10 ms ahead of step_args, 10 between flush_trees and decode_trees,
    # 20 after decode_trees
    assert got["device.idle_unnamed_s_per_iter"] == pytest.approx(0.040)
    lo, hi = run["profile"]["window"]
    idle = got["device.idle_pct"] / 100.0 * (hi - lo) / run["iterations"]
    assert sum(got[n] for n in NEW[5:]) == pytest.approx(idle, rel=1e-9)


def test_a_chips_mean_over_four_chips():
    run = hand_made_run()
    ops = run["profile"]["devices"]["/device:TPU:0"]
    run["profile"]["devices"] = {f"/device:TPU:{i}": list(ops)
                                 for i in range(4)}
    assert (metrics(run)["device.idle_in_wait_s_per_iter"]
            == pytest.approx(0.025))


@pytest.mark.parametrize("case", ["counts_differ", "offsets_spread",
                                  "no_profile"])
def test_clocks_that_cannot_be_matched_read_nothing(case):
    run = hand_made_run(3)
    if case == "counts_differ":
        run = hand_made_run(3, traced_updates=2)
    elif case == "offsets_spread":
        name, s, e = run["profile"]["host_spans"][1]
        run["profile"]["host_spans"][1] = (name, s + 0.0005, e + 0.0005)
    else:
        del run["profile"]
    got = metrics(run)
    assert not [n for n in got if n.startswith("device.")]
    assert len(got) == 5            # the host's clock alone reads the rest


def test_the_window_is_read_once_a_run(capsys):
    run = hand_made_run()
    metrics(run)
    lines = [json.loads(line) for line in capsys.readouterr().out.split("\n")
             if line.startswith("{")]
    assert len(lines) == 1
    assert lines[0]["ring_to_trace"]["offset_s"] == pytest.approx(OFFSET)


# --------------------------------------------- the program's own ring (CPU)
def test_closure_on_a_toy_run_of_higgs_train(tmp_path):
    """``higgs_train``'s own files at 6,000 rows on the compact grower,
    as the chip runs the cell: the five ``entry.*`` metrics read the ring
    the program wrote, and with the seconds of the spans no metric reads
    (``update_tick``; ``bag``, ``gradient`` and ``rank_grads``, none
    here) four of them add up to ``entry.host_s_per_iter``; ``tree_d2h``
    lies inside ``flush_trees``, which that metric leaves out."""
    cell, config = bench_run.load_cell("higgs_train")
    config = copy.deepcopy(config)
    config["sizes"]["rows"] = 6000
    config["params"].update(num_leaves=7, min_data_in_leaf=20, verbosity=-1,
                            tpu_grower="compact")
    # a ring that has dropped records reads as nothing: start it anew, as
    # the benchmark's own process does
    from lightgbm_tpu.obs import flight
    flight.recorder().clear()
    _, out, _, _ = bench_run.drive("higgs_train", 2**31 + 39, 0.3, False,
                                   scratch=str(tmp_path),
                                   files=(cell, config))
    got = metrics(out, NEW + ["entry.host_s_per_iter"])
    assert set(got) == set(NEW[:5]) | {"entry.host_s_per_iter"}
    parts = sum(got[f"entry.{n}_s_per_iter"] for n in
                ("step_args", "step_dispatch", "decode_trees",
                 "unnamed_host"))
    parts += sum(update_phase.reduce(out, name) or 0.0
                 for name in ("bag", "gradient", "rank_grads",
                              "update_tick"))
    assert parts == pytest.approx(got["entry.host_s_per_iter"], abs=1e-6)
    assert 0 < got["entry.tree_d2h_s_per_iter"]
    assert 0 < got["entry.unnamed_host_s_per_iter"] < 0.5 * parts
