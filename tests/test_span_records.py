"""The program's own records (ISSUE 28): host spans in the flight ring,
the update's counters, compile events on the spans' clock.

What a reducer of the benchmark reads in-process is pinned here on a toy
compact booster: each record's fields, the counters against the calls
really made, the coverage of ``construct`` by its children, the compile
events of a first round, the ring's ``dropped`` count, and that the
steady-state guards still hold with the spans recording.
"""
import json
import os
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.analysis import guards
from lightgbm_tpu.obs import flight, spans, summarize, tracing
from lightgbm_tpu.utils import log

from benchmarks import run as bench_run

FREQ = 3          # stop_check_freq of the toy booster
UPDATES = 6       # updates made after the first round


def _data(n=1500, f=8, seed=11):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, f)
    y = (X[:, 0] + 0.4 * X[:, 1] + 0.2 * rng.randn(n) > 0.7).astype(float)
    return X, y


def _span_records(events, name=None):
    return [e for e in events if e["event"] == "span"
            and (name is None or e["name"] == name)]


@pytest.fixture(scope="module")
def toy():
    """One compact booster: construct, a first ``lgb.train`` round, then
    UPDATES updates with the jitted step wrapped by a call counter.
    Returns the ring's records of that stretch and the calls counted."""
    flight.configure(capacity=flight.DEFAULT_CAPACITY)
    X, y = _data()
    params = {"objective": "binary", "num_leaves": 7, "max_bin": 31,
              "min_data_in_leaf": 5, "verbosity": -1,
              "tpu_grower": "compact", "stop_check_freq": FREQ}
    seq0 = max([e["seq"] for e in flight.recorder().events()] or [0])
    ds = lgb.Dataset(X, label=y, params=params)
    ds.construct()
    bst = lgb.train(params, ds, num_boost_round=1,
                    keep_training_booster=True)
    compact = bst._gbdt._compact
    step, calls = compact["step"], []

    def counted(*args, **kwargs):
        calls.append(bst._gbdt.iter_)
        return step(*args, **kwargs)

    compact["step"] = counted
    first = bst._gbdt.iter_
    for _ in range(UPDATES):
        bst.update()
    events = [e for e in flight.recorder().events() if e["seq"] > seq0]
    return {"events": events, "calls": calls, "first": first, "bst": bst}


# ---------------------------------------------------------- span records
@pytest.mark.parametrize("case", ["clock", "parent", "iteration"])
def test_span_record_fields(toy, case):
    recs = _span_records(toy["events"])
    assert recs
    if case == "clock":
        now = time.perf_counter()
        assert all(r["t0"] <= r["t1"] <= now for r in recs)
        # children lie inside their parents on that clock
        for it in _span_records(toy["events"], "iteration"):
            kids = [r for r in recs if r["parent"] == "iteration"
                    and r["iteration"] == it["iteration"]]
            assert kids and all(it["t0"] <= k["t0"] and k["t1"] <= it["t1"]
                                for k in kids)
    elif case == "parent":
        parents = {r["name"]: r["parent"] for r in recs}
        assert parents["construct"] is None
        assert parents["find_bins"] == "construct"
        assert parents["binning"] == "construct"
        assert parents["to_device"] == "booster_init"
        assert parents["iteration"] is None
        for name in ("compact_setup", "build_step", "step_dispatch",
                     "flush_trees"):
            assert parents[name] == "iteration", name
    else:
        # set-up's spans carry no iteration; an update's carry the
        # booster's iter_ as the update found it
        assert all(r["iteration"] is None for r in recs
                   if r["name"] in ("construct", "find_bins", "binning",
                                    "booster_init", "to_device"))
        its = [r["iteration"] for r in _span_records(toy["events"],
                                                     "iteration")]
        assert its == list(range(0, toy["first"] + UPDATES))
        steps = _span_records(toy["events"], "step_dispatch")
        assert [r["iteration"] for r in steps][-UPDATES:] == toy["calls"]


@pytest.mark.parametrize("counter", ["dispatches", "host_syncs",
                                     "d2h_bytes"])
def test_iteration_event_counters(toy, counter):
    ticks = [e for e in toy["events"] if e["event"] == "iteration"]
    ticks = ticks[-UPDATES:]
    assert [e["iteration"] for e in ticks] == list(
        range(toy["first"] + 1, toy["first"] + UPDATES + 1))
    values = [e[counter] for e in ticks]
    flushes = [e["iteration"] % FREQ == 0 for e in ticks]
    if counter == "dispatches":
        # one tree an iteration (binary): one call of the jitted step
        assert values == [toy["calls"].count(e["iteration"] - 1)
                          for e in ticks]
        assert values == [1] * UPDATES
    elif counter == "host_syncs":
        assert values == [int(f) for f in flushes]
    else:
        assert all((v > 0) == f for v, f in zip(values, flushes))
    # every tick is on the spans' clock, inside its update's span
    for tick, it in zip(ticks, _span_records(toy["events"],
                                             "iteration")[-UPDATES:]):
        assert it["t0"] <= tick["t1"] <= it["t1"]


# ------------------------------------------- the update's phases (ISSUE 39)
@pytest.mark.parametrize("name,parent,per_update", [
    ("step_args", "iteration", True),
    ("step_wait", "flush_trees", False),
    ("decode_trees", "iteration", False),
    ("update_tick", "iteration", True)])
def test_update_phase_span_records(toy, name, parent, per_update):
    """``step_args`` once a tree in every update, ``update_tick`` once an
    update and last in it; ``step_wait`` and ``decode_trees`` where the
    update flushes (every FREQ-th)."""
    recs = _span_records(toy["events"], name)
    assert recs and all(r["parent"] == parent for r in recs)
    updates = list(range(toy["first"], toy["first"] + UPDATES))
    mine = [r["iteration"] for r in recs if r["iteration"] in updates]
    if name == "update_tick":
        assert mine == updates
        for r in recs:
            rest = [k for k in _span_records(toy["events"])
                    if k["iteration"] == r["iteration"]
                    and k["name"] not in ("iteration", "update_tick")]
            assert all(k["t1"] <= r["t0"] for k in rest)
    elif per_update:
        assert mine == updates
        # the step's arguments are built ahead of the call, not in it
        for r in recs:
            call, = [d for d in _span_records(toy["events"], "step_dispatch")
                     if d["iteration"] == r["iteration"]]
            assert r["t1"] <= call["t0"]
    else:
        assert mine == [i for i in updates if (i + 1) % FREQ == 0]
        outer = {r["iteration"]: r
                 for r in _span_records(toy["events"], "flush_trees")}
        for r in recs:
            fl = outer[r["iteration"]]
            if name == "step_wait":
                assert fl["t0"] <= r["t0"] and r["t1"] <= fl["t1"]
            else:
                assert fl["t1"] <= r["t0"]


@pytest.mark.parametrize("field", ["phase_s", "cpu_s"])
def test_iteration_event_carries_the_updates_phases(toy, field):
    ticks = [e for e in toy["events"] if e["event"] == "iteration"]
    ticks = ticks[-UPDATES:]
    for tick in ticks:
        # the event's iteration counts completed updates; the update's
        # spans carry the iter_ it started from, one less
        # `update_tick` closes after the tick has written the table
        mine = [r for r in _span_records(toy["events"])
                if r["iteration"] == tick["iteration"] - 1
                and r["name"] not in ("iteration", "update_tick")]
        if field == "phase_s":
            sums = {}
            for r in mine:
                sums[r["name"]] = sums.get(r["name"], 0.0) + r["t1"] - r["t0"]
            assert set(tick["phase_s"]) == set(sums)
            for name, took in sums.items():
                assert tick["phase_s"][name] == pytest.approx(took, abs=2e-6)
            flushed = tick["iteration"] % FREQ == 0
            assert ("step_wait" in tick["phase_s"]) == flushed
        else:
            assert 0.0 <= tick["cpu_s"] <= tick["seconds"] + 0.05


def test_children_of_iteration_cover_it():
    """At a size where an update is tens of milliseconds, what no span
    names (the tick, ``Booster.update``'s own lines, the span boundaries)
    is under a twentieth of it."""
    flight.configure(capacity=flight.DEFAULT_CAPACITY)
    X, y = _data(60_000, 12)
    params = {"objective": "binary", "num_leaves": 31, "max_bin": 63,
              "verbosity": -1, "tpu_grower": "compact"}
    bst = lgb.train(params, lgb.Dataset(X, label=y, params=params),
                    num_boost_round=2, keep_training_booster=True)
    seq0 = max(e["seq"] for e in flight.recorder().events())
    for _ in range(4):
        bst.update()
    recs = [e for e in _span_records(flight.recorder().events())
            if e["seq"] > seq0]
    whole = sum(r["t1"] - r["t0"] for r in recs if r["name"] == "iteration")
    kids = [r for r in recs if r["parent"] == "iteration"]
    assert {r["name"] for r in kids} == {"step_args", "step_dispatch",
                                         "flush_trees", "decode_trees",
                                         "update_tick"}
    assert sum(r["t1"] - r["t0"] for r in kids) >= 0.95 * whole


@pytest.mark.parametrize("sampling,want", [
    ({}, 0),
    ({"bagging_fraction": 0.5, "bagging_freq": 2}, 4),   # drawn or reused
    ({"data_sample_strategy": "goss", "learning_rate": 0.5}, 2)])
def test_bag_records_only_where_the_strategy_samples(sampling, want):
    flight.configure(capacity=flight.DEFAULT_CAPACITY)
    X, y = _data()
    params = dict({"objective": "binary", "num_leaves": 7, "max_bin": 31,
                   "min_data_in_leaf": 5, "verbosity": -1,
                   "tpu_grower": "compact"}, **sampling)
    seq0 = max([e["seq"] for e in flight.recorder().events()] or [0])
    lgb.train(params, lgb.Dataset(X, label=y, params=params),
              num_boost_round=4)
    recs = [e for e in _span_records(flight.recorder().events(), "bag")
            if e["seq"] > seq0]
    # GOSS at lr 0.5 samples from its third iteration on
    assert len(recs) == want
    assert all(r["parent"] == "iteration" for r in recs)


# --------------------------------------------------------- slow_iteration
@pytest.fixture
def steady(monkeypatch):
    """A compact booster that flushes every update, ten steady updates
    made, its jitted step wrapped so that a test can plant a stall in
    one call; the warnings it logs are kept."""
    flight.configure(capacity=flight.DEFAULT_CAPACITY)
    X, y = _data()
    params = {"objective": "binary", "num_leaves": 7, "max_bin": 31,
              "min_data_in_leaf": 5, "verbosity": -1,
              "tpu_grower": "compact"}
    bst = lgb.train(params, lgb.Dataset(X, label=y, params=params),
                    num_boost_round=1, keep_training_booster=True)
    compact, plant = bst._gbdt._compact, []
    step = compact["step"]

    def stalling(*args, **kwargs):
        while plant:
            plant.pop()()
        return step(*args, **kwargs)

    compact["step"] = stalling
    for _ in range(spans.SLOW_MIN + 2):
        bst.update()
    warned = []
    monkeypatch.setattr(log, "warning", warned.append)
    return bst, plant, warned


def _slow_records(seq0):
    return [e for e in flight.recorder().events()
            if e["event"] == "slow_iteration" and e["seq"] > seq0]


@pytest.mark.parametrize("stall", ["sleep", "busy"])
def test_a_planted_stall_reports_itself(steady, stall):
    bst, plant, warned = steady
    # long against whatever an update takes on this host just now (the
    # suite's other workers share its cores)
    steady_s = [e["seconds"] for e in flight.recorder().events()
                if e["event"] == "iteration"][-spans.SLOW_MIN:]
    planted = max(0.5, 6 * max(steady_s))

    def busy():
        t0 = time.thread_time()
        while time.thread_time() - t0 < planted:
            pass

    seq0 = max(e["seq"] for e in flight.recorder().events())
    plant.append(busy if stall == "busy" else (lambda: time.sleep(planted)))
    bst.update()
    slow, = _slow_records(seq0)
    assert slow["iteration"] == bst._gbdt.iter_
    assert slow["seconds"] >= 3 * slow["median_s"] > 0
    # the stall names its phase, and the thread's CPU clock says whether
    # the host worked through it or waited
    assert max(slow["phase_s"], key=slow["phase_s"].get) == "step_dispatch"
    assert slow["phase_s"]["step_dispatch"] >= planted
    if stall == "sleep":
        assert slow["cpu_s"] < 0.3 * slow["seconds"]
    else:
        assert slow["cpu_s"] >= 0.9 * planted
    assert set(slow["compiles"]) == {"lowerings", "backend_compiles"}
    assert len(slow["gc_collections"]) == 3
    line, = [w for w in warned if w.startswith("slow_iteration ")]
    assert json.loads(line.split(" ", 1)[1]) == {
        k: v for k, v in slow.items() if k not in ("seq", "t", "event")}
    # the update after it is held against the same median and is not slow
    bst.update()
    assert len(_slow_records(seq0)) == 1


@pytest.mark.parametrize("case", ["steady", "flush_among_dispatches",
                                  "slow_flush", "too_few"])
def test_slow_updates_are_held_against_their_like(case):
    """The tick itself on planted seconds: nothing in a steady run,
    nothing for the update that flushes among updates that only dispatch
    (``stop_check_freq=3``: two hundred times their seconds), the flush
    that takes three times the other flushes', and no verdict before
    there are SLOW_MIN of a kind."""
    watch, found = spans.SlowUpdates(), []
    if case == "steady":
        ticks = [(0.40 + 0.01 * (i % 5), 1) for i in range(40)]
    elif case == "too_few":
        ticks = [(0.4, 1)] * (spans.SLOW_MIN - 1) + [(4.0, 1)]
    else:
        ticks = [(0.002, 0), (0.002, 0), (0.4, 1)] * 12
        if case == "slow_flush":
            ticks += [(0.002, 0), (0.002, 0), (1.3, 1)]
    for i, (seconds, syncs) in enumerate(ticks):
        slow = watch.check(i + 1, seconds, syncs, {"step_wait": seconds},
                           0.001)
        if slow is not None:
            found.append(slow)
    if case == "slow_flush":
        slow, = found
        assert slow["iteration"] == len(ticks)
        assert slow["median_s"] == 0.4 and slow["seconds"] == 1.3
        assert slow["phase_s"] == {"step_wait": 1.3}
    else:
        assert found == []


def test_a_flushing_update_among_dispatch_only_ones_is_not_slow(toy):
    """The toy booster flushes every third update (FREQ): its flushing
    updates take the device's seconds and the others microseconds, and
    none reported itself."""
    assert not [e for e in toy["events"] if e["event"] == "slow_iteration"]
    syncs = {e["host_syncs"] for e in toy["events"]
             if e["event"] == "iteration"}
    assert syncs == {0, 1}


# ------------------------------------------- the benchmark's reducers read them
@pytest.mark.parametrize("metric,want", [
    ("entry.step_args_s_per_iter", 0.004),
    ("entry.step_dispatch_s_per_iter", 0.001),
    ("entry.tree_d2h_s_per_iter", 0.002),
    ("entry.decode_trees_s_per_iter", 0.0005),
    ("entry.unnamed_host_s_per_iter", 0.0003)])
def test_the_benchmark_reads_the_phases_through_the_harness(metric, want):
    def update(t, it):
        return [("step_args", t + 0.0001, t + 0.0041, "iteration", it),
                ("step_dispatch", t + 0.0041, t + 0.0051, "iteration", it),
                ("step_wait", t + 0.0061, t + 0.3961, "flush_trees", it),
                ("flush_trees", t + 0.0051, t + 0.3971, "iteration", it),
                ("decode_trees", t + 0.3971, t + 0.3976, "iteration", it),
                ("iteration", t, t + 0.3978, None, it)]

    run = {"iterations": 2,
           "spans": [("update", 10.0, 10.4), ("update", 10.4, 10.8)],
           "records": {"spans": update(10.0, 5) + update(10.4, 6),
                       "compiles": [], "iterations": []}}
    got = bench_run.per_layer_metrics([metric], run)
    assert got[metric]["value"] == pytest.approx(want, abs=1e-9)
    assert got[metric]["unit"] == "s/iter"
    # a program before the spans (no `step_args` in its updates) reads
    # nothing, not zero
    run["records"]["spans"] = [r for r in run["records"]["spans"]
                               if r[0] in ("iteration", "step_dispatch",
                                           "flush_trees")]
    assert bench_run.per_layer_metrics([metric], run) == {}


def test_the_benchmark_lists_the_nine_metrics_for_all_five_cells():
    with open(bench_run.ROOT + "/BENCHMARK.json") as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    names = [f"entry.{n}_s_per_iter" for n in (
        "step_args", "step_dispatch", "tree_d2h", "decode_trees",
        "unnamed_host")] + [f"device.idle_{n}_s_per_iter" for n in (
            "before_step", "in_wait", "after_step", "unnamed")]
    new = [m for m in bench["per_layer"] if m["name"] in names]
    assert [m["name"] for m in new] == names
    for m in new:
        assert m["workloads"] == cells and m["moves"] == "train_s_per_iter"
        with open(f"{bench_run.ROOT}/benchmarks/metrics/{m['name']}.json") as f:
            spec = json.load(f)
        assert spec["reducer"] in ("update_phase", "idle_by_span")
        for key in ("name", "unit", "better", "layer", "source", "moves"):
            assert spec[key] == m[key], key


def test_construct_is_covered_by_its_children():
    flight.configure(capacity=flight.DEFAULT_CAPACITY)
    X, y = _data(60_000, 12)
    seq0 = max([e["seq"] for e in flight.recorder().events()] or [0])
    lgb.Dataset(X, label=y).construct()
    recs = [e for e in _span_records(flight.recorder().events())
            if e["seq"] > seq0]
    whole, = [r for r in recs if r["name"] == "construct"]
    kids = [r for r in recs if r["parent"] == "construct"]
    assert {r["name"] for r in kids} == {"find_bins", "binning"}
    covered = sum(r["t1"] - r["t0"] for r in kids)
    assert covered >= 0.9 * (whole["t1"] - whole["t0"])


# -------------------------------------------------------- compile events
@pytest.fixture(scope="module")
def first_round_compiles(tmp_path_factory):
    """The compile events of a first ``lgb.train`` round that finds its
    step in the persistent cache (a neighbour in the same rung wrote
    it), so that every kind of event shows."""
    import jax
    prev = jax.config.jax_compilation_cache_dir
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    env = os.environ.pop(guards.CACHE_DIR_ENV, None)
    cache = str(tmp_path_factory.mktemp("cc"))
    X, y = _data(800, 6)
    extra = {"objective": "binary", "verbosity": -1,
             "tpu_grower": "compact", "tpu_step_buckets": "on",
             "tpu_compile_cache_dir": cache}
    try:
        lgb.train(dict(extra, num_leaves=12, max_depth=6),
                  lgb.Dataset(X, label=y), 1)
        seq0 = max(e["seq"] for e in flight.recorder().events())
        lgb.train(dict(extra, num_leaves=9, max_depth=3),
                  lgb.Dataset(X, label=y), 1)
        return [e for e in flight.recorder().events()
                if e["seq"] > seq0 and e["event"] == "compile"]
    finally:
        # while the variable is still cleared: the helper yields to it
        guards.configure_compile_cache(prev, min_compile_secs=floor)
        if env is not None:
            os.environ[guards.CACHE_DIR_ENV] = env


@pytest.mark.parametrize("kind", ["traces", "lowerings", "backend_compiles",
                                  "cache_retrievals"])
def test_first_round_leaves_compile_events_keyed_train_step(
        first_round_compiles, kind):
    mine = [e for e in first_round_compiles
            if e["kind"] == kind and e["phase"] == "train_step"]
    assert mine, {(e["kind"], e["phase"]) for e in first_round_compiles}
    for e in mine:
        assert e["t0"] <= e["t1"]
        assert e["t1"] - e["t0"] == pytest.approx(e["seconds"], abs=1e-3)
    if kind != "cache_retrievals":      # jax names the function there
        assert all(e["fun"] for e in mine)
        assert any("step" in e["fun"] for e in mine)


# --------------------------------------------------------------- taxonomy
@pytest.mark.parametrize("case", ["no_update", "always_on", "no_repeat",
                                  "phase_of"])
def test_taxonomy(case):
    names = tracing.SPAN_TAXONOMY
    if case == "no_update":
        # benchmarks/trace.py takes host events named `update` as the
        # benchmark's window
        assert "update" not in names
    elif case == "always_on":
        assert spans.ALWAYS_ON <= set(names)
        assert not {"serve_tick", "predict_warmup",
                    "checkpoint_write"} & spans.ALWAYS_ON
    elif case == "no_repeat":
        assert len(set(names)) == len(names)
    else:
        assert tracing.phase_of("jit(step)/hist_build/dot") == "hist_build"


# ------------------------------------------------------------------- ring
def test_ring_of_8_reports_dropped_after_20_spans(monkeypatch):
    ring = flight.FlightRecorder(capacity=8)
    monkeypatch.setattr(flight, "_RECORDER", ring)
    for _ in range(20):
        with spans.span("bag"):
            pass
    assert len(ring.events()) == 8
    assert ring.dropped() == 12
    assert all(e["event"] == "span" and e["name"] == "bag"
               for e in ring.events())


def test_host_span_costs_microseconds(monkeypatch):
    """A loose ceiling that holds on a loaded CI host; the figure read
    on the benchmark's machine is in PERF.md."""
    monkeypatch.setattr(flight, "_RECORDER", flight.FlightRecorder(512))
    n = 2000
    t0 = time.perf_counter()
    for _ in range(n):
        with spans.span("bag"):
            pass
    assert (time.perf_counter() - t0) / n < 50e-6


# ------------------------------------------------------ steady-state guard
@pytest.mark.parametrize("case", ["between_flushes", "at_the_flush"])
def test_steady_state_guard_holds_with_spans(toy, case):
    """Spans recording, no session: an update between flushes lowers
    nothing and moves nothing to the host; the one transfer of the loop
    is the fetch inside ``flush_trees``."""
    bst = toy["bst"]
    while bst._gbdt.iter_ % FREQ:          # next update is right after a flush
        bst.update()
    if case == "between_flushes":
        before = time.perf_counter()
        with guards.steady_state_guard("spans on") as cc:
            for _ in range(FREQ - 1):
                bst.update()
        assert cc.lowerings == 0 and cc.backend_compiles == 0
        # spans went on recording (by the clock, not by their number: the
        # ring is full once a worker has run a few files, and then drops
        # an old record for every new one)
        assert any(r["t0"] >= before
                   for r in _span_records(flight.recorder().events()))
    else:
        for _ in range(FREQ - 1):
            bst.update()
        with pytest.raises(guards.HostTransferError) as err:
            with guards.no_host_transfers():
                bst.update()
        frames = [f.name for f in err.traceback]
        assert "_flush_trees_locked" in frames


# -------------------------------------------------------------- scripts/obs
def test_obs_renders_the_per_iteration_table_from_a_dump(toy, tmp_path,
                                                         capsys):
    path = flight.dump("unit", path=str(tmp_path / "f.jsonl"))
    summary = summarize.summarize([path])
    rows = summary["per_iteration"]
    assert rows and {"iteration", "seconds", "dispatches", "host_syncs",
                     "d2h_bytes"} <= set(rows[-1])
    assert "step_dispatch" in summary["phase_times"]
    assert summarize.main([path]) == 0
    out = capsys.readouterr().out
    assert "host_syncs" in out and "step_dispatch" in out
    # the `trace` subcommand went with the reader: it is a path now
    assert summarize.main(["trace", str(tmp_path)]) == 2
