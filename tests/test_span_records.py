"""The program's own records (ISSUE 28): host spans in the flight ring,
the update's counters, compile events on the spans' clock.

What a reducer of the benchmark reads in-process is pinned here on a toy
compact booster: each record's fields, the counters against the calls
really made, the coverage of ``construct`` by its children, the compile
events of a first round, the ring's ``dropped`` count, and that the
steady-state guards still hold with the spans recording.
"""
import os
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.analysis import guards
from lightgbm_tpu.obs import flight, spans, summarize, tracing

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
        for name in ("compact_setup", "build_step", "bag", "step_dispatch",
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
