"""Unified telemetry (ISSUE 10): spans, flight recorder, metrics plane.

The acceptance proofs live here:

* a 5-iteration compact (data-parallel) run under ``tpu_trace_dir`` plus
  a warm + served tick touches EVERY span-taxonomy phase, writes a
  profiler trace, and leaves a ``tpu_metrics_path`` JSONL stream whose
  counters bench.py can ingest;
* with telemetry fully enabled (spans + flight recorder + metrics
  stream) the steady-state guards still record 0 recompiles and 0 host
  transfers;
* injected ``kill@step`` and ``hang@swap`` each leave a parseable flight
  dump whose last events name the failing site.
"""
import json
import os
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.analysis import faultinject, guards
from lightgbm_tpu.obs import flight, metrics, summarize
from lightgbm_tpu.obs import spans


def _make_data(n=600, f=8, seed=3):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, f)
    y = (X[:, 0] + 0.3 * X[:, 1] + 0.2 * rng.randn(n) > 0.6).astype(
        np.float64)
    return X, y


# ------------------------------------------------------------------ spans
def test_span_disabled_is_shared_noop():
    """Zero-cost contract: outside a session, host-side span() returns
    ONE shared no-op object (no allocation, nothing recorded)."""
    assert not spans.annotations_enabled()
    s1, s2 = spans.span("zz_unit_off"), spans.span("zz_unit_off2")
    assert s1 is s2
    with s1:
        pass
    assert "zz_unit_off" not in spans.seen_spans()


def test_span_host_session_times_and_records():
    with spans.trace_session(None, "annotations"):
        assert spans.annotations_enabled()
        with spans.span("zz_unit_host"):
            pass
    assert not spans.annotations_enabled()      # nesting unwound
    assert "zz_unit_host" in spans.seen_spans()
    pt = spans.phase_times()["zz_unit_host"]
    assert pt["count"] >= 1 and pt["seconds"] >= 0.0


def test_span_under_trace_is_named_scope():
    """Inside a jit trace span() becomes a named_scope — recorded as seen
    (the device program carries the name) with NO session active, and the
    function still compiles and runs."""

    @jax.jit
    def f(x):
        with spans.span("zz_unit_traced"):
            return x * 2 + 1

    out = f(jnp.ones(3))
    assert float(out[0]) == 3.0
    assert "zz_unit_traced" in spans.seen_spans()


def test_compact_step_program_carries_hist_build_scope(monkeypatch):
    """The device program itself must carry the phase names: the compiled
    compact step's op metadata names ``hist_build``. When span() quietly
    stopped detecting a trace (jax 0.9 dropped the function it asked),
    every per-phase device-seconds number came back empty — this pins the
    text, so the next jax move cannot empty the traces silently."""
    monkeypatch.setenv("LGBM_TPU_COMM_ACCOUNTING", "1")
    X, y = _make_data()
    bst = lgb.train({"objective": "binary", "num_leaves": 7, "max_bin": 15,
                     "min_data_in_leaf": 5, "verbosity": -1,
                     "tpu_grower": "compact"}, lgb.Dataset(X, label=y), 1)
    text = bst._gbdt._comm_hlo["compact_step_k0"]
    assert "hist_build" in text
    assert "split_scan" in text


def test_trace_mode_validation():
    assert spans.resolve_trace_mode(None) == "full"
    assert spans.resolve_trace_mode("annotations") == "annotations"
    assert spans.resolve_trace_mode("FULL") == "full"
    assert spans.resolve_trace_mode("bogus") == "full"   # warn + fallback


def test_phase_times_since_is_a_per_run_delta():
    """Two runs in one process must not double-count each other's span
    seconds: engine snapshots phase_times at run start and reports the
    delta in its summary record."""
    with spans.trace_session(None, "annotations"):
        with spans.span("zz_delta_a"):
            pass
    base = spans.phase_times()
    with spans.trace_session(None, "annotations"):
        with spans.span("zz_delta_b"):
            pass
    delta = spans.phase_times_since(base)
    assert "zz_delta_b" in delta and delta["zz_delta_b"]["count"] == 1
    assert "zz_delta_a" not in delta


def test_trace_session_closes_on_error_paths():
    """The satellite-1 contract: a raise inside the session unwinds the
    enablement (annotations mode here; the profiler flavor of the same
    contract is covered by the slow full-trace test — opening a profiler
    session costs a one-time ~10s process init, too heavy for tier-1)."""
    with pytest.raises(RuntimeError):
        with spans.trace_session(None, "annotations"):
            assert spans.annotations_enabled()
            raise RuntimeError("boom")
    assert not spans.annotations_enabled()


# -------------------------------------------------------- flight recorder
def test_flight_ring_is_bounded_and_dump_parses(tmp_path):
    rec = flight.FlightRecorder(capacity=4)
    for i in range(10):
        rec.record("tick", i=i)
    assert len(rec.events()) == 4
    assert [e["i"] for e in rec.events()] == [6, 7, 8, 9]
    out = rec.dump("unit test", path=str(tmp_path / "f.jsonl"))
    lines = flight.read_dump(out)
    header, events = lines[0], lines[1:]
    assert header["event"] == "flight_dump"
    assert header["reason"] == "unit test"
    assert header["dropped"] == 6
    assert [e["i"] for e in events] == [6, 7, 8, 9]


def test_flight_capacity_zero_disables():
    rec = flight.FlightRecorder(capacity=0)
    rec.record("tick")
    assert rec.events() == []


def test_flight_dump_never_raises(tmp_path):
    rec = flight.FlightRecorder(capacity=2)
    rec.record("tick")
    # unwritable destination: dump reports None instead of raising
    assert rec.dump("x", path="/proc/definitely/not/writable.jsonl") is None


# ---------------------------------------------------------- metrics plane
def test_render_prometheus_flattens_nested_numbers():
    text = metrics.render_prometheus(
        {"ready": True, "queue": {"depth": 3}, "p99": 1.5,
         "name": "ignored-string", "rungs": [256, 1024]})
    assert "# TYPE lgbm_tpu_ready gauge" in text
    assert "lgbm_tpu_ready 1" in text
    assert "lgbm_tpu_queue_depth 3" in text
    assert "lgbm_tpu_p99 1.5" in text
    assert "lgbm_tpu_rungs_count 2" in text
    assert "ignored-string" not in text


def test_metrics_server_serves_text_and_json():
    srv = metrics.MetricsServer(lambda: {"up": 1, "depth": {"rows": 7}},
                                port=0)
    try:
        base = f"http://127.0.0.1:{srv.port}"
        body = urllib.request.urlopen(f"{base}/metrics", timeout=5).read()
        assert b"lgbm_tpu_up 1" in body
        assert b"lgbm_tpu_depth_rows 7" in body
        health = json.loads(urllib.request.urlopen(
            f"{base}/healthz", timeout=5).read())
        assert health == {"up": 1, "depth": {"rows": 7}}
    finally:
        srv.stop()


def test_compile_counter_keys_by_phase():
    sentinel = np.random.RandomState(0).randn()  # fresh program per run

    @jax.jit
    def f(x):
        return x * sentinel

    with guards.compile_counter() as cc:
        with guards.compile_phase("zz_unit_phase"):
            f(jnp.ones(9))
    assert cc.lowerings >= 1
    assert cc.by_phase["zz_unit_phase"]["lowerings"] >= 1
    # outside any scope the phase is "other"
    assert guards.current_compile_phase() == "other"


def test_bench_counters_from_stream(tmp_path):
    """obs/summarize.bench_counters diffs the cumulative snapshots the
    bench marks carry — the BENCH-row ingestion path."""
    p = tmp_path / "s.jsonl"
    s = metrics.MetricsStream(str(p))

    def snap(low, back, phase):
        return {"lowerings": low, "backend_compiles": back,
                "by_phase": {phase: {"lowerings": low,
                                     "backend_compiles": back}}}

    s.emit("mark", name="warmup_start", compiles=snap(2, 1, "train_step"),
           cache={"requests": 0, "hits": 0})
    s.emit("iteration", iteration=1, seconds=0.5,
           compiles=snap(10, 5, "train_step"),
           cache={"requests": 4, "hits": 1})
    s.emit("mark", name="warmup_end", compiles=snap(12, 6, "train_step"),
           cache={"requests": 5, "hits": 2})
    s.emit("mark", name="steady_end", compiles=snap(12, 6, "train_step"),
           cache={"requests": 5, "hits": 2})
    s.close()
    row = summarize.bench_counters(str(p))
    assert row["compile_events"] == 10
    assert row["compile_events_steady"] == 0
    assert row["compile_events_by_phase"] == {
        "train_step": {"lowerings": 10, "backend_compiles": 5}}
    assert row["compile_cache"] == {"requests": 5, "hits": 2, "misses": 3}
    assert row["warmup_seconds"] >= 0.0
    # unmarked stream -> None (bench falls back to inline counters)
    q = tmp_path / "bare.jsonl"
    metrics.MetricsStream(str(q)).close()
    assert summarize.bench_counters(str(q)) is None


def test_summarize_table_renders(tmp_path, capsys):
    p = tmp_path / "s.jsonl"
    s = metrics.MetricsStream(str(p))
    s.emit("iteration", iteration=1, seconds=0.25,
           compiles={"lowerings": 3, "backend_compiles": 1,
                     "by_phase": {"train_step": {"lowerings": 3,
                                                 "backend_compiles": 1}}},
           cache={"requests": 1, "hits": 1})
    s.emit("summary", phase_times={"hist_build": {"seconds": 1.0,
                                                  "count": 5}},
           spans_seen=["hist_build"])
    s.emit("collective_program", key="step", bytes={"all-reduce": 128},
           total=128, count=1)
    s.close()
    assert summarize.main([str(p)]) == 0
    out = capsys.readouterr().out
    assert "hist_build" in out
    assert "collective programs" in out
    assert "compiles: 3 lowerings" in out


# ------------------------------------------- the acceptance criterion (A)
def test_taxonomy_trace_metrics_acceptance(tmp_path):
    """5-iteration compact data-parallel run with spans enabled
    (annotations mode — the device programs carry the named scopes either
    way; the profiler-artifact flavor is the slow test below): the run +
    a warmed serve tick touch EVERY taxonomy span, the metrics stream
    parses, and bench ingestion finds the per-iteration counters."""
    spans.reset()
    X, y = _make_data(800, 8)
    mpath = tmp_path / "metrics.jsonl"
    ckpt = tmp_path / "ckpt"
    params = {
        "objective": "binary", "num_leaves": 7, "verbosity": -1,
        "tpu_grower": "compact", "tree_learner": "data", "num_shards": 2,
        # `bag` is entered only where the strategy draws or reuses a bag
        "bagging_fraction": 0.8, "bagging_freq": 2,
        "tpu_trace_mode": "annotations",
        "tpu_metrics_path": str(mpath),
        "tpu_checkpoint_dir": str(ckpt), "tpu_checkpoint_freq": 2,
        "tpu_flight_buffer": 256,
    }
    ds = lgb.Dataset(X, label=y)
    bst = lgb.train(params, ds, num_boost_round=5,
                    valid_sets=[ds.create_valid(X[:200], label=y[:200])])
    # serving side of the taxonomy: warm the ladder + one coalesced tick
    # (the default device featurizer traces the `featurize` span) + one
    # pred_contrib call for the `contrib` span
    with spans.trace_session(None, "annotations"):
        server = bst.serve(warm_max_rows=256, tick_ms=1.0)
        try:
            out = server.predict(X[:16])
        finally:
            server.close(drain=True)
        bst.predict(X[:4], pred_contrib=True)
    np.testing.assert_allclose(np.asarray(out),
                               bst.predict(X[:16]), rtol=0, atol=0)

    # the ranking objective's own spans: `rank_layout` at init and, on
    # the compact grower, `rank_grads` around its gradient program
    lgb.train({"objective": "lambdarank", "num_leaves": 7, "verbosity": -1,
               "tpu_grower": "compact", "min_data_in_leaf": 5},
              lgb.Dataset(X[:200], label=(y[:200] > 0).astype(np.float32),
                          group=[50, 30, 120]), num_boost_round=1)

    # quantized gradients: the discretizer and the leaf renewal in the step
    lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1,
               "tpu_grower": "compact", "min_data_in_leaf": 5,
               "use_quantized_grad": True, "quant_train_renew_leaf": True},
              lgb.Dataset(X[:200], label=y[:200]), num_boost_round=1)

    # `import` is stamped once, as the package loads: before the reset
    missing = set(spans.SPAN_TAXONOMY) - spans.seen_spans() - {"import"}
    assert not missing, f"taxonomy spans never entered: {missing}"

    # metrics stream: per-iteration records with cumulative compile
    # counts, a final summary with the phase-time table
    recs = metrics.read_stream(str(mpath))
    iters = [r for r in recs if r["kind"] == "iteration"]
    assert len(iters) == 5
    assert [r["iteration"] for r in iters] == [1, 2, 3, 4, 5]
    assert all(r["seconds"] >= 0 for r in iters)
    # the update's seconds by phase and the thread's CPU seconds ride along
    assert all({"step_args", "step_dispatch", "bag"} <= set(r["phase_s"])
               and r["cpu_s"] >= 0 for r in iters)
    lows = [r["compiles"]["lowerings"] for r in iters]
    assert lows == sorted(lows) and lows[0] > 0      # cumulative
    assert "train_step" in iters[-1]["compiles"]["by_phase"]
    summaries = [r for r in recs if r["kind"] == "summary"]
    assert summaries, "engine did not emit the run summary record"
    # per-run spans_seen: host spans always re-enter; traced spans only
    # when the program was traced THIS run (a jit-cache reuse keeps its
    # original names) — binning/checkpoint_write are the robust ones
    assert set(summaries[-1]["spans_seen"]) >= {"binning",
                                                "checkpoint_write"}
    # checkpoint_write is a host span: it appears in the phase-time table
    assert "checkpoint_write" in summaries[-1]["phase_times"]

    # bench-style ingestion over the same stream works once marks exist
    s = metrics.stream_for(str(mpath))
    snap = {"compiles": guards.phase_compile_counts(),
            "cache": guards.global_cache_counts()}
    for name in ("warmup_start", "warmup_end", "steady_end"):
        s.emit("mark", name=name, **snap)
    row = summarize.bench_counters(str(mpath))
    assert row is not None and row["compile_events_steady"] == 0

    # checkpoint ticks dumped the flight ring beside the snapshots
    dumps = [f for f in os.listdir(ckpt) if f.startswith("flight_")]
    assert dumps, "checkpoint tick left no flight dump"
    events = flight.read_dump(str(ckpt / dumps[0]))
    kinds = {e["event"] for e in events}
    assert {"flight_dump", "iteration", "snapshot"} <= kinds


@pytest.mark.slow
def test_full_profiler_trace_artifacts(tmp_path):
    """Full tpu_trace_dir mode: a 5-iteration compact (data-parallel)
    run writes real profiler artifacts, the session closes them on the
    way out, and the artifact opens with jax's own reader and holds
    the program's host spans (the ``iteration`` annotations). Slow
    lane: opening the FIRST jax profiler session in a process costs a
    one-time ~10s init regardless of content."""
    spans.reset()
    X, y = _make_data(400, 6)
    trace_dir = tmp_path / "trace"
    mpath = tmp_path / "metrics.jsonl"
    params = {
        "objective": "binary", "num_leaves": 7, "verbosity": -1,
        "tpu_grower": "compact", "tree_learner": "data",
        "tpu_trace_dir": str(trace_dir),
        "tpu_metrics_path": str(mpath),
    }
    bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=5)
    trace_files = [os.path.join(r, f)
                   for r, _, fs in os.walk(trace_dir) for f in fs]
    assert trace_files, "tpu_trace_dir produced no profiler artifacts"
    assert {"binning", "gradient", "hist_build", "split_scan",
            "partition"} <= spans.seen_spans()
    assert not spans.annotations_enabled()

    from jax.profiler import ProfileData
    xplanes = [f for f in trace_files if f.endswith(".xplane.pb")]
    assert xplanes
    names = {e.name for plane in ProfileData.from_file(xplanes[-1]).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events}
    assert {"iteration", "step_dispatch"} <= names
    recs = metrics.read_stream(str(mpath))
    assert sum(r["kind"] == "iteration" for r in recs) == 5


# ------------------------------------------- the acceptance criterion (B)
@pytest.fixture(scope="module")
def telemetry_booster(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("obs_steady")
    X, y = _make_data(1500, 10, seed=7)
    params = {
        "objective": "binary", "num_leaves": 15, "max_bin": 63,
        "verbosity": -1, "tpu_grower": "compact",
        "stop_check_freq": 10_000,          # no mid-loop host flush
        "tpu_metrics_path": str(tmp / "m.jsonl"),
        "tpu_flight_buffer": 128,
    }
    ds = lgb.Dataset(X, label=y, params=params)
    bst = lgb.Booster(params, ds)
    for _ in range(2):
        bst.update()
    return bst


def test_steady_state_guards_hold_with_telemetry_enabled(telemetry_booster):
    """The whole telemetry layer on (spans via an annotations session,
    flight ring, metrics stream): 3 post-warmup compact iterations still
    lower nothing and materialize nothing on the host."""
    bst = telemetry_booster
    with spans.trace_session(None, "annotations"):
        with guards.steady_state_guard("telemetry-on steady state") as cc:
            for _ in range(3):
                bst.update()
    assert cc.lowerings == 0
    assert cc.backend_compiles == 0
    # and the ticks were actually emitted while guarded
    recs = metrics.read_stream(
        str(bst.config.get("tpu_metrics_path")))
    assert sum(r["kind"] == "iteration" for r in recs) >= 5


# ---------------------------------- flight dumps x fault injection (C)
def test_kill_at_step_leaves_parseable_dump(tmp_path, monkeypatch):
    """An injected kill@step (the simulated SIGKILL) escapes every
    handler — but the engine's crash hook dumps the ring first, and the
    dump's tail names the failing site."""
    dump_path = tmp_path / "postmortem.jsonl"
    monkeypatch.setenv("LGBM_TPU_FLIGHT_PATH", str(dump_path))
    X, y = _make_data(400, 6)
    params = {
        "objective": "binary", "num_leaves": 7, "verbosity": -1,
        "tpu_checkpoint_dir": str(tmp_path / "ck"),
        "tpu_checkpoint_freq": 1,
    }
    with faultinject.inject("kill@step=2"):
        with pytest.raises(faultinject.SimulatedKill):
            lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=6)
    events = flight.read_dump(str(dump_path))
    assert events[0]["event"] == "flight_dump"
    assert events[0]["reason"].startswith("crash")
    assert "SimulatedKill" in events[0]["error"]
    tail = events[-5:]
    fires = [e for e in tail if e["event"] == "fault_fire"]
    assert fires and fires[-1]["site"] == "step" \
        and fires[-1]["kind"] == "kill"
    # the crash marker is the final event on the record
    assert events[-1]["event"] == "crash"


@pytest.fixture(scope="module")
def served_booster():
    """One small trained booster shared by the serving-side telemetry
    tests (training is the expensive part; the tests only serve it)."""
    X, y = _make_data(400, 6)
    params = {"objective": "binary", "num_leaves": 7, "verbosity": -1}
    bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=3)
    return bst, X


def test_construction_crash_dumps_too(tmp_path, monkeypatch):
    """The crash-dump site wraps ALL of lgb.train, not just the boosting
    loop: a death during dataset construction still ships a post-mortem
    (the r05 failure was attributable to nothing on disk)."""
    dump_path = tmp_path / "construct.jsonl"
    monkeypatch.setenv("LGBM_TPU_FLIGHT_PATH", str(dump_path))
    X, _ = _make_data(50, 4)
    bad_y = np.zeros(7)                     # label length mismatch
    with pytest.raises(Exception):
        lgb.train({"objective": "binary", "verbosity": -1},
                  lgb.Dataset(X, label=bad_y), num_boost_round=2)
    events = flight.read_dump(str(dump_path))
    assert events and events[0]["event"] == "flight_dump"
    assert events[0]["reason"].startswith("crash")


def test_hang_at_swap_leaves_parseable_dump(tmp_path, monkeypatch,
                                            served_booster):
    """hang@swap past the commit deadline: the swap rolls back (old model
    stays active) AND the registry dumps the ring naming the swap site."""
    dump_path = tmp_path / "swap.jsonl"
    monkeypatch.setenv("LGBM_TPU_FLIGHT_PATH", str(dump_path))
    bst, X = served_booster
    server = bst.serve(warm_max_rows=256, tick_ms=1.0)
    try:
        from lightgbm_tpu.serving import SwapFailed
        with faultinject.inject("hang@swap=1:seconds=2"):
            with pytest.raises(SwapFailed):
                # same booster under a new version: the registry treats
                # versions, not objects — cheap and sufficient to drive
                # the commit path into the injected hang
                server.deploy("v2", bst, deadline_s=0.3)
        assert server.registry.active_version() == "v0"
        events = flight.read_dump(str(dump_path))
        assert events[0]["event"] == "flight_dump"
        assert "swap" in events[0]["reason"]
        kinds = [e["event"] for e in events]
        assert "swap_failed" in kinds
        fires = [e for e in events if e["event"] == "fault_fire"]
        assert any(e["site"] == "swap" and e["kind"] == "hang"
                   for e in fires)
    finally:
        server.close(drain=True)


# ----------------------------------------------- serving metrics endpoint
def test_prediction_server_metrics_endpoint(served_booster):
    bst, X = served_booster
    server = bst.serve(warm_max_rows=256, tick_ms=1.0, metrics_port=0)
    try:
        assert server.metrics_port is not None
        server.predict(X[:8])
        base = f"http://127.0.0.1:{server.metrics_port}"
        body = urllib.request.urlopen(
            f"{base}/metrics", timeout=5).read().decode()
        assert "lgbm_tpu_ready 1" in body
        assert "lgbm_tpu_stats_served_requests" in body
        assert "lgbm_tpu_compiles_lowerings" in body
        health = json.loads(urllib.request.urlopen(
            f"{base}/healthz", timeout=5).read())
        assert health["active_version"] == "v0"
        # the text API mirrors the HTTP one (no socket needed)
        assert "lgbm_tpu_ready" in server.metrics_text()
    finally:
        server.close(drain=True)
    # endpoint down after close
    with pytest.raises(Exception):
        urllib.request.urlopen(f"{base}/metrics", timeout=1)


# ------------------------------- per-rank attribution (ISSUE 11, leg 2)
@pytest.fixture(scope="module")
def rank_stats_booster(tmp_path_factory):
    """Same shape as telemetry_booster (programs already jit-cached by
    the earlier test) with the sampled rank-stats timers armed."""
    tmp = tmp_path_factory.mktemp("obs_ranks")
    X, y = _make_data(1500, 10, seed=7)
    params = {
        "objective": "binary", "num_leaves": 15, "max_bin": 63,
        "verbosity": -1, "tpu_grower": "compact",
        "stop_check_freq": 10_000,
        "tpu_metrics_path": str(tmp / "m.jsonl"),
        "tpu_rank_stats_every": 2,
        "tpu_straggler_factor": 3.0,
    }
    ds = lgb.Dataset(X, label=y, params=params)
    bst = lgb.Booster(params, ds)
    for _ in range(2):                    # warm: compiles + first sample
        bst.update()
    return bst


def test_rank_stats_sampled_timers_keep_steady_state_guard(
        rank_stats_booster):
    """The acceptance contract for leg 2: with sampling armed
    (tpu_rank_stats_every=2) the steady-state region still lowers
    nothing and materializes nothing on the host — on-sample ticks take
    only block_until_ready (not a transfer) plus the pre-compiled
    probe, off-sample iterations take neither."""
    bst = rank_stats_booster
    assert bst._gbdt._rank_stats is not None
    with spans.trace_session(None, "annotations"):
        with guards.steady_state_guard("rank-stats steady state") as cc:
            for _ in range(4):            # iters 3..6: samples at 4, 6
                bst.update()
    assert cc.lowerings == 0
    assert cc.backend_compiles == 0
    recs = metrics.read_stream(str(bst.config.get("tpu_metrics_path")))
    rs = [r for r in recs if r["kind"] == "rank_stats"]
    # samples at iterations 2, 4, 6
    assert [r["iteration"] for r in rs] == [2, 4, 6]
    assert all(r["world"] == 1 and r["ranks_reporting"] == 1
               for r in rs)
    assert all(r["max_s"] >= r["median_s"] >= 0 for r in rs)
    samples = [e for e in flight.recorder().events()
               if e["event"] == "rank_sample"]
    assert samples and samples[-1]["iteration"] == 6


def test_rank_stats_mesh_probe_does_not_recompile():
    """The collective-arrival probe compiles at construction (outside
    the steady-state region); sampled probes after that lower nothing."""
    from lightgbm_tpu.obs.ranks import RankStats
    from lightgbm_tpu.parallel.mesh import make_mesh
    rs = RankStats(every=1, mesh=make_mesh(), rank=0, world=1)
    assert rs._probe_fn is not None       # 8 virtual devices: live probe
    rs.collective_wait(1)                 # settle any first-call cache
    with guards.compile_counter() as cc:
        w = rs.collective_wait(2)
    assert w >= 0.0
    assert cc.lowerings == 0


def test_training_metrics_endpoint_scrapeable_while_training(tmp_path):
    """Satellite: tpu_metrics_port under lgb.train — a scrape DURING the
    run sees the live training tree (iteration progress, phase-keyed
    compiles, rank-stats gauges), and the endpoint is gone when the run
    ends."""
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    X, y = _make_data(400, 6)
    params = {
        "objective": "binary", "num_leaves": 7, "verbosity": -1,
        "tpu_metrics_port": port,
        "tpu_rank_stats_every": 1,
    }
    seen = {}

    def scrape(env):
        if env.iteration == 2 and not seen:
            base = f"http://127.0.0.1:{port}"
            seen["text"] = urllib.request.urlopen(
                f"{base}/metrics", timeout=5).read().decode()
            seen["health"] = json.loads(urllib.request.urlopen(
                f"{base}/healthz", timeout=5).read())

    lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=4,
              callbacks=[scrape])
    assert "lgbm_tpu_training 1" in seen["text"]
    assert "lgbm_tpu_iteration" in seen["text"]
    assert "lgbm_tpu_compiles_lowerings" in seen["text"]
    assert "lgbm_tpu_rank_stats_median_s" in seen["text"]
    assert seen["health"]["training"] is True
    assert seen["health"]["rank_stats"]["world"] == 1
    # endpoint is torn down with the run
    with pytest.raises(Exception):
        urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics",
                               timeout=1)


# ------------------------------------------- R012 leak regressions
def test_raising_train_leaves_no_open_trace_session(resource_leak_witness):
    """engine.py holds the trace session with ``with`` — a SimulatedKill
    mid-train unwinds the annotation enablement (the runtime complement
    of tpulint R012's PR-10 exception-edge check)."""
    X, y = _make_data(300, 6)
    params = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
              "tpu_trace_mode": "annotations"}
    assert spans.active_sessions() == 0
    with faultinject.inject("kill@iteration=1"):
        with pytest.raises(faultinject.SimulatedKill):
            lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=4)
    assert spans.active_sessions() == 0
    assert not spans.annotations_enabled()
