"""Distributed performance observability (ISSUE 11): fast-lane units.

* obs/tracing.py — the span taxonomy (the profiler-artifact reader that
  used to live there is gone; benchmarks/trace.py reduces device time);
* obs/ranks.py — sampled publish/aggregate over an injected KV,
  straggler flags, heartbeat-miss reporting;
* obs/ledger.py — per-chip efficiency, measured-vs-model, atomic record;
* scripts/obs — cross-rank merge ordered by (time, rank).
"""
import json

import pytest

from lightgbm_tpu.obs import flight, ledger, summarize, tracing


def test_phase_of_outermost_scope_wins():
    assert tracing.phase_of(
        "jit(s)/split_scan/jit(x)/partition/op") == "split_scan"
    assert tracing.phase_of("no taxonomy here") is None


# ----------------------------------------------------------------- ledger
def test_per_chip_efficiency_vs_one_chip_row():
    rows = ledger.per_chip_efficiency([
        {"n_chips": 1, "iters_per_sec": 2.0},
        {"n_chips": 8, "iters_per_sec": 12.0},
    ])
    assert rows[0]["efficiency"] == 1.0
    assert rows[1]["per_chip"] == 1.5
    assert rows[1]["efficiency"] == 0.75
    # no 1-chip row -> efficiency is honest None, never a guess
    rows = ledger.per_chip_efficiency([{"n_chips": 4,
                                        "iters_per_sec": 6.0}])
    assert rows[0]["efficiency"] is None


def test_measured_vs_model_block():
    analysis = {"decomposition": {"busy_seconds": 2.0,
                                  "comm_seconds": 0.5},
                "collectives": {"all-reduce": {"seconds": 0.5,
                                               "count": 10}},
                "source": "device"}
    contract = {"measured": {"total": 1440}, "mode": "data_scatter",
                "num_devices": 8}
    block = ledger.measured_vs_model(analysis, contract, steps=100)
    assert block["measured"]["comm_fraction"] == 0.25
    assert block["model"]["bytes_per_step"] == 1440
    assert block["model"]["bytes_total"] == 144000
    assert block["implied_gbps"] == pytest.approx(144000 / 0.5 / 1e9)


def test_ledger_record_merges_atomically(tmp_path):
    path = tmp_path / "COMM.json"
    path.write_text(json.dumps({"existing": {"all-reduce": 24588}}))
    block = ledger.ledger_block("higgs", 1, 2.0)
    ledger.record(str(path), "higgs_x1", block)
    block8 = ledger.ledger_block(
        "higgs", 8, 12.0,
        prior_rows=ledger.prior_rows(str(path), "higgs"))
    ledger.record(str(path), "higgs_x8", block8)
    data = json.loads(path.read_text())
    assert data["existing"] == {"all-reduce": 24588}   # preserved
    led = data["scaling_ledger"]
    assert led["higgs_x1"]["scaling"][0]["efficiency"] == 1.0
    assert led["higgs_x8"]["scaling"][-1]["efficiency"] == 0.75
    assert led["higgs_x8"]["n_chips"] == 8


def test_load_contract_known_modes():
    c = ledger.load_contract("data_scatter")
    # the file's own number, whatever the XLA that recorded it
    assert c is not None
    assert ledger.model_bytes_per_step(c) == c["measured"]["total"] > 0
    assert ledger.load_contract("no_such_mode") is None


# ------------------------------------------------------- rank attribution
class _FakeKV:
    """Dict-backed stand-in for the coordination-service client."""

    def __init__(self):
        self.store = {}
        self.barriers = []

    def key_value_set(self, k, v):
        if k in self.store:
            raise RuntimeError(f"key exists: {k}")
        self.store[k] = v

    def blocking_key_value_get(self, k, timeout_ms):
        if k not in self.store:
            raise TimeoutError(k)
        return self.store[k]

    def wait_at_barrier(self, name, timeout_ms):
        self.barriers.append(name)


def _pair(kv, every=1, factor=3.0):
    from lightgbm_tpu.obs.ranks import RankStats
    r1 = RankStats(every=every, straggler_factor=factor, kv=kv,
                   rank=1, world=2)
    r0 = RankStats(every=every, straggler_factor=factor, kv=kv,
                   rank=0, world=2)
    # the two instances must agree on the KV namespace (in production
    # the run counter advances in program order on every rank)
    r0._run = r1._run
    return r0, r1


def test_rank_stats_aggregate_and_straggler_flag():
    kv = _FakeKV()
    r0, r1 = _pair(kv)
    flight.recorder().clear()
    for i in (1, 2):                      # healthy baseline window
        r1.sample_step(i, 0.01)
        r0.sample_step(i, 0.01)
    r1.sample_step(3, 2.0)                # rank 1 hangs at step 3
    r0.sample_step(3, 0.01)
    agg = r0.latest_tree()
    assert agg["world"] == 2 and agg["ranks_reporting"] == 2
    assert agg["stragglers"] == [1]
    assert agg["max_rank"] == 1
    assert r0.straggler_events == 1
    events = flight.recorder().events()
    st = [e for e in events if e["event"] == "straggler"]
    assert st and st[-1]["rank"] == 1 and st[-1]["iteration"] == 3
    # the arrival barrier was exercised on both ranks
    assert kv.barriers


def test_rank_stats_global_slowdown_is_not_a_straggler():
    kv = _FakeKV()
    r0, r1 = _pair(kv)
    for i in (1, 2):
        r1.sample_step(i, 0.01)
        r0.sample_step(i, 0.01)
    # BOTH ranks slow down 100x: rolling median protects against the
    # false positive — nobody is a straggler relative to the pod
    r1.sample_step(3, 1.0)
    r0.sample_step(3, 1.0)
    assert r0.latest_tree()["stragglers"] == []


def test_rank_stats_missing_rank_reports_heartbeat():
    kv = _FakeKV()
    r0, _ = _pair(kv)
    flight.recorder().clear()
    r0.sample_step(1, 0.01)               # rank 1 never publishes
    agg = r0.latest_tree()
    assert agg["missing"] == [1]
    assert agg["ranks_reporting"] == 1
    misses = [e for e in flight.recorder().events()
              if e["event"] == "rank_missing"]
    assert misses and misses[-1]["rank"] == 1


def test_rank_stats_sampling_cadence():
    from lightgbm_tpu.obs.ranks import RankStats
    rs = RankStats(every=4, kv=_FakeKV(), rank=0, world=1)
    assert [i for i in range(1, 13) if rs.due(i)] == [4, 8, 12]


# ------------------------------------------------------ cross-rank merge
def test_obs_merge_orders_by_time_then_rank(tmp_path, capsys):
    r0 = flight.FlightRecorder(capacity=16)
    r1 = flight.FlightRecorder(capacity=16)
    r0.record("rank_sample", rank=0, iteration=1)
    r1.record("rank_sample", rank=1, iteration=1)
    r1.record("fault_fire", site="step", kind="hang")
    r0.record("straggler", rank=1, iteration=3)
    p0 = r0.dump("end", path=str(tmp_path / "f_rank0.jsonl"))
    p1 = r1.dump("end", path=str(tmp_path / "f_rank1.jsonl"))
    merged = summarize.merge_ranks([p0, p1])
    # every record source-annotated (from the filename tag here)
    assert {r["src_rank"] for r in merged} == {0, 1}
    ts = [(r.get("t", 0.0), r["src_rank"]) for r in merged]
    assert ts == sorted(ts)
    kinds = [summarize._kind(r) for r in merged]
    assert "straggler" in kinds and "fault_fire" in kinds
    # the annotation must NOT clobber a payload rank: rank 0's dump
    # says rank 1 straggled, and the merged record still says so
    st = next(r for r in merged if summarize._kind(r) == "straggler")
    assert st["src_rank"] == 0 and st["rank"] == 1
    # CLI form (jsonl): one parseable record per line
    assert summarize.merge_main([p0, p1, "--jsonl"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == len(merged)
    assert all(isinstance(json.loads(line), dict) for line in out)


def test_flight_dump_carries_rank_field(tmp_path):
    rec = flight.FlightRecorder(capacity=4)
    rec.record("tick")
    out = rec.dump("unit", path=str(tmp_path / "f.jsonl"))
    header = flight.read_dump(out)[0]
    assert "rank" in header           # None single-process, int on pods
    assert header["rank"] is None
