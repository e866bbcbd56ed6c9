"""The trace arithmetic and the reducers on a hand-made profile: a
``while`` that encloses its body, a kernel inside it, a gap."""
import pytest

from benchmarks import trace
from benchmarks.reducers import (device_self_time, idle_share, mfu,
                                 roofline)

# window 0..10 s (two update spans with a gap between them);
# while.1 covers 1..7 and encloses fusion.2 (1..2), custom-call.3 (2..5)
# and fusion.4 (5..6); copy.5 runs alone 8..9
PROFILE = {
    "window": (0.0, 10.0),
    "devices": {"/device:TPU:0": [
        ("while.1", 1.0, 7.0), ("fusion.2", 1.0, 2.0),
        ("custom-call.3", 2.0, 5.0), ("fusion.4", 5.0, 6.0),
        ("copy.5", 8.0, 9.0), ("before.6", -2.0, -1.0)]},
    "host_spans": [("update", 0.0, 7.5), ("update", 7.9, 10.0)],
}
PEAK = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
RUN = {"profile": PROFILE, "iterations": 2, "peak": PEAK,
       "work": {"flops": 60.0, "bytes": 3.0}}


def test_busy_is_the_union_not_the_sum():
    busy = trace.busy_seconds(PROFILE)["/device:TPU:0"]
    assert busy == pytest.approx(7.0)       # 6 under the while + 1, not 12


def test_self_time_excludes_children():
    own = dict(trace.self_times(trace.clipped(
        PROFILE["devices"]["/device:TPU:0"], PROFILE["window"])))
    assert own["while.1"] == pytest.approx(1.0)
    assert own["custom-call.3"] == pytest.approx(3.0)
    assert sum(own.values()) == pytest.approx(7.0)
    assert "before.6" not in own


def test_idle_and_busy_make_the_window():
    idle = idle_share.reduce(RUN)
    busy = trace.busy_seconds(PROFILE)["/device:TPU:0"]
    assert idle == pytest.approx(30.0)
    assert idle / 100 * 10.0 + busy == pytest.approx(10.0)
    gaps = trace.gaps(trace.clipped(PROFILE["devices"]["/device:TPU:0"],
                                    PROFILE["window"]), PROFILE["window"])
    assert sum(e - s for s, e in gaps) == pytest.approx(3.0)


def test_kernel_and_the_rest_split_the_self_time():
    kernel = device_self_time.reduce(RUN, pattern="custom-call")
    other = device_self_time.reduce(RUN, pattern="custom-call", outside=True)
    assert kernel == pytest.approx(1.5)     # 3 s over 2 iterations
    assert other == pytest.approx(2.0)


def test_roofline_and_mfu():
    # least time: max(60/100, 3/10) = 0.6 s against 3 s of kernel
    assert roofline.reduce(RUN, pattern="custom-call") == pytest.approx(20.0)
    assert mfu.reduce(RUN) == pytest.approx(100 * 60.0 / (10.0 * 100.0))


def test_a_reducer_with_nothing_to_read_is_silent():
    assert roofline.reduce(RUN, pattern="no_such_kernel") is None
    assert device_self_time.reduce(RUN, pattern="no_such_kernel") is None
    empty = dict(RUN, profile=dict(PROFILE, devices={}))
    assert idle_share.reduce(empty) is None
    assert mfu.reduce(empty) is None
    assert mfu.reduce(dict(RUN, profile=None)) is None


def test_breakdown_names_ops_and_labels_gaps():
    out = trace.breakdown(PROFILE)
    assert out["device_ops"][0] == ["custom-call.3", pytest.approx(3.0)]
    labels = dict(out["idle_gaps"])
    assert labels["update.total"] == pytest.approx(2.6)   # 0-1, 7-7.5, 7.9-8, 9-10
    assert labels["between_spans.total"] == pytest.approx(0.4)
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
