"""Engine registry + startup microbench autotuner (ISSUE 12).

Pins the tentpole's contracts:

* resolve-order precedence — user > env > autotune cache > heuristic
  default — per knob, with provenance in ``Resolution.sources``;
* the autotune cache round-trips atomically, a corrupted cache falls
  back to heuristics (and a sweep-allowed run re-benches + rewrites);
* ``tpu_autotune=first_run`` on a fresh cache runs the microbench
  exactly ONCE; a second run with the same shape-class performs zero
  microbenches (and its setup lowers nothing new);
* ``reset_parameter`` re-resolves every engine knob through the
  registry (a mid-run change is never a silent no-op);
* the steady-state 0-recompile/0-d2h guard holds with autotune armed
  (the sweep runs strictly before the steady window, in the
  ``autotune`` compile phase);
* trees are bit-identical across ``tpu_autotune=off`` vs an autotuned
  selection (engine choice changes speed only).

Fast-lane tests stub ``autotune._time_candidate`` (tier-1 budget); the
REAL timed sweep and the offline CLI live in the ``slow`` lane.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.analysis import guards
from lightgbm_tpu.engines import autotune, registry

from utils import binary_data

SHAPE = registry.DatasetShape(rows=100_000, features=28, num_bins=255,
                              mode="serial")
BASE = {"objective": "binary", "max_bin": 31, "min_data_in_leaf": 5,
        "verbosity": -1, "seed": 7, "num_iterations": 5}


def _strip_knobs(model_text):
    return "\n".join(l for l in model_text.splitlines()
                     if not l.startswith("[tpu_"))


def _stub_timer(monkeypatch, times=None):
    """Replace the candidate timer: deterministic synthetic timings (by
    call order) and no device work — the fast-lane discipline."""
    seq = list(times or [])
    calls = []

    def fake(fn, *args, reps=0):
        calls.append(fn)
        return seq.pop(0) if seq else 1e-3

    monkeypatch.setattr(autotune, "_time_candidate", fake)
    return calls


def _decision_block(winner, platform="cpu", sclass=None):
    return {"winner": winner, "table": [], "platform": platform,
            "shape_class": sclass or registry.shape_class(SHAPE),
            "rows_sampled": 0, "reps": 0, "recorded": "test"}


# ---------------------------------------------------------- resolve order
def test_resolve_order_precedence(tmp_path, monkeypatch):
    """user > env > autotune cache > heuristic default, per knob, with
    the provenance recorded in Resolution.sources."""
    monkeypatch.delenv("LGBM_TPU_HIST_MBATCH", raising=False)
    cache = tmp_path / "at.json"
    autotune.store_decision(
        str(cache), autotune.cache_key("cpu", registry.shape_class(SHAPE)),
        _decision_block({"entry": "xla_lane", "hist_impl": "xla",
                         "hist_layout": "lane", "hist_mbatch": 16}))
    cfg = {"tpu_autotune": "first_run", "tpu_autotune_cache": str(cache)}
    # autotune rung: the cached winner applies where user/env are silent
    res = registry.resolve(cfg, shape=SHAPE, platform="cpu",
                           allow_sweep=False)
    assert res.hist_mbatch == 16
    assert res.sources["hist_mbatch"] == "autotune"
    assert res.hist_impl == "xla"
    assert res.autotuned and res.entry_id == "xla_lane"
    assert res.shape_class == registry.shape_class(SHAPE)
    # env beats the cache
    monkeypatch.setenv("LGBM_TPU_HIST_MBATCH", "4")
    res = registry.resolve(cfg, shape=SHAPE, platform="cpu",
                           allow_sweep=False)
    assert res.hist_mbatch == 4 and res.sources["hist_mbatch"] == "env"
    # user beats the env override
    res = registry.resolve(dict(cfg, tpu_hist_mbatch=12), shape=SHAPE,
                           platform="cpu", allow_sweep=False)
    assert res.hist_mbatch == 12 and res.sources["hist_mbatch"] == "user"
    monkeypatch.delenv("LGBM_TPU_HIST_MBATCH")
    # heuristic default with autotune off: no decision applies
    res = registry.resolve({"tpu_autotune": "off",
                            "tpu_autotune_cache": str(cache)},
                           shape=SHAPE, platform="cpu", allow_sweep=False)
    assert res.hist_mbatch == 8
    assert res.sources["hist_mbatch"] == "default"
    assert not res.autotuned


def test_resolve_unknown_values_warn_like_before():
    """Unknown knob values keep the warn-and-default behavior the old
    _pick_* helpers had (the delegates route through the registry)."""
    from lightgbm_tpu.boosting.gbdt import (_pick_hist_layout,
                                            _pick_hist_mbatch,
                                            _pick_step_buckets)
    assert _pick_hist_layout({"tpu_hist_layout": "bogus"}, 64) == "lane"
    assert _pick_hist_layout({"tpu_hist_layout": "sublane"}, 256) == "lane"
    assert _pick_hist_mbatch({"tpu_hist_mbatch": 99}) == 16
    assert _pick_step_buckets({"tpu_step_buckets": "bogus"}) is True
    assert registry.resolve_overlap({"tpu_hist_overlap": "bogus"}) == 0
    assert autotune.resolve_mode({"tpu_autotune": "bogus"}) == "first_run"


def test_auto_layout_honest_with_cached_sublane_win(tmp_path):
    """The PR 6 sweep measured sublane competitive at B <= 64 but `auto`
    could never select it; with a cached measured win it can — and
    without a cache the conservative lane default holds. A stale
    decision against a wider re-binned shape falls back to lane."""
    shape16 = registry.DatasetShape(rows=1 << 20, features=16,
                                    num_bins=16, mode="serial")
    cache = tmp_path / "at.json"
    autotune.store_decision(
        str(cache), autotune.cache_key("tpu", registry.shape_class(shape16)),
        _decision_block({"entry": "pallas_sublane", "hist_impl": "pallas",
                         "hist_layout": "sublane", "hist_mbatch": 8},
                        platform="tpu",
                        sclass=registry.shape_class(shape16)))
    cfg = {"tpu_autotune": "first_run", "tpu_autotune_cache": str(cache)}
    res = registry.resolve(cfg, shape=shape16, platform="tpu",
                           allow_sweep=False)
    assert res.hist_layout == "sublane"
    assert res.sources["hist_layout"] == "autotune"
    # no cache -> lane (the documented conservative default)
    res = registry.resolve({"tpu_autotune": "off"}, shape=shape16,
                           platform="tpu", allow_sweep=False)
    assert res.hist_layout == "lane"
    # stale sublane decision vs a wide-bin shape: lane, not a blowup
    wide = shape16._replace(num_bins=255)
    autotune.store_decision(
        str(cache), autotune.cache_key("tpu", registry.shape_class(wide)),
        _decision_block({"hist_layout": "sublane", "hist_mbatch": 8},
                        platform="tpu",
                        sclass=registry.shape_class(wide)))
    res = registry.resolve(cfg, shape=wide, platform="tpu",
                           allow_sweep=False)
    assert res.hist_layout == "lane"
    # user knob still beats the cache outright
    res = registry.resolve(dict(cfg, tpu_hist_layout="lane"),
                           shape=shape16, platform="tpu",
                           allow_sweep=False)
    assert res.hist_layout == "lane"
    assert res.sources["hist_layout"] == "user"


def test_shape_class_buckets_like_the_ladder():
    a = registry.DatasetShape(100_000, 28, 255, "serial")
    b = registry.DatasetShape(120_000, 30, 255, "serial")
    assert registry.shape_class(a) == registry.shape_class(b)
    assert registry.shape_class(a) != registry.shape_class(
        a._replace(mode="data"))
    assert registry.shape_class(a) != registry.shape_class(
        a._replace(rows=300_000))
    assert "quant" in registry.shape_class(a._replace(quant=True))


def test_sweep_candidates_respect_platform_and_bins():
    cands = registry.sweep_candidates(SHAPE, "cpu")
    assert cands and all(c.entry.id == "xla_lane" for c in cands)
    assert sorted({c.mbatch for c in cands}) == [1, 8, 16]
    # the default mbatch leads so a tie resolves to today's behavior
    assert cands[0].mbatch == 8
    tpu = registry.sweep_candidates(
        SHAPE._replace(num_bins=16), "tpu")
    ids = {c.entry.id for c in tpu}
    assert "pallas_lane" in ids and "pallas_sublane" in ids
    assert "fused_lane" not in ids          # structural, not swept
    wide = registry.sweep_candidates(SHAPE, "tpu")
    assert "pallas_sublane" not in {c.entry.id for c in wide}  # B > 64


# ------------------------------------------------------------- the cache
def test_cache_roundtrip_corruption_and_always(tmp_path, monkeypatch):
    """first_run: exactly one sweep on a fresh cache, zero on the warm
    rerun; a corrupted cache degrades to heuristics (no-sweep path) or
    re-benches + rewrites (sweep path); always re-sweeps over a hit."""
    _stub_timer(monkeypatch)
    cache = tmp_path / "at.json"
    shape = registry.DatasetShape(rows=512, features=4, num_bins=16,
                                  mode="serial")
    sample = np.zeros((512, 4), np.uint8)
    cfg = {"tpu_autotune": "first_run", "tpu_autotune_cache": str(cache)}
    n0 = autotune.SWEEPS_RUN
    res = registry.resolve(cfg, shape=shape, platform="cpu",
                           sample_provider=lambda n: sample[:n])
    assert autotune.SWEEPS_RUN == n0 + 1 and res.autotuned
    data = json.loads(cache.read_text())
    assert data["version"] == autotune.CACHE_VERSION
    (key, block), = data["entries"].items()
    assert key == f"cpu/{registry.shape_class(shape)}"
    assert block["winner"]["entry"] == "xla_lane"
    assert len(block["table"]) == 3 and all("ms" in r
                                            for r in block["table"])
    # warm rerun: ZERO microbenches, same decision
    res2 = registry.resolve(cfg, shape=shape, platform="cpu",
                            sample_provider=lambda n: sample[:n])
    assert autotune.SWEEPS_RUN == n0 + 1
    assert res2[:7] == res[:7]
    # always: re-sweeps over the cache hit
    res3 = registry.resolve(dict(cfg, tpu_autotune="always"), shape=shape,
                            platform="cpu",
                            sample_provider=lambda n: sample[:n])
    assert autotune.SWEEPS_RUN == n0 + 2 and res3.autotuned
    # corrupted cache, no sweep allowed: heuristic fallback, no raise
    cache.write_text("{definitely not json")
    res4 = registry.resolve(cfg, shape=shape, platform="cpu",
                            allow_sweep=False)
    assert not res4.autotuned and res4.sources["hist_mbatch"] == "default"
    # corrupted cache, sweep allowed: re-bench and rewrite atomically
    res5 = registry.resolve(cfg, shape=shape, platform="cpu",
                            sample_provider=lambda n: sample[:n])
    assert autotune.SWEEPS_RUN == n0 + 3 and res5.autotuned
    assert json.loads(cache.read_text())["entries"]


def test_unwritable_cache_still_uses_measured_winner(tmp_path,
                                                     monkeypatch):
    _stub_timer(monkeypatch)
    shape = registry.DatasetShape(rows=256, features=4, num_bins=16,
                                  mode="serial")
    sample = np.zeros((256, 4), np.uint8)
    bad = tmp_path / "no_dir_here"
    bad.write_text("")      # a FILE where the cache dir path must go
    cfg = {"tpu_autotune": "first_run",
           "tpu_autotune_cache": str(bad / "at.json")}
    res = registry.resolve(cfg, shape=shape, platform="cpu",
                           sample_provider=lambda n: sample[:n])
    assert res.autotuned        # this run still took the measured winner


def test_gspmd_partitioned_step_never_gets_a_mosaic_engine():
    """The masked grower under a mesh is partitioned by GSPMD, and
    lowering a Mosaic call there is refused ("cannot be automatically
    partitioned" — met compiling the voting step for a 4-chip v5e mesh):
    such shapes sweep and resolve the XLA einsum only, under their own
    cache key, and asking for pallas outright is an error."""
    shape = SHAPE._replace(mode="voting", gspmd=True)
    assert registry.shape_class(shape).endswith("-gspmd")
    assert {c.entry.id for c in registry.sweep_candidates(shape, "tpu")} \
        == {"xla_lane"}
    res = registry.resolve({"tpu_autotune": "off"}, shape=shape,
                           platform="tpu")
    assert res.hist_impl == "xla" and res.entry_id == "xla_lane"
    assert res.sources["hist_impl"] == "gspmd"
    with pytest.raises(ValueError, match="partitioned by GSPMD"):
        registry.resolve({"tpu_autotune": "off", "tpu_hist_impl": "pallas"},
                         shape=shape, platform="tpu")
    # the same mode under shard_map (compact grower) keeps the kernels
    res = registry.resolve({"tpu_autotune": "off"},
                           shape=shape._replace(gspmd=False), platform="tpu")
    assert res.hist_impl == "auto" and res.entry_id == "fused_lane"


def test_failed_candidate_stops_the_run(monkeypatch):
    """A candidate the backend refuses must not lose the race quietly to
    the XLA einsum (exit code 0, ~80x slower): the sweep raises with the
    candidate's name and shape."""
    def refuse(fn, *args, reps=0):
        raise ValueError("Mosaic failed to compile TPU kernel")
    monkeypatch.setattr(autotune, "_time_candidate", refuse)
    cands = registry.sweep_candidates(
        registry.DatasetShape(512, 4, 16, "serial"), "cpu")
    with pytest.raises(RuntimeError,
                       match=r"xla_lane-k8 failed on bins\(512, 4\)"):
        autotune.run_sweep(np.zeros((512, 4), np.uint8), 16, cands)


def test_fused_on_without_tpu_raises():
    """tpu_fused=on off-TPU used to warn and take the XLA walk; now it is
    an error (interpret mode, resolved first, stays the CI spelling)."""
    with pytest.raises(ValueError, match="tpu_fused=on requires a TPU"):
        registry.resolve({"tpu_fused": "on"})          # live backend: cpu
    with pytest.raises(ValueError, match="tpu_fused=on requires a TPU"):
        registry.resolve_fused_block({"tpu_fused": "on"}, platform="cpu")
    assert registry.resolve_fused_block(
        {"tpu_fused": "on", "tpu_fused_interpret": True,
         "tpu_fused_block": 128}, platform="cpu") == 128
    assert registry.resolve_fused_block({"tpu_fused": "auto"},
                                        platform="cpu") == 0
    X, y = binary_data(300, 4, seed=0)
    with pytest.raises(ValueError, match="tpu_fused=on requires a TPU"):
        lgb.train(dict(BASE, tpu_fused="on"), lgb.Dataset(X, label=y), 1)


def test_implicit_arming_stays_inert_on_cpu(monkeypatch):
    """The first_run DEFAULT must not tax CPU runs or small shapes: with
    tpu_autotune unset, nothing sweeps on cpu even at 1M rows, and on
    TPU platforms only shapes >= MIN_AUTOTUNE_ROWS arm."""
    def boom(*a, **k):  # pragma: no cover - the assertion IS the call
        raise AssertionError("sweep ran while unarmed")
    monkeypatch.setattr(autotune, "run_sweep", boom)
    big = registry.DatasetShape(rows=1 << 20, features=28, num_bins=255,
                                mode="serial")
    res = registry.resolve({}, shape=big, platform="cpu",
                           sample_provider=lambda n: np.zeros((n, 28)))
    assert not res.autotuned
    small = registry.DatasetShape(rows=1000, features=28, num_bins=255,
                                  mode="serial")
    res = registry.resolve({}, shape=small, platform="tpu",
                           sample_provider=lambda n: np.zeros((n, 28)))
    assert not res.autotuned


# ------------------------------------------ the fused kernel's own depth
BIG = registry.DatasetShape(rows=1 << 20, features=28, num_bins=255,
                            mode="serial")
OFF = {"tpu_autotune": "off"}


def _cached(tmp_path, shape, platform="tpu", layout="lane"):
    """cfg whose autotune cache holds a standalone Mosaic winner at depth
    16 in ``layout``."""
    cache = tmp_path / "at.json"
    autotune.store_decision(
        str(cache), autotune.cache_key(platform,
                                       registry.shape_class(shape)),
        _decision_block({"entry": f"pallas_{layout}", "hist_impl": "pallas",
                         "hist_layout": layout, "hist_mbatch": 16},
                        platform=platform,
                        sclass=registry.shape_class(shape)))
    return {"tpu_autotune": "first_run", "tpu_autotune_cache": str(cache)}


@pytest.mark.parametrize("cfg,platform,entry", [
    (OFF, "tpu", "fused_lane"),
    (dict(OFF, tpu_fused="on"), "tpu", "fused_lane"),
    # interpret mode on a CPU host is a fused entry too
    (dict(OFF, tpu_fused_interpret=True, tpu_fused_block=128), "cpu",
     "fused_lane"),
], ids=["tpu-auto", "tpu-on", "cpu-interpret"])
def test_fused_entry_resolves_its_own_depth(monkeypatch, cfg, platform,
                                            entry):
    """With a fused entry and nothing set, the resolution carries the
    fused kernel's depth (not the standalone engines' default of 8) and
    says where it came from."""
    monkeypatch.delenv("LGBM_TPU_HIST_MBATCH", raising=False)
    res = registry.resolve(cfg, shape=BIG, platform=platform)
    assert res.entry_id == entry and res.fused_block > 0
    assert res.hist_mbatch == registry.FUSED_MBATCH
    assert res.sources["hist_mbatch"] == "fused"
    # no shape context (a booster without a train set) fuses alike
    res = registry.resolve(cfg, shape=None, platform=platform)
    assert res.hist_mbatch == registry.FUSED_MBATCH
    assert res.sources["hist_mbatch"] == "fused"


def test_fused_sublane_entry_resolves_its_own_depth(monkeypatch):
    monkeypatch.delenv("LGBM_TPU_HIST_MBATCH", raising=False)
    res = registry.resolve(dict(OFF, tpu_hist_layout="sublane"),
                           shape=BIG._replace(num_bins=63), platform="tpu")
    assert res.entry_id == "fused_sublane"
    assert res.hist_mbatch == registry.FUSED_MBATCH
    assert res.sources["hist_mbatch"] == "fused"


def test_user_and_env_depth_still_reach_the_fused_kernel(monkeypatch):
    """An explicit tpu_hist_mbatch and LGBM_TPU_HIST_MBATCH win for the
    fused kernel as they do for the standalone engines, user first: the
    chip bisect (depth 1 against 8) stays reproducible."""
    monkeypatch.delenv("LGBM_TPU_HIST_MBATCH", raising=False)
    res = registry.resolve(dict(OFF, tpu_hist_mbatch=8), shape=BIG,
                           platform="tpu")
    assert res.entry_id == "fused_lane"
    assert res.hist_mbatch == 8 and res.sources["hist_mbatch"] == "user"
    monkeypatch.setenv("LGBM_TPU_HIST_MBATCH", "4")
    res = registry.resolve(OFF, shape=BIG, platform="tpu")
    assert res.hist_mbatch == 4 and res.sources["hist_mbatch"] == "env"
    res = registry.resolve(dict(OFF, tpu_hist_mbatch=16), shape=BIG,
                           platform="tpu")
    assert res.hist_mbatch == 16 and res.sources["hist_mbatch"] == "user"
    monkeypatch.setenv("LGBM_TPU_HIST_MBATCH", "99")     # clamped, not lost
    res = registry.resolve(OFF, shape=BIG, platform="tpu")
    assert res.hist_mbatch == 16 and res.sources["hist_mbatch"] == "env"


def test_fused_entry_takes_the_sweeps_layout_not_its_depth(tmp_path,
                                                           monkeypatch):
    """The autotuner times the STANDALONE engines: its winning depth does
    not reach the fused kernel (its winning layout still does), and the
    same cache entry applies in full once the run does not fuse."""
    monkeypatch.delenv("LGBM_TPU_HIST_MBATCH", raising=False)
    shape = BIG._replace(num_bins=16)
    cfg = _cached(tmp_path, shape, layout="sublane")
    res = registry.resolve(cfg, shape=shape, platform="tpu",
                           allow_sweep=False)
    assert res.autotuned and res.entry_id == "fused_sublane"
    assert res.sources["hist_layout"] == "autotune"
    assert res.hist_mbatch == registry.FUSED_MBATCH
    assert res.sources["hist_mbatch"] == "fused"
    res = registry.resolve(dict(cfg, tpu_fused="off"), shape=shape,
                           platform="tpu", allow_sweep=False)
    assert res.entry_id == "pallas_sublane" and res.fused_block == 0
    assert res.hist_mbatch == 16
    assert res.sources["hist_mbatch"] == "autotune"


@pytest.mark.parametrize("cfg,shape,platform", [
    (OFF, BIG, "cpu"),
    (dict(OFF, tpu_fused="off"), BIG, "tpu"),
    (OFF, BIG._replace(mode="voting", gspmd=True), "tpu"),
    (OFF, BIG._replace(mode="data", gspmd=True), "tpu"),
    # one chip's masked grower (small data, caller's gradients)
    (OFF, BIG._replace(compact=False), "tpu"),
    (dict(OFF, tpu_fused_interpret=True), BIG._replace(compact=False),
     "cpu"),
], ids=["cpu", "tpu-fused-off", "gspmd-voting", "gspmd-data",
        "tpu-masked", "cpu-interpret-masked"])
def test_standalone_engines_resolve_as_before(tmp_path, monkeypatch, cfg,
                                              shape, platform):
    """A resolution that does not fuse keeps the standalone depth: the
    default of 8, the sweep's winner above it, user and env above that."""
    monkeypatch.delenv("LGBM_TPU_HIST_MBATCH", raising=False)
    res = registry.resolve(cfg, shape=shape, platform=platform)
    assert not res.entry_id.startswith("fused")
    assert res.hist_mbatch == 8 and res.sources["hist_mbatch"] == "default"
    cached = dict(_cached(tmp_path, shape, platform), **{
        k: v for k, v in cfg.items() if k != "tpu_autotune"})
    res = registry.resolve(cached, shape=shape, platform=platform,
                           allow_sweep=False)
    assert res.autotuned
    assert res.hist_mbatch == 16
    assert res.sources["hist_mbatch"] == "autotune"
    res = registry.resolve(dict(cfg, tpu_hist_mbatch=2), shape=shape,
                           platform=platform)
    assert res.hist_mbatch == 2 and res.sources["hist_mbatch"] == "user"


def test_autotune_candidates_unchanged_by_the_fused_depth():
    """The sweep grid is the standalone engines' and keeps its depths,
    default first; no fused entry is ever timed."""
    assert registry.MBATCH_CANDIDATES == (8, 16, 1)
    keys = [c.key for c in registry.sweep_candidates(BIG, "tpu")]
    assert keys == ["xla_lane-k8", "xla_lane-k16", "xla_lane-k1",
                    "pallas_lane-k8", "pallas_lane-k16", "pallas_lane-k1"]
    assert [c.key for c in registry.sweep_candidates(BIG, "cpu")] \
        == keys[:3]
    assert registry.resolve_mbatch({}) == 8
    assert registry.resolve_mbatch({}, {"hist_mbatch": 16}) == 16
    assert registry.resolve_mbatch({}, {"hist_mbatch": 16},
                                   fused=True) == registry.FUSED_MBATCH


# ----------------------------------------------- booster-level integration
def test_first_run_once_then_zero_microbenches(tmp_path, monkeypatch):
    """The acceptance loop: a fresh cache sweeps exactly once at
    _setup_train; a second booster over the same shape-class resolves
    from the cache with 0 microbenches and no extra autotune-phase
    compiles (stubbed timer -> the sweep itself lowers nothing, so ANY
    autotune-phase compile on the rerun would be a leak)."""
    _stub_timer(monkeypatch)
    cache = tmp_path / "at.json"
    X, y = binary_data(600, 6, seed=1)
    params = dict(BASE, tpu_grower="compact",
                  tpu_autotune="first_run",
                  tpu_autotune_cache=str(cache))
    n0 = autotune.SWEEPS_RUN
    bst = lgb.Booster(params, lgb.Dataset(X, label=y, params=params))
    assert autotune.SWEEPS_RUN == n0 + 1
    assert bst._gbdt._engine_resolution.autotuned
    assert cache.exists()

    def _autotune_compiles():
        return dict(guards.phase_compile_counts()
                    .get("by_phase", {}).get("autotune", {}))

    phase0 = _autotune_compiles()
    bst2 = lgb.Booster(params, lgb.Dataset(X, label=y, params=params))
    assert autotune.SWEEPS_RUN == n0 + 1          # cache hit, no sweep
    assert bst2._gbdt._engine_resolution.autotuned
    assert _autotune_compiles() == phase0


def test_reset_parameter_reresolves_through_registry(tmp_path,
                                                     monkeypatch):
    """A mid-run engine-knob change must actually take effect (the PR 8
    stale-choice fix, now for every engine knob), and a cached autotune
    decision still applies on re-resolve — without re-benching."""
    _stub_timer(monkeypatch)
    cache = tmp_path / "at.json"
    X, y = binary_data(600, 6, seed=2)
    params = dict(BASE, tpu_grower="compact", tpu_autotune="first_run",
                  tpu_autotune_cache=str(cache))
    bst = lgb.Booster(params, lgb.Dataset(X, label=y, params=params))
    bst.update()
    gp = bst._gbdt.grower_params
    assert gp.hist_mbatch == 8      # stub tie -> the default-first cell
    n_swept = autotune.SWEEPS_RUN
    bst.reset_parameter({"tpu_hist_mbatch": 4, "tpu_hist_impl": "xla"})
    gp = bst._gbdt.grower_params
    assert gp.hist_mbatch == 4 and gp.hist_impl == "xla"
    src = bst._gbdt._engine_resolution.sources
    assert src["hist_mbatch"] == "user" and src["hist_impl"] == "user"
    assert autotune.SWEEPS_RUN == n_swept       # re-resolve, no re-bench
    bst.update()                                # trains on under the change
    # layout re-resolves too (warns + falls back on the invalid value)
    bst.reset_parameter({"tpu_hist_layout": "bogus"})
    assert bst._gbdt.grower_params.hist_layout == "lane"


def test_reset_uses_in_memory_decision_not_cache(tmp_path, monkeypatch):
    """The run's measured decision survives reset_parameter WITHOUT a
    cache re-read: an unwritable/deleted/rewritten cache file must
    neither drop nor flip the in-run engine choice, and the training
    loop (stock learning-rate callback calls reset every iteration)
    must not do cache file I/O."""
    _stub_timer(monkeypatch)
    cache = tmp_path / "at.json"
    X, y = binary_data(500, 6, seed=5)
    params = dict(BASE, tpu_grower="compact", tpu_autotune="first_run",
                  tpu_autotune_cache=str(cache))
    bst = lgb.Booster(params, lgb.Dataset(X, label=y, params=params))
    bst.update()
    decision0 = bst._gbdt._engine_resolution.decision
    assert decision0 is not None
    cache.unlink()                      # the file is GONE mid-run

    def no_reads(*a, **k):  # pragma: no cover - the assertion IS the call
        raise AssertionError("reset_parameter re-read the autotune cache")
    monkeypatch.setattr(autotune, "decision_for", no_reads)
    bst.reset_parameter({"learning_rate": 0.05})
    res = bst._gbdt._engine_resolution
    assert res.autotuned and res.decision == decision0
    assert res.hist_mbatch == decision0["hist_mbatch"]
    bst.update()


FUSED_CPU = dict(BASE, tpu_grower="compact", tpu_autotune="off",
                 tpu_fused_interpret=True, tpu_fused_block=128,
                 num_leaves=7)


def test_reset_parameter_reresolves_to_the_fused_depth(monkeypatch):
    """A fused run's depth survives reset_parameter (the learning-rate
    callback resets every iteration: no rebuild of the step), and a
    mid-run explicit depth still reaches the kernel."""
    monkeypatch.delenv("LGBM_TPU_HIST_MBATCH", raising=False)
    X, y = binary_data(600, 6, seed=3)
    bst = lgb.Booster(FUSED_CPU, lgb.Dataset(X, label=y, params=FUSED_CPU))
    g = bst._gbdt
    assert g._use_compact and g.grower_params.fused_block == 128
    assert g._engine_resolution.entry_id == "fused_lane"
    assert g.grower_params.hist_mbatch == registry.FUSED_MBATCH
    assert g._engine_resolution.sources["hist_mbatch"] == "fused"
    bst.update()
    step = g._compact["step"]
    bst.reset_parameter({"learning_rate": 0.05})
    assert g.grower_params.hist_mbatch == registry.FUSED_MBATCH
    assert g._engine_resolution.sources["hist_mbatch"] == "fused"
    assert g._compact["step"] is step           # nothing to rebuild
    bst.reset_parameter({"tpu_hist_mbatch": 8})
    assert g.grower_params.hist_mbatch == 8
    assert g._engine_resolution.sources["hist_mbatch"] == "user"
    bst.update()


def test_unfused_fallbacks_keep_the_standalone_depth(monkeypatch):
    """Where the fused kernel leaves a run AFTER the resolve, its
    histograms come from a standalone engine at the standalone depth:
    caller-supplied gradients (masked grower), and the record-width
    clamp finding no block (the XLA walk's segment_histogram)."""
    monkeypatch.delenv("LGBM_TPU_HIST_MBATCH", raising=False)
    X, y = binary_data(600, 6, seed=4)
    bst = lgb.Booster(FUSED_CPU, lgb.Dataset(X, label=y, params=FUSED_CPU))
    g = bst._gbdt
    assert g.grower_params.hist_mbatch == registry.FUSED_MBATCH
    n = len(y)
    g.train_one_iter(np.zeros(n, np.float32) + 0.1,
                     np.ones(n, np.float32))
    assert not g._use_compact and not g._engine_shape.compact
    assert g.grower_params.hist_mbatch == 8
    # the clamp takes the kernel off
    monkeypatch.setattr(registry, "clamp_fused_block", lambda *a, **k: 0)
    bst = lgb.Booster(FUSED_CPU, lgb.Dataset(X, label=y, params=FUSED_CPU))
    bst.update()
    gp = bst._gbdt.grower_params
    assert gp.fused_block == 0 and gp.hist_mbatch == 8
    bst.reset_parameter({"lambda_l2": 1.0})
    gp = bst._gbdt.grower_params
    assert gp.fused_block == 0 and gp.hist_mbatch == 8


def test_sweep_skipped_when_all_knobs_pinned(monkeypatch):
    """User/env pinning every swept knob means the microbench cannot
    influence anything — an armed run must not pay for it."""
    def boom(*a, **k):  # pragma: no cover - the assertion IS the call
        raise AssertionError("sweep ran with every knob pinned")
    monkeypatch.setattr(autotune, "run_sweep", boom)
    cfg = {"tpu_autotune": "first_run", "tpu_hist_mbatch": 8,
           "tpu_hist_layout": "lane", "tpu_hist_impl": "xla"}
    shape = registry.DatasetShape(rows=512, features=4, num_bins=16,
                                  mode="serial")
    res = registry.resolve(cfg, shape=shape, platform="cpu",
                           sample_provider=lambda n: np.zeros((n, 4)))
    assert not res.autotuned
    assert res.sources["hist_mbatch"] == "user"
    # one knob left to auto -> the sweep matters again
    cfg2 = dict(cfg)
    del cfg2["tpu_hist_mbatch"]
    with pytest.raises(AssertionError, match="every knob pinned"):
        registry.resolve(cfg2, shape=shape, platform="cpu",
                         sample_provider=lambda n: np.zeros((n, 4)))


def test_sweep_times_the_real_channel_layout():
    """quant shape-classes time int8 code channels (the int8 -> int32
    contraction), pack4 classes time nibble-packed blocks — the cached
    'measured' winner reflects the engine path that actually trains."""
    rng = np.random.RandomState(0)
    sample = rng.randint(0, 16, (512, 4)).astype(np.uint8)
    cands = registry.sweep_candidates(
        registry.DatasetShape(512, 4, 16, "serial"), "cpu")[:1]
    for kw in ({"quant": True}, {"pack4": True}):
        winner, table = autotune.run_sweep(sample, 16, cands, reps=1,
                                           **kw)
        assert winner is not None and "ms" in table[0], (kw, table)


def test_resolve_without_shape_keeps_explicit_layout():
    """No train-set context (loaded booster): the sublane bin-width
    bound cannot be checked, so an explicit layout is not spuriously
    rejected against a made-up width."""
    res = registry.resolve({"tpu_hist_layout": "sublane"}, shape=None,
                           platform="tpu")
    assert res.hist_layout == "sublane"


def test_steady_state_guard_with_autotune_armed(tmp_path):
    """The REAL sweep (no stub — candidates compile and run) on a tiny
    shape, then 4 post-warmup iterations: 0 lowerings, 0 backend
    compiles, 0 d2h. Autotune work lands strictly before the steady
    window, attributed to the 'autotune' compile phase."""
    cache = tmp_path / "at.json"
    X, y = binary_data(900, 6, seed=3)
    params = {
        "objective": "binary", "num_leaves": 15, "max_bin": 31,
        "min_data_in_leaf": 5, "verbosity": -1, "seed": 7,
        "tpu_grower": "compact", "stop_check_freq": 10_000,
        "tpu_autotune": "first_run", "tpu_autotune_cache": str(cache),
    }
    n0 = autotune.SWEEPS_RUN
    bst = lgb.Booster(params, lgb.Dataset(X, label=y, params=params))
    assert autotune.SWEEPS_RUN == n0 + 1
    (block,) = list(json.loads(cache.read_text())["entries"].values())
    assert any("ms" in r for r in block["table"])   # really timed
    # the sweep's compiles are attributed to the 'autotune' phase (one
    # candidate program each), not to train_step
    at = guards.phase_compile_counts().get("by_phase", {}) \
        .get("autotune", {})
    assert at.get("lowerings", 0) >= 3
    for _ in range(2):
        bst.update()
    with guards.steady_state_guard("4 autotuned iterations") as cc:
        for _ in range(4):
            bst.update()
    assert cc.lowerings == 0
    assert cc.backend_compiles == 0
    bst._gbdt._flush_trees()
    assert bst._gbdt.num_total_trees >= 5


# ------------------------------------------------------------------ parity
@pytest.mark.parametrize("mode_extra", [
    {"tpu_grower": "compact"},
    {"tpu_grower": "compact", "tree_learner": "data", "num_shards": 2},
])
def test_tree_parity_off_vs_autotuned(tmp_path, monkeypatch, mode_extra):
    """Engine choice changes speed ONLY: tpu_autotune=off vs an
    autotuned selection that elects a NON-default cell (mbatch 16)
    produce bit-identical models and predictions, per learner mode."""
    X, y = binary_data(700, 8, seed=4)
    params_off = dict(BASE, tpu_autotune="off", **mode_extra)
    ds = lgb.Dataset(X, label=y, params=params_off)
    bst_off = lgb.train(params_off, ds)
    pred_off = bst_off.predict(X)
    # force the autotuned winner to the non-default mbatch-16 cell via
    # a crafted cache for the exact shape-class the booster resolved
    shape = bst_off._gbdt._engine_shape
    cache = tmp_path / "at.json"
    autotune.store_decision(
        str(cache), autotune.cache_key("cpu", registry.shape_class(shape)),
        _decision_block({"entry": "xla_lane", "hist_impl": "xla",
                         "hist_layout": "lane", "hist_mbatch": 16},
                        sclass=registry.shape_class(shape)))
    params_on = dict(BASE, tpu_autotune="first_run",
                     tpu_autotune_cache=str(cache), **mode_extra)
    bst_on = lgb.train(params_on,
                       lgb.Dataset(X, label=y, params=params_on))
    gp = bst_on._gbdt.grower_params
    assert gp.hist_mbatch == 16 and gp.hist_impl == "xla"
    assert bst_on._gbdt._engine_resolution.sources["hist_mbatch"] \
        == "autotune"
    assert _strip_knobs(bst_on.model_to_string()) \
        == _strip_knobs(bst_off.model_to_string())
    np.testing.assert_array_equal(bst_on.predict(X), pred_off)


# ------------------------------------------------------------ bench + CLI
def test_sweep_tables_roundtrip(tmp_path):
    cache = tmp_path / "at.json"
    autotune.store_decision(str(cache), "cpu/serial-r512-f4-b16",
                            _decision_block({"hist_mbatch": 8}))
    autotune.store_decision(str(cache), "cpu/serial-r1024-f8-b16",
                            _decision_block({"hist_mbatch": 16}))
    tables = autotune.sweep_tables(str(cache))
    assert set(tables) == {"cpu/serial-r512-f4-b16",
                           "cpu/serial-r1024-f8-b16"}
    assert autotune.sweep_tables(str(tmp_path / "missing.json")) == {}


def test_bench_arms_autotune_cache(tmp_path, monkeypatch):
    """BENCH_AUTOTUNE=1 arms the same cache the trainer reads and tags
    the recorded row autotuned: true (the bench-side satellite)."""
    import bench
    monkeypatch.setenv("BENCH_AUTOTUNE", "1")
    monkeypatch.setenv("BENCH_AUTOTUNE_CACHE", str(tmp_path / "b.json"))
    params = {}
    path = bench._arm_autotune(params)
    assert path == str(tmp_path / "b.json")
    assert params["tpu_autotune"] == "first_run"
    assert params["tpu_autotune_cache"] == path
    monkeypatch.delenv("BENCH_AUTOTUNE")
    assert bench._arm_autotune({}) is None


@pytest.mark.slow
def test_real_timed_sweep_and_cli(tmp_path):
    """The REAL sweep through the offline CLI (scripts/autotune): a
    synthetic shape sweeps, prints the decision table, and writes the
    cache the trainer can consume."""
    cache = tmp_path / "cli.json"
    out = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(__file__), "..",
                                      "scripts", "autotune"),
         "--rows", "2048", "--features", "6", "--max-bin", "16",
         "--reps", "2", "--cache", str(cache)],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr
    assert "winner" in out.stdout
    data = json.loads(cache.read_text())
    (block,) = list(data["entries"].values())
    assert any("ms" in r for r in block["table"])
    assert block["winner"]["entry"] == "xla_lane"


# ------------------------------------------------- the flush's bound (PR 30)
@pytest.mark.parametrize("features, bins, cols, cfg, env, want", [
    (28, 255, 128, {}, "", (384, 2)),         # higgs keeps its block and depth
    (28, 63, 128, {}, "", (384, 2)),
    (137, 255, 256, {}, "", (192, 2)),        # 69 groups: at the bound
    # 110 groups: depth 2 would be cut to block 96; the same 192 rows a
    # flush at depth 1 are the same sums through a block twice as large
    (220, 255, 256, {}, "", (192, 1)),
    # a depth somebody named is kept, and the block pays for it
    (220, 255, 256, {"tpu_hist_mbatch": 2}, "", (96, 2)),
    (220, 255, 256, {"tpu_hist_mbatch": 4}, "", (32, 4)),
    # so is a block from the environment, inside the same bound
    (220, 255, 256, {}, "96", (96, 2)),
    (220, 255, 256, {}, "384", (96, 2)),
    (28, 255, 128, {"tpu_hist_mbatch": 8}, "", (224, 8)),
])
def test_fit_fused_flush_trades_the_default_depth_for_the_block(
        features, bins, cols, cfg, env, want):
    res = registry.resolve(
        dict({"tpu_autotune": "off"}, **cfg), platform="tpu",
        shape=registry.DatasetShape(7_000_000, features, bins, "serial"))
    assert res.entry_id == "fused_lane" and res.fused_block
    got = registry.fit_fused_flush(res, cols, bins + 1, features,
                                   env_override=env)
    assert got == want
