"""Engine registry (ISSUE 12; the start-up autotuner left in ISSUE 32).

Pins the registry's contracts:

* resolve-order precedence — user > env > what platform and shape
  decide — per knob, with provenance in ``Resolution.sources``;
* ``registry.resolve`` is pure: it reads no file, and a run with nothing
  set resolves what the benchmark's cells resolve;
* ``tpu_autotune`` is a retired key: accepted, ignored;
* ``reset_parameter`` re-resolves every engine knob through the
  registry (a mid-run change is never a silent no-op).
"""
import builtins
import json

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.engines import registry
from lightgbm_tpu.utils import log

from utils import binary_data

SHAPE = registry.DatasetShape(rows=100_000, features=28, num_bins=255,
                              mode="serial")
BASE = {"objective": "binary", "max_bin": 31, "min_data_in_leaf": 5,
        "verbosity": -1, "seed": 7, "num_iterations": 5}


# ---------------------------------------------------------- resolve order
def test_resolve_order_precedence(monkeypatch):
    """user > env > default, per knob, with the provenance recorded in
    Resolution.sources."""
    monkeypatch.delenv("LGBM_TPU_HIST_MBATCH", raising=False)
    res = registry.resolve({}, shape=SHAPE, platform="cpu")
    assert res.hist_mbatch == 8
    assert res.sources["hist_mbatch"] == "default"
    assert res.hist_impl == "auto" and res.entry_id == "xla_lane"
    # env beats the default
    monkeypatch.setenv("LGBM_TPU_HIST_MBATCH", "4")
    res = registry.resolve({}, shape=SHAPE, platform="cpu")
    assert res.hist_mbatch == 4 and res.sources["hist_mbatch"] == "env"
    # user beats the env override
    res = registry.resolve({"tpu_hist_mbatch": 12}, shape=SHAPE,
                           platform="cpu")
    assert res.hist_mbatch == 12 and res.sources["hist_mbatch"] == "user"
    res = registry.resolve({"tpu_hist_impl": "xla"}, shape=SHAPE,
                           platform="cpu")
    assert res.hist_impl == "xla" and res.sources["hist_impl"] == "user"
    assert set(res.sources.values()) <= {"user", "env", "default", "fused",
                                         "gspmd"}


def test_resolve_unknown_values_warn_like_before():
    """Unknown knob values warn and take the default."""
    assert registry.resolve_layout({"tpu_hist_layout": "bogus"}, 64) \
        == "lane"
    assert registry.resolve_layout({"tpu_hist_layout": "sublane"}, 256) \
        == "lane"
    assert registry.resolve_mbatch({"tpu_hist_mbatch": 99}) == 16
    assert registry.resolve_step_buckets({"tpu_step_buckets": "bogus"}) \
        is True
    assert registry.resolve_overlap({"tpu_hist_overlap": "bogus"}) == 0


def test_gspmd_partitioned_step_never_gets_a_mosaic_engine():
    """The masked grower under a mesh is partitioned by GSPMD, and
    lowering a Mosaic call there is refused ("cannot be automatically
    partitioned" — met compiling the voting step for a 4-chip v5e mesh):
    such shapes resolve the XLA einsum only, and asking for pallas
    outright is an error."""
    shape = SHAPE._replace(mode="voting", gspmd=True)
    res = registry.resolve({}, shape=shape, platform="tpu")
    assert res.hist_impl == "xla" and res.entry_id == "xla_lane"
    assert res.sources["hist_impl"] == "gspmd"
    with pytest.raises(ValueError, match="partitioned by GSPMD"):
        registry.resolve({"tpu_hist_impl": "pallas"}, shape=shape,
                         platform="tpu")
    # the same mode under shard_map (compact grower) keeps the kernels
    res = registry.resolve({}, shape=shape._replace(gspmd=False),
                           platform="tpu")
    assert res.hist_impl == "auto" and res.entry_id == "fused_lane"


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


# ------------------------------------------ the fused kernel's own depth
BIG = registry.DatasetShape(rows=1 << 20, features=28, num_bins=255,
                            mode="serial")
@pytest.mark.parametrize("cfg,platform,entry", [
    ({}, "tpu", "fused_lane"),
    ({"tpu_fused": "on"}, "tpu", "fused_lane"),
    # interpret mode on a CPU host is a fused entry too
    ({"tpu_fused_interpret": True, "tpu_fused_block": 128}, "cpu",
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
    res = registry.resolve({"tpu_hist_layout": "sublane"},
                           shape=BIG._replace(num_bins=63), platform="tpu")
    assert res.entry_id == "fused_sublane"
    assert res.hist_mbatch == registry.FUSED_MBATCH
    assert res.sources["hist_mbatch"] == "fused"


def test_user_and_env_depth_still_reach_the_fused_kernel(monkeypatch):
    """An explicit tpu_hist_mbatch and LGBM_TPU_HIST_MBATCH win for the
    fused kernel as they do for the standalone engines, user first: the
    chip bisect (depth 1 against 8) stays reproducible."""
    monkeypatch.delenv("LGBM_TPU_HIST_MBATCH", raising=False)
    res = registry.resolve({"tpu_hist_mbatch": 8}, shape=BIG,
                           platform="tpu")
    assert res.entry_id == "fused_lane"
    assert res.hist_mbatch == 8 and res.sources["hist_mbatch"] == "user"
    monkeypatch.setenv("LGBM_TPU_HIST_MBATCH", "4")
    res = registry.resolve({}, shape=BIG, platform="tpu")
    assert res.hist_mbatch == 4 and res.sources["hist_mbatch"] == "env"
    res = registry.resolve({"tpu_hist_mbatch": 16}, shape=BIG,
                           platform="tpu")
    assert res.hist_mbatch == 16 and res.sources["hist_mbatch"] == "user"
    monkeypatch.setenv("LGBM_TPU_HIST_MBATCH", "99")     # clamped, not lost
    res = registry.resolve({}, shape=BIG, platform="tpu")
    assert res.hist_mbatch == 16 and res.sources["hist_mbatch"] == "env"


@pytest.mark.parametrize("cfg,shape,platform", [
    ({}, BIG, "cpu"),
    ({"tpu_fused": "off"}, BIG, "tpu"),
    ({}, BIG._replace(mode="voting", gspmd=True), "tpu"),
    ({}, BIG._replace(mode="data", gspmd=True), "tpu"),
    # one chip's masked grower (small data, caller's gradients)
    ({}, BIG._replace(compact=False), "tpu"),
    ({"tpu_fused_interpret": True}, BIG._replace(compact=False), "cpu"),
], ids=["cpu", "tpu-fused-off", "gspmd-voting", "gspmd-data",
        "tpu-masked", "cpu-interpret-masked"])
def test_standalone_engines_resolve_as_before(monkeypatch, cfg, shape,
                                              platform):
    """A resolution that does not fuse keeps the standalone depth: the
    default of 8, user and env above it."""
    monkeypatch.delenv("LGBM_TPU_HIST_MBATCH", raising=False)
    res = registry.resolve(cfg, shape=shape, platform=platform)
    assert not res.entry_id.startswith("fused")
    assert res.hist_mbatch == 8 and res.sources["hist_mbatch"] == "default"
    monkeypatch.setenv("LGBM_TPU_HIST_MBATCH", "4")
    res = registry.resolve(cfg, shape=shape, platform=platform)
    assert res.hist_mbatch == 4 and res.sources["hist_mbatch"] == "env"
    res = registry.resolve(dict(cfg, tpu_hist_mbatch=2), shape=shape,
                           platform=platform)
    assert res.hist_mbatch == 2 and res.sources["hist_mbatch"] == "user"


# ----------------------------------------------- booster-level integration
def test_reset_parameter_reresolves_through_registry(monkeypatch):
    """A mid-run engine-knob change must actually take effect (the PR 8
    stale-choice fix, now for every engine knob)."""
    monkeypatch.delenv("LGBM_TPU_HIST_MBATCH", raising=False)
    X, y = binary_data(600, 6, seed=2)
    params = dict(BASE, tpu_grower="compact")
    bst = lgb.Booster(params, lgb.Dataset(X, label=y, params=params))
    bst.update()
    gp = bst._gbdt.grower_params
    assert gp.hist_mbatch == 8 and gp.hist_impl == "auto"
    bst.reset_parameter({"tpu_hist_mbatch": 4, "tpu_hist_impl": "xla"})
    gp = bst._gbdt.grower_params
    assert gp.hist_mbatch == 4 and gp.hist_impl == "xla"
    src = bst._gbdt._engine_resolution.sources
    assert src["hist_mbatch"] == "user" and src["hist_impl"] == "user"
    bst.update()                                # trains on under the change
    # layout re-resolves too (warns + falls back on the invalid value)
    bst.reset_parameter({"tpu_hist_layout": "bogus"})
    assert bst._gbdt.grower_params.hist_layout == "lane"


FUSED_CPU = dict(BASE, tpu_grower="compact", tpu_fused_interpret=True,
                 tpu_fused_block=128, num_leaves=7)


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


def test_resolve_without_shape_keeps_explicit_layout():
    """No train-set context (loaded booster): the sublane bin-width
    bound cannot be checked, so an explicit layout is not spuriously
    rejected against a made-up width."""
    res = registry.resolve({"tpu_hist_layout": "sublane"}, shape=None,
                           platform="tpu")
    assert res.hist_layout == "sublane"


# ------------------------------------------------- the flush's bound (PR 30)
@pytest.mark.parametrize("features, bins, cols, cfg, env, want", [
    (28, 255, 128, {}, "", (384, 2)),         # higgs keeps its block and depth
    (28, 63, 128, {}, "", (384, 2)),
    # 69 and 110 groups: depth 2 would be cut to block 128; the same 256
    # rows a flush at depth 1 are the same sums through a block twice as
    # large (whole lane tiles: PR 33)
    (137, 255, 256, {}, "", (256, 1)),
    (220, 255, 256, {}, "", (256, 1)),
    # a depth somebody named is kept, and the block pays for it
    (220, 255, 256, {"tpu_hist_mbatch": 2}, "", (128, 2)),
    (220, 255, 256, {"tpu_hist_mbatch": 4}, "", (64, 4)),
    # so is a block from the environment, inside the same bound
    (220, 255, 256, {}, "96", (96, 2)),
    (220, 255, 256, {}, "384", (128, 2)),
    (28, 255, 128, {"tpu_hist_mbatch": 8}, "", (128, 8)),
])
def test_fit_fused_flush_trades_the_default_depth_for_the_block(
        features, bins, cols, cfg, env, want):
    res = registry.resolve(
        cfg, platform="tpu",
        shape=registry.DatasetShape(7_000_000, features, bins, "serial"))
    assert res.entry_id == "fused_lane" and res.fused_block
    got = registry.fit_fused_flush(res, cols, bins + 1, features,
                                   env_override=env)
    assert got == want


# ------------------------------------- the default is the benchmark's (PR 32)
# the three cells' shapes with their row record's bytes, and what PERF.md
# section 4 prints for them: entry, block, depth
CELLS = {
    "higgs_train": (registry.DatasetShape(10_500_000, 28, 255), 128,
                    ("fused_lane", 384, 2)),
    "higgs_b63_train": (registry.DatasetShape(10_500_000, 28, 63), 128,
                        ("fused_lane", 384, 2)),
    "istella_train": (registry.DatasetShape(7_325_625, 220, 255), 256,
                      ("fused_lane", 256, 1)),
}


# all five cells of the benchmark: what `registry.fit_fused_flush` gives
# each is held where PR 33 and PR 30 measured it, whatever the flush costs
# (PR 38's two-level one-hot left block and depth alone: same rows, same
# groups, same sums)
FLUSH_OF_CELL = {
    "higgs_train": ({}, registry.DatasetShape(10_500_000, 28, 255), 128,
                    (384, 2)),
    "higgs_b63_train": ({}, registry.DatasetShape(10_500_000, 28, 63), 128,
                        (384, 2)),
    "istella_train": ({}, registry.DatasetShape(7_325_625, 220, 255), 256,
                      (256, 1)),
    "criteo_dp4_train": ({"tree_learner": "data"},
                         registry.DatasetShape(40_000_000, 67, 255, "data"),
                         128, (384, 2)),
    "higgs_quant_train": ({"use_quantized_grad": True},
                          registry.DatasetShape(10_500_000, 28, 255,
                                                quant=True), 128, (384, 2)),
}


@pytest.mark.parametrize("cell", sorted(FLUSH_OF_CELL))
def test_fit_fused_flush_holds_the_five_cells_block_and_depth(monkeypatch,
                                                              cell):
    monkeypatch.delenv("LGBM_TPU_HIST_MBATCH", raising=False)
    monkeypatch.delenv("LGBM_TPU_FUSED_BS", raising=False)
    params, shape, cols, want = FLUSH_OF_CELL[cell]
    res = registry.resolve(Config(params), shape=shape, platform="tpu")
    assert res.entry_id == "fused_lane"
    assert registry.fit_fused_flush(res, cols, shape.num_bins + 1,
                                    shape.features) == want


def _craft_old_cache(home, platform, shape):
    """An ``autotune.json`` at the path the start-up sweep used to keep
    (``~/.cache/lightgbm_tpu``), naming sublane / pallas / depth 16 under
    the key it filed ``shape`` under. Returns its path."""
    def rung(x):
        return 1 << max(0, (max(1, int(x)) - 1).bit_length())
    winner = {"entry": "pallas_sublane", "hist_impl": "pallas",
              "hist_layout": "sublane", "hist_mbatch": 16,
              "serve_engine": "walk"}
    block = {"winner": winner, "table": [], "platform": platform}
    keys = [f"{platform}/{shape.mode}-r{rung(shape.rows)}"
            f"-f{rung(shape.features)}-b{shape.num_bins}",
            f"{platform}/serve-t16-d4-k1"]
    path = home / ".cache" / "lightgbm_tpu" / "autotune.json"
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(
        {"version": 1, "entries": {k: block for k in keys}}))
    return str(path)


def _resolved_on_the_chip(params, shape, cols):
    res = registry.resolve(Config(params), shape=shape, platform="tpu")
    block, depth = registry.fit_fused_flush(res, cols, shape.num_bins + 1,
                                            shape.features)
    return res._replace(fused_block=block, hist_mbatch=depth)


@pytest.mark.parametrize("autotune", [None, "off", "first_run", "always"])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_default_run_resolves_what_the_cells_resolve(tmp_path, monkeypatch,
                                                     cell, autotune):
    """With nothing set (and whatever the retired key says, and whatever
    an earlier version left in the home directory) a run on the chip
    resolves, field for field, what the cell's ``tpu_autotune=off``
    resolves, and what PERF.md section 4 prints."""
    monkeypatch.delenv("LGBM_TPU_HIST_MBATCH", raising=False)
    monkeypatch.setenv("HOME", str(tmp_path))
    shape, cols, (entry, block, depth) = CELLS[cell]
    _craft_old_cache(tmp_path, "tpu", shape)
    params = {} if autotune is None else {"tpu_autotune": autotune}
    got = _resolved_on_the_chip(params, shape, cols)
    assert got == _resolved_on_the_chip({"tpu_autotune": "off"}, shape, cols)
    assert (got.entry_id, got.fused_block, got.hist_mbatch) \
        == (entry, block, depth)
    assert (got.hist_impl, got.hist_layout) == ("auto", "lane")
    assert got.sources == {
        "hist_layout": "default", "hist_impl": "default",
        "fused_block": "default", "step_buckets": "default",
        "hist_overlap": "default", "hist_mbatch": "fused"}


@pytest.mark.parametrize("case", ["train", "reset_parameter", "serving"])
def test_resolve_is_pure(tmp_path, monkeypatch, case):
    """A file where the sweep's cache used to be changes nothing and is
    not opened: not at set-up, not in reset_parameter, not in serving."""
    monkeypatch.delenv("LGBM_TPU_HIST_MBATCH", raising=False)
    monkeypatch.delenv("LGBM_TPU_PREDICT_ENGINE", raising=False)
    monkeypatch.setenv("HOME", str(tmp_path))
    opened = []
    real_open = builtins.open

    def spy(file, *a, **k):
        opened.append(str(file))
        return real_open(file, *a, **k)
    monkeypatch.setattr(builtins, "open", spy)
    X, y = binary_data(600, 6, seed=6)
    params = dict(BASE, tpu_grower="compact", tpu_autotune="first_run")

    def run():
        if case == "serving":
            return registry.resolve_serving_engine(
                {"tpu_predict_engine": "auto", "tpu_autotune": "first_run"},
                depth=4, level_cap=10)
        bst = lgb.Booster(params, lgb.Dataset(X, label=y, params=params))
        if case == "reset_parameter":
            bst.update()
            bst.reset_parameter({"learning_rate": 0.05})
        return bst._gbdt._engine_resolution

    want = run()
    shape = registry.DatasetShape(rows=600, features=6, num_bins=32)
    path = _craft_old_cache(tmp_path, "cpu", shape)
    del opened[:]
    assert run() == want
    assert path not in opened
    if case != "serving":
        assert (want.entry_id, want.hist_impl, want.hist_layout,
                want.hist_mbatch) == ("xla_lane", "auto", "lane", 8)


@pytest.mark.parametrize("key, known", [("tpu_autotune", True),
                                        ("tpu_autotune_cache", False)])
def test_retired_key_is_accepted_and_its_cache_key_is_gone(monkeypatch, key,
                                                           known):
    """``tpu_autotune`` is still a key (the benchmark's configurations
    pass ``off``): no "Unknown parameter" warning, and a value that once
    armed the sweep says it has no effect. ``tpu_autotune_cache`` went
    with the cache."""
    said = []
    monkeypatch.setattr(log, "warning", said.append)
    Config({key: "off"})
    assert any("Unknown parameter" in m for m in said) is not known
    if known:
        assert not said
        Config({key: "first_run"})
        assert len(said) == 1 and "retired" in said[0]
