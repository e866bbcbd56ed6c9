"""Compile-once training: the bucketed grower-step ladder, the persistent
compilation cache, and the async histogram-collective overlap (ISSUE 8).

The three acceptance claims, verified mechanically:

* **rung budget** — a full compact training run compiles a fixed, small
  number of DISTINCT step programs (one per (leaf rung, depth bucket)
  pair, never one per node or per exact config), and every config in a
  rung lowers byte-identical HLO (same canonical fingerprint), so the
  persistent cache serves one rung's whole neighborhood;
* **ladder parity** — trees and predictions are bit-identical with
  ``tpu_step_buckets`` on vs the exact-keyed ``off`` escape hatch, on the
  compact AND masked growers, including the bagging/GOSS/extra-trees/
  monotone-rescan paths whose PRNG folds must not see the rung padding;
* **overlap parity** — the data-parallel (psum and reduce-scatter) and
  voting learners produce bit-identical trees with ``tpu_hist_overlap``
  on vs off, and the lowered step program moves EXACTLY the same
  collective bytes (the grouping pipelines latency, it never adds
  traffic — the contract twin lives in
  analysis/contracts/*_overlap.json).
"""
import os

import jax
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.analysis import guards
from lightgbm_tpu.analysis.hlo import collective_bytes, fingerprint
from lightgbm_tpu.boosting.gbdt import bucketed_tree_shape
from lightgbm_tpu.ops.grower import depth_rung, leaf_rung

from utils import binary_data

BASE = {"objective": "binary", "max_bin": 31, "min_data_in_leaf": 5,
        "verbosity": -1, "seed": 7, "num_iterations": 6,
        "device_type": "tpu"}


def _strip_knobs(model_text):
    """Model text minus the parameters echo (the only intended delta
    between the two sides of a parity pair is the knob itself)."""
    return "\n".join(l for l in model_text.splitlines()
                     if not l.startswith("[tpu_"))


def _train(extra, n=800, f=12, seed=0):
    X, y = binary_data(n, f, seed)
    params = dict(BASE)
    params.update(extra)
    bst = lgb.train(params, lgb.Dataset(X, label=y))
    return bst, bst.predict(X)


# ------------------------------------------------------------- rung units
def test_leaf_rung_powers_of_two():
    assert [leaf_rung(v) for v in (2, 3, 4, 5, 8, 9, 31, 32, 33)] == \
        [2, 4, 4, 8, 8, 16, 32, 32, 64]


def test_depth_rung_two_buckets():
    """Depth only gates candidate gains (no depth-sized arrays), so the
    ladder's depth axis collapses to {unlimited, bounded} — the O(log)
    end of the compile-budget contract."""
    assert depth_rung(-1) == depth_rung(0) == -1
    assert depth_rung(1) == depth_rung(6) == depth_rung(63) == 1


def test_bucketed_tree_shape_modes():
    assert bucketed_tree_shape(True, 13, 7) == (16, 1)
    assert bucketed_tree_shape(True, 16, -1) == (16, -1)
    # the tpu_step_buckets=off escape hatch keys on the exact shape
    assert bucketed_tree_shape(False, 13, 7) == (13, 7)


# ---------------------------------------------------------- ladder parity
@pytest.mark.parametrize("extra", [
    # non-power-of-two leaves, unlimited depth: 3 padded leaf slots
    dict(tpu_grower="compact", num_leaves=13, max_depth=-1),
    # exact rung + bounded depth: zero padding, traced depth gate live
    dict(tpu_grower="compact", num_leaves=16, max_depth=5),
    # masked grower takes the same (rung, bucket) key
    dict(tpu_grower="masked", num_leaves=9, max_depth=4),
    # bagging + GOSS iteration-derived PRNG must not see the padding
    dict(tpu_grower="compact", num_leaves=12, max_depth=6,
         bagging_fraction=0.7, bagging_freq=1),
    dict(tpu_grower="compact", num_leaves=10, max_depth=-1,
         boosting="goss"),
    # extra_trees threshold draws ride the fixed rescan fold stride —
    # the draw stream must be leaf-array-size independent
    dict(tpu_grower="compact", num_leaves=11, max_depth=7,
         extra_trees=True),
    # RF's own train_one_iter feeds the masked grower the traced budgets
    dict(boosting="rf", num_leaves=11, max_depth=5,
         bagging_fraction=0.6, bagging_freq=1, feature_fraction=0.8),
], ids=["compact", "compact-depth", "masked", "bagging", "goss",
        "extra-trees", "rf"])
def test_step_buckets_bit_parity(extra):
    """Rung-padded programs grow the SAME trees as exact-keyed ones:
    inactive leaves are masked zero-weight segments and the budgets ride
    as traced scalars, so padding is invisible to the split math."""
    bst_on, pred_on = _train(dict(extra, tpu_step_buckets="on"))
    bst_off, pred_off = _train(dict(extra, tpu_step_buckets="off"))
    assert _strip_knobs(bst_on.model_to_string()) \
        == _strip_knobs(bst_off.model_to_string())
    np.testing.assert_array_equal(pred_on, pred_off)


def test_monotone_rescan_parity():
    """monotone intermediate re-scans split candidates with fresh
    extra-trees draws; the fold stride is fixed (not the leaf-array
    length), so the rung-padded rescan draws identical thresholds."""
    extra = dict(tpu_grower="compact", num_leaves=9, max_depth=5,
                 extra_trees=True,
                 monotone_constraints=[1, -1] + [0] * 10,
                 monotone_constraints_method="intermediate")
    bst_on, pred_on = _train(dict(extra, tpu_step_buckets="on"))
    bst_off, pred_off = _train(dict(extra, tpu_step_buckets="off"))
    assert _strip_knobs(bst_on.model_to_string()) \
        == _strip_knobs(bst_off.model_to_string())
    np.testing.assert_array_equal(pred_on, pred_off)


# ---------------------------------------------------------- rung budget
def _step_fingerprints(configs, monkeypatch):
    """Canonical fingerprints of every step program the configs lower."""
    monkeypatch.setenv("LGBM_TPU_COMM_ACCOUNTING", "1")
    prints = set()
    for extra in configs:
        bst, _ = _train(extra)
        g = bst._gbdt
        step_keys = [k for k in g._comm_hlo if "step" in k]
        assert step_keys, sorted(g._comm_hlo)
        for k in step_keys:
            # a full run never re-lowers its step: one text per key
            assert len(g._comm_hlo_history[k]) == 1, k
            prints.add(fingerprint(g._comm_hlo[k]))
    return prints


def test_one_program_per_rung_not_per_config(monkeypatch):
    """The fingerprint-history acceptance assertion: a grid of
    (num_leaves, max_depth) configs lowers ONE distinct step program per
    (leaf rung, depth bucket) pair — the exact-keyed escape hatch lowers
    one per config."""
    grid = [dict(tpu_grower="compact", num_leaves=nl, max_depth=md)
            for nl, md in ((5, 3), (7, 6), (12, 9), (14, 2))]
    # rungs: 8, 8, 16, 16 — depth bucket 'bounded' throughout
    on = _step_fingerprints(
        [dict(c, tpu_step_buckets="on") for c in grid], monkeypatch)
    assert len(on) == 2, len(on)
    off = _step_fingerprints(
        [dict(c, tpu_step_buckets="off") for c in grid], monkeypatch)
    assert len(off) == len(grid), len(off)


def test_depth_bucket_shares_program(monkeypatch):
    """Every bounded max_depth at a rung shares one program (the bound is
    a traced scalar); unlimited compiles the gate away — a second,
    distinct program."""
    grid = [dict(tpu_grower="compact", num_leaves=8, max_depth=md,
                 tpu_step_buckets="on") for md in (2, 5, 9, -1)]
    prints = _step_fingerprints(grid, monkeypatch)
    assert len(prints) == 2, len(prints)


def test_steady_state_no_recompile_with_buckets(compile_guard):
    """The traced budgets never re-key the program: post-warmup
    iterations lower nothing (the PR 1 steady-state guard, now on the
    default bucketed path)."""
    X, y = binary_data(800, 12, 0)
    params = dict(BASE, tpu_grower="compact", num_leaves=13, max_depth=7,
                  tpu_step_buckets="on", num_iterations=2)
    bst = lgb.train(params, lgb.Dataset(X, label=y),
                    keep_training_booster=True)
    before = compile_guard.lowerings
    for _ in range(3):
        bst.update()
    bst._gbdt._flush_trees()
    assert compile_guard.lowerings == before


# ------------------------------------------------------ persistent cache
def test_configure_compile_cache_noop_on_empty(cache_config_restored):
    prev = jax.config.jax_compilation_cache_dir
    assert guards.configure_compile_cache("") is False
    assert guards.configure_compile_cache(None) is False
    assert jax.config.jax_compilation_cache_dir == prev


def test_configure_compile_cache_sets_config(tmp_path,
                                             cache_config_restored):
    cache = str(tmp_path / "cc")
    assert guards.configure_compile_cache(cache) is True
    assert jax.config.jax_compilation_cache_dir == cache
    # admission thresholds zeroed so tiny CPU programs qualify
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0
    # idempotent re-arm
    assert guards.configure_compile_cache(cache) is True


def test_compile_cache_yields_to_environment(tmp_path, monkeypatch,
                                             cache_config_restored):
    """The one cache rule: with JAX_COMPILATION_CACHE_DIR set, neither
    the helper nor the tpu_compile_cache_dir knob names another
    directory (the thresholds still drop, so step programs qualify)."""
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(guards.CACHE_DIR_ENV, str(tmp_path / "from_env"))
    assert guards.configure_compile_cache(str(tmp_path / "cc")) is True
    assert jax.config.jax_compilation_cache_dir == prev
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    _train(dict(tpu_compile_cache_dir=str(tmp_path / "knob")))
    assert jax.config.jax_compilation_cache_dir == prev
    assert not (tmp_path / "knob").exists()


def test_checkout_cache_dir_is_fixed_and_ignored():
    """Without the variable the repo's entry points use ONE fixed path
    inside the checkout (the path is part of jax's cache key), and git
    ignores it."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert guards.checkout_cache_dir() == os.path.join(repo, ".jax_cache")
    assert guards.checkout_cache_dir() == guards.checkout_cache_dir()
    with open(os.path.join(repo, ".gitignore")) as fh:
        assert ".jax_cache/" in fh.read().split()


def test_same_rung_shares_cache_entries(tmp_path, cache_config_restored):
    """The ladder and the cache compose: a config in an already-trained
    rung re-lowers but backend-compiles NOTHING (every request hits the
    entries its rung neighbor wrote); a new rung misses."""
    cache = str(tmp_path / "cc")
    extra = dict(tpu_grower="compact", tpu_compile_cache_dir=cache,
                 tpu_step_buckets="on")
    _train(dict(extra, num_leaves=12, max_depth=6))
    assert os.listdir(cache), "cache dir stayed empty"
    with guards.cache_counter() as warm:
        _train(dict(extra, num_leaves=9, max_depth=3))   # same (16, 1)
    assert warm.requests > 0
    assert warm.misses == 0, (warm.requests, warm.hits)
    with guards.cache_counter() as cold:
        _train(dict(extra, num_leaves=40, max_depth=5))  # rung 64
    assert cold.misses > 0, (cold.requests, cold.hits)


def test_cache_counter_inactive_without_cache_dir(cache_config_restored):
    """No cache dir configured -> no cache lookups counted (the BENCH
    rows' hit/miss columns stay 0/0 instead of lying)."""
    jax.config.update("jax_compilation_cache_dir", None)
    with guards.cache_counter() as cc:
        jax.jit(lambda x: x * 3)(np.arange(8.0)).block_until_ready()
    assert cc.requests == 0 and cc.hits == 0 and cc.misses == 0


# ------------------------------------------------------- overlap parity
needs_mesh = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs the 8-device virtual mesh")


@needs_mesh
@pytest.mark.parametrize("extra", [
    # reduce-scatter reduction: 16 features / 8 shards = 2 owned columns,
    # the smallest live 2-group split
    dict(tpu_grower="compact", tree_learner="data", tpu_hist_scatter="on"),
    # plain psum reduction groups the full feature axis
    dict(tpu_grower="compact", tree_learner="data", tpu_hist_scatter="off"),
    # the masked grower groups inside ops/histogram.histogram itself
    dict(tpu_grower="masked", tree_learner="data"),
    # voting reduces the 2k elected features in groups
    dict(tree_learner="voting", top_k=3),
], ids=["data-scatter", "data-psum", "masked", "voting"])
def test_hist_overlap_bit_parity(extra):
    """Grouping a histogram reduce never changes which shard-local
    addends reach an element: trees bit-identical with overlap on/off."""
    bst_on, pred_on = _train(dict(extra, tpu_hist_overlap="on"), f=16)
    bst_off, pred_off = _train(dict(extra, tpu_hist_overlap="off"), f=16)
    assert _strip_knobs(bst_on.model_to_string()) \
        == _strip_knobs(bst_off.model_to_string())
    np.testing.assert_array_equal(pred_on, pred_off)


@needs_mesh
def test_hist_overlap_same_collective_bytes(monkeypatch):
    """COMM accounting on the live step program: overlap on moves
    byte-for-byte the collectives of overlap off — more collectives
    (one per group, the pipelining mechanism), identical traffic."""
    monkeypatch.setenv("LGBM_TPU_COMM_ACCOUNTING", "1")
    extra = dict(tpu_grower="compact", tree_learner="data",
                 tpu_hist_scatter="on")
    accts = {}
    for mode in ("on", "off"):
        bst, _ = _train(dict(extra, tpu_hist_overlap=mode), f=16)
        g = bst._gbdt
        key = [k for k in g._comm_hlo if "step" in k][0]
        accts[mode] = collective_bytes(g._comm_hlo[key])
    on, off = accts["on"], accts["off"]
    for kind in set(on) | set(off):
        if kind == "count":
            continue
        assert on.get(kind, 0) == off.get(kind, 0), kind
    assert on["count"] > off["count"]
