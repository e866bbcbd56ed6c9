"""Serving engines (ROADMAP item 4): level-order relayout, quantized
leaf slabs, precomputed TreeSHAP UNWIND tables, background contrib lane.

The acceptance surface this file pins:

  * the level engine is BIT-IDENTICAL to the depth-batched walk across
    the full parity matrix — NaN defaults, categorical bitsets, EFB
    col_of, multiclass, iteration windows, pred_leaf;
  * trees deeper than tpu_level_depth_cap fall back to the walk per
    bucket (resolve-level demotion with a warning), answers unchanged;
  * resolve_serving_engine honors the user > env > depth-heuristic order;
  * quantized serving stays within the RECORDED max-score-error bound
    (leaf_quant_bound), the bound is exact/tight on a single tree, and
    quantized scores are identical across the walk and level routers;
  * the precomputed UNWIND tables are bit-identical to the per-row loop
    kernel, match the host reference, sum to the raw score, respect
    the tpu_shap_table_mb budget gate, and their cache is bounded by
    the R012 resource witness via the registered cache probe;
  * the background contrib lane only cuts a batch when no live
    foreground request is queued and never reorders foreground FIFO;
  * mixed-endpoint chaos traffic with a mid-stream hot-swap lowers 0
    programs and survives the lock-order + resource-leak witnesses.
"""
import collections
import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.analysis import guards
from lightgbm_tpu.engines import registry
from lightgbm_tpu.ops.predict import quantize_leaves
from lightgbm_tpu.serving.coalescer import MicroBatchCoalescer, ServeFuture

from utils import FAST_PARAMS, binary_data, multiclass_data

LADDER = "32,256"


def _params(**kw):
    # max_depth pins the stack under tpu_level_depth_cap (default 10) so
    # the parity matrix genuinely exercises the level router instead of
    # silently demoting to the walk
    return dict(FAST_PARAMS, objective="binary", max_depth=8,
                tpu_predict_buckets=LADDER, **kw)


def _engines(bst, fn):
    """(level_result, walk_result) of ``fn(bst)`` under each router."""
    g = bst._gbdt
    g.config.set({"tpu_predict_engine": "level"})
    try:
        lvl = fn(bst)
        memo = getattr(g, "_serve_engine_memo", None) or {}
        assert "level" in memo.values(), \
            "level engine never engaged — parity run is vacuous"
    finally:
        g.config.set({"tpu_predict_engine": "batched"})
    return lvl, fn(bst)


# ----------------------------------------------------- level parity matrix
def test_level_parity_nan_defaults():
    X, y = binary_data()
    Xn = np.array(X, np.float64)
    rng = np.random.RandomState(0)
    Xn[rng.rand(*Xn.shape) < 0.08] = np.nan
    p = _params(use_missing=True)
    bst = lgb.train(p, lgb.Dataset(Xn, label=y, params=p), 12)
    q = Xn[:257]
    (raw_l, leaf_l), (raw_w, leaf_w) = _engines(
        bst, lambda b: (b.predict(q, raw_score=True),
                        b.predict(q, pred_leaf=True)))
    np.testing.assert_array_equal(raw_l, raw_w)
    np.testing.assert_array_equal(leaf_l, leaf_w)


def test_level_parity_categorical_bitsets():
    rng = np.random.RandomState(1)
    n = 900
    Xc = rng.randn(n, 6)
    Xc[:, 0] = rng.randint(0, 40, n)   # wide cats -> multi-word bitset
    Xc[:, 1] = rng.randint(0, 6, n)
    y = ((np.isin(Xc[:, 0], [1, 3, 5, 8, 13, 21, 34])
          | (Xc[:, 1] > 3)) ^ (rng.rand(n) < 0.05)).astype(np.float64)
    p = _params(max_cat_to_onehot=2)
    bst = lgb.train(p, lgb.Dataset(Xc, label=y, params=p,
                                   categorical_feature=[0, 1]), 12)
    assert any(np.any(m.cat_bitset) for m in bst._gbdt.models), \
        "test did not exercise categorical splits"
    q = Xc[:300]
    (raw_l, leaf_l), (raw_w, leaf_w) = _engines(
        bst, lambda b: (b.predict(q, raw_score=True),
                        b.predict(q, pred_leaf=True)))
    np.testing.assert_array_equal(raw_l, raw_w)
    np.testing.assert_array_equal(leaf_l, leaf_w)


def test_level_parity_efb_col_of():
    rng = np.random.RandomState(2)
    n, groups, card = 900, 50, 6       # 300 one-hot cols (EFB needs >= 256)
    X = np.zeros((n, groups * card), np.float64)
    for g in range(groups):
        X[np.arange(n), g * card + rng.randint(0, card, n)] = 1.0
    y = (X[:, ::card].sum(1) + 0.3 * rng.randn(n) > 0.5).astype(np.float64)
    p = _params(enable_bundle=True)
    bst = lgb.train(p, lgb.Dataset(X, label=y, params=p), 8)
    assert bst._gbdt._efb is not None, "test did not exercise EFB"
    q = X[:200]
    raw_l, raw_w = _engines(bst, lambda b: b.predict(q, raw_score=True))
    np.testing.assert_array_equal(raw_l, raw_w)


def test_level_parity_multiclass():
    X, y = multiclass_data()
    p = dict(FAST_PARAMS, objective="multiclass", num_class=3,
             max_depth=8, tpu_predict_buckets=LADDER)
    bst = lgb.train(p, lgb.Dataset(X, label=y, params=p), 6)
    q = X[:200]
    lvl, walk = _engines(bst, lambda b: b.predict(q))
    np.testing.assert_array_equal(lvl, walk)


def test_level_parity_windowed():
    X, y = binary_data()
    p = _params()
    bst = lgb.train(p, lgb.Dataset(X, label=y, params=p), 10)
    q = X[:100]
    for kw in ({"num_iteration": 4}, {"start_iteration": 3},
               {"start_iteration": 2, "num_iteration": 5}):
        lvl, walk = _engines(
            bst, lambda b: b.predict(q, raw_score=True, **kw))
        np.testing.assert_array_equal(lvl, walk)


def test_level_depth_cap_demotes_to_walk():
    # registry level: an explicit level request over the cap keeps the
    # walk (with the quantized entry id when a slab rides along)
    res = registry.resolve_serving_engine(
        {"tpu_predict_engine": "level"}, depth=12, level_cap=10)
    assert (res.engine, res.source) == ("walk", "user")
    res = registry.resolve_serving_engine(
        {"tpu_predict_engine": "level"}, depth=5, level_cap=10)
    assert (res.engine, res.entry_id) == ("level", "serve_level")
    res = registry.resolve_serving_engine(
        {"tpu_predict_engine": "level"}, depth=5, level_cap=10,
        quant="int8")
    assert (res.engine, res.entry_id) == ("level", "serve_qleaf")
    # end to end: a cap below the stacked depth serves via the walk
    # fallback and still answers exactly
    X, y = binary_data()
    p = _params()
    bst = lgb.train(p, lgb.Dataset(X, label=y, params=p), 8)
    ref = bst.predict(X[:64], raw_score=True)
    g = bst._gbdt
    g.config.set({"tpu_predict_engine": "level",
                  "tpu_level_depth_cap": 1})
    try:
        g._serve_engine_memo = None
        np.testing.assert_array_equal(
            bst.predict(X[:64], raw_score=True), ref)
    finally:
        g.config.set({"tpu_predict_engine": "batched",
                      "tpu_level_depth_cap": 10})
        g._serve_engine_memo = None


# ------------------------------------------------------- resolve order
@pytest.mark.parametrize("cfg, env, depth, want", [
    # user beats env
    ({"tpu_predict_engine": "walk"}, "level", 4, ("walk", "user")),
    # env beats the heuristic when the knob is unset
    ({}, "level", 4, ("level", "env")),
    # auto: shallow stacks take the level heuristic, deep ones the walk
    ({"tpu_predict_engine": "auto"}, "", 4, ("level", "default")),
    ({"tpu_predict_engine": "auto"}, "", 12, ("walk", "default")),
], ids=["user-over-env", "env-over-heuristic", "auto-shallow", "auto-deep"])
def test_serving_resolve_order_user_env_heuristic(monkeypatch, cfg, env,
                                                  depth, want):
    """user > LGBM_TPU_PREDICT_ENGINE > the depth heuristic: nothing is
    timed and nothing is read from a file."""
    monkeypatch.setenv("LGBM_TPU_PREDICT_ENGINE", env)
    res = registry.resolve_serving_engine(cfg, depth=depth, level_cap=10)
    assert (res.engine, res.source) == want


# -------------------------------------------------- quantized leaf slabs
@pytest.fixture(scope="module")
def quant_booster():
    X, y = binary_data()
    p = _params()
    return lgb.train(p, lgb.Dataset(X, label=y, params=p), 10), X


def _with_quant(bst, mode, fn):
    g = bst._gbdt
    g.config.set({"tpu_leaf_quant": mode})
    g._invalidate_device_trees()
    try:
        return fn(bst)
    finally:
        g.config.set({"tpu_leaf_quant": "off"})
        g._invalidate_device_trees()


@pytest.mark.parametrize("mode", ["int8", "f16"])
def test_quant_within_recorded_bound(quant_booster, mode):
    bst, X = quant_booster
    ref = bst.predict(X[:256], raw_score=True)
    q_raw, bound = _with_quant(
        bst, mode, lambda b: (b.predict(X[:256], raw_score=True),
                              b._gbdt.leaf_quant_bound()))
    assert bound is not None and bound >= 0.0
    diff = np.max(np.abs(q_raw - ref))
    assert diff <= bound + 1e-6, (diff, bound)
    if mode == "int8":
        assert diff > 0.0, "int8 quantization changed nothing — vacuous"


def test_quant_identical_across_routers(quant_booster):
    """The slab and scale are shared state: walk and level serve the
    SAME quantized scores bit for bit."""
    bst, X = quant_booster
    lvl, walk = _with_quant(
        bst, "int8",
        lambda b: _engines(b, lambda bb: bb.predict(X[:128],
                                                    raw_score=True)))
    np.testing.assert_array_equal(lvl, walk)


def test_quant_bound_exact_and_tight():
    """ops level: the recorded bound equals the numpy-recomputed exact
    per-tree worst case; model level: on a single tree the bound is
    ACHIEVED by the rows landing in the worst-error leaf."""
    rng = np.random.RandomState(7)
    lv = rng.randn(3, 8).astype(np.float32) * np.array(
        [[1.0], [0.01], [5.0]], np.float32)
    cid = np.zeros(3, np.int32)
    slab, scale, bound = quantize_leaves(jnp.asarray(lv),
                                         jnp.asarray(cid), "int8")
    slab, scale, bound = (np.asarray(slab), np.asarray(scale),
                          float(bound))
    amax = np.abs(lv).max(axis=1)
    exp_scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    np.testing.assert_allclose(scale, exp_scale, rtol=1e-6)
    deq = slab.astype(np.float32) * scale[:, None]
    exp_bound = np.abs(deq - lv).max(axis=1).sum()
    np.testing.assert_allclose(bound, exp_bound, rtol=1e-6)
    # tightness on one tree: the train rows cover every leaf, so the
    # max observed |q_score - f32_score| IS the single tree's bound
    X, y = binary_data()
    p = _params()
    bst = lgb.train(p, lgb.Dataset(X, label=y, params=p), 1)
    ref = bst.predict(X, raw_score=True)
    q_raw, b1 = _with_quant(
        bst, "int8", lambda b: (b.predict(X, raw_score=True),
                                b._gbdt.leaf_quant_bound()))
    observed = np.max(np.abs(q_raw - ref))
    np.testing.assert_allclose(observed, b1, rtol=1e-5, atol=1e-9)


# ------------------------------------------- precomputed TreeSHAP tables
@pytest.fixture(scope="module")
def shap_booster():
    X, y = binary_data()
    Xn = np.array(X, np.float64)
    rng = np.random.RandomState(3)
    Xn[rng.rand(*Xn.shape) < 0.05] = np.nan
    Xn[:, 2] = rng.randint(0, 5, len(Xn))
    p = _params(use_missing=True,
                tpu_serve_endpoints="predict,leaf,contrib")
    bst = lgb.train(p, lgb.Dataset(Xn, label=y, params=p,
                                   categorical_feature=[2]), 8)
    return bst, Xn


def _contrib_with_tables(bst, x, mode, **kw):
    g = bst._gbdt
    g.config.set({"tpu_shap_tables": mode})
    g._shap_tables_cache = None
    try:
        return bst.predict_contrib_serving(x, **kw)
    finally:
        g.config.set({"tpu_shap_tables": "auto"})
        g._shap_tables_cache = None


def test_shap_tables_bit_identical_to_loop_kernel(shap_booster):
    bst, X = shap_booster
    x = X[:60].astype(np.float32)
    tab, nv = _contrib_with_tables(bst, x, "on")
    loop, nv2 = _contrib_with_tables(bst, x, "off")
    assert nv == nv2 == 60
    np.testing.assert_array_equal(tab, loop)   # same f32 op sequence
    ref = bst.predict(x, pred_contrib=True)
    np.testing.assert_allclose(tab[:nv], ref, rtol=2e-5, atol=2e-5)
    raw = bst.predict(x, raw_score=True)
    np.testing.assert_allclose(tab[:nv].sum(axis=1), raw,
                               rtol=1e-5, atol=1e-5)


def test_shap_tables_windowed_and_multiclass(shap_booster):
    bst, X = shap_booster
    x = X[:25].astype(np.float32)
    for kw in ({"num_iteration": 3}, {"start_iteration": 2},
               {"start_iteration": 2, "num_iteration": 3}):
        tab, nv = _contrib_with_tables(bst, x, "on", **kw)
        loop, _ = _contrib_with_tables(bst, x, "off", **kw)
        np.testing.assert_array_equal(tab, loop)
    Xm, ym = multiclass_data()
    p = dict(FAST_PARAMS, objective="multiclass", num_class=3,
             tpu_predict_buckets=LADDER,
             tpu_serve_endpoints="predict,contrib")
    mb = lgb.train(p, lgb.Dataset(Xm, label=ym, params=p), 4)
    xm = Xm[:20].astype(np.float32)
    tab, nv = _contrib_with_tables(mb, xm, "on")
    loop, _ = _contrib_with_tables(mb, xm, "off")
    np.testing.assert_array_equal(tab, loop)
    raw = mb.predict(xm, raw_score=True)
    sums = tab[:nv].reshape(nv, 3, -1).sum(axis=2)
    np.testing.assert_allclose(sums, raw, rtol=1e-5, atol=1e-5)


def test_shap_tables_budget_gate(shap_booster):
    bst, X = shap_booster
    x = X[:20].astype(np.float32)
    g = bst._gbdt
    g.config.set({"tpu_shap_table_mb": 0})
    try:
        # auto: over-budget falls back to the loop kernel, answers stand
        out, nv = _contrib_with_tables(bst, x, "auto")
        ref = bst.predict(x, pred_contrib=True)
        np.testing.assert_allclose(out[:nv], ref, rtol=2e-5, atol=2e-5)
        # on: over-budget is a structured refusal, not a silent downgrade
        with pytest.raises(ValueError, match="tpu_shap_table_mb"):
            _contrib_with_tables(bst, x, "on")
    finally:
        g.config.set({"tpu_shap_table_mb": 64})
        g._shap_tables_cache = None


def test_shap_table_cache_probe_and_witness(shap_booster):
    """R012 integration: the table cache reports its entry count through
    the registered witness probe, invalidation returns it to zero, and a
    WARM serving pass holds the resource witness."""
    bst, X = shap_booster
    x = X[:20].astype(np.float32)
    g = bst._gbdt
    g.config.set({"tpu_shap_tables": "on"})
    try:
        g._invalidate_device_trees()

        def probed():
            return sum(p() for p in guards._witness_cache_probes)

        base = probed()
        bst.predict_contrib_serving(x)            # builds one table entry
        assert probed() == base + 1
        assert len(g._shap_tables_cache) == 1
        with guards.resource_witness() as w:
            bst.predict_contrib_serving(x)        # warm: no growth
        w.assert_no_leaks("warm table-backed contrib")
        g._invalidate_device_trees()
        assert probed() == base
    finally:
        g.config.set({"tpu_shap_tables": "auto"})
        g._invalidate_device_trees()


# ------------------------------------------------- background contrib lane
def _mk_coalescer(bg=()):
    """A lock-stepped coalescer: no worker thread, zero tick window —
    _pop_batch_locked is driven directly so lane order is deterministic."""
    co = object.__new__(MicroBatchCoalescer)
    co._cv = threading.Condition()
    co._closing = False
    co._tick_s = 0.0
    co._max_batch_rows = 32
    co._background_kinds = frozenset(bg)
    co._q = collections.deque()
    co._rows = 0
    return co


def _put(co, n, kind):
    r = ServeFuture(np.zeros((n, 2), np.float32), None, 1000.0, kind=kind)
    co._q.append(r)
    co._rows += n
    return r


def test_background_lane_defers_until_foreground_idle():
    co = _mk_coalescer(bg=("contrib",))
    c1 = _put(co, 2, "contrib")
    p1 = _put(co, 3, "predict")
    c2 = _put(co, 1, "contrib")
    p2 = _put(co, 4, "predict")
    # tick 1: foreground queued -> only the predicts cut, background
    # skipped IN PLACE (order kept)
    batch = co._pop_batch_locked([])
    assert [r is x for r, x in zip(batch, (p1, p2))] == [True, True]
    assert list(co._q) == [c1, c2]
    # tick 2: foreground idle -> the background batch serves, FIFO
    batch = co._pop_batch_locked([])
    assert batch == [c1, c2]
    assert not co._q and co._rows == 0


def test_background_lane_preserves_foreground_fifo():
    co = _mk_coalescer(bg=("contrib",))
    l1 = _put(co, 2, "leaf")
    _put(co, 2, "contrib")
    p1 = _put(co, 3, "predict")
    # one endpoint per tick: leaf cuts first, predict stays QUEUED AHEAD
    # of nothing it didn't already trail — strict foreground FIFO
    batch = co._pop_batch_locked([])
    assert batch == [l1]
    assert [r.kind for r in co._q] == ["contrib", "predict"]
    batch = co._pop_batch_locked([])
    assert batch == [p1]
    assert [r.kind for r in co._q] == ["contrib"]


def test_background_kinds_knob_rejects_predict():
    """predict is never demotable; unknown kinds warn and drop."""
    from lightgbm_tpu.serving.server import PredictionServer
    kinds = PredictionServer._background_kinds(
        {"tpu_serve_background_kinds": "contrib,predict,bogus"})
    assert kinds == frozenset({"contrib"})
    assert PredictionServer._background_kinds({}) == frozenset()


# ------------------------------------------------ mixed-endpoint chaos
@pytest.fixture(scope="module")
def chaos_boosters():
    """Two boosters serving all three endpoints with the contrib lane
    demoted to background — pre-warmed (programs AND shap-table caches)
    so the witness-armed chaos test reads warm state end to end."""
    X, y = binary_data()
    p = _params(tpu_serve_endpoints="predict,leaf,contrib",
                tpu_serve_background_kinds="contrib")
    b1 = lgb.train(p, lgb.Dataset(X, label=y, params=p), 8)
    b2 = lgb.train(p, lgb.Dataset(X, label=y, params=p), 8)
    srv = b1.serve(tick_ms=1.0, deadline_ms=8000.0)
    try:
        for s in (3, 40):
            srv.predict(X[:s])
            srv.predict_leaf(X[:s])
            srv.predict_contrib(X[:s])
        srv.deploy("warm2", b2)        # warms b2's programs + caches
        srv.predict_contrib(X[:5])
    finally:
        srv.close(drain=True)
    return b1, b2, X


def test_mixed_endpoint_chaos_hot_swap_zero_recompile(
        chaos_boosters, lock_order_witness, resource_leak_witness):
    """THE serving-engine acceptance guard: mixed predict/leaf/contrib
    traffic with the contrib lane in the background tier, across a
    mid-stream hot-swap, completes every request, lowers ZERO programs,
    and holds both runtime witnesses (lock order, resource leaks)."""
    b1, b2, X = chaos_boosters
    srv = b1.serve(tick_ms=1.0, deadline_ms=8000.0)
    try:
        for s in (3, 40):               # re-touch every (kind, rung)
            srv.predict(X[:s])
            srv.predict_leaf(X[:s])
            srv.predict_contrib(X[:s])
        stop = threading.Event()
        errors = []
        served = collections.Counter()
        mu = threading.Lock()

        def hammer(kind, sizes):
            submit = {"predict": srv.submit, "leaf": srv.submit_leaf,
                      "contrib": srv.submit_contrib}[kind]
            i = 0
            while not stop.is_set():
                fut = submit(X[:sizes[i % len(sizes)]])
                try:
                    fut.result()
                    with mu:
                        served[kind] += 1
                except Exception as err:  # pragma: no cover
                    errors.append((kind, err))
                    return
                i += 1

        with guards.compile_counter() as cc:
            threads = [threading.Thread(target=hammer, args=a)
                       for a in (("predict", (1, 17, 32)),
                                 ("predict", (5, 40)),
                                 ("leaf", (3, 29)),
                                 ("contrib", (2, 11)))]
            for t in threads:
                t.start()
            time.sleep(0.15)
            srv.deploy("v2", b2)        # mid-stream atomic hot-swap
            time.sleep(0.15)
            stop.set()
            for t in threads:
                t.join()
        assert not errors, errors[:2]
        assert cc.lowerings == 0, \
            f"chaos traffic lowered {cc.lowerings} programs"
        assert served["predict"] > 0 and served["leaf"] > 0
        assert served["contrib"] > 0, \
            "background contrib lane starved under foreground load"
        assert srv.health()["active_version"] == "v2"
        np.testing.assert_array_equal(srv.predict(X[:5]),
                                      b2.predict(X[:5]))
    finally:
        srv.close(drain=True)
