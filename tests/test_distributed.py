"""Distributed (data-parallel) training tests on the virtual 8-device mesh.

Mirrors the reference's distributed test strategy
(reference: tests/distributed/_test_distributed.py — N local CLI processes with
partitioned data, asserting accuracy and identical models across workers). Here
the 8 XLA CPU devices form a real `jax.sharding.Mesh`; GSPMD partitions the
histogram build over rows and inserts the ICI collectives the reference did
with socket ReduceScatter (data_parallel_tree_learner.cpp:223-300).
"""
import jax
import numpy as np
import pytest
from sklearn.metrics import roc_auc_score

import lightgbm_tpu as lgb

from utils import FAST_PARAMS, binary_data, train_test_split_simple


def _params(**kw):
    p = dict(FAST_PARAMS)
    p.update(kw)
    return p


@pytest.fixture(autouse=True)
def need_devices():
    if len(jax.devices()) < 2:
        pytest.skip("needs multi-device backend")


def test_data_parallel_quality():
    X, y = binary_data()
    Xtr, ytr, Xte, yte = train_test_split_simple(X, y)
    bst = lgb.train(_params(objective="binary", tree_learner="data"),
                    lgb.Dataset(Xtr, label=ytr), 30)
    assert roc_auc_score(yte, bst.predict(Xte)) > 0.93
    # the mesh really was used: training score is sharded over the data axis
    g = bst._gbdt
    assert g.mesh is not None
    assert len(g.mesh.devices.ravel()) == len(jax.devices())


def test_data_parallel_matches_serial_auc():
    X, y = binary_data()
    Xtr, ytr, Xte, yte = train_test_split_simple(X, y)
    p_serial = lgb.train(_params(objective="binary"),
                         lgb.Dataset(Xtr, label=ytr), 20).predict(Xte)
    p_data = lgb.train(_params(objective="binary", tree_learner="data"),
                       lgb.Dataset(Xtr, label=ytr), 20).predict(Xte)
    # split decisions can differ on fp ties; model quality must match
    assert abs(roc_auc_score(yte, p_serial) - roc_auc_score(yte, p_data)) < 0.01


def test_data_parallel_uneven_rows():
    # row count not divisible by the device count: padding path
    X, y = binary_data()
    n = len(y) - 5  # 595: not divisible by 8
    X, y = X[:n], y[:n]
    bst = lgb.train(_params(objective="binary", tree_learner="data"),
                    lgb.Dataset(X, label=y), 10)
    p = bst.predict(X)
    assert len(p) == n
    assert roc_auc_score(y, p) > 0.95


def test_data_parallel_with_valid_and_weights():
    X, y = binary_data()
    Xtr, ytr, Xte, yte = train_test_split_simple(X, y)
    w = np.where(ytr > 0, 2.0, 1.0)
    ds = lgb.Dataset(Xtr, label=ytr, weight=w)
    dv = ds.create_valid(Xte, label=yte)
    hist = {}
    bst = lgb.train(_params(objective="binary", tree_learner="data",
                            metric="binary_logloss"),
                    ds, 15, valid_sets=[dv],
                    callbacks=[lgb.record_evaluation(hist)])
    assert len(hist["valid_0"]["binary_logloss"]) == 15
    assert hist["valid_0"]["binary_logloss"][-1] < \
        hist["valid_0"]["binary_logloss"][0]


def test_voting_parallel_alias_runs():
    # voting-parallel currently shares the data-parallel path (full histogram
    # psum; the top-k comm optimization is meaningless under GSPMD until the
    # explicit shard_map learner lands)
    X, y = binary_data()
    bst = lgb.train(_params(objective="binary", tree_learner="voting"),
                    lgb.Dataset(X, label=y), 8)
    assert roc_auc_score(y, bst.predict(X)) > 0.95


def test_multiclass_data_parallel():
    from utils import multiclass_data
    X, y = multiclass_data()
    bst = lgb.train(
        _params(objective="multiclass", num_class=3, tree_learner="data"),
        lgb.Dataset(X, label=y), 10)
    p = bst.predict(X)
    assert (p.argmax(1) == y).mean() > 0.9


def test_data_parallel_model_equality_with_serial():
    """Bit-level split parity: same binning + exactly-representable
    gradients => identical trees serial vs data-parallel (the reference's
    distributed tests assert per-worker model-file equality,
    ref tests/distributed/_test_distributed.py:168)."""
    X, y = binary_data()
    # first-iteration gradients of l2 with boost_from_average=False are
    # exactly -y (integers): histogram sums are exact in any order
    params = _params(objective="regression", boost_from_average=False,
                     learning_rate=1.0, num_leaves=8)
    serial = lgb.train(params, lgb.Dataset(X, label=y), 1)
    data = lgb.train(dict(params, tree_learner="data"),
                     lgb.Dataset(X, label=y), 1)
    ts = serial._gbdt.models[0]
    td = data._gbdt.models[0]
    np.testing.assert_array_equal(ts.split_feature, td.split_feature)
    np.testing.assert_array_equal(ts.split_bin, td.split_bin)
    np.testing.assert_array_equal(ts.left_child, td.left_child)
    np.testing.assert_allclose(ts.leaf_value, td.leaf_value,
                               rtol=1e-6, atol=1e-7)
    # and the full-model text agrees after multiple iterations within fp noise
    s5 = lgb.train(params, lgb.Dataset(X, label=y), 5)
    d5 = lgb.train(dict(params, tree_learner="data"),
                   lgb.Dataset(X, label=y), 5)
    np.testing.assert_allclose(d5.predict(X), s5.predict(X),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("learner,grower", [("data", "compact"),
                                            ("voting", "auto")])
def test_second_iteration_under_mesh_lowers_nothing(learner, grower):
    """The step's small carried state starts where the step hands it
    back (replicated over the mesh). Fed uncommitted, the second
    iteration re-lowered the whole step program — on the chip a second
    full compile."""
    from lightgbm_tpu.analysis import guards
    X, y = binary_data()
    bst = lgb.train(_params(objective="binary", tree_learner=learner,
                            tpu_grower=grower),
                    lgb.Dataset(X, label=y), 1, keep_training_booster=True)
    with guards.compile_counter() as cc:
        bst.update()
        bst._gbdt._flush_trees()
    assert cc.lowerings == 0, cc.by_phase


def test_feature_parallel_learner():
    """Feature-parallel: data replicated, split finding sharded by feature
    (reference: feature_parallel_tree_learner.cpp)."""
    X, y = binary_data()
    Xtr, ytr, Xte, yte = train_test_split_simple(X, y)
    bst = lgb.train(_params(objective="binary", tree_learner="feature"),
                    lgb.Dataset(Xtr, label=ytr), 20)
    assert roc_auc_score(yte, bst.predict(Xte)) > 0.93
    # serial parity on the first exactly-representable tree
    params = _params(objective="regression", boost_from_average=False,
                     learning_rate=1.0, num_leaves=8)
    s1 = lgb.train(params, lgb.Dataset(Xtr, label=ytr), 1)
    f1 = lgb.train(dict(params, tree_learner="feature"),
                   lgb.Dataset(Xtr, label=ytr), 1)
    np.testing.assert_array_equal(s1._gbdt.models[0].split_feature,
                                  f1._gbdt.models[0].split_feature)


def test_voting_parallel_caps_features_and_learns():
    """Voting-parallel: per-shard top-k vote; only elected features carry
    reduced histograms (reference: voting_parallel_tree_learner.cpp:151).
    With 2k >= F every feature is elected and the result must equal the
    data-parallel learner; harder vote caps still learn (PV-Tree is a
    large-shard approximation, so toy-scale quality degrades)."""
    X, y = binary_data()
    Xtr, ytr, Xte, yte = train_test_split_simple(X, y)
    p_all = lgb.train(_params(objective="binary", tree_learner="voting",
                              top_k=5), lgb.Dataset(Xtr, label=ytr), 20)
    p_data = lgb.train(_params(objective="binary", tree_learner="data"),
                       lgb.Dataset(Xtr, label=ytr), 20)
    np.testing.assert_allclose(p_all.predict(Xte), p_data.predict(Xte),
                               rtol=1e-4, atol=1e-5)
    g = p_all._gbdt
    assert g.grower_params.voting_k == 5
    assert g.grower_params.voting_shards == len(jax.devices())
    capped = lgb.train(_params(objective="binary", tree_learner="voting",
                               top_k=3), lgb.Dataset(Xtr, label=ytr), 20)
    assert roc_auc_score(yte, capped.predict(Xte)) > 0.65


def test_multihost_config_parsing():
    """Multi-host bootstrap plumbing (reference: linkers_socket.cpp machine
    list parsing; actual multi-process init needs real hosts)."""
    from lightgbm_tpu.parallel.multihost import (_parse_machines,
                                                 infer_process_id)
    ms = _parse_machines("10.0.0.1:12400, 10.0.0.2:12400", "")
    assert ms == ["10.0.0.1:12400", "10.0.0.2:12400"]
    assert infer_process_id(["10.9.9.9:1", "127.0.0.1:2"]) == 1
    import os
    os.environ["LIGHTGBM_TPU_PROCESS_ID"] = "0"
    try:
        assert infer_process_id(ms) == 0
    finally:
        del os.environ["LIGHTGBM_TPU_PROCESS_ID"]
    # num_machines=1 is a no-op
    from lightgbm_tpu.parallel.multihost import init_distributed
    from lightgbm_tpu.config import Config
    assert init_distributed(Config({"num_machines": 1})) is False
    # inconsistent machine list raises
    import pytest as _pytest
    with _pytest.raises(ValueError):
        init_distributed(Config({"num_machines": 3,
                                 "machines": "a:1,b:2"}))


class TestMeshCompact:
    """Data-parallel COMPACT grower: shard-local physical partitions with
    psum-ed histograms (reference: DataParallelTreeLearner keeps the local
    partition beside global_data_count_in_leaf_,
    data_parallel_tree_learner.cpp:223-340). The serial compact model is the
    golden reference — split decisions must agree because both scan the same
    (summed) histograms."""

    def _data(self, n=20_003, f=6, seed=3):
        rng = np.random.RandomState(seed)
        X = rng.randn(n, f).astype(np.float32)
        y = ((X[:, 0] - 0.4 * X[:, 2] + 0.3 * rng.randn(n)) > 0).astype(
            np.float64)
        return X, y

    def test_matches_serial_compact(self):
        X, y = self._data()                    # n % 8 != 0: pad rows live
        base = _params(objective="binary", tpu_grower="compact",
                       num_leaves=31)
        b_ser = lgb.train(dict(base), lgb.Dataset(X, label=y), 6)
        b_mesh = lgb.train(dict(base, tree_learner="data"),
                           lgb.Dataset(X, label=y), 6)
        assert b_mesh._gbdt.mesh is not None
        assert b_mesh._gbdt._use_compact
        d = np.abs(b_ser.predict(X) - b_mesh.predict(X)).max()
        assert d < 1e-4                        # psum reassociation only

    def test_bagging_and_eval(self):
        X, y = self._data(12_007)
        params = _params(objective="binary", metric="auc",
                         tpu_grower="compact", tree_learner="data",
                         bagging_fraction=0.6, bagging_freq=1)
        bst = lgb.Booster(params, lgb.Dataset(X, label=y))
        for _ in range(5):
            bst.update()
        (_, name, val, _), = bst.eval_train()
        assert name == "auc" and val > 0.9

    def test_multiclass(self):
        X, _ = self._data(9_000)
        y3 = np.digitize(X[:, 1], [-0.4, 0.6]).astype(np.float64)
        bst = lgb.train(_params(objective="multiclass", num_class=3,
                                tpu_grower="compact", tree_learner="data",
                                num_leaves=15),
                        lgb.Dataset(X, label=y3), 4)
        acc = (bst.predict(X).argmax(1) == y3).mean()
        assert acc > 0.97

    def test_fused_kernel_under_mesh_interpret(self):
        # the Mosaic kernel inside shard_map, in Pallas interpret mode —
        # validates the multi-chip fused path without multi-chip hardware
        X, y = self._data(4_099, seed=9)
        base = _params(objective="binary", tpu_grower="compact",
                       num_leaves=15)
        b_ref = lgb.train(dict(base, tree_learner="data"),
                          lgb.Dataset(X, label=y), 3)
        b_fus = lgb.train(dict(base, tree_learner="data", tpu_fused="on",
                               tpu_fused_interpret=True, tpu_fused_block=128),
                          lgb.Dataset(X, label=y), 3)
        d = np.abs(b_ref.predict(X) - b_fus.predict(X)).max()
        assert d < 2e-3                        # hi/lo-bf16 histogram split
