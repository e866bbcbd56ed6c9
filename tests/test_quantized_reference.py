"""Quantized-gradient training (``use_quantized_grad``) held to the plain
reference of the method (``benchmarks/reference/quantized.py``: float64
and int64 numpy, after ``gradient_discretizer.cpp`` and Shi et al.,
NeurIPS 2022), at a small size on the CPU: the discretizer's codes, the
integer histogram, the first tree's structure and counts, the renewed
leaf values, the path the step says it runs, and the dequantising shim
where the integer sums could leave int32.

The data carry an ``init_score`` a row, so that the first tree's
gradients are not the two values a constant score gives a binary label.
"""
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.boosting.gbdt import GBDT, _discretize_gradients
from lightgbm_tpu.obs import flight
from lightgbm_tpu.ops.histogram import histogram_block

from benchmarks.modeltext import parse_trees
from benchmarks.reference import quantized as ref

ROWS, FEATURES, LEAVES = 4000, 8, 15
BINS = (16, 4)
PARAMS = {"objective": "binary", "num_leaves": LEAVES, "max_bin": 63,
          "learning_rate": 0.1, "min_data_in_leaf": 20,
          "min_sum_hessian_in_leaf": 1e-3, "lambda_l2": 0.0,
          "verbosity": -1, "tpu_grower": "compact",
          "use_quantized_grad": True, "quant_train_renew_leaf": True,
          "stochastic_rounding": False}


@pytest.fixture(scope="module")
def data():
    rng = np.random.RandomState(20221128)
    X = rng.randn(ROWS, FEATURES).astype(np.float32)
    w = rng.randn(FEATURES) / np.sqrt(FEATURES)
    y = (X @ w + 0.6 * np.abs(X[:, 0]) - 0.4
         + 0.5 * rng.randn(ROWS) > 0).astype(np.float64)
    score = 0.8 * rng.randn(ROWS)
    p = 1.0 / (1.0 + np.exp(-score))
    return {"X": X, "y": y, "score": score, "g": p - y, "h": p * (1 - p)}


def train(data, bins, rounds=1, **more):
    params = dict(PARAMS, num_grad_quant_bins=bins, **more)
    ds = lgb.Dataset(data["X"], label=data["y"], init_score=data["score"],
                     params=params)
    return lgb.train(params, ds, num_boost_round=rounds), ds


@pytest.fixture(scope="module")
def grown(data):
    """For each number of levels: the program's first tree beside the
    reference's, grown from the program's own binned matrix."""
    out = {}
    for bins in BINS:
        bst, ds = train(data, bins)
        inner = ds._inner
        feature_bins = np.array([m.num_bins for m in inner.mappers])
        code_g, code_h, g_s, h_s = ref.discretize(
            data["g"], data["h"], bins, 0.5, 0.5)
        want = ref.grow_tree(inner.binned, code_g, code_h, g_s, h_s,
                             feature_bins, PARAMS)
        out[bins] = {"got": parse_trees(bst.model_to_string())[0],
                     "want": want, "bst": bst, "inner": inner,
                     "codes": (code_g, code_h, g_s, h_s)}
    return out


# ------------------------------------------------------ the discretizer
def program_codes(data, bins, key=None):
    """The program's discretizer on the data's float32 gradients: to
    nearest, or stochastically where a key is given."""
    return _discretize_gradients(
        jnp.asarray(data["g"], jnp.float32),
        jnp.asarray(data["h"], jnp.float32),
        jax.random.PRNGKey(0) if key is None else key, bins,
        key is not None, False)


@pytest.mark.parametrize("bins", BINS)
def test_codes_to_nearest_are_the_references(data, bins):
    qg, qh, g_s, h_s = program_codes(data, bins)
    code_g, code_h, want_gs, want_hs = ref.discretize(
        data["g"], data["h"], bins, 0.5, 0.5)
    np.testing.assert_array_equal(np.asarray(qg).astype(np.int64), code_g)
    np.testing.assert_array_equal(np.asarray(qh).astype(np.int64), code_h)
    # float32 against float64: one rounding of a maximum and a division
    assert float(g_s) == pytest.approx(want_gs, rel=1e-6)
    assert float(h_s) == pytest.approx(want_hs, rel=1e-6)


@pytest.mark.parametrize("bins", BINS)
def test_stochastic_codes_are_the_references_for_the_same_uniforms(data,
                                                                   bins):
    key = jax.random.PRNGKey(bins)
    qg, qh, _, _ = program_codes(data, bins, key)
    kg, kh = jax.random.split(key)
    u_g = np.asarray(jax.random.uniform(kg, (ROWS,)), np.float64)
    u_h = np.asarray(jax.random.uniform(kh, (ROWS,)), np.float64)
    code_g, code_h, _, _ = ref.discretize(data["g"], data["h"], bins,
                                          u_g, u_h)
    # a row whose scaled value plus its uniform lies within float32's
    # rounding of an integer may fall either way: none does at this seed
    np.testing.assert_array_equal(np.asarray(qg).astype(np.int64), code_g)
    np.testing.assert_array_equal(np.asarray(qh).astype(np.int64), code_h)


@pytest.mark.parametrize("bins", BINS)
def test_stochastic_rounding_is_unbiased_and_in_range(bins):
    """The property the paper rests on: a code times its scale has the
    gradient as its expectation. Over 1e5 rows the mean error is within 4
    standard errors of 0, and no code leaves its levels."""
    rng = np.random.RandomState(bins)
    n = 100_000
    score = rng.randn(n)
    p = 1.0 / (1.0 + np.exp(-score))
    g = (p - (rng.rand(n) < 0.4)).astype(np.float32)
    h = (p * (1 - p)).astype(np.float32)
    qg, qh, g_s, h_s = map(np.asarray, _discretize_gradients(
        jnp.asarray(g), jnp.asarray(h), jax.random.PRNGKey(7), bins, True,
        False))
    assert np.abs(qg).max() <= bins // 2
    assert qh.min() >= 0 and qh.max() <= bins
    assert (qg == np.rint(qg)).all() and (qh == np.rint(qh)).all()
    for code, scale, true in ((qg, g_s, g), (qh, h_s, h)):
        err = code.astype(np.float64) * float(scale) - true
        assert abs(err.mean()) <= 4 * err.std() / np.sqrt(n)
    # to nearest is not unbiased on the hessians of these rows: the test
    # above can tell the two apart
    _, nh, _, nh_s = map(np.asarray, _discretize_gradients(
        jnp.asarray(g), jnp.asarray(h), jax.random.PRNGKey(7), bins, False,
        False))
    near = nh.astype(np.float64) * float(nh_s) - h
    assert abs(near.mean()) > 4 * near.std() / np.sqrt(n)


# --------------------------------------------------- the integer histogram
@pytest.mark.parametrize("bins", BINS)
def test_root_histogram_code_sums_are_the_references(data, grown, bins):
    inner = grown[bins]["inner"]
    qg, qh, _, _ = program_codes(data, bins)
    ones = jnp.ones((ROWS,), jnp.int8)
    channels = jnp.stack([qg.astype(jnp.int8), qh.astype(jnp.int8), ones,
                          ones], axis=1)
    got = histogram_block(jnp.asarray(inner.binned), channels,
                          int(inner.max_num_bins), impl="xla")
    assert got.dtype == jnp.int32
    want = grown[bins]["want"]["root_hist"]     # count, codes of g, of h
    np.testing.assert_array_equal(
        np.asarray(got)[:, :want.shape[1], [2, 0, 1]], want)
    assert not np.asarray(got)[:, want.shape[1]:].any()


# ---------------------------------------------------------- the first tree
@pytest.mark.parametrize("bins", BINS)
def test_first_tree_has_the_references_splits_and_counts(grown, bins):
    got, want = grown[bins]["got"], grown[bins]["want"]
    mappers = grown[bins]["inner"].mappers
    assert got["num_leaves"] == len(want["leaf_count"]) == LEAVES
    for key in ("split_feature", "left_child", "right_child", "leaf_count"):
        np.testing.assert_array_equal(got[key], want[key], err_msg=key)
    upper = np.array([mappers[f].bin_upper_bounds[t] for f, t in
                      zip(want["split_feature"], want["threshold_bin"])])
    # the model text prints a threshold to 17 digits
    np.testing.assert_allclose(got["threshold"], upper, rtol=1e-12)


@pytest.mark.parametrize("bins", BINS)
def test_renewed_leaf_values_are_the_references(data, grown, bins):
    """1e-5, relative: the program sums a leaf's few hundred float32
    gradients in float32 (a cumulative sum over all 4,000 rows, a leaf its
    difference of two prefixes), the reference in float64; a float32
    prefix of 4,000 terms of size 0.5 is good to some 1e-7 of the largest
    prefix, which a leaf's own sum is a tenth to a hundredth of."""
    got, want = grown[bins]["got"], grown[bins]["want"]
    renewed = ref.renewed_leaf_values(
        data["g"], data["h"], want["leaf_of_row"], LEAVES,
        PARAMS["learning_rate"], PARAMS["lambda_l2"])
    gap = np.abs(got["leaf_value"] - renewed) / np.maximum(
        np.abs(renewed), np.median(np.abs(renewed)))
    assert gap.max() <= 1e-5, gap
    # and they are not the values the codes alone give: leaving the
    # renewal out would fail here by orders of magnitude
    code_g, code_h, g_s, h_s = grown[bins]["codes"]
    coarse = ref.quantized_leaf_values(
        code_g, code_h, g_s, h_s, want["leaf_of_row"], LEAVES,
        PARAMS["learning_rate"], PARAMS["lambda_l2"])
    assert np.abs(coarse - renewed).max() > 1e-3 * np.abs(renewed).max()


def test_without_renewal_leaves_hold_the_code_sums_values(data, grown):
    bins = 16
    bst, _ = train(data, bins, quant_train_renew_leaf=False)
    got = parse_trees(bst.model_to_string())[0]
    want = grown[bins]["want"]
    np.testing.assert_array_equal(got["leaf_count"], want["leaf_count"])
    code_g, code_h, g_s, h_s = grown[bins]["codes"]
    coarse = ref.quantized_leaf_values(
        code_g, code_h, g_s, h_s, want["leaf_of_row"], LEAVES,
        PARAMS["learning_rate"], PARAMS["lambda_l2"])
    # integer sums are exact; float32 holds the two scales and the quotient
    np.testing.assert_allclose(got["leaf_value"], coarse, rtol=1e-5)


# ------------------------------------------- which path the step says it ran
def quant_ticks():
    return [e for e in flight.recorder().events()
            if e["event"] == "iteration" and "quant_hist" in e]


@pytest.mark.parametrize("grower,int_hist", [("compact", 1), ("masked", 0)])
def test_iteration_events_say_which_path_ran(data, grower, int_hist):
    flight.recorder().clear()
    bst, _ = train(data, 4, tpu_grower=grower)
    tick = quant_ticks()[-1]
    assert tick["quant_hist"] == int_hist
    assert tick["quant_bins"] == 4 and tick["quant_renew"] == 1
    # the booster's GrowerParams are what an engine note reads
    assert bst._gbdt.grower_params.quant_hist is bool(int_hist)
    assert bst._gbdt.grower_params.quant_max == (5 if int_hist else 127)


def test_a_float32_run_carries_no_quant_counters(data):
    flight.recorder().clear()
    train(data, 4, use_quantized_grad=False, quant_train_renew_leaf=False)
    ticks = [e for e in flight.recorder().events()
             if e["event"] == "iteration"]
    assert ticks and not quant_ticks()


def test_sums_that_could_leave_int32_take_the_shim_and_say_so(
        data, grown, monkeypatch, caplog):
    """``num_data * num_grad_quant_bins >= 2^31`` (faked: the step's
    builder sees that many rows while it chooses its path): the step
    histograms dequantised codes in float32, logs it, counts it, and
    grows the same first tree."""
    bins = 16
    real = GBDT._build_compact_step_fn

    def build(self):
        rows, self.num_data = self.num_data, (1 << 31) // bins
        try:
            return real(self)
        finally:
            self.num_data = rows

    monkeypatch.setattr(GBDT, "_build_compact_step_fn", build)
    flight.recorder().clear()
    with caplog.at_level(logging.WARNING, logger="lightgbm_tpu"):
        bst, _ = train(data, bins, verbosity=0)
    assert "using the dequantized-f32 histogram path" in caplog.text
    tick = quant_ticks()[-1]
    assert tick["quant_hist"] == 0 and tick["quant_renew"] == 1
    assert bst._gbdt.grower_params.quant_hist is False
    got, want = parse_trees(bst.model_to_string())[0], grown[bins]["want"]
    for key in ("split_feature", "left_child", "right_child", "leaf_count"):
        np.testing.assert_array_equal(got[key], want[key], err_msg=key)
