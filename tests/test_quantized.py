"""Quantized-gradient integer histogram pipeline (ops/histogram.py int8
path, ops/grower_compact.py quant_hist, boosting/gbdt._discretize_gradients).

Covers the PR's acceptance contract on CPU:
  * int-path histograms are EXACT int32 code sums and dequantize to within
    the quantization-error bound of the f32 histograms;
  * end-to-end synthetic-higgs quality: quantized training with
    quant_train_renew_leaf stays within the f32 runs' own AUC spread of
    the f32 path (the band's readings are in the test's docstring);
  * the post-warmup steady-state guard (0 recompiles, 0 d2h) holds with
    the quantized path enabled;
  * the data-parallel reduce-scatter histogram reduction produces
    bit-identical trees to the all-reduce path, with and without
    quantization.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.analysis import guards
from lightgbm_tpu.boosting.gbdt import _discretize_gradients
from lightgbm_tpu.ops.histogram import (_xla_histogram, dequantize_hist,
                                        histogram_block)


def _higgs_like(n, f, seed=7):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    w1 = rng.randn(f) / np.sqrt(f)
    w2 = rng.randn(f) / np.sqrt(f)
    logits = X @ w1 + 0.7 * np.abs(X @ w2) - 0.4 + 0.5 * rng.randn(n)
    y = (logits > 0).astype(np.float64)
    return X, y


# ------------------------------------------------- histogram-level parity
class TestIntHistogram:
    def test_int_hist_exact_vs_f32_codes(self):
        """The int8 contraction sums the SAME codes as the f32 einsum —
        bit-exact int32, on both the XLA and Pallas-interpret engines."""
        rng = np.random.RandomState(0)
        n, f, b = 6000, 7, 64
        binned = jnp.asarray(rng.randint(0, b, (n, f)).astype(np.uint8))
        qg = rng.randint(-8, 9, n).astype(np.int8)
        qh = rng.randint(0, 17, n).astype(np.int8)
        inbag = (rng.rand(n) < 0.8).astype(np.int8)
        ch = jnp.asarray(np.stack(
            [qg * inbag, qh * inbag, inbag, np.ones(n)], axis=1)
            .astype(np.int8))
        h_int = _xla_histogram(binned, ch, b)
        assert h_int.dtype == jnp.int32
        h_f32 = _xla_histogram(binned, ch.astype(jnp.float32), b)
        np.testing.assert_array_equal(np.asarray(h_int),
                                      np.asarray(h_f32).astype(np.int64))
        from lightgbm_tpu.ops.pallas_histogram import pallas_histogram
        h_pl = pallas_histogram(binned, ch, b, mode="int8", interpret=True)
        np.testing.assert_array_equal(np.asarray(h_pl), np.asarray(h_int))

    def test_dequantized_hist_within_quant_error_bound(self):
        """|dequantized int sums - true f32 sums| <= per-bin row count *
        scale per channel (each row's discretization error is < 1 code)."""
        rng = np.random.RandomState(3)
        n, f, b = 8000, 5, 32
        binned = jnp.asarray(rng.randint(0, b, (n, f)).astype(np.uint8))
        grad = jnp.asarray(rng.randn(n).astype(np.float32))
        hess = jnp.asarray((rng.rand(n) * 0.25).astype(np.float32))
        qg, qh, g_s, h_s = _discretize_gradients(
            grad[None], hess[None], jax.random.PRNGKey(0), 16, True, False)
        ones = jnp.ones((n,), jnp.int8)
        ch_q = jnp.stack([qg[0].astype(jnp.int8), qh[0].astype(jnp.int8),
                          ones, ones], axis=1)
        hist_q = histogram_block(binned, ch_q, b, impl="xla")
        assert hist_q.dtype == jnp.int32
        dq = np.asarray(dequantize_hist(hist_q, g_s, h_s))
        onesf = jnp.ones((n,), jnp.float32)
        hist_f = np.asarray(histogram_block(
            binned, jnp.stack([grad, hess, onesf, onesf], axis=1), b,
            impl="xla"))
        counts = hist_f[:, :, 3]
        g_err = np.abs(dq[:, :, 0] - hist_f[:, :, 0])
        h_err = np.abs(dq[:, :, 1] - hist_f[:, :, 1])
        assert (g_err <= counts * float(g_s) + 1e-4).all()
        assert (h_err <= counts * float(h_s) + 1e-4).all()
        # count channels are exact
        np.testing.assert_allclose(dq[:, :, 2:], hist_f[:, :, 2:])

    def test_quantized_histogram_requires_preferred_int32(self):
        """The einsum without preferred_element_type would wrap at +-127;
        prove the pipeline's sums exceed the int8 range (i.e. the pin is
        load-bearing, not decorative)."""
        rng = np.random.RandomState(1)
        n, b = 4000, 4
        binned = jnp.zeros((n, 1), jnp.uint8)      # all rows -> one bin
        ch = jnp.asarray(np.stack([np.full(n, 3), np.full(n, 2),
                                   np.ones(n), np.ones(n)], axis=1)
                         .astype(np.int8))
        h = _xla_histogram(binned, ch, b)
        assert int(h[0, 0, 0]) == 3 * n            # >> 127
        assert int(h[0, 0, 1]) == 2 * n


# ------------------------------------------------------- end-to-end AUC
class TestQuantizedTraining:
    def test_synthetic_higgs_auc_within_1e3(self):
        """Quantized training with renewed leaves reaches the float32
        path's held-out AUC to within the spread float32 runs show among
        themselves. The name keeps the band the seed set, 1e-3, which 2,000
        held-out rows cannot resolve: the test was red from the seed on,
        with the quantized model the *better* one (0.87095 against
        0.87455). Measured here on the CPU (PR 37; 40 trees, 15 leaves,
        16 levels):

        * float32 over ten training draws of this problem, the same 2,000
          held-out rows: 0.86440 to 0.87258 (range 8.2e-3, sd 2.2e-3);
          quantized on the same draws 0.86388 to 0.87478, the pairs'
          differences -2.5e-3 to +2.2e-3 (mean -0.5e-3, sd 1.9e-3);
        * quantized over ten rounding seeds on this test's data: 0.86862 to
          0.87501 (mean 0.87166, sd 1.9e-3) around float32's 0.87095;
        * over ten seeds of data and problem: differences -4.7e-3 to
          +5.2e-3 (mean +0.8e-3, sd 2.9e-3).

        The quantized runs sit inside the float32 runs' own spread, so the
        old band was the fault and not the path. The band is 8e-3: the
        range of the float32 runs among themselves (4 sd: 8.8e-3); the
        largest of the thirty differences above is 5.2e-3."""
        from sklearn.metrics import roc_auc_score
        X, y = _higgs_like(9000, 10)
        Xt, yt, Xv, yv = X[:7000], y[:7000], X[7000:], y[7000:]
        base = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
                "verbosity": -1, "tpu_grower": "compact",
                "min_data_in_leaf": 20, "learning_rate": 0.1}
        b_f = lgb.train(dict(base), lgb.Dataset(Xt, label=yt, params=base),
                        40)
        qp = dict(base, use_quantized_grad=True, num_grad_quant_bins=16,
                  quant_train_renew_leaf=True)
        b_q = lgb.train(dict(qp), lgb.Dataset(Xt, label=yt, params=qp), 40)
        auc_f = roc_auc_score(yv, b_f.predict(Xv))
        auc_q = roc_auc_score(yv, b_q.predict(Xv))
        assert abs(auc_f - auc_q) <= 8e-3, (auc_f, auc_q)
        # sanity: the quantized model actually learned
        assert auc_q > 0.8

    def test_quant_compact_matches_masked_shim_statistics(self):
        """The compact int path and the masked dequantize-shim implement
        the same discretization; with deterministic rounding and a fixed
        bag their models agree closely."""
        from sklearn.metrics import roc_auc_score
        X, y = _higgs_like(4000, 8, seed=11)
        base = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
                "verbosity": -1, "min_data_in_leaf": 20,
                "use_quantized_grad": True, "num_grad_quant_bins": 32,
                "stochastic_rounding": False}
        b_c = lgb.train(dict(base, tpu_grower="compact"),
                        lgb.Dataset(X, label=y, params=base), 10)
        b_m = lgb.train(dict(base, tpu_grower="masked"),
                        lgb.Dataset(X, label=y, params=base), 10)
        a_c = roc_auc_score(y, b_c.predict(X))
        a_m = roc_auc_score(y, b_m.predict(X))
        assert abs(a_c - a_m) < 5e-3, (a_c, a_m)


# ---------------------------------------------------- steady-state guard
class TestQuantizedSteadyState:
    @pytest.fixture(scope="class")
    def warm_quant_booster(self):
        X, y = _higgs_like(1500, 10)
        params = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
                  "learning_rate": 0.1, "min_data_in_leaf": 20,
                  "verbosity": -1, "tpu_grower": "compact",
                  "use_quantized_grad": True, "num_grad_quant_bins": 8,
                  "quant_train_renew_leaf": True,
                  "stop_check_freq": 10_000}
        ds = lgb.Dataset(X, label=y, params=params)
        bst = lgb.Booster(params, ds)
        for _ in range(2):
            bst.update()
        return bst

    def test_quantized_boosting_no_recompiles_no_transfers(
            self, warm_quant_booster):
        """The acceptance criterion: 5 post-warmup iterations of the
        QUANTIZED compact step — zero lowerings, zero backend compiles,
        zero device->host transfers (np.asarray funnel armed too)."""
        bst = warm_quant_booster
        with guards.steady_state_guard("5 quantized iterations") as cc:
            for _ in range(5):
                bst.update()
        assert cc.lowerings == 0
        assert cc.backend_compiles == 0
        bst._gbdt._flush_trees()
        assert bst._gbdt.num_total_trees >= 7


# ------------------------------------------- data-parallel reduce-scatter
@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs >= 4 devices")
class TestHistScatter:
    def _train(self, X, y, extra, n_iter=6):
        params = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
                  "verbosity": -1, "tree_learner": "data",
                  "tpu_grower": "compact", "min_data_in_leaf": 5}
        params.update(extra)
        return lgb.train(dict(params),
                         lgb.Dataset(X, label=y, params=params), n_iter)

    def test_scatter_matches_allreduce_trees(self):
        """psum_scatter over the feature axis + best-split all-gather
        produces the same trees as the full-histogram all-reduce."""
        X, y = _higgs_like(2048, 10, seed=3)
        b_off = self._train(X, y, {"tpu_hist_scatter": "off"})
        b_on = self._train(X, y, {"tpu_hist_scatter": "on"})
        np.testing.assert_allclose(b_on.predict(X), b_off.predict(X),
                                   atol=1e-6)

    def test_scatter_quantized_trains(self):
        from sklearn.metrics import roc_auc_score
        X, y = _higgs_like(2048, 10, seed=5)
        bst = self._train(X, y, {"use_quantized_grad": True,
                                 "num_grad_quant_bins": 16})
        assert bst._gbdt.grower_params is not None
        assert roc_auc_score(y, bst.predict(X)) > 0.8

    def test_scatter_incompatible_configs_fall_back(self):
        """EFB bundles keep the all-reduce (a shard's slice cannot serve
        a bundled feature whose column lives elsewhere); the config knob
        warns instead of crashing."""
        rng = np.random.RandomState(2)
        n, G, card = 2048, 10, 8
        cats = rng.randint(0, card, size=(n, G))
        X = np.zeros((n, G * card), np.float32)
        for g in range(G):
            X[np.arange(n), g * card + cats[:, g]] = 1.0
        y = (X @ (rng.randn(G * card) * 0.5) > 0).astype(np.float64)
        bst = self._train(X, y, {"tpu_hist_scatter": "on"}, n_iter=3)
        assert np.isfinite(bst.predict(X)).all()
