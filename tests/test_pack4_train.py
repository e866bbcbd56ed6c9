"""Round-6 training-bandwidth features: pack4 bins through the training hot
path, the bins-on-sublanes Mosaic layout, and per-leaf bit-width narrowing.

Acceptance properties (ISSUE 6):

  * pack4 training (tpu_bin_pack4 + compact grower) produces BIT-IDENTICAL
    trees and predictions vs the u8 path — dense, categorical, EFB-bundled,
    and at non-multiple row counts (partial-block drains);
  * the narrowed quantized engine (acc_bits=16, packed-pair channels) is
    bit-identical to the int8 -> int32 engine, and per-leaf hist-bits
    selection (ops/renew.py hist_bits_in_leaf) mirrors the reference's
    GetHistBitsInLeaf thresholds;
  * the bins-on-sublanes layout (tpu_hist_layout=sublane) matches the lane
    layout exactly for counts/int32 and within f32 regrouping for sums, in
    both the standalone Mosaic kernel and the fused kernel;
  * the steady-state guard holds with tpu_bin_pack4=true training: zero
    recompiles, zero device->host transfers post warmup.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.analysis import guards
from lightgbm_tpu.ops.compact import RowLayout, pack_rows, unpack_rows
from lightgbm_tpu.ops.fused_split import fused_split
from lightgbm_tpu.ops.histogram import histogram_block, narrow_chunk_rows
from lightgbm_tpu.ops.pallas_histogram import pallas_histogram
from lightgbm_tpu.ops.renew import hist_bits_in_leaf

I32 = jnp.int32


def _strip_params(model_text: str) -> str:
    """Model text minus the parameters echo (the only intended delta
    between a pack4 and a u8 run is the knob itself)."""
    return "\n".join(l for l in model_text.splitlines()
                     if not l.startswith("[tpu_"))


def _higgs_like(n, f, seed=7, cat_col=None):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    if cat_col is not None:
        X[:, cat_col] = rng.randint(0, 6, n)
    y = (X[:, 0] - 0.4 * X[:, 2] + 0.3 * rng.randn(n) > 0).astype(np.float64)
    return X, y


def _onehot_wide(n=3000, groups=100, card=3, seed=0):
    """>= 256 sparse one-hot columns so EFB bundling actually triggers."""
    rng = np.random.RandomState(seed)
    cats = rng.randint(0, card, size=(n, groups))
    X = np.zeros((n, groups * card), np.float32)
    for g in range(groups):
        X[np.arange(n), g * card + cats[:, g]] = 1.0
    w = rng.randn(X.shape[1]) * 0.5
    y = ((X @ w + 0.4 * rng.randn(n)) > 0).astype(np.float64)
    return X, y


BASE = {"objective": "binary", "num_leaves": 15, "max_bin": 15,
        "learning_rate": 0.1, "min_data_in_leaf": 20, "verbosity": -1,
        "tpu_grower": "compact", "stop_check_freq": 10_000}


def _train(X, y, extra, n_iter=6):
    p = dict(BASE, **extra)
    return lgb.train(p, lgb.Dataset(X, label=y, params=p), n_iter)


# ------------------------------------------------- pack4 training parity
class TestPack4Training:
    @pytest.mark.parametrize("n", [3072, 3003])  # non-multiple row counts
    def test_dense_bit_identical(self, n):
        X, y = _higgs_like(n, 9, cat_col=3)
        b_u8 = _train(X, y, {"categorical_feature": [3]})
        b_p4 = _train(X, y, {"categorical_feature": [3],
                             "tpu_bin_pack4": True})
        assert b_p4._gbdt._compact["layout"].packed4
        assert not b_u8._gbdt._compact["layout"].packed4
        np.testing.assert_array_equal(b_u8.predict(X), b_p4.predict(X))
        assert _strip_params(b_u8.model_to_string()) \
            == _strip_params(b_p4.model_to_string())

    def test_efb_bundled_bit_identical(self):
        X, y = _onehot_wide()
        p = dict(BASE, num_leaves=31, min_data_in_leaf=10)
        ds_u8 = lgb.Dataset(X, label=y, params=p)
        b_u8 = lgb.train(dict(p), ds_u8, 5)
        p4 = dict(p, tpu_bin_pack4=True)
        ds_p4 = lgb.Dataset(X, label=y, params=p4)
        b_p4 = lgb.train(dict(p4), ds_p4, 5)
        # the bundled matrix must actually be in play AND nibble-packed
        assert ds_p4._inner.bundle_info is not None
        assert b_p4._gbdt._compact["layout"].packed4
        np.testing.assert_array_equal(b_u8.predict(X), b_p4.predict(X))

    def test_fused_interpret_bit_identical(self):
        """pack4 through the fused Mosaic kernel (interpret mode): the
        in-kernel nibble routing + nibble one-hot build must reproduce the
        u8 kernel's trees bit for bit."""
        X, y = _higgs_like(1203, 6, seed=3)
        extra = {"tpu_fused_interpret": True, "tpu_fused_block": 128,
                 "tpu_hist_mbatch": 4}
        b_u8 = _train(X, y, dict(extra), n_iter=3)
        b_p4 = _train(X, y, dict(extra, tpu_bin_pack4=True), n_iter=3)
        assert b_p4._gbdt._compact["layout"].packed4
        np.testing.assert_array_equal(b_u8.predict(X), b_p4.predict(X))

    def test_wide_bins_fall_back_to_u8(self):
        X, y = _higgs_like(1500, 6)
        b = _train(X, y, {"max_bin": 31, "tpu_bin_pack4": True}, n_iter=2)
        assert not b._gbdt._compact["layout"].packed4     # warned + u8
        assert b._gbdt.num_total_trees >= 1

    def test_quantized_pack4_bit_identical(self):
        """nibble bins + int8 gradient codes compose: same trees as u8."""
        X, y = _higgs_like(2048, 8, seed=11)
        q = {"use_quantized_grad": True, "num_grad_quant_bins": 8}
        b_u8 = _train(X, y, dict(q))
        b_p4 = _train(X, y, dict(q, tpu_bin_pack4=True))
        np.testing.assert_array_equal(b_u8.predict(X), b_p4.predict(X))


# ----------------------------------------------- pack4 row-record helpers
def test_packed_layout_roundtrip():
    rng = np.random.RandomState(0)
    n, f = 517, 7                       # odd F exercises the pad nibble
    binned = rng.randint(0, 16, (n, f)).astype(np.uint8)
    g = rng.randn(n).astype(np.float32)
    h = rng.rand(n).astype(np.float32)
    cnt = np.ones(n, np.float32)
    extras = rng.randn(2, n).astype(np.float32)
    layout = RowLayout(num_features=f, num_extra=2, packed4=True)
    assert layout.feat_cols == 4
    work = pack_rows(jnp.asarray(binned), jnp.asarray(g), jnp.asarray(h),
                     jnp.asarray(cnt), jnp.asarray(extras), layout,
                     pad_rows=32)
    b2, g2, h2, c2, e2 = unpack_rows(work, n, layout)
    np.testing.assert_array_equal(np.asarray(b2), binned)
    np.testing.assert_array_equal(np.asarray(g2), g)
    np.testing.assert_array_equal(np.asarray(e2), extras)


# --------------------------------------------- narrowed quantized engine
class TestNarrowedQuantized:
    def _codes(self, n, qmax, seed=0):
        rng = np.random.RandomState(seed)
        codes = np.zeros((n, 4), np.int8)
        codes[:, 0] = rng.randint(-qmax, qmax + 1, n)
        codes[:, 1] = rng.randint(0, qmax + 1, n)     # hess codes >= 0
        codes[:, 2] = rng.rand(n) > 0.3
        codes[:, 3] = 1
        return codes

    @pytest.mark.parametrize("n,qmax", [(1000, 5), (5000, 9), (700, 31)])
    def test_bit_identical_vs_int32_engine(self, n, qmax):
        rng = np.random.RandomState(1)
        b = 16
        binned = rng.randint(0, b, (n, 7)).astype(np.uint8)
        codes = self._codes(n, qmax)
        wide = histogram_block(jnp.asarray(binned), jnp.asarray(codes), b,
                               impl="xla")
        narrow = histogram_block(jnp.asarray(binned), jnp.asarray(codes), b,
                                 impl="xla", acc_bits=16, quant_max=qmax)
        assert narrow.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(wide), np.asarray(narrow))

    def test_pack4_narrow_compose(self):
        rng = np.random.RandomState(2)
        n, f, b = 1500, 9, 16
        binned = rng.randint(0, b, (n, f)).astype(np.uint8)
        codes = self._codes(n, 9, seed=3)
        padded = np.pad(binned, ((0, 0), (0, 1)))
        packed = (padded[:, 0::2] | (padded[:, 1::2] << 4)).astype(np.uint8)
        ref = histogram_block(jnp.asarray(binned), jnp.asarray(codes), b,
                              impl="xla")
        out = histogram_block(jnp.asarray(packed), jnp.asarray(codes), b,
                              impl="xla", packed4_features=f, acc_bits=16,
                              quant_max=9)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))

    def test_narrow_chunk_rows_bounds(self):
        # chunk * qmax must stay under the 4096 radix; too-wide code
        # bounds have no eligible chunk at all
        assert narrow_chunk_rows(5) * 5 < 4096
        assert narrow_chunk_rows(5) % 128 == 0
        assert narrow_chunk_rows(31) >= 128
        assert narrow_chunk_rows(127) == 0
        with pytest.raises(ValueError):
            histogram_block(jnp.zeros((256, 2), jnp.uint8),
                            jnp.zeros((256, 4), jnp.int8), 16,
                            impl="xla", acc_bits=16, quant_max=127)

    def test_invalid_bits_value_warns_to_32(self):
        X, y = _higgs_like(1200, 6, seed=21)
        q = {"use_quantized_grad": True, "num_grad_quant_bins": 8}
        b = _train(X, y, dict(q, tpu_quant_hist_bits=8), n_iter=2)
        assert not b._gbdt.grower_params.quant_narrow   # warned, 32-bit engine

    def test_hist_bits_in_leaf_thresholds(self):
        # reference semantics: narrow while count * qmax fits 2^15
        bits = hist_bits_in_leaf(jnp.asarray([100, 3000, 4000, 100000]), 9)
        np.testing.assert_array_equal(np.asarray(bits), [16, 16, 32, 32])

    def test_training_bit_identical_and_auto(self):
        X, y = _higgs_like(2500, 8, seed=5)
        q = {"use_quantized_grad": True, "num_grad_quant_bins": 8}
        b32 = _train(X, y, dict(q, tpu_quant_hist_bits=32))
        b16 = _train(X, y, dict(q, tpu_quant_hist_bits=16))
        b_auto = _train(X, y, dict(q))
        assert b16._gbdt.grower_params.quant_narrow
        assert not b32._gbdt.grower_params.quant_narrow
        # auto keeps the int8 engine (narrow is the measured opt-in —
        # the sweep shows its radix-capped chunks lose at B <= 64)
        assert not b_auto._gbdt.grower_params.quant_narrow
        np.testing.assert_array_equal(b32.predict(X), b16.predict(X))
        np.testing.assert_array_equal(b32.predict(X), b_auto.predict(X))


# --------------------------------------------------- bins-on-sublanes
class TestSublaneLayout:
    @pytest.mark.parametrize("mbatch", [1, 4])
    def test_pallas_sublane_int8_bit_identical(self, mbatch):
        rng = np.random.RandomState(4)
        n, f, b = 900, 6, 16
        binned = rng.randint(0, b, (n, f)).astype(np.uint8)
        codes = np.stack([rng.randint(-5, 6, n), rng.randint(0, 6, n),
                          np.ones(n), np.ones(n)], axis=1).astype(np.int8)
        lane = pallas_histogram(jnp.asarray(binned), jnp.asarray(codes), b,
                                mode="int8", interpret=True, mbatch=mbatch,
                                row_block=256)
        sub = pallas_histogram(jnp.asarray(binned), jnp.asarray(codes), b,
                               mode="int8", interpret=True, mbatch=mbatch,
                               row_block=256, hist_layout="sublane")
        np.testing.assert_array_equal(np.asarray(lane), np.asarray(sub))

    def test_pallas_sublane_split_close(self):
        rng = np.random.RandomState(5)
        n, f, b = 900, 6, 64
        binned = rng.randint(0, b, (n, f)).astype(np.uint8)
        ch = rng.randn(n, 4).astype(np.float32)
        lane = np.asarray(pallas_histogram(
            jnp.asarray(binned), jnp.asarray(ch), b, interpret=True,
            row_block=256))
        sub = np.asarray(pallas_histogram(
            jnp.asarray(binned), jnp.asarray(ch), b, interpret=True,
            row_block=256, hist_layout="sublane"))
        np.testing.assert_allclose(lane, sub, rtol=3e-3, atol=1e-4)

    def test_pallas_sublane_rejects_wide_bins(self):
        with pytest.raises(ValueError):
            pallas_histogram(jnp.zeros((256, 2), jnp.uint8),
                             jnp.zeros((256, 4), jnp.float32), 128,
                             interpret=True, hist_layout="sublane")

    # 8 bins pad to a stride of 128: the lane flush is two levels there
    # (bin = 64 hi + lo), the sublane arm one level at any stride
    @pytest.mark.parametrize("b", [16, 8])
    @pytest.mark.parametrize("quant", [False, True])
    def test_fused_sublane_matches_lane(self, quant, b):
        rng = np.random.RandomState(6)
        n, f, bs = 1408 - 37, 5, 128
        binned = rng.randint(0, b, (n, f)).astype(np.uint8)
        if quant:
            g = rng.randint(-8, 9, n).astype(np.float32)
            h = rng.randint(0, 9, n).astype(np.float32)
        else:
            g = rng.randn(n).astype(np.float32)
            h = (rng.rand(n) + 0.5).astype(np.float32)
        cnt = (rng.rand(n) > 0.25).astype(np.float32)
        layout = RowLayout(num_features=f, num_extra=1)
        extras = np.zeros((1, n), np.float32)
        work = pack_rows(jnp.asarray(binned), jnp.asarray(g),
                         jnp.asarray(h), jnp.asarray(cnt),
                         jnp.asarray(extras), layout, pad_rows=bs + 32)
        zero = jnp.asarray(0, I32)

        def run(hist_layout):
            _, _, hist = fused_split(
                work, jnp.zeros_like(work), jnp.asarray(1, I32), zero,
                jnp.asarray(n, I32), zero, zero, zero, zero, zero, zero,
                jnp.zeros((1,), jnp.uint32), layout, b, bs, 1,
                interpret=True, num_rows=n, quant=quant, mbatch=4,
                hist_layout=hist_layout)
            return np.asarray(hist)

        lane, sub = run("lane"), run("sublane")
        if quant:
            np.testing.assert_array_equal(lane, sub)
        else:
            np.testing.assert_array_equal(lane[:, :, 2:], sub[:, :, 2:])
            np.testing.assert_allclose(lane, sub, rtol=3e-3, atol=1e-4)

    def test_training_sublane_fused_interpret(self):
        """End-to-end: sublane fused training reproduces lane training
        (counts drive partitions, so trees must match exactly)."""
        X, y = _higgs_like(1203, 6, seed=9)
        extra = {"tpu_fused_interpret": True, "tpu_fused_block": 128,
                 "tpu_hist_mbatch": 4, "use_quantized_grad": True,
                 "num_grad_quant_bins": 8}
        b_lane = _train(X, y, dict(extra), n_iter=3)
        b_sub = _train(X, y, dict(extra, tpu_hist_layout="sublane"),
                       n_iter=3)
        assert b_sub._gbdt.grower_params.hist_layout == "sublane"
        np.testing.assert_array_equal(b_lane.predict(X), b_sub.predict(X))

    def test_layout_knob_validation(self):
        from lightgbm_tpu.engines.registry import resolve_layout
        assert resolve_layout({"tpu_hist_layout": "auto"}, 256) == "lane"
        assert resolve_layout({"tpu_hist_layout": "sublane"}, 64) \
            == "sublane"
        # wide bins cannot lay on sublanes — warn + lane
        assert resolve_layout({"tpu_hist_layout": "sublane"}, 256) \
            == "lane"
        assert resolve_layout({"tpu_hist_layout": "bogus"}, 64) == "lane"


# ------------------------------------------------------ steady-state guard
def test_steady_state_guard_with_pack4_training():
    """5 post-warmup compact iterations with tpu_bin_pack4=true: zero
    lowerings, zero backend compiles, zero d2h transfers — the packed bin
    matrix must not smuggle a host round trip or a shape-driven recompile
    into the training loop."""
    X, y = _higgs_like(1200, 8, seed=17)
    params = dict(BASE, tpu_bin_pack4=True)
    ds = lgb.Dataset(X, label=y, params=params)
    bst = lgb.Booster(params, ds)
    for _ in range(2):
        bst.update()
    assert bst._gbdt._compact["layout"].packed4
    with guards.steady_state_guard("5 pack4 iterations") as cc:
        for _ in range(5):
            bst.update()
    assert cc.lowerings == 0
    assert cc.backend_compiles == 0
    bst._gbdt._flush_trees()
    assert bst._gbdt.num_total_trees >= 7


def test_steady_state_guard_with_narrowed_quant():
    """Per-leaf hist-bits narrowing is a lax.cond inside one compiled
    program — leaves crossing the 16/32-bit threshold at run time must not
    trigger recompiles or host syncs."""
    X, y = _higgs_like(1500, 8, seed=19)
    params = dict(BASE, use_quantized_grad=True, num_grad_quant_bins=8,
                  tpu_quant_hist_bits=16)
    ds = lgb.Dataset(X, label=y, params=params)
    bst = lgb.Booster(params, ds)
    for _ in range(2):
        bst.update()
    assert bst._gbdt.grower_params.quant_narrow
    with guards.steady_state_guard("5 narrowed iterations") as cc:
        for _ in range(5):
            bst.update()
    assert cc.lowerings == 0
    assert cc.backend_compiles == 0
