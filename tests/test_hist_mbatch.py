"""Batched-M histogram parity (ISSUE 4 tentpole acceptance).

The K-deep pending ring (ops/fused_split.py hist_flush), the Mosaic
kernel's window partition (ops/pallas_histogram.py), and the XLA engine's
chunk widening (ops/histogram.py) must all be EXACT-parity engines:

  * counts (in-bag + raw) bit-identical to the K=1 sync path at every K;
  * int32 quantized histograms bit-identical at every K;
  * bf16/f32 grad/hess sums within 2^-17 relative (the f32 accumulation
    regroups across the batch boundary, nothing more);
  * the drain flushes partial batches exactly at non-multiple block
    counts (pushes % K remainder blocks);
  * the steady-state guard holds with tpu_hist_mbatch set: 0 recompiles,
    0 device->host transfers post warmup.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.analysis import guards
from lightgbm_tpu.engines import registry
from lightgbm_tpu.ops.compact import RowLayout, pack_rows
from lightgbm_tpu.ops.fused_split import (fused_block_cap, fused_ring_bytes,
                                          fused_split)
from lightgbm_tpu.ops.histogram import _xla_histogram, histogram_block
from lightgbm_tpu.ops.pallas_histogram import pallas_histogram

REL_BOUND = 2.0 ** -17
I32 = jnp.int32


def _mk_rows(n, f, b, seed=0, quant=False):
    rng = np.random.RandomState(seed)
    binned = rng.randint(0, b, (n, f)).astype(np.uint8)
    if quant:
        g = rng.randint(-63, 64, n).astype(np.float32)
        h = rng.randint(0, 64, n).astype(np.float32)
    else:
        g = rng.randn(n).astype(np.float32)
        h = (rng.rand(n) + 0.5).astype(np.float32)
    cnt = (rng.rand(n) > 0.25).astype(np.float32)
    return binned, g, h, cnt


def _fused_hist(binned, g, h, cnt, b, bs, mbatch, quant=False):
    n, f = binned.shape
    layout = RowLayout(num_features=f, num_extra=1)
    extras = np.zeros((1, n), np.float32)
    work = pack_rows(jnp.asarray(binned), jnp.asarray(g), jnp.asarray(h),
                     jnp.asarray(cnt), jnp.asarray(extras), layout,
                     pad_rows=bs + 32)
    zero = jnp.asarray(0, I32)
    _, _, hist = fused_split(
        work, jnp.zeros_like(work), jnp.asarray(1, I32), zero,
        jnp.asarray(n, I32), zero, zero, zero, zero, zero, zero,
        jnp.zeros((1,), jnp.uint32), layout, b, bs, 1, interpret=True,
        num_rows=n, quant=quant, mbatch=mbatch)
    return np.asarray(hist)


# ------------------------------------------------------------ fused kernel
@pytest.mark.parametrize("mbatch", [4, 8, 16])
def test_fused_counts_bit_exact_vs_sync(mbatch):
    # 11 blocks of 128 rows: 11 % K != 0 for every K — the drain flushes
    # a partial batch on each configuration
    binned, g, h, cnt = _mk_rows(1408 - 37, 5, 16)
    sync = _fused_hist(binned, g, h, cnt, 16, 128, 1)
    out = _fused_hist(binned, g, h, cnt, 16, 128, mbatch)
    np.testing.assert_array_equal(sync[:, :, 2], out[:, :, 2])
    np.testing.assert_array_equal(sync[:, :, 3], out[:, :, 3])
    # raw counts also match an independent numpy histogram
    for j in range(binned.shape[1]):
        np.testing.assert_array_equal(
            out[j, :, 3], np.bincount(binned[:, j], minlength=16))


@pytest.mark.parametrize("mbatch", [4, 8])
def test_fused_grad_hess_within_2p17(mbatch):
    binned, g, h, cnt = _mk_rows(1408 - 37, 5, 16, seed=3)
    sync = _fused_hist(binned, g, h, cnt, 16, 128, 1)
    out = _fused_hist(binned, g, h, cnt, 16, 128, mbatch)
    # relative to the magnitude of the summands (signed sums cancel)
    mag_g = np.zeros_like(sync[:, :, 0])
    mag_h = np.zeros_like(mag_g)
    for j in range(binned.shape[1]):
        for bb in range(16):
            sel = binned[:, j] == bb
            mag_g[j, bb] = np.abs(g[sel]).sum()
            mag_h[j, bb] = np.abs(h[sel]).sum()
    dg = np.abs(out[:, :, 0] - sync[:, :, 0]) / np.maximum(mag_g, 1e-6)
    dh = np.abs(out[:, :, 1] - sync[:, :, 1]) / np.maximum(mag_h, 1e-6)
    assert dg.max() <= REL_BOUND
    assert dh.max() <= REL_BOUND


@pytest.mark.parametrize("mbatch", [4, 8, 16])
def test_fused_quantized_int32_bit_exact(mbatch):
    binned, g, h, cnt = _mk_rows(1100, 4, 8, seed=5, quant=True)
    sync = _fused_hist(binned, g, h, cnt, 8, 128, 1, quant=True)
    out = _fused_hist(binned, g, h, cnt, 8, 128, mbatch, quant=True)
    assert out.dtype == np.int32 and sync.dtype == np.int32
    np.testing.assert_array_equal(sync, out)


def test_fused_partial_drain_single_block():
    """count < one block: the drain is the ONLY flush (pushes=1 < K)."""
    binned, g, h, cnt = _mk_rows(90, 4, 8, seed=7)
    sync = _fused_hist(binned, g, h, cnt, 8, 128, 1)
    out = _fused_hist(binned, g, h, cnt, 8, 128, 8)
    np.testing.assert_array_equal(sync[:, :, 3], out[:, :, 3])
    assert out[0, :, 3].sum() == 90


def test_fused_split_mode_parity_with_mbatch():
    """mode=0 (partition + smaller-child histogram) agrees across K."""
    n, f, b, bs = 700, 4, 8, 128
    binned, g, h, cnt = _mk_rows(n, f, b, seed=11)
    layout = RowLayout(num_features=f, num_extra=1)
    extras = np.zeros((1, n), np.float32)
    outs = {}
    for mb in (1, 8):
        work = pack_rows(jnp.asarray(binned), jnp.asarray(g),
                         jnp.asarray(h), jnp.asarray(cnt),
                         jnp.asarray(extras), layout, pad_rows=bs + 32)
        zero = jnp.asarray(0, I32)
        n_left = int((binned[:, 1] <= 3).sum())
        w, s, hist = fused_split(
            work, jnp.zeros_like(work), zero, zero, jnp.asarray(n, I32),
            jnp.asarray(n_left, I32), jnp.asarray(1, I32),
            jnp.asarray(3, I32), zero, zero, zero,
            jnp.zeros((1,), jnp.uint32), layout, b, bs, 1, interpret=True,
            num_rows=n, mbatch=mb)
        outs[mb] = (np.asarray(w), np.asarray(s), np.asarray(hist))
    np.testing.assert_array_equal(outs[1][0], outs[8][0])   # partition
    np.testing.assert_array_equal(outs[1][2][:, :, 2:], outs[8][2][:, :, 2:])


def _forest_data():
    rng = np.random.RandomState(23)
    n, f = 1500, 8
    X = rng.randn(n, f).astype(np.float32)
    y = (X[:, 0] - 0.5 * X[:, 3] + 0.3 * X[:, 5] * X[:, 1]
         + 0.4 * rng.randn(n) > 0).astype(np.float64)
    base = {
        "objective": "binary", "num_leaves": 15, "max_bin": 63,
        "learning_rate": 0.1, "min_data_in_leaf": 20, "verbosity": -1,
        "tpu_grower": "compact",
        "tpu_fused_interpret": True, "tpu_fused_block": 128,
    }
    return X, y, base


def _walk(node, out):
    if "leaf_index" in node:
        out.append(("leaf", node["leaf_index"], node["leaf_count"]))
        return
    out.append((node["split_feature"], node["threshold"],
                node["decision_type"], node["internal_count"]))
    _walk(node["left_child"], out)
    _walk(node["right_child"], out)


def _assert_same_forest(trees_a, trees_b, n_trees):
    assert len(trees_a) == len(trees_b) == n_trees
    for a, b in zip(trees_a, trees_b):
        assert a["num_leaves"] == b["num_leaves"] > 1
        wa, wb = [], []
        _walk(a["tree_structure"], wa)
        _walk(b["tree_structure"], wb)
        assert wa == wb


def test_compact_grower_tree_identical_at_fused_default_and_depth_8():
    """Grower level: a compact-grower forest grown at the depth a fused
    entry now resolves by default and at an explicit depth 8 (the
    standalone engines' default, which fused entries used to inherit)
    has identical splits and leaf counts — the depth regroups an f32
    accumulation and nothing else."""
    X, y, base = _forest_data()
    trees = {}
    for name, extra in (("default", {}), ("k8", {"tpu_hist_mbatch": 8})):
        params = dict(base, **extra)
        bst = lgb.train(params, lgb.Dataset(X, label=y, params=params), 3,
                        keep_training_booster=True)
        gp = bst._gbdt.grower_params
        assert gp.fused_block == 128
        assert gp.hist_mbatch == (8 if extra else registry.FUSED_MBATCH)
        trees[name] = bst.dump_model()["tree_info"]
    assert registry.FUSED_MBATCH != 8       # else this compares nothing
    _assert_same_forest(trees["default"], trees["k8"], 3)


def test_compact_grower_forest_identical_with_fused_kernel_and_without():
    """Grower level: the fused kernel's partition (a block's permutation
    into ring carries) leaves every row where the XLA compact walk
    (``tpu_fused=off``) leaves it: the same splits, the same leaf counts,
    and after three trees of 14 splits each the same row order. The block
    of 32 makes a ring wrap in nearly every block of every split."""
    X, y, base = _forest_data()
    trees, perms = {}, {}
    for name, extra in (("fused", {"tpu_fused_block": 32}),
                        ("xla", {"tpu_fused": "off"})):
        params = dict(base, **extra)
        bst = lgb.train(params, lgb.Dataset(X, label=y, params=params), 3,
                        keep_training_booster=True)
        gp = bst._gbdt.grower_params
        assert gp.fused_block == (32 if name == "fused" else 0)
        trees[name] = bst.dump_model()["tree_info"]
        perms[name] = bst._gbdt._compact_perm()
    _assert_same_forest(trees["fused"], trees["xla"], 3)
    assert sorted(perms["fused"]) == list(range(len(y)))
    np.testing.assert_array_equal(perms["fused"], perms["xla"])


# --------------------------------------------------- standalone Mosaic
@pytest.mark.parametrize("mbatch", [2, 4, 8])
def test_pallas_histogram_split_parity(mbatch):
    rng = np.random.RandomState(2)
    n, f, b = 3000, 6, 32
    binned = jnp.asarray(rng.randint(0, b, (n, f)).astype(np.uint8))
    ch = jnp.asarray(rng.randn(n, 4).astype(np.float32))
    base = np.asarray(pallas_histogram(binned, ch, b, row_block=512,
                                       interpret=True, mbatch=1))
    out = np.asarray(pallas_histogram(binned, ch, b, row_block=512,
                                      interpret=True, mbatch=mbatch))
    mag = np.asarray(_xla_histogram(binned, jnp.abs(ch), b))
    rel = np.abs(out - base) / np.maximum(mag, 1e-6)
    assert rel.max() <= REL_BOUND
    # integer channels: bit-exact
    ci = jnp.asarray((rng.rand(n, 4) > 0.5).astype(np.float32))
    a = np.asarray(pallas_histogram(binned, ci, b, row_block=512,
                                    interpret=True, mbatch=1))
    bb = np.asarray(pallas_histogram(binned, ci, b, row_block=512,
                                     interpret=True, mbatch=mbatch))
    np.testing.assert_array_equal(a, bb)


@pytest.mark.parametrize("mbatch", [4, 16])
def test_pallas_histogram_int8_bit_exact(mbatch):
    rng = np.random.RandomState(4)
    n, f, b = 2500, 5, 16
    binned = jnp.asarray(rng.randint(0, b, (n, f)).astype(np.uint8))
    codes = rng.randint(-16, 17, (n, 4)).astype(np.int8)
    codes[:, 2:] = 1
    ch = jnp.asarray(codes)
    outs = [np.asarray(pallas_histogram(binned, ch, b, row_block=512,
                                        mode="int8", interpret=True,
                                        mbatch=mb)) for mb in (1, mbatch)]
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_array_equal(
        outs[1], np.asarray(_xla_histogram(binned, ch, b)))


def test_pallas_mbatch_clamps_to_divisor():
    """row_block % mbatch != 0 rounds K down to a divisor instead of
    mis-partitioning windows."""
    rng = np.random.RandomState(6)
    n, f, b = 1000, 3, 8
    binned = jnp.asarray(rng.randint(0, b, (n, f)).astype(np.uint8))
    ch = jnp.asarray(rng.randn(n, 4).astype(np.float32))
    out = np.asarray(pallas_histogram(binned, ch, b, row_block=384,
                                      interpret=True, mbatch=7))
    base = np.asarray(pallas_histogram(binned, ch, b, row_block=384,
                                       interpret=True, mbatch=1))
    np.testing.assert_allclose(out, base, rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------ XLA engine
def test_xla_engine_mbatch_parity():
    rng = np.random.RandomState(8)
    n, f, b = 4000, 5, 16
    binned = jnp.asarray(rng.randint(0, b, (n, f)).astype(np.uint8))
    codes = rng.randint(-8, 9, (n, 4)).astype(np.int8)
    ch = jnp.asarray(codes)
    a = np.asarray(_xla_histogram(binned, ch, b, mbatch=1))
    for mb in (8, 16):
        np.testing.assert_array_equal(
            a, np.asarray(_xla_histogram(binned, ch, b, mbatch=mb)))
    # dispatch wrapper threads mbatch
    d = np.asarray(histogram_block(binned, ch, b, impl="xla", mbatch=8))
    np.testing.assert_array_equal(a, d)


# --------------------------------------------------------- VMEM contract
def test_fused_block_cap_accounts_for_ring_depth():
    """The pending ring multiplies VMEM residency by K: a deeper ring
    must never produce a LARGER block cap, and the chosen cap's ring must
    fit the budget for both channel layouts."""
    from lightgbm_tpu.ops.fused_split import _VMEM_RING_BUDGET
    caps = [fused_block_cap(128, k) for k in (1, 2, 8, 16)]
    assert caps == sorted(caps, reverse=True)
    for k in (1, 8, 16):
        bs = fused_block_cap(128, k)
        assert bs % 32 == 0 and bs >= 32
        if bs > 32:
            assert fused_ring_bytes(bs, 128, k) <= _VMEM_RING_BUDGET
            assert fused_ring_bytes(bs, 128, k, quant=True) \
                <= _VMEM_RING_BUDGET
    # wide EFB-bundled records stay at least as constrained as before
    assert fused_block_cap(640, 8) <= fused_block_cap(128, 8)


@pytest.mark.parametrize("cols, f, b, k, block", [
    (128, 28, 256, 2, 384),      # higgs: 14 groups, 10,752 rows of one-hot
    (128, 28, 64, 2, 384),       # 63 bins: 4 groups
    (256, 220, 256, 1, 256),     # istella: 110 groups, 28,160 rows: the bound
    (256, 137, 256, 1, 256),     # 69 groups, whole lane tiles from 192
    (256, 137, 256, 2, 128),     # 204 rows fit: the lane tiles below
    (256, 220, 256, 2, 128),
    (256, 220, 256, 4, 64),      # under a lane tile: multiples of 32
    (128, 28, 256, 8, 128),      # 251 rows fit
    (128, 28, 256, 4, 384),
    (2048, 2000, 64, 2, 32),     # 250 groups: the floor
])
def test_fused_block_cap_bounds_the_rows_of_one_hot_a_flush_unrolls(
        cols, f, b, k, block):
    """The flush's text grows with feature groups x depth x block; past
    8 MB of it every streamed row pays (PERF.md, PR 30), and
    ``_FLUSH_ONEHOT_ROWS`` is the largest flush that has run clean. From
    128 rows up a block is whole lane tiles (PR 33): the histogram holds
    a block's rows along lanes."""
    from lightgbm_tpu.engines.registry import clamp_fused_block
    from lightgbm_tpu.ops.fused_split import (_FLUSH_ONEHOT_ROWS,
                                              _hist_packing)
    assert fused_block_cap(cols, k, num_features=f, num_bins=b) == block
    assert clamp_fused_block(512, cols, k, "lane", b, f) == block
    # an override for experiments is held to the same bound
    assert clamp_fused_block(512, cols, k, "lane", b, f,
                             env_override="512") == block
    _, f_pad, group = _hist_packing(f, b)
    assert (block == 32
            or -(-f_pad // group) * k * block <= _FLUSH_ONEHOT_ROWS)
    assert block < 128 or block % 128 == 0
    # without the features the cap is the record's alone, as it was
    assert fused_block_cap(cols, k) >= block


@pytest.mark.parametrize("cols, block", [
    (128, 384),     # the stream cap's own block, whole lane tiles already
    (256, 256),     # 192 at the cap: the next tile, 15 x 256 x 256 B fits
    (384, 128),
    (512, 128),     # 96 at the cap
    (640, 64),      # 128 rows would pass what the cap held: multiples of 32
])
def test_fused_block_cap_takes_whole_lane_tiles(cols, block):
    from lightgbm_tpu.ops.fused_split import (_STREAM_BYTES_A_CELL,
                                              _VMEM_STREAM_BYTES)
    assert fused_block_cap(cols, 1) == block
    assert _STREAM_BYTES_A_CELL * block * cols <= _VMEM_STREAM_BYTES


# ------------------------------------------------------ steady-state guard
def test_steady_state_guard_with_mbatch_set():
    """5 post-warmup compact iterations with tpu_hist_mbatch=4: zero
    lowerings, zero backend compiles, zero d2h transfers."""
    rng = np.random.RandomState(17)
    n, f = 1200, 8
    X = rng.randn(n, f).astype(np.float32)
    y = (X[:, 0] - 0.3 * X[:, 2] + 0.4 * rng.randn(n) > 0).astype(
        np.float64)
    params = {
        "objective": "binary", "num_leaves": 15, "max_bin": 63,
        "learning_rate": 0.1, "min_data_in_leaf": 20, "verbosity": -1,
        "tpu_grower": "compact", "tpu_hist_mbatch": 4,
        "stop_check_freq": 10_000,
    }
    ds = lgb.Dataset(X, label=y, params=params)
    bst = lgb.Booster(params, ds)
    assert bst._gbdt.grower_params.hist_mbatch == 4
    for _ in range(2):
        bst.update()
    with guards.steady_state_guard("5 mbatch iterations") as cc:
        for _ in range(5):
            bst.update()
    assert cc.lowerings == 0
    assert cc.backend_compiles == 0
    bst._gbdt._flush_trees()
    assert bst._gbdt.num_total_trees >= 7


def test_hist_mbatch_env_override_validated():
    """Round-12 resolve order (engines/registry.py): an explicit user
    knob beats the env override, the env override beats the default —
    and out-of-range env values are still clamped to [1, 16]."""
    import os
    from lightgbm_tpu.engines.registry import resolve_mbatch
    assert resolve_mbatch({"tpu_hist_mbatch": 12}) == 12
    os.environ["LGBM_TPU_HIST_MBATCH"] = "99"
    try:
        # explicit user knob wins over the env override
        assert resolve_mbatch({"tpu_hist_mbatch": 4}) == 4
        # env override (validated: 99 clamps to 16) wins over the default
        assert resolve_mbatch({}) == 16
        os.environ["LGBM_TPU_HIST_MBATCH"] = "5"
        assert resolve_mbatch({}) == 5
    finally:
        del os.environ["LGBM_TPU_HIST_MBATCH"]
