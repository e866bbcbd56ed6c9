"""Fused per-split Mosaic kernel (ops/fused_split.py) vs the XLA reference.

Runs the kernel in Pallas interpret mode on the CPU test backend; the
partition must match ops/compact.py partition_segment byte-for-byte, the
histogram count channels must be exact, and grad/hess must sit within the
hi/lo-bf16 split tolerance (same contract as ops/pallas_histogram.py).

Reference analogue: the CUDA per-split kernels
(src/treelearner/cuda/cuda_data_partition.cu:288,679,907 and
cuda_histogram_constructor.cu:17-68) are validated by the reference's
test_engine.py end-to-end runs; here we check the fused kernel directly
against the independently-tested XLA implementation.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops.compact import (RowLayout, pack_rows,
                                      partition_segment, segment_histogram)
from lightgbm_tpu.ops.fused_split import fused_split

i32 = jnp.int32


def _make_work(rng, n, f, b, extra=1, route=None, packed4=False,
               quant=False):
    """``route``: (feature, fn(column) -> None) rewrites one feature's bins
    in place before the rows are packed. ``quant``: the gradients and
    hessians are the small integer codes of quantized training."""
    layout = RowLayout(num_features=f, num_extra=extra, packed4=packed4)
    binned = rng.randint(0, b, size=(n, f)).astype(np.uint8)
    if route is not None:
        route[1](binned[:, route[0]])
    if quant:
        g = rng.randint(-63, 64, n).astype(np.float32)
        h = rng.randint(0, 64, n).astype(np.float32)
    else:
        g = rng.randn(n).astype(np.float32)
        h = np.abs(rng.randn(n)).astype(np.float32)
    cnt = (rng.rand(n) > 0.25).astype(np.float32)
    extras = rng.randn(extra, n).astype(np.float32)
    work = jax.jit(pack_rows, static_argnames=("layout", "pad_rows"))(
        jnp.asarray(binned), jnp.asarray(g), jnp.asarray(h),
        jnp.asarray(cnt), jnp.asarray(extras), layout, 256)
    return layout, np.asarray(work)


def _run_fused(work0, layout, b, mode, start, count, n_left, feat, bin_,
               default_left=0, nan_bin=0, is_cat=0, bits=None, bs=128,
               dual=True, quant=False):
    bits = (jnp.zeros((8,), jnp.uint32) if bits is None
            else jnp.asarray(bits, jnp.uint32))
    return fused_split(
        jnp.asarray(work0), jnp.zeros((work0.shape), jnp.uint8),
        jnp.asarray(mode, i32), jnp.asarray(start, i32),
        jnp.asarray(count, i32), jnp.asarray(n_left, i32),
        jnp.asarray(feat, i32), jnp.asarray(bin_, i32),
        jnp.asarray(default_left, i32), jnp.asarray(nan_bin, i32),
        jnp.asarray(is_cat, i32), bits, layout, b, bs, 8, interpret=True,
        dual=dual, quant=quant)


def _merged(wf, sf, start, count, n_left, dual=True):
    """Dual residency: the right child lives in the scratch array at its
    final offsets; merge for comparison against the single-array reference.
    The copy-back variant (dual=False) already holds everything in work."""
    out = np.asarray(wf).copy()
    if dual:
        rs, re = start + n_left, start + count
        out[rs:re] = np.asarray(sf)[rs:re]
    return out


def _run_ref(work0, b, layout, start, count, n_left, feat, bin_,
             default_left=False, nan_bin=0, is_cat=False, bits=None):
    bits = (jnp.zeros((8,), jnp.uint32) if bits is None
            else jnp.asarray(bits, jnp.uint32))
    wr, _ = partition_segment(
        jnp.asarray(work0), jnp.zeros(work0.shape, jnp.uint8),
        jnp.asarray(start, i32), jnp.asarray(count, i32),
        jnp.asarray(n_left, i32), jnp.asarray(feat, i32),
        jnp.asarray(bin_, i32), jnp.asarray(default_left),
        jnp.asarray(nan_bin, i32), jnp.asarray(is_cat), bits, 128,
        packed4=layout.packed4)
    n_right = count - n_left
    s_small = start if n_left <= n_right else start + n_left
    m_small = min(n_left, n_right)
    href = segment_histogram(wr, jnp.asarray(s_small, i32),
                             jnp.asarray(m_small, i32), layout, b, 128, "xla")
    return np.asarray(wr), np.asarray(href)


FEAT = 2                # the routing feature of the named cases
LEFT, RIGHT = 0, 255    # bins on either side of a 256-bin case's threshold


def _ring_case(rng, case, start, count, n=3000):
    """(layout, work0, bins, threshold bin, fused block, n_left, quant) of
    a named partition case.

    Each stream's carry is a ring of one block: a row lands at
    (cnt + rank) mod block, and a block that fills the ring flushes the
    carry's rows, then its own first rows, and keeps the rows that wrapped.
    The cases place blocks of the walk (block k holds the rows from
    32 * (start // 32) + k * block on) where the ring's arithmetic has an
    edge. ``psi`` is the right ring's first count, (start + n_left) % 32.
    A carry holds its rows as packed bytes, four slots a 32-bit word:
    where a count is no multiple of 4 the masks of a flush or a tail split
    a word."""
    f, b, bin_, bs, col = 5, 256, 100, 128, None
    packed4 = quant = False

    def block0_right(c, psi):
        """Block 0's rows all go right, into a ring that starts at psi
        (spare rows past block 3 trim n_left to it)."""
        c[start:start + bs] = RIGHT
        spare = start + 4 * bs + np.arange(31)
        c[spare] = RIGHT
        n_left = int((c[start:start + count] <= bin_).sum())
        c[spare[:(psi - n_left - start) % 32]] = LEFT

    def odd_blocks(c):
        """Every block of the walk sends its first ``odd`` rows left and
        the rest right: the rings' counts run through every residue mod 4
        at their flushes and at the tails."""
        pos = (np.arange(n) - start // 32 * 32) % bs
        seg = slice(start, start + count)
        c[seg] = np.where(pos[seg] < odd, LEFT, RIGHT)

    if case == "median_bs32":
        # half a block to each stream: one of the two rings wraps in
        # nearly every block, at a block of the alignment's own size
        bin_, bs = 127, 32
    elif case == "median_bs128":
        bin_ = 127
    elif case == "no_spare_lane":
        # 112 + 12 + 4 bytes fill the 128 lanes: the last lane carries a
        # row's own byte through the permutation and the carries
        f, b, bin_ = 112, 64, 31
    elif case == "wide_rows":
        # 118 + 12 + 4 bytes: 256-byte records, two lane tiles a carry row
        f, b, bin_ = 118, 64, 31
    elif case == "quant":
        # the int8 kernel: integer gradient codes, int32 histograms
        quant = True
    elif case == "packed4":
        # two features a byte: the routing bin is a nibble
        f, b, bin_, packed4 = 5, 16, 7, True
    elif case in ("odd_counts", "odd_counts_bs32"):
        # 65 of 128 (17 of 32) rows a block go left
        if case == "odd_counts_bs32":
            bs = 32
        odd = bs // 2 + 1
        col = odd_blocks
    elif case == "all_left_blocks":
        # block 0 fills an empty ring exactly (n == bs at cnt == 0, no
        # wrap); block 3 sends a whole block through a ring part full
        def col(c):
            c[start:start + bs] = LEFT
            c[start + 3 * bs:start + 4 * bs] = LEFT
    elif case == "all_right_blocks":
        # the same for the right ring, empty at first (psi == 0)
        def col(c):
            c[start + 3 * bs:start + 4 * bs] = RIGHT
            block0_right(c, 0)
    elif case.startswith("head_wrap_block0"):
        # phi > 0 head rows ride the left ring while block 0's rows all go
        # right into a ring that starts at psi > 0: it wraps in block 0
        # (``_phiK``: the case at phi = K, set by the caller's start)
        def col(c):
            block0_right(c, 17)
    else:
        assert case == "mixed"
    layout, work0 = _make_work(rng, n, f, b, route=col and (FEAT, col),
                               packed4=packed4, quant=quant)
    n_left = int((_route_bins(layout, work0)[start:start + count]
                  <= bin_).sum())
    phi, psi = start % 32, (start + n_left) % 32
    if case == "no_spare_lane":
        assert layout.num_real_cols == layout.num_cols
    if case == "wide_rows":
        assert layout.num_cols == 256
    if case.startswith("odd_counts"):
        assert odd % 4
    if case == "all_right_blocks":
        assert phi == psi == 0
    if case.startswith("head_wrap_block0"):
        assert phi > 0 and psi + (bs - phi) > bs
    return layout, work0, b, bin_, bs, n_left, quant


def _route_bins(layout, work0):
    """The routing feature's bins of every row (a nibble under packed4)."""
    if layout.packed4:
        return (work0[:, FEAT // 2] >> (4 * (FEAT % 2))) & 0xF
    return work0[:, FEAT]


RING_CASES = [(0, 3000, "all_left_blocks"), (0, 3000, "all_right_blocks"),
              (0, 3000, "median_bs32"), (37, 2219, "median_bs32"),
              (37, 2219, "median_bs128"), (37, 2219, "head_wrap_block0"),
              (0, 3000, "no_spare_lane"), (37, 2219, "no_spare_lane"),
              # packed carries: counts that split a word, phi of 1 to 3,
              # two lane tiles a row, the int8 kernel, nibble-packed bins
              (37, 2219, "odd_counts"), (0, 3000, "odd_counts_bs32"),
              (37, 2219, "odd_counts_bs32"),
              (33, 2219, "head_wrap_block0"), (66, 2219, "head_wrap_block0"),
              (99, 2219, "head_wrap_block0"), (37, 2219, "wide_rows"),
              (37, 2219, "quant"), (37, 2219, "packed4")]


class TestFusedSplit:
    @pytest.mark.parametrize("dual", [True, False])
    @pytest.mark.parametrize(
        "start,count,case",
        [(0, 3000, "mixed"), (37, 2219, "mixed"), (96, 128, "mixed"),
         (500, 1, "mixed"), (200, 0, "mixed")] + RING_CASES)
    def test_partition_and_hist_parity(self, rng, start, count, case, dual):
        n, feat = 3000, FEAT
        layout, work0, b, bin_, bs, n_left, quant = _ring_case(
            rng, case, start, count)
        wf, sf, hf = _run_fused(work0, layout, b, 0, start, count, n_left,
                                feat, bin_, dual=dual, bs=bs, quant=quant)
        wr, href = _run_ref(work0, b, layout, start, count, n_left, feat,
                            bin_)
        wm = _merged(wf, sf, start, count, n_left, dual)
        np.testing.assert_array_equal(wm[:n], wr[:n])
        _assert_hist(hf, href, quant)

    def test_nan_default_left(self, rng):
        n, f, b = 2000, 4, 64
        layout, work0 = _make_work(rng, n, f, b)
        feat, bin_, nan_bin = 1, 20, 63
        col = work0[:, feat]
        gl = (col <= bin_) | (col == nan_bin)
        n_left = int(gl.sum())
        wf, sf, _ = _run_fused(work0, layout, b, 0, 0, n, n_left, feat, bin_,
                               default_left=1, nan_bin=nan_bin)
        wr, _ = _run_ref(work0, b, layout, 0, n, n_left, feat, bin_,
                         default_left=True, nan_bin=nan_bin)
        np.testing.assert_array_equal(_merged(wf, sf, 0, n, n_left)[:n],
                                      wr[:n])

    @pytest.mark.parametrize("dual", [True, False])
    def test_categorical_bitset(self, rng, dual):
        n, f, b = 1500, 4, 256
        layout, work0 = _make_work(rng, n, f, b)
        feat = 3
        bits = np.zeros(8, np.uint32)
        for cat in (3, 17, 100, 255):
            bits[cat // 32] |= np.uint32(1) << (cat % 32)
        col = work0[:, feat]
        gl = (bits[col // 32] >> (col % 32)) & 1
        n_left = int(gl.sum())
        wf, sf, _ = _run_fused(work0, layout, b, 0, 0, n, n_left, feat, 0,
                               is_cat=1, bits=bits, dual=dual)
        wr, _ = _run_ref(work0, b, layout, 0, n, n_left, feat, 0,
                         is_cat=True, bits=bits)
        np.testing.assert_array_equal(_merged(wf, sf, 0, n, n_left, dual)[:n],
                                      wr[:n])

    def test_mode1_root_histogram(self, rng):
        n, f, b = 2500, 5, 256
        layout, work0 = _make_work(rng, n, f, b)
        start, count = 41, 2300
        _, _, hf = _run_fused(work0, layout, b, 1, start, count, 0, 0, 0)
        href = segment_histogram(
            jnp.asarray(work0), jnp.asarray(start, i32),
            jnp.asarray(count, i32), layout, b, 128, "xla")
        hf, href = np.asarray(hf), np.asarray(href)
        np.testing.assert_array_equal(hf[:, :, 2:], href[:, :, 2:])
        np.testing.assert_allclose(hf[:, :, :2], href[:, :, :2], atol=2e-2)

    @pytest.mark.parametrize("dual", [True, False])
    @pytest.mark.parametrize("case", ["mixed", "median_bs32",
                                      "all_right_blocks", "head_wrap_block0",
                                      "no_spare_lane", "odd_counts",
                                      "odd_counts_bs32", "head_wrap_block0_phi1",
                                      "head_wrap_block0_phi2",
                                      "head_wrap_block0_phi3", "wide_rows",
                                      "quant", "packed4"])
    def test_untouched_outside_segment(self, rng, case, dual):
        n, feat = 2000, FEAT
        start, count = (608, 700) if case == "all_right_blocks" else (613, 700)
        if case.startswith("head_wrap_block0_phi"):
            start = 608 + int(case[-1])
        layout, work0, b, bin_, bs, n_left, quant = _ring_case(
            rng, case, start, count, n=n)
        wf, sf, _ = _run_fused(work0, layout, b, 0, start, count, n_left,
                               feat, bin_, dual=dual, bs=bs, quant=quant)
        wf, sf = np.asarray(wf), np.asarray(sf)
        np.testing.assert_array_equal(wf[:start], work0[:start])
        np.testing.assert_array_equal(wf[start + count:n],
                                      work0[start + count:n])
        if dual:
            # the other array's live neighbours survive the right ring's
            # read-modify-write blends (it starts as zeros here)
            assert not sf[:start + n_left].any()
            assert not sf[start + count:].any()
        # the left child stays in place in the parent's array
        np.testing.assert_array_equal(wf[start:start + n_left],
                                      _run_ref(work0, b, layout, start,
                                               count, n_left, feat, bin_)[0]
                                      [start:start + n_left])


# ------------------------------------------- the histogram's lane build
# (bins, quant, packed4, block, features, depth): every bin stride
# _hist_packing produces (16, 64, 128-padded, 256), both channel layouts,
# nibble-packed bins, a block of whole lane tiles and one that leaves a
# tile part empty
LANE_HIST_CASES = (
    [(b, q, False, bs, 5, 2) for b in (16, 64, 100, 256) for q in (False, True)
     for bs in (128, 192)]
    + [(16, False, True, 128, 5, 2), (16, True, True, 128, 5, 2),
       (16, False, True, 192, 5, 2)]
    # the two-level flush (bin = 64 hi + lo): a stride of 256 (G = 4) with
    # its last bin empty and full, a stride of 128 (G = 2); a last pair
    # half empty (5, 7 features) and whole pairs; one and two lane tiles a
    # block; the flush of one staged block and of two
    + [(b, q, False, bs, f, k) for b in (255, 256, 100) for q in (False, True)
       for bs, f, k in ((128, 5, 1), (256, 4, 1), (256, 7, 2), (128, 6, 2))]
    # 8 bins or fewer pad to a whole tile, a stride of 128: two levels too
    + [(8, False, False, 128, 5, 2), (8, True, True, 128, 5, 2)])


def _lane_rows(rng, n, f, b, quant, packed4):
    layout = RowLayout(num_features=f, num_extra=1, packed4=packed4)
    binned = rng.randint(0, b, size=(n, f)).astype(np.uint8)
    if quant:
        g = rng.randint(-63, 64, n).astype(np.float32)
        h = rng.randint(0, 64, n).astype(np.float32)
    else:
        g = rng.randn(n).astype(np.float32)
        h = np.abs(rng.randn(n)).astype(np.float32)
    cnt = (rng.rand(n) > 0.25).astype(np.float32)
    work = pack_rows(jnp.asarray(binned), jnp.asarray(g), jnp.asarray(h),
                     jnp.asarray(cnt), jnp.zeros((1, n), jnp.float32), layout,
                     pad_rows=256)
    return layout, binned, np.stack([g, h, cnt, np.ones_like(g)], 1), work


def _reference_hist(binned, channels, rows, b, quant):
    """ops/histogram.py's einsum over the selected rows: f32 at HIGHEST
    precision, or int8 codes into int32."""
    from lightgbm_tpu.ops.histogram import _xla_histogram
    ch = channels[rows].astype(np.int8 if quant else np.float32)
    return np.asarray(_xla_histogram(jnp.asarray(binned[rows]),
                                     jnp.asarray(ch), b))


def _assert_hist(hist, ref, quant):
    hist = np.asarray(hist)
    if quant:
        assert hist.dtype == np.int32
        np.testing.assert_array_equal(hist, ref)
    else:
        np.testing.assert_array_equal(hist[:, :, 2:], ref[:, :, 2:])
        np.testing.assert_allclose(hist[:, :, :2], ref[:, :, :2], atol=2e-2)


@pytest.mark.parametrize("mode", [0, 1])
@pytest.mark.parametrize("b,quant,packed4,bs,f,depth", LANE_HIST_CASES)
def test_lane_histogram_against_reference(rng, b, quant, packed4, bs, f,
                                          depth, mode):
    """The histogram half's build (rows along lanes) against the XLA
    reference: a segment whose start leaves ``phi`` head rows in its first
    block (and ``psi`` in the right stream's), whose smaller child fills
    whole blocks and a masked tail. Counts exact, grad/hess within the
    hi/lo-bf16 tolerance, quantized sums exact: at more than 64 bins those
    int32 sums are the bit-for-bit check that the two-level contraction
    sums the one-level form's products."""
    n, start, count, feat, thr = 1000, 37, 901, 1, (b - 1) // 3
    layout, binned, channels, work = _lane_rows(rng, n, f, b, quant, packed4)
    seg = np.arange(start, start + count)
    left = seg[binned[seg, feat] <= thr]
    right = seg[binned[seg, feat] > thr]
    rows = seg if mode else (left if len(left) <= len(right) else right)
    assert start % 32 and (start + len(left)) % 32 and len(rows) > bs
    _, _, hist = fused_split(
        work, jnp.zeros_like(work), jnp.asarray(mode, i32),
        jnp.asarray(start, i32), jnp.asarray(count, i32),
        jnp.asarray(len(left), i32), jnp.asarray(feat, i32),
        jnp.asarray(thr, i32), jnp.asarray(0, i32), jnp.asarray(0, i32),
        jnp.asarray(0, i32), jnp.zeros((8,), jnp.uint32), layout, b, bs, 8,
        interpret=True, num_rows=n, quant=quant, mbatch=depth)
    _assert_hist(hist, _reference_hist(binned, channels, rows, b, quant),
                 quant)
