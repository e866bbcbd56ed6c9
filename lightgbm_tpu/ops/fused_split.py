"""Fused per-split Pallas kernel: partition + smaller-child histogram.

TPU-native re-design of the reference's per-split device work (reference:
CUDA kernels GenDataToLeftBitVectorKernel / AggregateBlockOffsetKernel /
SplitInnerKernel, src/treelearner/cuda/cuda_data_partition.cu:288,679,907,
plus CUDAConstructHistogramDenseKernel,
src/treelearner/cuda/cuda_histogram_constructor.cu:17-68 — there three
separate kernel launches per split; here ONE fused streaming walk).

The XLA compact path (ops/compact.py) implements the same stable partition as
a chain of slice / compare / one-hot-matmul / roll / cond-flush ops per
2048-row block; measured on v5e it sustains only ~22-45 Mrows/s in context
because every block is ~10 separate XLA ops and the Pallas histogram calls
inside the dynamic while_loop cannot pipeline. This kernel internalizes the
whole walk:

  * the parent leaf's contiguous segment streams HBM -> VMEM once, with
    double-buffered DMA;
  * each block stably partitions via ONE dest-indexed one-hot MXU matmul
    into the two streams' carries. A carry is a RING of one block, bs
    rows of C bytes, whose rows at and above its count are zero; a
    selected row lands at dest = (count + rank) mod bs, the right
    stream's ring bs slots below the left's, so the one-hot is [2 bs, bs]
    and the carry append costs nothing extra. A block that does not fill
    a ring ORs into it. One that does flushes the carry's rows and then
    the block's first bs - count, and the rows that wrapped, already in
    place below the old count, are the
    new carry: nothing shifts. (Through PR 30 a carry was 2 bs tall and
    shifted down a block on every flush, and the one-hot was [4 bs, bs].)
  * a carry holds PACKED BYTES: [bs / 4, C] i32 words, four rows'
    (byte - 128) bytes a word, as ``pltpu.bitcast`` lays out an int8
    [bs, C] block. The permutation's product is cast to int8 and bitcast
    to words once, as it leaves the MXU. Each slot receives at most one
    row and every slot at or above a count is zero, so the append is an
    OR, a flush is ``carry | (block & ~m)`` with the new carry ``block &
    m`` (``m`` the byte mask of the slots below the count: the slots
    between the wrapped rows and the count are zero, so one mask serves
    both), and the staged bytes are the words with their top bits
    flipped. The mask is arithmetic on the words (``below``), a few
    registers and no [bs, C] compare. Held one byte an i32 word, a
    carry cost an append 48 adds on spilled registers and a flush two
    selects and an i32 -> u8 pack of 48 registers at higgs's 128-byte
    rows (PERF.md section 6 has both listings).
  * what a row decides (its routing bin, its side, its rank, its slot)
    is computed with the block's rows along LANES, [32, bs]: the matmul
    that picks the routing column out of the block also transposes it,
    the ranks are a matmul against a triangular constant, and `dest`
    comes out as the row vector the one-hot's compare broadcasts. Held
    one row a sublane, as a lane reduction leaves a column, each of the
    chain's forty operations cost bs / 8 registers where it now costs
    bs / 128; with the [4 bs, bs] permutation that was the walk's time:
    the loop body is straight-line code the chip runs a bundle a cycle,
    2,780 bundles a block of 384 rows before PR 31 (4.85 ns a parent
    row) and 1,295 since (PERF.md section 6, PR 31).
  * left rows flush in place into the PARENT's residency array (the left
    write cursor can never overtake the read cursor); right rows flush to
    the OTHER array at the same global offsets (dual residency);
  * the SMALLER child's histogram accumulates in VMEM whenever that stream
    flushes a full block — histogram work is n_smaller rows exactly, like the
    reference's smaller-leaf trick (serial_tree_learner.cpp:404);
  * what the histogram needs of a row (each feature's bin, the gradient,
    hessian and count words, the mask) is held with the block's rows along
    LANES too (PR 33): one int8 identity contraction, the routing pick's
    own form, transposes the block's byte columns into the pending ring as
    [HR, bs] i32 rows (the block is the MXU's transposed weight load, no
    XLU); a feature's one-hot is its bin ROW against a sublane iota,
    [bins, bs], and the channel operand is assembled on [1, bs] rows and
    born [8, bs]. Held one row a sublane (through PR 32) every feature's
    bin column cost one XLU lane broadcast per 8 rows, 8 cycles each on 3
    units: F / 3 cycles a histogrammed row whatever its bins (9.1 ns on
    higgs at 255 bins, 10.7 at 63, 58 at 220 features; 5.2, 1.7 and 37.5
    since), the XLU 94% full and the MXU 18%; the flush of 2 x 384 rows
    was 7,379 bundles and 4,005 after, with no lane broadcast, select,
    value pack or spill in it (hist_contract; PERF.md section 6, PR 33);
  * what a histogrammed row then cost was the one-hot's transposed weight
    loads, F x stride x bs / 2,048 pushes a block at 0.52 a cycle, each
    loaded tile used for one 8-row matmul. Where a feature's stride is
    128 or 256 bins the flush contracts a TWO-LEVEL one-hot (PR 38): bin
    = 64 hi + lo, only the 64-wide one-hot of ``lo`` is loaded (two
    features a 128-lane tile, a quarter of the pushes at 256 bins) and
    the channel rows stream stacked by ``hi``, [16 G, bs] a pair of
    features with G = stride / 64; the accumulator is [8 G, F_pad x 64]
    and fused_split() undoes the order. The same products in the same
    sums: the flush of 2 x 384 rows of 28 x 256 bins is 1,283 bundles,
    scheduled at the push rate itself. A stride of 64 or less (63 bins,
    packed nibbles, the sublane arm) is one level, the code of PR 33
    operand for operand (hist_contract; PERF.md section 6, PR 38);
  * `mode=1` turns the kernel into a plain segment histogram (used for the
    root), skipping all partition work.

Dual residency (round 4): every leaf segment owns the SAME address range
[start, start+count) in both arrays but is live in exactly one of them,
tracked by a per-leaf side bit. A split reads the parent from its side,
keeps the left child there, and writes the right child to the other array —
whose bytes in that range are dead by induction (they were the parent's
range). This removes the whole copy-back pass of the previous design, which
re-streamed the entire right child (read scratch + read work + blend +
write) after every split — about a third of the old kernel's DMA traffic.
The grower merges the two arrays once per tree (ops/grower_compact.py).

Alignment: Mosaic requires dynamic DMA offsets provably divisible by the
sublane tiling (8 rows; 32 covers int8 packing), so the segment start is
rounded down to 32 and the `phi` pre-segment rows ride the left stream as
preserved head rows (they rank first in block 0, flush back to their original
slots, and are masked out of the histogram). The right stream's first block
similarly spans `psi` pre-rows and its last block may overrun the segment —
both are read-modify-write blended against the destination array so live
neighbour segments resident there survive. All DMA offsets in the kernel are
of the form `32*t + k*BS`, which the compiler can prove aligned.

Numerics: row bytes move through the permutation matmul as (byte - 128) int8
values at 2x the bf16 MXU rate (one-hot contraction, i32 accumulate — exact).
byte <-> byte - 128 is the top bit flipped, done on the packed bytes on the
way in and on the way out (the carries' words keep the product's (byte -
128) bytes, so their flush is the same flip); a carry slot that no row has
reached holds 0 and would flush as 128, and every such slot is one a blend
replaces or that lands in dead bytes. Histogram channels use the same hi/lo-bf16
split as ops/pallas_histogram.py: counts exact, grad/hess ~2^-17 relative.

The pending ring (round 6's batched-M pipeline; measured on the chip in
PR 29: no speed at any depth, a tenfold loss at the old default of 8): with
``mbatch`` = K > 1 the kernel stages K row blocks (transposed bins + [8, bs]
channel operands) and contracts them together, once per K pushes; the drain
flushes the ``pushes % K`` remainder exactly (stale slots zero out on the
channel side). The design's argument was the MXU's rows: the contraction's
output has 8 rows (the channel count), and a block-diagonal [8K, K*bs]
channel operand against the K blocks' concatenated one-hots issued M = 8K
(the TPU analogue of the reference CUDA constructor accumulating many
row-blocks per launch, cuda_histogram_constructor.cu:17-68). On a v5e it
never held inside this kernel: the one-hot is the MXU's WEIGHTS, a tile of
it is loaded once whatever streams through it, and K = 1 / 2 / 4 trained at
0.932 / 0.939 / 0.949 s an iteration (PERF.md section 6, PR 29: higgs,
10.5M x 28, 255 leaves, block 384), K = 6 / 7 / 8 / 16 at 6.6 / 8.3 / 9.7 /
25.3 s: past 8 MB of kernel text (PR 30) the walk pays 60-96 ns for every
row a split STREAMS, histogrammed or not. Since PR 33 a flush contracts
each staged block with its own [8, bs] operand (the same products and the
same sums without the block diagonal's zeros). What the ring does buy is
fewer roundings: a flush folds K blocks' partial sums before the one
addition into the f32 accumulator, and at 63 bins K = 2 halves the worst
leaf's hessian error against K = 1 (1.8e-4 against 3.4e-4 relative). So a
fused entry runs K = 2, 0.7% slower than K = 1, unless the user or
LGBM_TPU_HIST_MBATCH names a depth (engines/registry.py FUSED_MBATCH). The
deeper arms stay for that bisect and for the parity tests
(tests/test_hist_mbatch.py); ROADMAP (Design) says what a simplicity pass
can put in the ring's place.
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .compact import RowLayout

_A = 32  # row alignment every DMA offset is provably divisible by

# ---- scoped-VMEM accounting (shared with boosting/gbdt.py and tpulint) ----
# The kernel's fixed streaming buffers scale with block_size * num_cols:
# the double-buffered input block, the two streams' double-buffered stages
# and the read-modify-write block as bytes (7 bs*C, 8 in the copy-back
# variant), and the two ring carries as packed bytes (2 bs*C; 8 bs*C as
# a 32-bit word a byte, as the cap still counts them). 49152 is the
# empirical bs*C product the round-3 kernel tolerated on v5e with the
# taller carries, 23 bytes a cell; the cap and the 15 bytes a cell below
# count the carries as words, so every shape keeps the block it was
# measured at (the 6 bs*C the packed carries freed are for a change of
# block to spend, and to measure). The cap stays where the cells' blocks
# were measured, with one exception. The histogram half holds
# a block's rows along LANES (PR 33): a block that leaves a lane tile part
# empty (192 rows: 256-byte records at the cap) sends the whole one-hot
# down the compiler's select-and-pack path instead of the masked weight
# load, 60 cycles a row against 36 at 220 features. Such a block takes
# the next multiple of 128 where the stream's buffers, 15 bytes a cell
# as counted, stay under what the cap held when it was measured
# (fused_block_cap). The pending ring (hist_accum) ADDS
# mbatch-proportional residency: the staged transposed blocks, the
# channel slots, and the per-feature-group one-hot of a contraction — so
# the block size must shrink as the ring deepens, bounded by
# _VMEM_RING_BUDGET.
_VMEM_STREAM_CAP = 49152
_VMEM_STREAM_BYTES = 23 * _VMEM_STREAM_CAP
_STREAM_BYTES_A_CELL = 15
_VMEM_RING_BUDGET = 4 << 20
_LANES = 128


def fused_ring_bytes(block_size: int, num_cols: int, mbatch: int,
                     quant: bool = False, hist_layout: str = "lane") -> int:
    """Scoped-VMEM bytes of the pending ring + its flush transients.

    Counted per slot: the block TRANSPOSED, its byte columns as [C, bs]
    i32 rows (``num_cols`` already reflects the nibble-packed width under
    RowLayout.packed4 — the packed layout halves this term, it does not
    escape the accounting; the kernel stages only the columns the
    histogram reads, this charges them all), the [8, bs] channel operand
    (bf16 padded to 16 sublanes / int8 to 32; the two-level flush stages
    it uncast, f32 / i32 on 8 sublanes: the same bytes), and the one-hot
    of one feature group (<= 512 bins, bf16, which covers the int8 layout
    and the two-level flush's 128 rows of one-hot and 64 streamed rows a
    pair). Both ``hist_layout`` values stage the same operands."""
    del hist_layout
    elt = 1 if quant else 2
    bins = 4 * mbatch * block_size * num_cols
    cht = mbatch * (32 if quant else 16) * block_size * elt
    oh = mbatch * block_size * 512 * elt
    return bins + cht + oh


def fused_block_cap(num_cols: int, mbatch: int, quant: bool = False,
                    hist_layout: str = "lane", num_features: int = 0,
                    num_bins: int = 0) -> int:
    """Largest block size whose streaming buffers AND pending ring fit the
    scoped-VMEM caps (the automatic derivation and the LGBM_TPU_FUSED_BS
    clamp both go through here) and, where the caller says how many
    features of how many bins the rows hold, whose flush stays inside
    ``_FLUSH_ONEHOT_ROWS``: a multiple of 128 rows (whole lane tiles)
    from 128 up, of 32 below."""
    cols = max(num_cols, 1)
    bs = max(32, (_VMEM_STREAM_CAP // cols) // 32 * 32)
    full = _round_up(bs, _LANES)
    if _STREAM_BYTES_A_CELL * full * cols <= _VMEM_STREAM_BYTES:
        bs = full
    while bs > 32 and fused_ring_bytes(bs, num_cols, mbatch, quant,
                                       hist_layout) > _VMEM_RING_BUDGET:
        bs -= 32
    if num_features and num_bins:
        _, f_pad, group = _hist_packing(num_features, num_bins)
        groups = -(-f_pad // group)
        bs = min(bs, max(32, _FLUSH_ONEHOT_ROWS
                         // (groups * max(1, mbatch)) // 32 * 32))
    return bs // _LANES * _LANES if bs > _LANES else bs

# most per-feature one-hot compare tiles a matmul group may hold at once
# (see _hist_packing)
_MAX_GROUP_TILES = 8

# The flush's feature loop is unrolled, and Mosaic unrolls every vector
# operation over its registers: the kernel's text grows with groups x depth
# x block, the rows of one-hot one flush builds. Past 8 MB or so of text
# every streamed row pays, histogrammed or not (PERF.md section 6, PR 30:
# the v5e compiler's generated_code_size_in_bytes beside the chip's ns a
# parent row; clean at 7.5 MB and under, 62-183 ns from 8.6 MB). With the
# rows along lanes (PR 33) a row of one-hot is a compare, a mask pack and
# a masked weight load, no select, pack or spill, and costs half the text
# it did (rows of one-hot: MB now; MB through PR 32):
#   14 groups x 2 x 384 = 10,752: 1.97; 3.51  14 x 4 x 384 = 21,504: 3.50; 6.63
#   110 x 1 x 256 = 28,160: 3.69 (istella)      69 x 2 x 256 = 35,328: 5.71
#   14 x 8 x 384 = 43,008: 6.62; 12.61          110 x 2 x 256 = 56,320: 8.94
# so 7.5 MB is near 48,000 rows now. The bound is the largest flush that
# has RUN clean in this form (fused_block_cap), not that estimate. The
# two-level flush (PR 38) builds a quarter of those rows at 256 bins and
# its text is half (1.02 MB for 1.97, 1.79 for istella's 3.69); the bound
# and the rows it counts (groups x depth x block at _hist_packing's group)
# stay, so every shape sums the rows a flush that it summed before: what
# the freed text allows is the next change's to measure.
_FLUSH_ONEHOT_ROWS = 28_160

# sp scalar-prefetch vector layout (i32[16])
_MODE, _BASE_T, _PHI, _COUNT, _NLEFT, _FEAT, _BIN, _DLEFT, _NANBIN, _ISCAT, \
    _SMALLER_L, _RBASE_T, _PSI, _SIDE = range(14)

# smem bookkeeping slots
_LCNT, _RCNT, _LF, _RF, _CBW, _PEND = range(6)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _hist_packing(f: int, b: int):
    """Histogram lane packing: (bin stride per feature, padded feature
    count, matmul group width in features).

    Bin counts that tile 128 lanes exactly (64/32/16/128/256...) pack
    tightly — at B <= 64 that fits 2+ features per lane tile (the
    reference's GPU learner defaults to 63 bins for the same reason,
    ref: docs/GPU-Performance.rst:133). Awkward bin counts whose
    lcm(b, 128) exceeds the 512-lane matmul target fall back to
    128-padded strides so the one-hot operand stays bounded.

    The group is bounded twice: by the 512 one-hot lanes of one matmul,
    and by 8 per-feature compare tiles. Each [bs, stride] compare result
    occupies a full 128-lane tile in VMEM until the group concatenates,
    whatever its stride — at B = 16 a 512-lane group is 32 such tiles per
    staged block, and the v5e compiler refused the kernel (23.3 MB of
    scoped VMEM against the 16 MB limit at block 384; B = 32: 16.2 MB).
    Eight tiles is what B = 64 has always used and compiles with. Strides
    so narrow that even one aligned unit exceeds that (B <= 8) pad to a
    full tile like the awkward counts do."""
    align = 128 // math.gcd(b, 128)
    stride = b
    if align * b > 512 or align > _MAX_GROUP_TILES:
        stride = _round_up(b, 128)
        align = 1
    f_pad = _round_up(f, align)
    group = align * max(1, min(512 // (align * stride),
                               _MAX_GROUP_TILES // align))
    return stride, f_pad, group


# bins one level of the flush's one-hot spans where a feature has more: a
# bin is 64 hi + lo, and two features' 64 lo bins fill one 128-lane tile
_LO_BINS = 64


def _hist_flush_shape(f: int, b: int, hist_layout: str = "lane"):
    """The flush's contraction, read off the bin stride: (levels ``G``,
    one-hot width per feature, padded feature count, matmul group width
    in features).

    A stride of 64 or less is one level, ``G`` = 1: a feature's one-hot
    spans its stride and the group is _hist_packing's. A stride of 128 or
    256 is contracted in two levels, bin = 64 hi + lo with ``G`` = stride
    / 64 values of hi: the one-hot spans the 64 values of lo, a group is
    the two features of one 128-lane tile, and the feature count pads to
    whole pairs (hist_contract). The sublane arm, whose one-hot streams,
    is one level at any stride."""
    stride, f_pad, group = _hist_packing(f, b)
    if stride <= _LO_BINS or hist_layout == "sublane":
        return 1, stride, f_pad, group
    return stride // _LO_BINS, _LO_BINS, _round_up(f, 2), 2


def hist_levels(f: int, b: int, hist_layout: str = "lane") -> int:
    """Levels of the one-hot the fused kernel's flush contracts for ``f``
    features of ``b`` bins: 2 where a bin is split 64 hi + lo (a stride
    of 128 or 256: more than 64 bins a feature, or a count _hist_packing
    pads to a whole tile), 1 where the one-hot spans the whole stride
    (the ``hist_levels`` counter of an ``iteration`` event)."""
    return 2 if _hist_flush_shape(f, b, hist_layout)[0] > 1 else 1


def _carry_shape(block_size: int, num_cols: int) -> Tuple[int, int]:
    """A ring carry's VMEM scratch: [bs / 4, C] i32 words, four rows'
    bytes a word (module docstring)."""
    return block_size // 4, num_cols


def carry_bytes(block_size: int, num_cols: int) -> int:
    """Bytes of VMEM that one byte of a carried row takes in the fused
    kernel's ring carries, read off the carry's scratch shape: 1 where the
    carries hold packed bytes (the ``carry_bytes`` counter of an
    ``iteration`` event; one byte an i32 word would read 4)."""
    rows, cols = _carry_shape(block_size, num_cols)
    return 4 * rows * cols // (block_size * num_cols)


def _hist_rows(layout: RowLayout) -> int:
    """Byte columns of a row record the histogram reads (the bins and the
    gradient, hessian and count words), rounded up to whole int8 tiles:
    the rows of a block's transposed form."""
    return min(layout.num_cols, _round_up(layout.feat_cols + 12, 32))


def _assemble_f32(rows_ref, t, off: int):
    """4 byte ROWS at static offset ``off`` of slot ``t`` of a transposed
    [K, HR, bs] i32 block ref -> the f32 words as a [1, bs] row.

    Assembles via multiplies, NOT shifts: Mosaic miscompiles `<< 16` on
    values cast from u8 (observed on v5e: some lanes come back zero), while
    integer multiply wraps correctly — byte3 * 2^24 overflowing into the sign
    bit is exactly the bit pattern we want.
    """
    b0, b1, b2, b3 = (rows_ref[t, off + k:off + k + 1, :] for k in range(4))
    w = b0 + b1 * 256 + b2 * 65536 + b3 * 16777216
    return lax.bitcast_convert_type(w, jnp.float32)


def _fused_kernel(sp_ref, bits_ref, work_in, scr_in, work_out, scr_out,
                  hist_ref, sem_in, sem_l, sem_r, sem_aux, inbuf, lcarry,
                  rcarry, lstage, rstage, auxbuf, pendT, pendch, smem, *,
                  layout: RowLayout, num_bins: int, bs: int,
                  bitset_words: int,
                  interpret: bool, dual: bool,
                  hist_debug: str = "", quant: bool = False,
                  mbatch: int = 1, hist_layout: str = "lane"):
    # dual=True: dual residency — rights land LIVE in the other array at the
    #   same offsets (RMW blends protect neighbour segments; auxbuf=[bs,C]
    #   rmw buffer, sem_aux=single DMA sem). The grower merges once per tree.
    # dual=False: copy-back — side must be 0, rights stage through scratch
    #   (garbage there is dead) and a copy-back epilogue blends them into
    #   work (auxbuf=[2,bs,C] staging ring, sem_aux=(2,) DMA sems). This is
    #   the round-3 behavior, kept as a bisect probe and safe fallback.
    F = layout.num_features
    C = layout.num_cols
    B = num_bins
    # G: levels of hi; OW: one-hot width per feature; both off the stride
    G, OW, F_pad, group_w = _hist_flush_shape(F, B, hist_layout)
    packed4 = layout.packed4
    i32 = jnp.int32

    def bin_row(t, j):
        """Bins of LOGICAL feature ``j`` (static) in staged slot ``t`` as a
        [1, bs] i32 row, the block's rows along lanes.

        packed4 records store two features per byte: the byte row
        j >> 1 carries feature j in the nibble selected by j & 1. The
        & 0xF mask is load-bearing — without it the neighbour feature's
        nibble rides along and every one-hot compare mismatches
        (tpulint R004 flags unmasked pack4 nibble extracts)."""
        if packed4:
            byte = pendT[t, j // 2:j // 2 + 1, :]
            return (byte >> (4 * (j % 2))) & 0xF
        return pendT[t, j:j + 1, :]

    mode = sp_ref[_MODE]
    base = sp_ref[_BASE_T] * _A
    phi = sp_ref[_PHI]
    count = sp_ref[_COUNT]
    n_left = sp_ref[_NLEFT]
    feature = sp_ref[_FEAT]
    bin_ = sp_ref[_BIN]
    default_left = sp_ref[_DLEFT]
    nan_bin = sp_ref[_NANBIN]
    is_cat = sp_ref[_ISCAT]
    smaller_left = sp_ref[_SMALLER_L]
    rbase = sp_ref[_RBASE_T] * _A
    psi = sp_ref[_PSI]
    side = sp_ref[_SIDE]

    start = base + phi
    span = phi + count
    nblocks = (span + bs - 1) // bs
    n_rows = work_out.shape[0]          # static padded row count

    def clamp_base(b):
        """Clamp a 32-aligned row base into [0, n_rows - bs], keeping the
        provable alignment Mosaic's DMA checker needs (t * 32 form).
        Defense-in-depth: a split whose scan-side n_left disagrees with the
        kernel's own routing (garbage histograms, or a latent scan bug) must
        corrupt data at worst — never DMA outside the arrays and fault the
        worker."""
        cap_t = (n_rows - bs) // _A
        return jnp.clip(b // _A, 0, cap_t) * _A

    hist_ref[:, :] = jnp.zeros_like(hist_ref)
    smem[_LCNT] = 0
    smem[_RCNT] = psi
    smem[_LF] = 0
    smem[_RF] = 0
    smem[_CBW] = 0
    smem[_PEND] = 0
    lcarry[:, :] = jnp.zeros_like(lcarry)
    rcarry[:, :] = jnp.zeros_like(rcarry)
    auxbuf[...] = jnp.zeros_like(auxbuf)

    iota = lax.broadcasted_iota(i32, (bs, 1), 0)[:, 0]
    io2 = lax.broadcasted_iota(i32, (bs, bs), 0)
    jo2 = lax.broadcasted_iota(i32, (bs, bs), 1)
    # strict upper triangular: ranks via MXU (int8 runs at 2x bf16 rate)
    ut = (io2 < jo2).astype(jnp.int8)
    # rows of a [ROWS, bs] operand that holds one value a block row, along
    # lanes: a whole int8 tile
    ROWS = 32
    lane_t = lax.broadcasted_iota(i32, (ROWS, bs), 1)
    row_t = lax.broadcasted_iota(i32, (ROWS, bs), 0)
    iota2 = lax.broadcasted_iota(i32, (2 * bs, bs), 0)

    # the top bit of each byte of a word
    TOP = jnp.int32(-0x7F7F7F80)

    def flip_offset(bytes8, out_t):
        """byte <-> (byte - 128) on [BS, C] packed bytes: the top bit
        flipped, whichever way."""
        return pltpu.bitcast(pltpu.bitcast(bytes8, i32) ^ TOP, out_t)

    # The u8 buffers seen as words, four consecutive rows a word, as
    # pltpu.bitcast packs them and as their (8,128)(4,1) tiles hold them
    # (measured on the chip: PERF.md section 6). A staged block is stored as
    # words, 12 registers at higgs's rows; a u8 value is first unpacked
    # and packed to the buffer's 8-row tiles and stored a quarter
    # register at a time, 48 stores.
    inbuf_w, auxbuf_w = inbuf.bitcast(i32), auxbuf.bitcast(i32)

    def put_words(ref, slot, words):
        """Store [BS / 4, C] words into slot ``slot`` of a u8 buffer.
        Pallas's interpreter writes through no bitcast view of a ref; its
        value bitcast packs the same bytes in the same order."""
        if interpret:
            ref[slot] = pltpu.bitcast(words, ref.dtype)
        else:
            ref.bitcast(i32)[slot] = words

    # The byte mask of the slots below a count n, on the carry's words. A
    # word's four slots lie in one tile of 32 slots, and a register of
    # words (8 rows) is one such tile: the tiles below n >> 5 are all
    # below, those above it none, and tile n >> 5 holds the r = n & 31
    # slots below n at the offsets o < r. Within a tile each byte's slot
    # offset o is made through the same bitcast as the data, held as
    # 0x7F - o in its byte (one register); with r in every byte, a byte's
    # 0x7F - o + r has its top bit set exactly where o < r (no byte
    # carries into the next: 0x60 + 0 to 0x7F + 31), and that bit times
    # 0xFF is the byte's mask. The partial tile is one register of work,
    # each other tile two compares and two selects against immediates.
    # Built on one lane tile and repeated along the lanes of a wider row.
    BW = _carry_shape(bs, C)[0]
    slot_off = lax.broadcasted_iota(i32, (_A, _LANES), 0)
    off_tile = pltpu.bitcast((0x7F - slot_off).astype(jnp.int8), i32)

    def below(n):
        """[BS / 4, C] words: 0xFF in each byte whose slot is below ``n``
        (a scalar, 0 <= n <= BS), 0 elsewhere."""
        top = ((n & (_A - 1)) * 0x01010101 + off_tile) & TOP
        part = lax.shift_right_logical(top, 7) * 0xFF
        tile = jnp.full(part.shape, n >> 5, i32)
        m = jnp.concatenate(
            [jnp.where(tile > k, -1, jnp.where(tile == k, part, 0))
             for k in range(BW // 8)], axis=0)
        return m if C == _LANES else jnp.concatenate([m] * (C // _LANES),
                                                     axis=1)

    def blend_words(m, a, b):
        """Bytes of ``a`` where the mask ``m`` is set, of ``b`` elsewhere."""
        return b ^ ((a ^ b) & m)

    def start_read(i, slot):
        """Issue the parent-segment block read from its residency array."""
        if not dual:
            pltpu.make_async_copy(
                work_out.at[pl.ds(base + i * bs, bs), :], inbuf.at[slot],
                sem_in.at[slot]).start()
            return

        @pl.when(side == 0)
        def _():
            pltpu.make_async_copy(
                work_out.at[pl.ds(base + i * bs, bs), :], inbuf.at[slot],
                sem_in.at[slot]).start()

        @pl.when(side != 0)
        def _():
            pltpu.make_async_copy(
                scr_out.at[pl.ds(base + i * bs, bs), :], inbuf.at[slot],
                sem_in.at[slot]).start()

    def wait_read(slot):
        # wait is by semaphore + transfer size; the source ref is a stand-in
        pltpu.make_async_copy(
            work_out.at[pl.ds(0, bs), :], inbuf.at[slot],
            sem_in.at[slot]).wait()

    def rmw_read(off):
        """Synchronously fetch one block of the right-destination array
        (dual residency only — the destination may hold live neighbours)."""
        off = clamp_base(off)

        @pl.when(side == 0)
        def _():
            pltpu.make_async_copy(
                scr_out.at[pl.ds(off, bs), :], auxbuf, sem_aux).start()

        @pl.when(side != 0)
        def _():
            pltpu.make_async_copy(
                work_out.at[pl.ds(off, bs), :], auxbuf, sem_aux).start()
        pltpu.make_async_copy(
            work_out.at[pl.ds(0, bs), :], auxbuf, sem_aux).wait()

    # ---------------- histogram half: rows along lanes ----------------
    # (module docstring) a staged block is [HR, bs] i32: a feature's bins
    # are one ROW, which a compare against a sublane iota broadcasts along
    # sublanes (a replicated register, no XLU), and the channel words
    # assemble on [1, bs] rows, bs / 128 registers an operation
    HR = pendT.shape[1]
    lane1 = lax.broadcasted_iota(i32, (1, bs), 1)
    sub8 = lax.broadcasted_iota(i32, (8, bs), 0)
    sub_b = lax.broadcasted_iota(i32, (OW, bs), 0)
    eye_h = (lax.broadcasted_iota(i32, (HR, C), 0)
             == lax.broadcasted_iota(i32, (HR, C), 1)).astype(jnp.int8)
    # quant: int8 one-hot x int8 packed channels -> int32 (exact, 2x MXU
    # rate); f32: bf16 one-hot with f32 accumulation
    cht = jnp.int8 if quant else jnp.bfloat16
    acc_t = jnp.int32 if quant else jnp.float32
    oh_src = jnp.int32 if quant else jnp.float32   # the one-hot, uncast
    one, zero = jnp.ones((), oh_src), jnp.zeros((), oh_src)
    # a staged channel operand (fused_split sizes the ring): cast where it
    # is assembled at one level; at two it stays uncast, f32 / i32, until
    # group_product has stacked it by hi
    ch_staged = pendch.dtype

    def stage_block(t, rows_u8):
        """Transpose a [bs, C] u8 block's first HR byte columns into
        slot ``t`` of the pending ring: eye[HR, C] x (byte - 128)[bs, C]^T
        on int8, the partition's own `pick` form. The block is the MXU's
        transposed weight load (``vmatpush.s8.xpose``), HR streamed rows
        a block; exact (one nonzero a sum, i32 accumulation)."""
        pendT[t] = lax.dot_general(
            eye_h, flip_offset(rows_u8, jnp.int8),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=i32) + 128                   # [HR, bs]

    def assemble_chT(t, mask):
        """Masked rows of staged slot ``t`` -> the [8, bs] channel operand,
        born transposed: every operation runs on [1, bs] rows.

        The operand is cast here (``ch_staged``) where the flush is one
        level; the two-level flush stacks it by ``hi`` first and casts
        the stack, so it is staged as f32 / i32 words holding the same
        values (hist_contract).

        f32 mode (bf16 output): (grad-hi, hess-hi, in-bag, raw, grad-lo,
        hess-lo, 0, 0) — the hi/lo split recovers ~f32 accuracy.
        quant mode (int8 output): the PACKED integer channel layout
        (qgrad, qhess, in-bag, raw, 0, 0, 0, 0) — the grad/hess columns
        hold small integer discretizer codes (exact in f32), so the hi/lo
        split collapses and the one-hot contraction runs
        int8 x int8 -> int32 at 2x the bf16 MXU rate with exact sums."""
        g = _assemble_f32(pendT, t, layout.grad_off) * mask
        h = _assemble_f32(pendT, t, layout.hess_off) * mask
        cw = _assemble_f32(pendT, t, layout.cnt_off)
        inbag = jnp.where(cw != 0.0, mask, 0.0)
        if quant:
            chans = [g, h, inbag, mask]
        else:
            if interpret:
                # interpret mode traces through XLA, where
                # --xla_allow_excess_precision elides f32->bf16->f32 as
                # identity (zeroing the lo channels); reduce_precision is
                # not elidable
                ghi, hhi = (lax.reduce_precision(x, exponent_bits=8,
                                                 mantissa_bits=7)
                            for x in (g, h))
            else:
                # Mosaic has no reduce_precision lowering and does not
                # elide the round-trip today (verified on v5e)
                ghi, hhi = (x.astype(jnp.bfloat16).astype(jnp.float32)
                            for x in (g, h))
            chans = [ghi, hhi, inbag, mask, g - ghi, h - hhi]
        ch = jnp.zeros((8, bs), jnp.float32)
        for k, c in enumerate(chans):
            ch = jnp.where(sub8 == k, c, ch)
        if quant:
            # f32 -> int8 is exact: codes are integers with |code| <= 127
            return ch.astype(i32).astype(ch_staged)
        return ch.astype(ch_staged)

    def group_product(chT, *rows):
        """Partial sums of one matmul group of features over one staged
        block (hist_contract): ``chT`` its [8, bs] channel operand as
        staged, ``rows`` the features' [1, bs] bin rows (None: a pad
        feature). [8 G, wc*OW]; bin-major [wc*OW, 8] on the sublane arm."""
        ohT = jnp.concatenate(
            [jnp.zeros((OW, bs), oh_src) if b is None else
             jnp.where(sub_b == (b if G == 1 else b & (OW - 1)), one, zero)
             for b in rows], axis=0).astype(cht)               # [wc*OW, bs]
        if G > 1:
            # row r of a feature's [8 G, bs] streamed rows holds the
            # channels of the block's rows whose hi is r // 8
            hi_of_row = lax.broadcasted_iota(i32, (8 * G, bs), 0) >> 3
            chG = jnp.concatenate([chT] * G, axis=0)
            none = jnp.zeros_like(chG)
            chT = jnp.concatenate(
                [none if b is None else
                 jnp.where(hi_of_row == (b >> (OW.bit_length() - 1)),
                           chG, none) for b in rows],
                axis=0).astype(cht)                            # [16 G, bs]
        lhs, rhs = (ohT, chT) if hist_layout == "sublane" else (chT, ohT)
        part = lax.dot_general(
            lhs, rhs, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=acc_t)
        if G > 1:
            # the pair's diagonal blocks: feature 0's rows on lanes
            # 0-63, feature 1's on 64-127
            first = lax.broadcasted_iota(i32, (8 * G, 2 * OW), 1) < OW
            part = jnp.where(first, part[:8 * G], part[8 * G:])
        return part

    if G > 1:
        # traced once a kernel and inlined at every pair of every flush
        # (Pallas lowers a jit call in place): the two-level body is 2.5
        # times the one-level body's equations, and the step's trace and
        # lowering are in every run's set-up (PERF.md section 6, PR 38)
        group_product = jax.jit(group_product)

    def hist_contract(slots):
        """One-hot contraction of staged blocks against their channel
        operands, accumulated into hist_ref. ``slots``: (ring slot,
        [8, bs] channel operand) pairs. group_product builds the two
        operands of one group of features and one block and multiplies
        them; this is the loop over groups and blocks, and what it does.

        A feature's one-hot is born transposed, [OW, bs]: its bin row
        against a sublane iota, one compare a register and no lane
        broadcast. A group's features concatenate along sublanes (aligned
        at every stride _hist_packing produces; grouping bounds the
        operand near 512 bins) and the group is cast ONCE, with the
        contraction its only reader: Mosaic then packs it straight into
        the bf16 tile, and the v5e compiler folds compare, select and
        pack into a masked transposed weight load (``vmpackc`` +
        ``vmatpush.bf16.xpose.msk``). Cast a feature at a time, as through
        PR 32, the pieces take the f32 tiling and are unpacked and packed
        again on their way to the MXU: 36% of the old flush's VALU work.
        The one-hot is the operand the MXU LOADS, as its weights, and the
        channels are the rows that STREAM through them. What a histogram
        row costs is the push count: one-hot rows x bs / 2,048 transposed
        pushes a block (bf16), which the chip takes at 0.52 a cycle
        whatever the compiler schedules.

        One level (``G`` = 1: a stride of 64 bins or less): the one-hot
        spans the feature's stride and the [8, bs] channel operand streams
        as it was staged, the same for every feature: F x stride x bs /
        2,048 pushes a block (1.7 ns a row at 28 x 64 bins).

        Two levels (a stride of 128 or 256: ``G`` = 2 or 4): bin = 64 hi
        + lo, and H[c, 64 hi + lo] = sum over rows of (ch[c] . [hi_row =
        hi]) x [lo_row = lo]. Only the 64-wide one-hot of ``lo`` is
        loaded, two features a 128-lane tile: F x 64 x bs / 2,048 pushes,
        a quarter of the one-level count at 256 bins (through PR 37 every
        feature loaded all its 256 bins for one 8-row matmul a tile: 5.2
        ns a row on higgs, 37.5 at 220 features). The streamed operand
        takes the other level: a feature's rows are the channels where
        the row's ``hi`` is k and 0 elsewhere, k = 0..G-1 stacked to
        [8 G, bs] (one select against the staged operand tiled G times),
        the pair's two features stacked again to [16 G, bs] and cast
        ONCE, like the weights. The product is [16 G, 128]; its two
        diagonal blocks (feature 0's rows on lanes 0-63, feature 1's on
        64-127) are the pair's histograms and one select keeps them, so
        the accumulator is [8 G, F_pad x 64], row 8 k + c holding channel
        c of the bins 64 k .. 64 k + 63 (fused_split undoes it). Every
        nonzero term of every sum is the one-level form's ``ch x 1`` at
        the same row, the rest exact zeros.

        Each block's partial sums fold before the one add into the
        accumulator, so a flush of K blocks rounds as one (module
        docstring).

        hist_layout="sublane" (tpu_hist_layout, B <= 64) swaps the roles
        of the same two operands: the one-hot streams and the channels
        are the weights, so the output lands BIN-major [group, 8]."""
        fc = 0
        while fc < F_pad:
            wc = min(group_w, F_pad - fc)
            red = None
            for t, chT in slots:
                part = group_product(
                    chT, *(bin_row(t, f) if f < F else None
                           for f in range(fc, fc + wc)))
                red = part if red is None else red + part
            if hist_layout == "sublane":
                hist_ref[fc * OW:(fc + wc) * OW, :] += red     # [wc*OW, 8]
            else:
                hist_ref[:, fc * OW:(fc + wc) * OW] += red     # [8G, wc*OW]
            fc += wc

    def hist_flush(n_valid):
        """Contract the first ``n_valid`` staged blocks of the pending
        ring. Slots past ``n_valid`` (a partial drain, or stale data from
        a previous ring wrap) are zeroed on the channel side, so whatever
        their bins one-hot into contributes exactly zero — counts stay
        bit-identical to the K=1 sync path and int32 quantized sums stay
        exact."""
        staged = [pendch[t] for t in range(mbatch)]
        hist_contract([(t, jnp.where(n_valid > t, ch, jnp.zeros_like(ch)))
                       for t, ch in enumerate(staged)])

    def hist_accum(rows_u8, mask):
        """Histogram push: the block is transposed into the pending ring
        and its channel operand assembled NOW; the one-hot contractions
        issue once per K pushes (hist_flush), each block's partial sums
        folded before they meet the accumulator. ``mask``: [1, bs] f32,
        the block's rows that count."""
        if hist_debug == "off":
            return  # timing bisect: histograms disabled (results invalid)
        if hist_debug:
            # timing bisect probes: the pre-batching behavior, slot 0 only
            stage_block(0, rows_u8)
            if hist_debug == "assembly":
                hist_ref[0:8, 0:128] += lax.dot_general(
                    assemble_chT(0, mask).astype(jnp.bfloat16),
                    jnp.ones((128, bs), jnp.bfloat16),
                    dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
            elif hist_debug == "matmul":
                hist_contract([(0, jnp.ones((8, bs), ch_staged))])
            elif hist_debug == "matmul2":
                # data-dependent but trivially cheap channels: defeats
                # constant folding/hoisting so the matmuls' true cost is
                # measured
                hist_contract([(0, (pendT[0, 0:8, :] + 1)
                                .astype(ch_staged))])
            else:   # "sync"
                hist_contract([(0, assemble_chT(0, mask))])
            return

        pushes = smem[_PEND]
        cur = lax.rem(pushes, mbatch)
        stage_block(cur, rows_u8)
        pendch[cur] = assemble_chT(cur, mask)
        smem[_PEND] = pushes + 1

        @pl.when(cur == mbatch - 1)
        def _():
            hist_flush(jnp.asarray(mbatch, i32))

    def hist_drain():
        """Flush the partial pending batch (end of kernel): exactly the
        ``pushes % mbatch`` blocks staged since the last full-ring flush."""
        pushes = smem[_PEND]
        pending = lax.rem(pushes, mbatch)

        @pl.when(pending > 0)
        def _():
            hist_flush(pending)
            smem[_PEND] = pushes - pending

    def stage_flush(stream, data_w, hbm_base, do_hist, h0, n_valid=bs,
                    head_rows=None):
        """Write one full block, ``data_w`` its bytes as [BS / 4, C]
        words, via the stream's staging ring; maybe hist (of the block's
        rows [h0, n_valid)). ``head_rows`` (dual residency, the right
        stream's first block): so many leading rows belong to a segment
        that may be live in the destination array, and are read from there
        and written back as they were."""
        stage, sem, cslot = ((lstage, sem_l, _LF) if stream == 0
                             else (rstage, sem_r, _RF))
        # left stream writes the parent's residency array, right the other
        to_work = (side == 0) if stream == 0 else (side != 0)
        cnt = smem[cslot]
        slot = lax.rem(cnt, 2)

        @pl.when(cnt >= 2)
        def _():
            pltpu.make_async_copy(
                stage.at[slot], work_out.at[pl.ds(0, bs), :],
                sem.at[slot]).wait()

        put_words(stage, slot, data_w)
        if head_rows is not None:
            @pl.when(head_rows > 0)
            def _():
                rmw_read(hbm_base)
                put_words(stage, slot, blend_words(
                    below(head_rows), auxbuf_w[:, :],
                    stage.bitcast(i32)[slot]))
        hbm_base = clamp_base(hbm_base)

        @pl.when(to_work)
        def _():
            pltpu.make_async_copy(
                stage.at[slot], work_out.at[pl.ds(hbm_base, bs), :],
                sem.at[slot]).start()

        @pl.when(jnp.logical_not(to_work))
        def _():
            pltpu.make_async_copy(
                stage.at[slot], scr_out.at[pl.ds(hbm_base, bs), :],
                sem.at[slot]).start()

        @pl.when(do_hist)
        def _():
            hist_accum(stage[slot], jnp.logical_and(
                lane1 >= h0, lane1 < n_valid).astype(jnp.float32))
        smem[cslot] = cnt + 1

    def drain(stream):
        stage, sem, cslot = ((lstage, sem_l, _LF) if stream == 0
                             else (rstage, sem_r, _RF))
        cnt = smem[cslot]
        for back in (2, 1):
            @pl.when(cnt >= back)
            def _():
                slot = lax.rem(cnt - back, 2)
                pltpu.make_async_copy(
                    stage.at[slot], work_out.at[pl.ds(0, bs), :],
                    sem.at[slot]).wait()

    # ---------------- main walk ----------------
    @pl.when(nblocks > 0)
    def _():
        start_read(0, 0)

    def body(i, _):
        slot = lax.rem(i, 2)

        @pl.when(i + 1 < nblocks)
        def _():
            start_read(i + 1, lax.rem(i + 1, 2))

        wait_read(slot)
        blk_u8 = inbuf[slot]

        @pl.when(mode == 1)
        def _():
            g_idx = base + i * bs + lane1
            in_seg = jnp.logical_and(g_idx >= start, g_idx < start + count)
            hist_accum(blk_u8, in_seg.astype(jnp.float32))

        @pl.when(mode == 0)
        def _():
            # bytes ride the MXU as (b - 128) int8, made on the packed
            # bytes as they arrive
            blk8 = flip_offset(blk_u8, jnp.int8)
            # Everything a row decides (its bin, side, rank and slot) is
            # held with the block's rows along LANES, [ROWS, bs]: bs / 128
            # registers an operation. One row a sublane, as a lane
            # reduction leaves it, is bs / 8 registers an operation, and
            # the routing chain is some forty operations long. So the
            # routing column is transposed by the matmul that picks it:
            fcol = (feature >> 1) if packed4 else feature
            pick = (lax.broadcasted_iota(i32, (ROWS, C), 1)
                    == fcol).astype(jnp.int8)
            col = lax.dot_general(
                pick, blk8, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=i32) + 128               # [R, BS]
            if packed4:
                # two features per byte: the nibble of the byte column
                # (the & 0xF mask strips the neighbour feature)
                col = (col >> ((feature & 1) * 4)) & 0xF
            g_idx = base + i * bs + lane_t
            in_seg = jnp.logical_and(g_idx >= start, g_idx < start + count)
            head = g_idx < start
            # routing predicate — mirrors ops/split.py go_left_pred
            gl_num = jnp.logical_or(
                col <= bin_,
                jnp.logical_and(default_left != 0, col == nan_bin))
            word = col >> 5
            bw = jnp.zeros_like(col)
            for wd in range(bitset_words):
                bw = jnp.where(word == wd, bits_ref[wd].astype(i32), bw)
            gl_cat = ((bw >> (col & 31)) & 1) != 0
            # no select on i1 vectors in Mosaic — combine logically
            gl = jnp.logical_or(jnp.logical_and(is_cat != 0, gl_cat),
                                jnp.logical_and(is_cat == 0, gl_num))
            sel_l = jnp.logical_or(jnp.logical_and(gl, in_seg), head)
            sel_r = jnp.logical_and(jnp.logical_not(gl), in_seg)

            # ranks via the MXU: row 0 counts the lefts ahead of each row,
            # row 1 the rights
            sel2 = jnp.where(row_t == 0, sel_l.astype(i32),
                             jnp.where(row_t == 1, sel_r.astype(i32), 0))
            ranks = lax.dot_general(
                sel2.astype(jnp.int8), ut,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=i32)                     # [R, BS]
            sel_l, sel_r = sel_l[0:1], sel_r[0:1]
            rank_l = ranks[0:1]
            rank_r = ranks[1:2]
            nl_b = jnp.sum(sel_l.astype(i32))
            nr_b = jnp.sum(sel_r.astype(i32))

            # each stream's carry is a ring of bs slots: a selected row
            # lands at (cnt + rank) mod bs, the right stream's ring bs
            # slots below the left's in the one permutation
            lcnt = smem[_LCNT]
            rcnt = smem[_RCNT]
            dl = lcnt + rank_l
            dr = rcnt + rank_r
            dest = jnp.where(
                sel_l, jnp.where(dl >= bs, dl - bs, dl),
                jnp.where(sel_r, jnp.where(dr >= bs, dr, dr + bs), 2 * bs))
            oh = (iota2 == dest)                                # [2BS, BS] i1
            comp = lax.dot_general(
                oh.astype(jnp.int8), blk8,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=i32)                     # [2BS, C]
            # the carries' packed form, made once as the product leaves
            # the MXU: the left ring's words, then the right's
            compw = pltpu.bitcast(comp.astype(jnp.int8), i32)  # [2BS/4, C]
            comp_l = compw[:BW]
            comp_r = compw[BW:]
            new_l = lcnt + nl_b
            new_r = rcnt + nr_b

            def ring_flush(carry, comp_s, cnt):
                """The block a full ring flushes, in carry form: the carry's
                rows below ``cnt``, then the block's first bs - cnt. The
                block's later rows wrapped to slots below ``cnt``, already
                in place: they are the new carry. The carry is zero from
                ``cnt`` up and the block between its wrapped rows and
                ``cnt``, so one mask splits both. Flipped to bytes
                (``^ TOP``), a slot that received no row reads 128 and not
                0: every such slot is one a caller blends away or that
                lands in dead bytes (the right stream's psi head slots, a
                tail's rows past its count)."""
                keep = comp_s & below(cnt)
                full = carry[:, :] | (comp_s ^ keep)
                carry[:, :] = keep
                return full

            @pl.when(new_l < bs)
            def _():
                # the slots written were zero (rows at and above cnt are)
                lcarry[:, :] = lcarry[:, :] | comp_l

            @pl.when(new_l >= bs)
            def _():
                lf = smem[_LF]
                h0 = jnp.where(lf == 0, phi, 0)
                full = ring_flush(lcarry, comp_l, lcnt)
                stage_flush(0, full ^ TOP, base + lf * bs,
                            smaller_left == 1, h0)
            smem[_LCNT] = new_l - bs * (new_l >= bs).astype(i32)

            @pl.when(new_r < bs)
            def _():
                rcarry[:, :] = rcarry[:, :] | comp_r

            @pl.when(new_r >= bs)
            def _():
                rf = smem[_RF]
                h0 = jnp.where(rf == 0, psi, 0)
                full = ring_flush(rcarry, comp_r, rcnt)
                # the psi pre-rows: an RMW blend under dual residency; in
                # copy-back mode they land in dead scratch bytes
                stage_flush(1, full ^ TOP, rbase + rf * bs,
                            smaller_left == 0, h0,
                            head_rows=h0 if dual else None)
            smem[_RCNT] = new_r - bs * (new_r >= bs).astype(i32)
        return 0

    lax.fori_loop(0, nblocks, body, 0)

    # ---------------- tails ----------------
    @pl.when(jnp.logical_and(mode == 0, count > 0))
    def _():
        lcnt = smem[_LCNT]
        rcnt = smem[_RCNT]

        @pl.when(lcnt > 0)
        def _():
            lf = smem[_LF]
            # RMW blend: rows beyond lcnt may belong to a live neighbour
            # (read from the parent's own residency array — lefts stay there)
            start_read_at = base + lf * bs
            if not dual:
                pltpu.make_async_copy(
                    work_out.at[pl.ds(start_read_at, bs), :], inbuf.at[0],
                    sem_in.at[0]).start()
            else:
                @pl.when(side == 0)
                def _():
                    pltpu.make_async_copy(
                        work_out.at[pl.ds(start_read_at, bs), :],
                        inbuf.at[0], sem_in.at[0]).start()

                @pl.when(side != 0)
                def _():
                    pltpu.make_async_copy(
                        scr_out.at[pl.ds(start_read_at, bs), :],
                        inbuf.at[0], sem_in.at[0]).start()
            wait_read(0)
            blend = blend_words(below(lcnt), lcarry[:, :] ^ TOP,
                                inbuf_w[0])
            h0 = jnp.where(lf == 0, phi, 0)
            stage_flush(0, blend, base + lf * bs, smaller_left == 1, h0,
                        lcnt)

        @pl.when(rcnt > 0)
        def _():
            rf = smem[_RF]
            h0 = jnp.where(rf == 0, psi, 0)
            data = rcarry[:, :] ^ TOP
            if dual:
                # RMW blend against the destination array: the psi head rows
                # (rf == 0) and everything beyond rcnt may be live neighbours
                rmw_read(rbase + rf * bs)
                data = blend_words(below(rcnt) & ~below(h0), data,
                                   auxbuf_w[:, :])
            # (copy-back mode: full-block write, overrun lands in dead
            # scratch bytes)
            stage_flush(1, data, rbase + rf * bs, smaller_left == 0, h0,
                        rcnt)

        drain(0)
        drain(1)

        if not dual:
            # ------------- copy-back of the right stream -------------
            # blend the scratch-staged right rows into work over the exact
            # row range; neighbours resident in work survive bit-for-bit
            n_right_cb = count - n_left
            nb_cb = (psi + n_right_cb + bs - 1) // bs

            def cb_body(t, _):
                win = clamp_base(rbase + t * bs)
                d1 = pltpu.make_async_copy(
                    scr_out.at[pl.ds(win, bs), :], inbuf.at[0], sem_in.at[0])
                d2 = pltpu.make_async_copy(
                    work_out.at[pl.ds(win, bs), :], inbuf.at[1], sem_in.at[1])
                d1.start()
                d2.start()
                d1.wait()
                d2.wait()
                g = win + iota
                keep = jnp.logical_and(g >= start + n_left,
                                       g < start + count)
                out = jnp.where(keep[:, None], inbuf[0].astype(i32),
                                inbuf[1].astype(i32)).astype(jnp.uint8)
                cw = smem[_CBW]
                slot = lax.rem(cw, 2)

                @pl.when(cw >= 2)
                def _():
                    pltpu.make_async_copy(
                        auxbuf.at[slot], work_out.at[pl.ds(0, bs), :],
                        sem_aux.at[slot]).wait()
                auxbuf[slot] = out
                pltpu.make_async_copy(
                    auxbuf.at[slot], work_out.at[pl.ds(win, bs), :],
                    sem_aux.at[slot]).start()
                smem[_CBW] = cw + 1
                return 0

            lax.fori_loop(0, nb_cb, cb_body, 0)
            cw = smem[_CBW]
            for back in (2, 1):
                @pl.when(cw >= back)
                def _():
                    pltpu.make_async_copy(
                        auxbuf.at[lax.rem(cw - back, 2)],
                        work_out.at[pl.ds(0, bs), :],
                        sem_aux.at[lax.rem(cw - back, 2)]).wait()

    # deferred histogram block from the software pipeline (both modes)
    hist_drain()


@functools.partial(
    jax.jit,
    static_argnames=("layout", "num_bins", "block_size", "bitset_words",
                     "interpret", "dual", "hist_debug", "num_rows", "quant",
                     "mbatch", "hist_layout", "name"))
def fused_split(
    work: jnp.ndarray,          # [N + pad, C] u8, C % 128 == 0
    scratch: jnp.ndarray,       # [N + pad, C] u8
    mode: jnp.ndarray,          # i32: 0 = partition+hist, 1 = hist-only
    start: jnp.ndarray,         # i32 segment start
    count: jnp.ndarray,         # i32 segment rows
    n_left: jnp.ndarray,        # i32 exact left-row count (from the scan)
    feature: jnp.ndarray,
    bin_: jnp.ndarray,
    default_left: jnp.ndarray,  # bool/i32
    nan_bin: jnp.ndarray,
    is_cat: jnp.ndarray,        # bool/i32
    cat_bitset: jnp.ndarray,    # [W] u32
    layout: RowLayout,
    num_bins: int,
    block_size: int = 512,
    bitset_words: int = 8,
    interpret: bool = False,
    smaller_left=None,
    side=None,                  # i32: 0 = parent lives in work, 1 = scratch
    dual: bool = True,
    hist_debug: str = "",       # timing bisect only (see GrowerParams)
    num_rows: int = None,       # real (unpadded) row count, for pad checks
    quant: bool = False,        # packed int8 channel layout -> int32 hist
    mbatch: int = 8,            # batched-M pending-ring depth (1-16)
    hist_layout: str = "lane",  # lane | sublane (tpu_hist_layout, B <= 64)
    name: str = "fused_split",  # the kernel's name in HLO and in a profile
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One fused split. Returns (work', scratch', hist_smaller [F, B, 4]);
    the histogram is int32 when ``quant`` (quantized-gradient codes,
    int8 x int8 -> int32 contraction — see assemble_chT). The kernel's
    own accumulator is [8 G, F_pad x OW] (_hist_flush_shape: G levels of
    hi where a feature's stride is over 64 bins); it is undone here, in
    the one fusion after the call, and no caller sees it.

    ``mbatch`` (env/param ``tpu_hist_mbatch``) is the depth of the
    histogram pending ring: K staged row blocks are contracted together,
    their partial sums folded before the one addition into the f32
    accumulator (hist_flush). K = 1 is the reference path; the engine
    registry hands a fused entry K = 2 by default: on the chip every
    deeper ring measured slower, K = 8 by a factor of ten, and K = 2 buys
    half the roundings for 0.7% (module docstring). Counts and int32
    histograms are bit-identical at any K; bf16 grad/hess within ~2^-17
    relative — the f32 accumulation regroups. The ring multiplies
    histogram-side VMEM residency by K, so callers must size
    ``block_size`` through :func:`fused_block_cap`; a block of whole lane
    tiles (a multiple of 128 rows) keeps the one-hot on the masked weight
    load, any other multiple of 32 is correct and slower.
    The signature's default of 8 is the standalone engines' and is what
    a direct caller gets; the grower always passes the resolved depth.

    CONTRACT — pad >= block_size: the row arrays must be padded past the
    real row count by at least ``block_size`` rows (internal callers pad by
    ``fused_block + 32``, boosting/gbdt._setup_compact_state), because the
    kernel's aligned block writes may overrun a segment end by up to one
    block. The scalar sanitization below clamps ``count`` to
    ``n_rows - block_size - start`` as defense-in-depth; with a smaller pad
    that clamp would silently drop legitimate tail rows. Pass ``num_rows``
    (the real row count, a static int) to turn a violated pad contract into
    a static ValueError instead of silent row loss.

    In mode 1 the partition is skipped and the histogram covers the whole
    segment (hist channels: grad, hess, in-bag count, raw count).

    ``name`` names the Mosaic call: a device profile lists each call as
    ``<name>.<n>``. The compact grower gives its two call sites names of
    their own (``fused_split_root``: the hist-only call on all rows,
    ``fused_split_step``: one call a split), so a trace reader tells
    them apart by name and not by XLA's numbering; keep the
    ``fused_split`` prefix, which the benchmark's kernel metrics match.

    ``smaller_left`` overrides which side's histogram is accumulated —
    the data-parallel learner must histogram the GLOBALLY smaller child on
    every shard even where it is locally the larger one.

    ``side`` selects the parent's residency array (dual residency, see the
    module docstring): the left child stays there, the right child lands in
    the other array at the same global offsets.

    ``dual=False`` selects the copy-back variant: every segment lives in
    ``work`` (side must be 0), rights stage through scratch and a copy-back
    epilogue re-streams them into work. ~1/3 more DMA per split, but no RMW
    blends and no side-dependent DMA — the round-3 design, kept as a safe
    fallback while the dual-residency fault on EFB-bundled deep trees is
    open (see boosting/gbdt._setup_compact_state).
    """
    F = layout.num_features
    C = layout.num_cols
    if C % 128:
        raise ValueError(f"fused_split needs 128-aligned row records, C={C}")
    if block_size % _A:
        raise ValueError(f"block_size must be a multiple of {_A}")
    B = num_bins
    G, OW, F_pad, _ = _hist_flush_shape(F, B, hist_layout)
    i32 = jnp.int32

    n_rows = work.shape[0]
    if num_rows is not None:
        pad_rows = n_rows - int(num_rows)
        if pad_rows < block_size:
            raise ValueError(
                f"fused_split pad contract violated: work has {n_rows} rows "
                f"for num_rows={int(num_rows)} real rows (pad={pad_rows}), "
                f"but block_size={block_size} requires pad >= block_size — "
                "the defense-in-depth count clamp would silently drop tail "
                "rows. Pad the row arrays by at least block_size (internal "
                "callers use fused_block + 32).")
    # scalar sanitization (defense-in-depth, no effect on legit inputs):
    # bounds the kernel's block-loop trip counts and read windows even if a
    # caller hands a segment produced from corrupt histograms
    start = jnp.clip(start.astype(i32), 0, n_rows - _A)
    count = jnp.clip(count.astype(i32), 0,
                     jnp.maximum(n_rows - block_size - start, 0))
    n_left = jnp.clip(n_left.astype(i32), 0, count)
    n_left_eff = jnp.where(mode == 1, count, n_left)
    base_t = start // _A
    phi = start - base_t * _A
    rstart = start + n_left_eff
    rbase_t = rstart // _A
    psi = rstart - rbase_t * _A
    n_right = count - n_left_eff
    if smaller_left is None:
        smaller_left = (n_left_eff <= n_right).astype(i32)
    smaller_left = jnp.where(mode == 1, jnp.asarray(1, i32),
                             smaller_left.astype(i32))
    if side is None:
        side = jnp.asarray(0, i32)
    if not dual:
        # the copy-back variant's invariant is that every segment lives in
        # work; enforce it here rather than trusting distant callers
        side = jnp.zeros_like(jnp.asarray(side, i32))
    sp = jnp.stack([
        mode.astype(i32), base_t, phi, count, n_left_eff,
        feature.astype(i32), bin_.astype(i32), default_left.astype(i32),
        nan_bin.astype(i32), is_cat.astype(i32), smaller_left, rbase_t, psi,
        side.astype(i32), jnp.asarray(0, i32), jnp.asarray(0, i32)])

    bs = block_size
    W = bitset_words
    if quant:
        hist_debug = ""     # bisect probes assume the bf16 channel layout
    if hist_layout not in ("lane", "sublane"):
        raise ValueError(f"hist_layout must be 'lane' or 'sublane', "
                         f"got {hist_layout!r}")
    if hist_layout == "sublane":
        hist_debug = ""     # bisect probes assume the lane accumulator
    mbatch = max(1, min(int(mbatch), 16))   # 8*mbatch <= 128 MXU rows
    hist_t = jnp.int32 if quant else jnp.float32
    # the staged channel operand: cast as assembled at one level, kept
    # uncast until the flush has stacked it by hi at two (hist_contract)
    if G == 1:
        ch_t = jnp.int8 if quant else jnp.bfloat16
    else:
        ch_t = hist_t
    kernel = functools.partial(
        _fused_kernel, layout=layout, num_bins=B, bs=bs, bitset_words=W,
        interpret=interpret, dual=dual,
        hist_debug=hist_debug, quant=quant, mbatch=mbatch,
        hist_layout=hist_layout)

    work_o, scr_o, hist8 = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec(memory_space=pl.ANY),
                       pl.BlockSpec(memory_space=pl.ANY),
                       pl.BlockSpec(memory_space=pltpu.VMEM)],
            scratch_shapes=[
                pltpu.SemaphoreType.DMA((2,)),      # sem_in
                pltpu.SemaphoreType.DMA((2,)),      # sem_l
                pltpu.SemaphoreType.DMA((2,)),      # sem_r
                # dual: single rmw sem + [bs, C] rmw buffer;
                # copy-back: (2,) staging sems + [2, bs, C] staging ring
                (pltpu.SemaphoreType.DMA if dual
                 else pltpu.SemaphoreType.DMA((2,))),       # sem_aux
                pltpu.VMEM((2, bs, C), jnp.uint8),  # inbuf
                pltpu.VMEM(_carry_shape(bs, C), jnp.int32),  # lcarry
                pltpu.VMEM(_carry_shape(bs, C), jnp.int32),  # rcarry
                pltpu.VMEM((2, bs, C), jnp.uint8),  # lstage
                pltpu.VMEM((2, bs, C), jnp.uint8),  # rstage
                (pltpu.VMEM((bs, C), jnp.uint8) if dual
                 else pltpu.VMEM((2, bs, C), jnp.uint8)),   # auxbuf
                # pending ring: K staged blocks, TRANSPOSED (the byte
                # columns the histogram reads as [HR, bs] i32 rows), and
                # their [8, bs] channel operands (hist_accum), cast
                # already where the flush is one level
                pltpu.VMEM((mbatch, _hist_rows(layout), bs),
                           jnp.int32),                    # pendT
                pltpu.VMEM((mbatch, 8, bs), ch_t),        # pendch
                pltpu.SMEM((8,), jnp.int32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(work.shape, work.dtype),
            jax.ShapeDtypeStruct(scratch.shape, scratch.dtype),
            (jax.ShapeDtypeStruct((F_pad * OW, 8), hist_t)
             if hist_layout == "sublane"
             else jax.ShapeDtypeStruct((8 * G, F_pad * OW), hist_t)),
        ],
        input_output_aliases={2: 0, 3: 1},
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
        interpret=interpret,
        name=name,
    )(sp, cat_bitset, work, scratch)

    if hist_layout == "sublane":
        # bin-major accumulator: [F*OW, 8] -> [F, B, 4] with no transpose
        hb = hist8.reshape(F_pad, OW, 8)[:F, :B, :]
        hist = hb[:, :, :4] + hb[:, :, 4:]
    else:
        # row 8 k + c, lane 64 f + lo (hist_contract) -> [8, F, bins]: the
        # G levels side by side along the bins, as a concatenate and not a
        # transpose of the [G, 8, F, OW] view. XLA lays a transposed view
        # out as a bitcast and hands the odd layout on: under shard_map the
        # grower's [leaves, F, B x 4] histogram pool took it and was copied
        # whole at every split, 0.21 s an iteration on four chips (PERF.md
        # section 6, PR 38). From [8, F, B] on this is the one-level code
        hist8 = jnp.concatenate(
            [hist8[8 * k:8 * (k + 1)].reshape(8, F_pad, OW)
             for k in range(G)], axis=2)[:, :F, :B]        # [8, F, B]
        hist = jnp.transpose(hist8[:4] + hist8[4:], (1, 2, 0))  # [F, B, 4]
    return work_o, scr_o, hist
