"""Compacted (physically partitioned) row storage for the serial tree learner.

TPU-native re-design of the reference's DataPartition
(reference: src/treelearner/data_partition.hpp Split — per-thread stable
partition of leaf row indices; CUDA variant
src/treelearner/cuda/cuda_data_partition.cu:288 GenDataToLeftBitVectorKernel +
:679 AggregateBlockOffsetKernel + :907 SplitInnerKernel — bitvector, prefix
sums, stable scatter).

The reference keeps an index permutation and gathers rows through it. On TPU,
random gathers/scatters run ~100x slower than streaming (measured ~0.05-0.1
Gelem/s vs 800 GB/s streams on v5e), so this module keeps the *rows
themselves* physically partitioned instead: every leaf owns a contiguous
segment of a packed row-record array, and each split streams the parent's
segment once, stably partitioning it in place. All data movement is
contiguous DMA (dynamic_slice / dynamic_update_slice), prefix sums, and
one-hot MXU matmuls — no gather/scatter anywhere.

Row records pack into a single uint8 matrix ``[N, C]``:

    [0, F)          binned features (uint8)
    [F, F+4)        grad   (f32 bytes, pre-multiplied by the sample weight)
    [F+4, F+8)      hess   (f32 bytes, pre-multiplied by the sample weight)
    [F+8, F+12)     sample weight (f32 bytes: 0 = out of bag, GOSS rows carry
                    their amplification — persists across trees so a bag
                    drawn in one row order stays the same *set of rows* after
                    later permutations, like the reference's bag_data_indices)
    [F+12, ..+4E)   E extra 4-byte columns carried through the permutation
                    (scores, label, weight — anything that must stay
                    row-aligned across trees); the trainer's last one is
                    the row's original index, an int32: its bytes are
                    never read as an f32 (a small integer's bits are an
                    f32 denormal, which the TPU flushes to zero)

f32 fields move through the one-hot compaction matmul as 4 exact uint8
columns (bf16 represents 0..255 exactly; each output row receives exactly one
input row, so the contraction is exact).

In-block stable compaction is a one-hot permutation matmul: rows' destination
slots are ranks from a prefix sum over the predicate, applied on the MXU.
Cross-block stitching uses double-width carry buffers flushed in full blocks
at dynamic offsets; right-child rows stream to a scratch array at their final
offsets and are copied back after the walk (in-place forward writes of the
right stream could overtake the read cursor).
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .split import go_left_pred


class RowLayout(NamedTuple):
    """Static description of the packed row record (part of the jit key).

    ``packed4``: the bin columns are NIBBLE-packed — two features per byte
    (low nibble = even feature, high nibble = odd, the io/dataset.py
    pack4_matrix layout; reference: the 4-bit dense bin store,
    src/io/dense_bin.hpp DenseBin<true>). ``num_features`` stays the
    LOGICAL feature count; ``feat_cols`` is the stored byte width. Every
    consumer extracts nibbles with ``(byte >> 4*(f & 1)) & 0xF`` at its
    read site, so the full-width matrix never materializes and the
    streamed bin bytes halve (tpu_bin_pack4 training)."""
    num_features: int
    num_extra: int          # number of carried f32 columns (scores/label/...)
    packed4: bool = False   # bin columns nibble-packed (two features/byte)

    @property
    def feat_cols(self) -> int:
        """Stored bin byte columns (ceil(F/2) when nibble-packed)."""
        if self.packed4:
            return (self.num_features + 1) // 2
        return self.num_features

    @property
    def grad_off(self) -> int:
        return self.feat_cols

    @property
    def hess_off(self) -> int:
        return self.feat_cols + 4

    @property
    def cnt_off(self) -> int:
        return self.feat_cols + 8

    @property
    def extra_off(self) -> int:
        return self.feat_cols + 12

    @property
    def num_real_cols(self) -> int:
        """Columns carrying actual record bytes (rest is lane padding)."""
        return self.feat_cols + 12 + 4 * self.num_extra

    @property
    def num_cols(self) -> int:
        c = self.num_real_cols
        # round lanes up to the full 128-lane tile: TPU HBM layouts pad the
        # minor dimension to 128 anyway (tiled storage), so this costs no
        # physical memory, and the fused Pallas kernel (ops/fused_split.py)
        # requires the logical and physical layouts to coincide
        return -(-c // 128) * 128


def _f32_to_u8(x: jnp.ndarray) -> jnp.ndarray:
    """[N] f32 -> [N, 4] u8 (exact bitcast)."""
    return lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint8)


def _u8_to_f32(x: jnp.ndarray) -> jnp.ndarray:
    """[..., 4] u8 -> [...] f32 (exact bitcast)."""
    return lax.bitcast_convert_type(x, jnp.float32)


def _i32_to_u8(x: jnp.ndarray) -> jnp.ndarray:
    """[N] i32 -> [N, 4] u8 (exact bitcast)."""
    return lax.bitcast_convert_type(x.astype(jnp.int32), jnp.uint8)


def _u8_to_i32(x: jnp.ndarray) -> jnp.ndarray:
    """[..., 4] u8 -> [...] i32 (exact bitcast)."""
    return lax.bitcast_convert_type(x, jnp.int32)


def pack_rows(
    binned: jnp.ndarray,     # [N, F] uint8
    grad: jnp.ndarray,       # [N] f32
    hess: jnp.ndarray,       # [N] f32
    cnt: jnp.ndarray,        # [N] f32/bool {0,1} in-bag mask
    extras: jnp.ndarray,     # [E, N] f32 carried columns
    layout: RowLayout,
    pad_rows: int,
    row_id: jnp.ndarray = None,   # [N] i32: the record's last extra column
) -> jnp.ndarray:
    """Pack per-row arrays into the work matrix, padded by ``pad_rows``
    garbage rows so blocked dynamic slices never clamp at the array end.

    With ``layout.packed4`` a full-width [N, F] bin matrix nibble-packs
    here (an already-packed [N, ceil(F/2)] matrix passes through).

    ``row_id`` fills the last of the layout's ``num_extra`` columns with
    the four bytes of an int32 (``extras`` then holds the E - 1 before
    it); ``_u8_to_i32`` reads it back."""
    n = binned.shape[0]
    if layout.packed4 and binned.shape[1] == layout.num_features:
        if layout.num_features % 2:
            binned = jnp.pad(binned, ((0, 0), (0, 1)))
        binned = (binned[:, 0::2] | (binned[:, 1::2] << 4))
    parts = [
        binned.astype(jnp.uint8),
        _f32_to_u8(grad),
        _f32_to_u8(hess),
        _f32_to_u8(cnt.astype(jnp.float32)),
    ]
    if extras.shape[0]:
        e = _f32_to_u8(extras.T.astype(jnp.float32))  # [N, E, 4]
        parts.append(e.reshape(n, 4 * extras.shape[0]))
    if row_id is not None:
        parts.append(_i32_to_u8(row_id))
    work = jnp.concatenate(parts, axis=1)
    c = layout.num_cols
    pad_c = c - work.shape[1]
    return jnp.pad(work, ((0, pad_rows), (0, pad_c)))


def unpack_rows(work: jnp.ndarray, n: int, layout: RowLayout):
    """Inverse of pack_rows (on the first ``n`` rows; packed4 layouts
    unpack the nibbles back to the full [n, F] width)."""
    f = layout.num_features
    binned = work[:n, :layout.feat_cols]
    if layout.packed4:
        from .packed import unpack4
        binned = unpack4(binned, f)
    grad = _u8_to_f32(work[:n, layout.grad_off:layout.grad_off + 4])
    hess = _u8_to_f32(work[:n, layout.hess_off:layout.hess_off + 4])
    cnt = _u8_to_f32(work[:n, layout.cnt_off:layout.cnt_off + 4])
    if layout.num_extra:
        e = work[:n, layout.extra_off:layout.extra_off + 4 * layout.num_extra]
        extras = _u8_to_f32(e.reshape(n, layout.num_extra, 4)).T
    else:
        extras = jnp.zeros((0, n), jnp.float32)
    return binned, grad, hess, cnt, extras


def block_grad_hess_cnt(block: jnp.ndarray, layout: RowLayout):
    """Extract (grad, hess, sample weight) from a row-record block [BS, C]."""
    g = _u8_to_f32(block[:, layout.grad_off:layout.grad_off + 4])
    h = _u8_to_f32(block[:, layout.hess_off:layout.hess_off + 4])
    c = _u8_to_f32(block[:, layout.cnt_off:layout.cnt_off + 4])
    return g, h, c


def _compact_block(block: jnp.ndarray, go_left: jnp.ndarray, valid: jnp.ndarray
                   ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Stable-partition one block: returns ([2*BS, C] u8 with lefts compacted
    at [0, BS) and rights at [BS, 2*BS), n_left, n_right).

    One one-hot permutation matmul on the MXU (exact: each destination row
    receives exactly one 0..255-valued source row; bf16 holds 0..255 exactly
    and accumulation is f32).
    """
    bs, c = block.shape
    sel_l = go_left & valid
    sel_r = jnp.logical_not(go_left) & valid
    rank_l = jnp.cumsum(sel_l.astype(jnp.int32)) - sel_l
    rank_r = jnp.cumsum(sel_r.astype(jnp.int32)) - sel_r
    n_l = rank_l[-1] + sel_l[-1]
    n_r = rank_r[-1] + sel_r[-1]
    dest = jnp.where(sel_l, rank_l, jnp.where(sel_r, bs + rank_r, 2 * bs))
    iota2 = jnp.arange(2 * bs, dtype=jnp.int32)
    onehot = (dest[None, :] == iota2[:, None]).astype(jnp.bfloat16)  # [2BS, BS]
    comp = lax.dot_general(
        onehot, block.astype(jnp.bfloat16),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return comp.astype(jnp.uint8), n_l, n_r


def _append_buf(buf: jnp.ndarray, cnt: jnp.ndarray, rows: jnp.ndarray,
                nrows: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Append the first ``nrows`` of ``rows`` [BS, C] into the double-width
    carry buffer [2*BS, C] at offset ``cnt`` (zeros elsewhere)."""
    bs = rows.shape[0]
    iota = jnp.arange(bs, dtype=jnp.int32)
    masked = jnp.where((iota < nrows)[:, None], rows, 0)
    shifted = jnp.roll(jnp.pad(masked, ((0, bs), (0, 0))), cnt, axis=0)
    return buf + shifted, cnt + nrows


def _flush_full(dst: jnp.ndarray, buf: jnp.ndarray, cnt: jnp.ndarray,
                ptr: jnp.ndarray):
    """If the carry holds >= BS rows, write one full block at ``ptr``."""
    bs = buf.shape[0] // 2

    def do(args):
        dst, buf, cnt, ptr = args
        dst = lax.dynamic_update_slice(dst, buf[:bs], (ptr, 0))
        buf = jnp.concatenate([buf[bs:], jnp.zeros_like(buf[:bs])], axis=0)
        return dst, buf, cnt - bs, ptr + bs

    return lax.cond(cnt >= bs, do, lambda a: a, (dst, buf, cnt, ptr))


def _flush_tail(dst: jnp.ndarray, buf: jnp.ndarray, cnt: jnp.ndarray,
                ptr: jnp.ndarray) -> jnp.ndarray:
    """Blend-write the remaining < BS carry rows at ``ptr`` (read-modify-write
    so rows beyond the segment are preserved)."""
    bs = buf.shape[0] // 2
    cur = lax.dynamic_slice(dst, (ptr, 0), (bs, dst.shape[1]))
    iota = jnp.arange(bs, dtype=jnp.int32)
    out = jnp.where((iota < cnt)[:, None], buf[:bs], cur)
    return lax.dynamic_update_slice(dst, out, (ptr, 0))


def partition_segment(
    work: jnp.ndarray,       # [N + pad, C] u8 row records
    scratch: jnp.ndarray,    # [N + pad, C] u8 scratch (right-stream staging)
    start: jnp.ndarray,      # i32 segment start
    count: jnp.ndarray,      # i32 segment row count
    n_left: jnp.ndarray,     # i32 exact left-row count (from the split scan)
    feature: jnp.ndarray,    # i32 split feature
    bin_: jnp.ndarray,       # i32 threshold bin
    default_left: jnp.ndarray,
    nan_bin: jnp.ndarray,    # i32 NaN bin of the split feature
    is_cat: jnp.ndarray,     # bool
    cat_bitset: jnp.ndarray,  # [W] u32 bin bitset (categorical splits)
    block_size: int,
    packed4: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Stably partition ``work[start:start+count]`` so left-child rows occupy
    ``[start, start+n_left)`` and right-child rows the remainder.

    ``packed4``: bin columns are nibble-packed (RowLayout.packed4) — the
    routing column reads the byte at ``feature >> 1`` and extracts the
    nibble selected by ``feature & 1``.

    Returns the updated (work, scratch). Everything streams: per block one
    contiguous read, one one-hot compaction matmul, and carry-buffered
    contiguous writes (lefts in place — the left write cursor can never
    overtake the read cursor; rights via scratch at final offsets, copied
    back afterwards).
    """
    bs = block_size
    c = work.shape[1]
    nblocks = (count + bs - 1) // bs
    iota = jnp.arange(bs, dtype=jnp.int32)
    zeros2 = jnp.zeros((2 * bs, c), jnp.uint8)

    def body(state):
        i, work, scratch, lbuf, lcnt, lptr, rbuf, rcnt, rptr = state
        blk = lax.dynamic_slice(work, (start + i * bs, 0), (bs, c))
        if packed4:
            byte = lax.dynamic_slice_in_dim(
                blk, feature >> 1, 1, axis=1)[:, 0].astype(jnp.int32)
            col = (byte >> ((feature & 1) * 4)) & 0xF
        else:
            col = lax.dynamic_slice_in_dim(blk, feature, 1, axis=1)[:, 0]
        valid = iota < (count - i * bs)
        gl = go_left_pred(col, bin_, default_left, nan_bin, is_cat,
                          cat_bitset)
        comp, n_l, n_r = _compact_block(blk, gl, valid)
        lbuf, lcnt = _append_buf(lbuf, lcnt, comp[:bs], n_l)
        rbuf, rcnt = _append_buf(rbuf, rcnt, comp[bs:], n_r)
        work, lbuf, lcnt, lptr = _flush_full(work, lbuf, lcnt, lptr)
        scratch, rbuf, rcnt, rptr = _flush_full(scratch, rbuf, rcnt, rptr)
        return i + 1, work, scratch, lbuf, lcnt, lptr, rbuf, rcnt, rptr

    state = (jnp.asarray(0, jnp.int32), work, scratch,
             zeros2, jnp.asarray(0, jnp.int32), start,
             zeros2, jnp.asarray(0, jnp.int32), start + n_left)
    state = lax.while_loop(lambda s: s[0] < nblocks, body, state)
    _, work, scratch, lbuf, lcnt, lptr, rbuf, rcnt, rptr = state

    work = _flush_tail(work, lbuf, lcnt, lptr)
    scratch = _flush_tail(scratch, rbuf, rcnt, rptr)

    # copy the right stream back from scratch (contiguous, block-aligned)
    n_right = count - n_left
    rblocks = (n_right + bs - 1) // bs

    def copy_body(state):
        j, work = state
        off = start + n_left + j * bs
        blk = lax.dynamic_slice(scratch, (off, 0), (bs, c))
        cur = lax.dynamic_slice(work, (off, 0), (bs, c))
        keep = iota < (n_right - j * bs)
        out = jnp.where(keep[:, None], blk, cur)
        work = lax.dynamic_update_slice(work, out, (off, 0))
        return j + 1, work

    _, work = lax.while_loop(
        lambda s: s[0] < rblocks, copy_body,
        (jnp.asarray(0, jnp.int32), work))
    return work, scratch


def segment_histogram(
    work: jnp.ndarray,       # [N + pad, C] u8
    start: jnp.ndarray,
    count: jnp.ndarray,
    layout: RowLayout,
    num_bins: int,
    block_size: int,
    impl: str = "auto",
    quantized: bool = False,
    mbatch: int = 1,
    acc_bits: int = 32,
    quant_max: int = 127,
    hist_layout: str = "lane",
    feat_idx=None,           # static int sequence: stored columns to build
    chunk_f: int = 0,        # feature width the row-chunk size derives from
) -> jnp.ndarray:            # [F, B, 4] f32 (int32 when quantized)
    """Histogram of one contiguous leaf segment, streamed in fixed blocks.

    Channels: (grad, hess, in-bag count, raw count). The in-bag count is the
    {0,1} indicator of a nonzero sample weight (reference: cnt_ counts bagged
    rows, not their weights). Counts accumulate in f32 and stay exact below
    2^24 rows — the raw-count channel drives the physical partition offsets,
    so exactness is required, not a nicety.

    ``quantized``: the grad/hess columns hold integer discretizer codes
    (|code| <= 127, stored as exact f32 — the row-record layout is
    unchanged); they re-pack into an int8 channel matrix per block and the
    contraction runs int8 x int8 -> int32 on the MXU (ops/histogram.py).
    All four channels come back as exact int32 sums (the GBDT bounds
    global num_data * quant_bins inside int32 before selecting this path).

    ``acc_bits=16`` (quantized only) selects the narrowed packed-pair
    accumulation — bit-identical int32 sums at half the contraction work
    where leaf bounds allow (ops/histogram.py _xla_histogram_narrow;
    reference: GetHistBitsInLeaf). ``layout.packed4`` streams nibble-packed
    bin bytes and unpacks per block inside histogram_block.

    ``feat_idx`` restricts the build to a feature GROUP (hist_overlap):
    only those stored columns are histogrammed, in the given order, so
    the distributed grower can issue one collective per group while the
    next group's walk still accumulates. ``chunk_f`` then pins the XLA
    engines' row-chunk size to the FULL feature width — the group build
    keeps the full-width call's accumulation order and stays
    bit-identical to the corresponding slice of the ungrouped histogram.
    """
    from .histogram import histogram_block

    f = layout.num_features
    b = num_bins
    bs = block_size
    c = work.shape[1]
    if feat_idx is not None:
        if layout.packed4:
            raise ValueError("feat_idx feature groups need byte-addressed "
                             "bin columns; packed4 layouts build ungrouped")
        feat_idx = jnp.asarray(feat_idx, jnp.int32)
        f = int(feat_idx.shape[0])
    nblocks = (count + bs - 1) // bs
    iota = jnp.arange(bs, dtype=jnp.int32)

    def body(state):
        j, acc = state
        blk = lax.dynamic_slice(work, (start + j * bs, 0), (bs, c))
        g, h, cw = block_grad_hess_cnt(blk, layout)
        if quantized:
            valid = iota < (count - j * bs)
            v8 = valid.astype(jnp.int8)
            inbag = (cw != 0.0).astype(jnp.int8) * v8
            # f32 -> int8 casts are exact: the codes are integers <= 127
            chans = jnp.stack([g.astype(jnp.int8) * v8,
                               h.astype(jnp.int8) * v8, inbag, v8], axis=1)
        else:
            valid = (iota < (count - j * bs)).astype(jnp.float32)
            cw = (cw != 0.0).astype(jnp.float32)
            chans = jnp.stack([g * valid, h * valid, cw * valid, valid],
                              axis=1)
        cols = blk[:, :layout.feat_cols]
        if feat_idx is not None:
            cols = jnp.take(cols, feat_idx, axis=1)
        acc = acc + histogram_block(
            cols, chans, b, impl=impl, mbatch=mbatch,
            packed4_features=f if layout.packed4 else 0,
            layout=hist_layout, acc_bits=acc_bits, quant_max=quant_max,
            chunk_f=chunk_f)
        return j + 1, acc

    acc0 = jnp.zeros((f, b, 4), jnp.int32 if quantized else jnp.float32)
    _, acc = lax.while_loop(
        lambda s: s[0] < nblocks, body, (jnp.asarray(0, jnp.int32), acc0))
    return acc


def segments_to_leaf_vectors(
    leaf_start: jnp.ndarray,   # [L] i32 (final leaf segments, disjoint tiling)
    leaf_rows: jnp.ndarray,    # [L] i32
    leaf_value: jnp.ndarray,   # [L] f32
    n: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expand per-leaf segments into per-row (leaf_id, leaf_value) vectors.

    Because final leaf segments tile [0, N) disjointly, a sparse
    delta-then-cumsum is exact (each closing delta cancels its opening delta
    completely before the next segment opens): no gathers, two O(N) scans.
    """
    ends = leaf_start + leaf_rows
    # 2L-point sparse delta arrays (tiny scatters), then exact prefix sums.
    # Values go through an int32 cumsum of their f32 *bit patterns*: wrapping
    # integer deltas cancel exactly (modular arithmetic) even when an open and
    # a close collide on the same scatter index, so every row reads back its
    # leaf value bit-for-bit — no gathers, two O(N) scans.
    idx = jnp.concatenate([leaf_start, ends])
    lid = jnp.arange(leaf_start.shape[0], dtype=jnp.int32)
    d_leaf = jnp.concatenate([lid, -lid])
    bits = lax.bitcast_convert_type(leaf_value.astype(jnp.float32), jnp.int32)
    d_val = jnp.concatenate([bits, -bits])
    # leaves with zero rows contribute cancelling deltas at the same index
    delta_leaf = jnp.zeros((n + 1,), jnp.int32).at[idx].add(d_leaf, mode="drop")
    delta_val = jnp.zeros((n + 1,), jnp.int32).at[idx].add(d_val, mode="drop")
    row_leaf = jnp.cumsum(delta_leaf)[:n]
    row_val = lax.bitcast_convert_type(jnp.cumsum(delta_val)[:n], jnp.float32)
    return row_leaf, row_val
