"""``record_write``: per-row f32 columns written into the row records in one
streamed pass (a Mosaic kernel, ops/compact.py's record layout).

The compact step writes g·w, h·w, w, the K scores (and, at a multiclass
step's first tree, the 2K class gradients) into lanes
``[grad_off, grad_off + 4 ncols)`` of every ``[N + pad, C]`` u8 record
once a tree, before the tree grows. As XLA's lane-slice update of the
whole u8 array that write cost 35 ms an iteration at higgs's 10.5M
128-byte records (a column-major ``[N, 16]`` u8 operand laid out again
row-major, a select over it, and a ``dynamic-update-slice`` of the whole
array at 2.5 ns a row; PERF.md section 6, PR 40), ten times its roofline:
read and write each record once and read the columns lane-dense.

The kernel streams row blocks HBM -> VMEM -> HBM through the Pallas
pipeline (double-buffered), the record array aliased to the output so the
donated buffer is written in place. Only the 128-lane tiles that hold the
written lanes move (one of istella's two). Each block's columns arrive
lane-dense, ``[ncols, bs]`` f32; their bit patterns split into byte rows
by shift and mask, row ``off + 4 c + j`` of a ``[W, bs]`` i32 array
holding byte ``j`` of column ``c``, transposed to ``[bs, W]`` on the XLU
and merged into the block under a lane mask. Every other byte of the
record is written back as it was read, and the f32 bits land where the
bitcast update put them: the same bytes either way. On the chip: 4.56 ms
an iteration at higgs's size, 0.43 ns a row (627 GB/s: bound by HBM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# rows a grid step streams: a [4096, 128] u8 block in and out,
# double-buffered (2 MB of VMEM). The v5e listing of the loop body
# (PERF.md section 6, PR 40): 1,534 bundles a block at higgs's 4 columns,
# 0.25 ns a row at 1.5 GHz against 0.33 ns for the 272 bytes a row moves
# at 819 GB/s; per 1,024 rows 425 bundles at a block of 2,048, 467 at
# 1,024 (the grid step's own bundles)
BLOCK_ROWS = 4096


def lane_window(num_cols: int, lo: int, hi: int):
    """(first lane, lanes) of the whole 128-lane tiles of a ``num_cols``
    record that hold lanes ``[lo, hi)``: the block's lane extent. A span
    whose tiles do not start at a multiple of their own width takes the
    whole record (a block index counts in block widths)."""
    t0, t1 = lo // LANES, -(-hi // LANES)
    width = (t1 - t0) * LANES
    if (t0 * LANES) % width:
        return 0, num_cols
    return t0 * LANES, width


def _kernel(cols_ref, work_ref, out_ref, *, off: int, ncols: int):
    """One block: ``cols_ref`` [ncols, bs] f32, ``work_ref`` / ``out_ref``
    [bs, W] u8. The byte rows are built a sublane tile (8 rows) at a
    time, only the tiles the window touches: each sublane ``s`` of tile
    ``t`` is byte ``(8 t + s - off) % 4`` of column ``(8 t + s - off) //
    4``, a broadcast word shifted by a per-sublane amount. The zero tiles
    around them only fill the transpose; the lane mask drops them."""
    bits = lax.bitcast_convert_type(cols_ref[...], jnp.int32)  # [ncols, bs]
    bs, width = work_ref.shape
    lo, hi = off, off + 4 * ncols
    t0, t1 = lo // 8, -(-hi // 8)
    sub = lax.broadcasted_iota(jnp.int32, (8, bs), 0)
    tiles = [jnp.zeros((8 * t0, bs), jnp.int32)] if t0 else []
    for t in range(t0, t1):
        r = sub + (8 * t - off)              # byte of the window a sublane is
        shift = (r & 3) * 8
        tile = jnp.zeros((8, bs), jnp.int32)
        for c in range(max(0, (8 * t - off) // 4),
                       min(ncols, -(-(8 * t + 8 - off) // 4))):
            word = jnp.broadcast_to(bits[c:c + 1, :], (8, bs))
            tile = jnp.where((r >= 4 * c) & (r < 4 * c + 4),
                             lax.shift_right_logical(word, shift), tile)
        tiles.append(tile & 255)
    if width > 8 * t1:
        tiles.append(jnp.zeros((width - 8 * t1, bs), jnp.int32))
    rows = jnp.concatenate(tiles, axis=0).T                    # [bs, W]
    lane = lax.broadcasted_iota(jnp.int32, rows.shape, 1)
    mine = (lane >= lo) & (lane < hi)
    out_ref[...] = jnp.where(mine, rows,
                             work_ref[...].astype(jnp.int32)
                             ).astype(jnp.uint8)


def record_write(work: jax.Array, cols: jax.Array, grad_off: int, *,
                 block_rows: int = BLOCK_ROWS,
                 interpret: bool = False) -> jax.Array:
    """``work`` [R, C] u8 with lanes ``[grad_off, grad_off + 4 ncols)`` of
    every row replaced by the bytes of ``cols`` [ncols, R] f32 (row r's
    column c little-endian at ``grad_off + 4 c``): byte for byte what
    ``work.at[:, grad_off:grad_off + 4 ncols].set(bitcast(cols.T))``
    writes, in place where the caller donates ``work``."""
    rows, num_cols = work.shape
    ncols = cols.shape[0]
    if cols.shape != (ncols, rows) or num_cols % LANES:
        raise ValueError(f"record_write: columns {cols.shape} for records "
                         f"{work.shape}; records are whole lane tiles")
    lane0, width = lane_window(num_cols, grad_off, grad_off + 4 * ncols)
    bs = min(block_rows, -(-rows // LANES) * LANES)
    lane_blk = lane0 // width
    block = pl.BlockSpec((bs, width), lambda i: (i, lane_blk))
    return pl.pallas_call(
        functools.partial(_kernel, off=grad_off - lane0, ncols=ncols),
        grid=(pl.cdiv(rows, bs),),
        in_specs=[pl.BlockSpec((ncols, bs), lambda i: (0, i)), block],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(work.shape, work.dtype),
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="record_write",
    )(cols, work)
