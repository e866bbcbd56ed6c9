"""Pallas TPU histogram kernel.

TPU-native re-design of the reference's histogram kernels (reference: CUDA
shared-memory atomicAdd kernels, src/treelearner/cuda/
cuda_histogram_constructor.cu:17-68 CUDAConstructHistogramDenseKernel).

The XLA fallback (ops/histogram.py) materializes the row-block one-hot in HBM
(~B× expansion of the bin matrix) and goes HBM-bandwidth-bound. This kernel
forms the one-hot **in VMEM** per (row-block, feature-chunk) — a broadcast
compare against a bin iota — feeds it straight to the MXU, and accumulates the
[F*B, K] histogram in an output block that stays resident in VMEM across the
whole row grid. HBM traffic drops to reading bins and channels once per pass.
Measured on v5e at [1M, 28] x B=256: ~0.59 Telem/s of one-hot work vs ~0.007
for the XLA path.

Where the CUDA kernel resolves collisions with atomicAdd into shared memory,
the one-hot contraction has no collisions by construction: each row contributes
to exactly one bin column per feature, and the MXU reduces over rows.

Batched-M issue (round 6, shared design with ops/fused_split.py hist_flush):
the contraction's natural output has only 8 rows (the padded channel count),
so each MXU issue ran at M=8 of 128 rows. Channels now arrive CHANNEL-MAJOR
([KP, N], transposed once on the XLA side — no in-kernel relayout), each
grid step's row block subdivides into ``mbatch`` windows, and the kernel
builds a block-diagonal [8K, R] channel LHS (tile the [KP, R] slab K times
along sublanes, mask each 8-row band to its own lane window) contracted in
ONE matmul per feature chunk with M = 8*mbatch rows; the K per-window
partial sums reduce with K-1 vector adds. Counts and int32 sums are
bit-identical to mbatch=1; f32/split sums regroup within ~1 ulp.

Precision modes (the one-hot itself is exact in bf16 — values 0/1):

  * ``split`` (default) — channels decompose as hi+lo bf16 pairs occupying the
    8 padded lanes (hi = bf16(x), lo = bf16(x - hi)); both halves contract at
    full MXU rate with f32 accumulation and are summed after the kernel.
    Error ~2^-17 relative — between f32 (2^-24) and the reference's own int8
    quantized-histogram mode (src/treelearner/gradient_discretizer.cpp).
    Integer-valued count channels stay exact (lo == 0, f32 accumulate).
  * ``bf16`` — channels rounded to bf16; fastest, ~2^-9 relative error.
  * ``f32``  — fp32-accurate MXU mode (3-pass); ~5x slower, for bit-level
    comparisons against the XLA path.
  * ``int8`` — quantized-gradient mode (reference:
    cuda_histogram_constructor.cu:249-524): channels are int8 grad/hess
    codes, the one-hot forms in int8, and the contraction runs
    int8 x int8 -> int32 (``preferred_element_type=int32``) at 2x the bf16
    MXU rate with EXACT integer sums — no hi/lo split needed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl

# K channels padded to the f32 sublane width
_K_PAD = 8


def _hist_kernel(bins_ref, ch_ref, out_ref, *, num_bins: int, f_chunk: int,
                 mode: str, mbatch: int):
    """One grid step: accumulate a row-block into the [KP, F*B] histogram.

    The output is CHANNEL-major: [KP, F*B] keeps the lane dimension wide
    (F*B) instead of padding an 8-lane channel dimension to 128, so the
    VMEM-resident accumulator costs 8 x F*B x 4B (1.1MB at F=137, B=256)
    rather than 32x that.

    ``ch_ref`` is the CHANNEL-MAJOR [KP, R] slab of this row block; with
    ``mbatch`` > 1 the block subdivides into K row windows of R/K rows and
    the channel LHS becomes block-diagonal [8K, R] so every matmul issues
    M = 8K MXU rows (see module docstring). The drain of a ragged tail
    needs no special casing here: padding rows carry zero channels, so
    whatever they one-hot into sums to zero. pushes % mbatch == 0 always
    holds because the window partition is exact (R % mbatch == 0,
    enforced by the wrapper).

    The unrolled chunk loop makes the register allocator spill the one-hot
    temporaries to the VMEM stack when F*B is large (measured on v5e at
    B=256: F=200 compiles, F=320 wants 149MB of spill slots against the
    128MB budget); the auto dispatch (ops/histogram.py _resolve_impl)
    routes such configs to the XLA path instead."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    # uint8 -> int32 (Mosaic has no direct uint8 -> float cast)
    bins = bins_ref[:].astype(jnp.int32)          # [R, F]
    ch = ch_ref[:]                                # [KP, R] f32/int8
    r = bins.shape[0]
    f = bins.shape[1]
    b = num_bins
    w = f_chunk
    assert f % w == 0
    assert r % mbatch == 0
    sub = r // mbatch

    if mode == "int8":
        oh_dtype = jnp.int8
        acc_dtype = jnp.int32
        precision = None
    else:
        oh_dtype = jnp.float32 if mode == "f32" else jnp.bfloat16
        acc_dtype = jnp.float32
        if mode != "f32":
            ch = ch.astype(jnp.bfloat16)
        precision = (lax.Precision.HIGHEST if mode == "f32"
                     else lax.Precision.DEFAULT)
    if mbatch > 1:
        # block-diagonal [8K, R] channel LHS: K sublane-tiled copies of the
        # [KP, R] slab, each 8-row band masked to its own lane window
        tiled = jnp.concatenate([ch] * mbatch, axis=0)        # [8K, R]
        band = lax.broadcasted_iota(jnp.int32, tiled.shape, 0) // _K_PAD
        win = lax.broadcasted_iota(jnp.int32, tiled.shape, 1) // sub
        ch_lhs = jnp.where(band == win, tiled, jnp.zeros_like(tiled))
    else:
        ch_lhs = ch
    iota_b = lax.broadcasted_iota(jnp.int32, (r, b), 1)

    for fc in range(0, f, w):
        # one-hot for w features side by side: [R, W*B] built by broadcast
        # compares in VMEM (never touches HBM)
        oh = jnp.concatenate(
            [(bins[:, fc + j:fc + j + 1] == iota_b).astype(oh_dtype)
             for j in range(w)], axis=1)
        # MXU contraction over rows: [8K, R] x [R, W*B] -> [8K, W*B]
        # (int8 mode: int8 x int8 -> int32, preferred_element_type pins the
        # accumulator so the int8 operands cannot narrow the output)
        part = lax.dot_general(
            ch_lhs, oh,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=acc_dtype,
            precision=precision,
        )
        red = part[0:_K_PAD]
        for t in range(1, mbatch):
            red = red + part[_K_PAD * t:_K_PAD * (t + 1)]
        out_ref[:, fc * b:(fc + w) * b] += red


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


# sublane-layout constraint: bins lie along sublanes, so the padded per-
# feature bin stride must leave room for at least one feature per 128-row
# MXU tile — B <= 64 (the README's "bins-on-sublanes for B <= 64" case)
_SUBLANE_MAX_BINS = 64


def sublane_bin_stride(num_bins: int, mode: str) -> int:
    """Per-feature sublane stride of the bins-on-sublanes one-hot.

    Rounded up to the one-hot dtype's sublane tile (int8: 32, bf16: 16,
    f32: 8) so the per-feature [stride, R] compare tiles concatenate along
    sublanes without relayouts."""
    tile = 32 if mode == "int8" else (8 if mode == "f32" else 16)
    return _round_up(num_bins, tile)


def _hist_kernel_sublane(bins_ref, ch_ref, out_ref, *, num_bins: int,
                         b_sub: int, f_group: int, mode: str, mbatch: int):
    """Bins-on-sublanes grid step (tpu_hist_layout=sublane, B <= 64).

    The lane layout's per-feature one-hot compare produces a [R, B] tile —
    at B <= 64 that fills under half of the 128 register lanes, and the
    output M dimension is the 8 padded channels. Here the bins input
    arrives FEATURE-major ([F, N], one XLA-side transpose like the channel
    slab of the lane kernel), so the compare runs as
    ``bins[f:f+1, :] == iota_sublane`` — a [b_sub, R] tile whose LANE
    dimension is the full row block. A group of ``f_group`` features
    concatenates along sublanes into the [f_group * b_sub, R] one-hot LHS
    (M = 128 output rows at b_sub * f_group = 128), contracted against a
    block-diagonal [R, KP * mbatch] channel RHS whose lane bands hold the
    mbatch row windows — N = 8 * mbatch lanes. The per-window partial sums
    land in separate lane bands of the [F * b_sub, KP * mbatch] output and
    are reduced band-wise on the XLA side (exact for int32; f32 regroups
    within ~1 ulp, same contract as the lane kernel's batched-M reduce).
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    bins = bins_ref[:].astype(jnp.int32)          # [F, R] feature-major
    ch = ch_ref[:]                                # [R, KP] row-major
    f, r = bins.shape
    assert f % f_group == 0
    assert r % mbatch == 0
    sub = r // mbatch

    if mode == "int8":
        oh_dtype, acc_dtype, precision = jnp.int8, jnp.int32, None
    else:
        oh_dtype = jnp.float32 if mode == "f32" else jnp.bfloat16
        acc_dtype = jnp.float32
        if mode != "f32":
            ch = ch.astype(jnp.bfloat16)
        precision = (lax.Precision.HIGHEST if mode == "f32"
                     else lax.Precision.DEFAULT)
    if mbatch > 1:
        # block-diagonal [R, KP*mb] channel RHS: the KP lanes tile mb
        # times and each band keeps only its own row window
        tiled = jnp.concatenate([ch] * mbatch, axis=1)       # [R, KP*mb]
        band = lax.broadcasted_iota(jnp.int32, tiled.shape, 1) // _K_PAD
        win = lax.broadcasted_iota(jnp.int32, tiled.shape, 0) // sub
        ch_rhs = jnp.where(band == win, tiled, jnp.zeros_like(tiled))
    else:
        ch_rhs = ch
    # bins-on-SUBLANES iota: dimension 0 (pad sublanes past num_bins can
    # never match a bin value, so they contribute exact zeros)
    iota_b = lax.broadcasted_iota(jnp.int32, (b_sub, r), 0)

    for fc in range(0, f, f_group):
        oh = jnp.concatenate(
            [(bins[fc + j:fc + j + 1, :] == iota_b).astype(oh_dtype)
             for j in range(f_group)], axis=0)    # [G*b_sub, R]
        part = lax.dot_general(
            oh, ch_rhs,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=acc_dtype,
            precision=precision,
        )                                          # [G*b_sub, KP*mb]
        out_ref[fc * b_sub:(fc + f_group) * b_sub, :] += part


def _resolve_mbatch(mbatch: int, row_block: int) -> int:
    """Clamp the batched-M depth to a divisor of the row block (exact
    window partition) with 8*K <= 128 MXU rows and windows >= 128 lanes."""
    mb = max(1, min(int(mbatch), 16, row_block // 128))
    while mb > 1 and row_block % mb:
        mb -= 1
    return mb


@functools.partial(
    jax.jit,
    static_argnames=("num_bins", "row_block", "f_chunk", "mode", "interpret",
                     "mbatch", "hist_layout"))
def pallas_histogram(
    binned: jax.Array,       # [N, F] uint8/int32
    channels: jax.Array,     # [N, K] f32 (int8 for mode='int8'), K <= 8
    #                          (K <= 4 for mode='split')
    num_bins: int,
    row_block: int = 2048,   # v5e sweet spot (with f_chunk=2): 0.59 Telem/s
    f_chunk: int = 2,
    mode: str = "split",     # split | bf16 | f32 | int8 (see module doc)
    interpret: bool = False,
    mbatch: int = 1,         # batched-M windows per row block (1-16)
    hist_layout: str = "lane",   # lane | sublane (tpu_hist_layout)
) -> jax.Array:              # [F, B, K] f32 (int32 for mode='int8')
    n, f_in = binned.shape
    k = channels.shape[1]
    b = num_bins
    if hist_layout == "sublane" and b > _SUBLANE_MAX_BINS:
        raise ValueError(
            f"hist_layout=sublane supports num_bins <= {_SUBLANE_MAX_BINS} "
            f"(got {b}): bins lie along sublanes, and wider bin counts "
            "leave no room to group features into the 128 MXU rows")
    # Mosaic VMEM scales ~ row_block * F * B * 0.83B (measured on v5e:
    # 138.7MB at [2048, 320] x B=256 against the 128MB budget); clamp the
    # row block so wide-F configs compile instead of OOMing vmem
    rb_cap = max(128, (121_000_000 // max(1, f_in * b)) // 128 * 128)
    row_block = min(row_block, rb_cap)
    mbatch = _resolve_mbatch(mbatch, row_block)

    if mode == "int8" and not jnp.issubdtype(channels.dtype, jnp.integer):
        raise ValueError("mode='int8' needs integer channels (grad/hess "
                         "codes from the gradient discretizer)")
    if mode == "int8":
        channels = channels.astype(jnp.int8)
    if mode == "split":
        if 2 * k > _K_PAD:
            raise ValueError(f"mode='split' supports K<={_K_PAD // 2}, got {k}")
        # reduce_precision, NOT a bf16 cast round-trip: under
        # --xla_allow_excess_precision (set on TPU by default) XLA elides
        # f32->bf16->f32 as identity, which silently folds lo to zero
        hi = lax.reduce_precision(channels, exponent_bits=8, mantissa_bits=7)
        lo = channels - hi
        channels = jnp.concatenate([hi, lo], axis=1)  # [N, 2K]

    # pad rows to the block size (zero channels contribute nothing), features
    # to the chunk/group width, and channels to the sublane width
    b_sub = sublane_bin_stride(b, mode)
    f_group = max(1, 128 // b_sub)
    f_unit = f_group if hist_layout == "sublane" else f_chunk
    n_pad = (-n) % row_block
    f_pad = (-f_in) % f_unit
    if n_pad or f_pad:
        binned = jnp.pad(binned, ((0, n_pad), (0, f_pad)))
    if n_pad:
        channels = jnp.pad(channels, ((0, n_pad), (0, 0)))
    kc = channels.shape[1]
    if kc < _K_PAD:
        channels = jnp.pad(channels, ((0, 0), (0, _K_PAD - kc)))
    n_tot = n + n_pad
    f = f_in + f_pad

    if hist_layout == "sublane":
        # bins feed FEATURE-major (one XLA transpose — the mirror of the
        # lane layout's channel slab) and channels stay row-major: the
        # kernel's compare tiles then span the full row block on lanes
        kernel = functools.partial(
            _hist_kernel_sublane, num_bins=b, b_sub=b_sub, f_group=f_group,
            mode=mode, mbatch=mbatch)
        acc_dtype = jnp.int32 if mode == "int8" else jnp.float32
        out = pl.pallas_call(
            kernel,
            grid=(n_tot // row_block,),
            in_specs=[
                pl.BlockSpec((f, row_block), lambda i: (0, i)),
                pl.BlockSpec((row_block, _K_PAD), lambda i: (i, 0)),
            ],
            out_specs=pl.BlockSpec((f * b_sub, _K_PAD * mbatch),
                                   lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((f * b_sub, _K_PAD * mbatch),
                                           acc_dtype),
            interpret=interpret,
        )(binned.T, channels)
        # band-wise reduction of the mbatch row windows, then bin-major ->
        # [F, B, K] (int32 adds exact; f32 regroups within ~1 ulp)
        out = out.reshape(f, b_sub, mbatch, _K_PAD).sum(axis=2)
        out = out[:f_in, :b, :]
        if mode == "split":
            return out[:, :, :k] + out[:, :, k:2 * k]
        return out[:, :, :k]

    # channel-major slab: ONE XLA-side transpose instead of an in-kernel
    # Mosaic relayout per block (relayouts dominate on this toolchain)
    channels_t = channels.T                       # [KP, N]

    kernel = functools.partial(
        _hist_kernel, num_bins=b, f_chunk=f_chunk, mode=mode, mbatch=mbatch)

    acc_dtype = jnp.int32 if mode == "int8" else jnp.float32
    out = pl.pallas_call(
        kernel,
        grid=(n_tot // row_block,),
        in_specs=[
            pl.BlockSpec((row_block, f), lambda i: (i, 0)),
            pl.BlockSpec((_K_PAD, row_block), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((_K_PAD, f * b), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((_K_PAD, f * b), acc_dtype),
        interpret=interpret,
    )(binned, channels_t)
    out = jnp.transpose(out.reshape(_K_PAD, f, b), (1, 2, 0))[:f_in]
    if mode == "split":
        return out[:, :, :k] + out[:, :, k:2 * k]
    return out[:, :, :k]
