"""Histogram construction on TPU.

TPU-native re-design of the reference's histogram kernels
(reference: CUDA shared-memory atomicAdd kernels in
src/treelearner/cuda/cuda_histogram_constructor.cu:17-68 and the CPU templated
``Dataset::ConstructHistograms`` include/LightGBM/dataset.h:727).

TPUs have no fast scatter/atomics, so the scatter-add is re-formulated as a
one-hot contraction that XLA maps onto the MXU:

    hist[f, b, k] = sum_r (binned[r, f] == b) * channels[r, k]

``channels`` carries (grad, hess, count-weight) per row, already multiplied by
the leaf-membership mask.

Two implementations sit behind ``impl=``:

  * ``xla``    — chunked one-hot einsum (rows scanned in blocks to bound the
                 materialized one-hot); f32 HIGHEST precision, runs anywhere.
  * ``pallas`` — Mosaic kernel that forms the one-hot in VMEM and feeds the
                 MXU directly (ops/pallas_histogram.py); TPU only.
  * ``auto``   — pallas on a TPU backend, else xla.

The dispatch is resolved at trace time (backend is static under jit).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..obs.spans import span

# target elements for the materialized one-hot per scan step
_CHUNK_ELEMS = 1 << 23


def _chunk_rows(n: int, f: int, b: int) -> int:
    per_row = max(1, f * b)
    c = max(128, _CHUNK_ELEMS // per_row)
    # round to a multiple of 128 rows for clean TPU tiling
    c = (c // 128) * 128
    return max(128, min(c, max(128, n)))


def _xla_histogram(binned, channels, num_bins: int, mbatch: int = 1,
                   chunk_f: int = 0):
    n, f = binned.shape
    k = channels.shape[1]
    b = num_bins
    # batched-M port (ops/fused_split.py hist_flush is the reference
    # design): the XLA engine's analogue of staging K row blocks per MXU
    # issue is contracting K chunks of rows in ONE einsum — the scan trip
    # count drops K-fold and XLA sees a K-times-deeper contraction to
    # tile, instead of K back-to-back launches over small one-hots.
    # ``chunk_f`` overrides the feature count the row-chunk size derives
    # from: a feature-GROUP call (hist_overlap) must keep the full-width
    # call's chunk boundaries, or the f32 accumulation order changes and
    # the grouped histogram stops being bit-identical to the full one
    chunk = _chunk_rows(n, chunk_f or f, b) * max(1, int(mbatch))
    chunk = max(128, min(chunk, -(-max(n, 1) // 128) * 128))
    iota = jnp.arange(b, dtype=jnp.int32)

    quantized = jnp.issubdtype(channels.dtype, jnp.integer)
    acc_dtype = jnp.int32 if quantized else channels.dtype

    def contract(onehot, ch):
        if quantized:
            # quantized-gradient path (reference: gradient_discretizer.cpp
            # + the int histogram kernels, cuda_histogram_constructor
            # .cu:249-524): int8 one-hot x int8 codes accumulate
            # int8*int8 -> int32 on the MXU. preferred_element_type=int32
            # is load-bearing: without it XLA's dot output dtype follows
            # the int8 operands and the sums wrap (tpulint R003).
            return jnp.einsum("rfb,rk->fbk", onehot, ch,
                              preferred_element_type=jnp.int32)
        # histogram sums need full f32 accuracy (hessian sums drive leaf
        # outputs; SURVEY §7 "bf16 is out for hessian sums") — the TPU
        # MXU's default bf16 matmul precision is not enough, so force the
        # fp32-accurate mode.
        return jnp.einsum("rfb,rk->fbk", onehot, ch,
                          precision=lax.Precision.HIGHEST)

    if n <= chunk:
        onehot = (binned.astype(jnp.int32)[:, :, None] == iota).astype(channels.dtype)
        hist = contract(onehot, channels)
    else:
        n_chunks = -(-n // chunk)
        pad = n_chunks * chunk - n
        if pad:
            binned = jnp.pad(binned, ((0, pad), (0, 0)))
            channels = jnp.pad(channels, ((0, pad), (0, 0)))
        binned_c = binned.reshape(n_chunks, chunk, f)
        channels_c = channels.reshape(n_chunks, chunk, k)

        def step(hist, inp):
            bc, cc = inp
            onehot = (bc.astype(jnp.int32)[:, :, None] == iota).astype(cc.dtype)
            return hist + contract(onehot, cc), None

        hist0 = jnp.zeros((f, b, k), dtype=acc_dtype)
        hist, _ = lax.scan(step, hist0, (binned_c, channels_c))
    return hist


# narrowed (16-bit) quantized accumulation: the packed-pair radix. Two code
# sums share one f32 channel exactly when the per-chunk sums stay below the
# radix: with R = 4096 and chunk sums capped at R - 1 = 4095, the worst
# packed chunk sum is R * 4095 + 4095 = 4095 * 4097 = 2^24 - 1 — the last
# exactly-representable f32 integer, so no larger power-of-two radix works.
_NARROW_RADIX = 4096
_NARROW_SHIFT = 12


def narrow_chunk_rows(quant_max: int) -> int:
    """Largest row chunk whose packed-pair sums stay exact (128-multiple).

    The bound: chunk * quant_max <= RADIX - 1 keeps the hess-code sum
    strictly below the radix (unpackable) and the packed grad+hess sum
    below 2^24 (exact in f32). Returns 0 when ``quant_max`` is too large
    for even a 128-row chunk — callers must keep the int32 path then."""
    c = ((_NARROW_RADIX - 1) // max(1, quant_max)) // 128 * 128
    return c if c >= 128 else 0


def _xla_histogram_narrow(binned, channels, num_bins: int, quant_max: int,
                          chunk_f: int = 0):
    """16-bit narrowed quantized histogram (reference: the narrow hist-bits
    mode of GradientDiscretizer::GetHistBitsInLeaf + the 16-bit packed
    gradient-hessian histogram entries, gradient_discretizer.cpp).

    The int8 grad/hess codes pack as ``P = qg * 4096 + qh`` and the {0,1}
    count channels as ``W = inbag * 4096 + raw`` — TWO f32 channels instead
    of four — and the one-hot contraction rides the fp32-HIGHEST MXU/BLAS
    path. Per chunk the packed sums are exact f32 integers (see
    narrow_chunk_rows), unpack to int32 with an arithmetic shift/mask pair,
    and accumulate int32 across chunks, so the result is BIT-IDENTICAL to
    the int8 x int8 -> int32 engine at half the contraction work."""
    n, f = binned.shape
    b = num_bins
    if channels.shape[1] != 4:
        raise ValueError(
            f"acc_bits=16 packs the (qgrad, qhess, inbag, raw) channel "
            f"quad; got {channels.shape[1]} channels — the narrowed "
            "engine has no packing for other channel layouts")
    chunk = narrow_chunk_rows(quant_max)
    if not chunk:
        raise ValueError(
            f"acc_bits=16 needs quant_max <= {(_NARROW_RADIX - 1) // 128} "
            f"(got {quant_max}): a 128-row chunk's code sums must stay "
            "below the packing radix")
    chunk = min(chunk, _chunk_rows(n, chunk_f or f, b))
    iota = jnp.arange(b, dtype=jnp.int32)
    radix = jnp.float32(_NARROW_RADIX)

    def pack2(ch):
        chf = ch.astype(jnp.float32)
        p = chf[:, 0] * radix + chf[:, 1]       # qg*R + qh (qh >= 0 < R)
        w = chf[:, 2] * radix + chf[:, 3]       # inbag*R + raw
        return jnp.stack([p, w], axis=1)

    def unpack2(part):
        # exact integer-valued f32 -> int32, then split each packed sum
        # with an arithmetic shift (floor division by the radix) and the
        # low-bits mask — exact for negative grad sums too
        pi = part.astype(jnp.int32)
        hi = pi >> _NARROW_SHIFT
        lo = pi & (_NARROW_RADIX - 1)
        return jnp.stack([hi[..., 0], lo[..., 0], hi[..., 1], lo[..., 1]],
                         axis=-1)               # [F, B, 4]

    def contract(bc, cc):
        onehot = (bc.astype(jnp.int32)[:, :, None] == iota) \
            .astype(jnp.float32)
        part = jnp.einsum("rfb,rk->fbk", onehot, pack2(cc),
                          precision=lax.Precision.HIGHEST)
        return unpack2(part)

    if n <= chunk:
        return contract(binned, channels)
    n_chunks = -(-n // chunk)
    pad = n_chunks * chunk - n
    if pad:
        binned = jnp.pad(binned, ((0, pad), (0, 0)))
        channels = jnp.pad(channels, ((0, pad), (0, 0)))
    binned_c = binned.reshape(n_chunks, chunk, f)
    channels_c = channels.reshape(n_chunks, chunk, channels.shape[1])

    def step(hist, inp):
        bc, cc = inp
        return hist + contract(bc, cc), None

    hist0 = jnp.zeros((f, b, 4), jnp.int32)
    hist, _ = lax.scan(step, hist0, (binned_c, channels_c))
    return hist


def dequantize_hist(hist: jax.Array, g_scale, h_scale) -> jax.Array:
    """int32 quantized histogram ``[..., 4+]`` -> f32.

    THE sanctioned int->f32 histogram boundary (tpulint R003 contract): the
    grad/hess code sums multiply by the per-iteration scales; count channels
    cast exactly (int32 counts are exact at any row count, unlike the f32
    path's 2^24 ceiling). Split finding calls this on the LEAF's int32
    per-bin sums right before gain computation (reference:
    CUDABestSplitFinder unpacks the int histogram with grad_scale/hess_scale,
    cuda_best_split_finder.cu)."""
    g = hist[..., 0:1].astype(jnp.float32) * g_scale
    h = hist[..., 1:2].astype(jnp.float32) * h_scale
    rest = hist[..., 2:].astype(jnp.float32)
    return jnp.concatenate([g, h, rest], axis=-1)


def _resolve_impl(impl: str, num_bins: int, num_features: int = 0) -> str:
    """Resolve 'auto' to a concrete implementation.

    Measured on v5e (2026-07, 1M rows x 28 features): at B=256 the Mosaic
    kernel sustains ~0.59 Telem/s of one-hot work vs ~0.007 for the chunked
    XLA einsum (which materializes the one-hot in HBM and goes
    bandwidth-bound); at B<=64 the XLA path is competitive (~0.45 Telem/s)
    because the one-hot is 4x smaller. Pallas needs the per-feature one-hot
    width to tile cleanly into 128 lanes, so it takes over at B >= 128.
    Wide F*B makes the Mosaic kernel's unrolled chunk loop spill registers
    past the VMEM budget (F=320 at B=256 wants 149MB of spill slots on
    v5e) — those configs stay on the XLA path.
    """
    if impl != "auto":
        return impl
    from ..engines.registry import on_tpu
    if (num_bins >= 128 and on_tpu()
            and num_features * num_bins <= 50_000):
        return "pallas"
    return "xla"


def histogram_block(
    binned: jax.Array,      # [BS, F] uint8
    channels: jax.Array,    # [BS, K] f32, or int8 (quantized-gradient path)
    num_bins: int,
    impl: str = "auto",
    mbatch: int = 1,
    packed4_features: int = 0,
    layout: str = "lane",
    acc_bits: int = 32,
    quant_max: int = 127,
    chunk_f: int = 0,
) -> jax.Array:             # [F, B, K] f32 (int32 for int8 channels)
    """Histogram of one already-sliced row block (no psum, no jit wrapper —
    call sites are inside jitted loops).

    Integer ``channels`` select the quantized-gradient pipeline: int8
    one-hot x int8 codes contracted with ``preferred_element_type=int32``
    (native int8 MXU throughput, exact int32 sums).

    ``mbatch`` (env/param ``tpu_hist_mbatch``) is the batched-M depth:
    the Mosaic kernel issues M = 8*mbatch MXU rows per contraction, the
    XLA engine contracts mbatch row chunks per einsum. Counts and int32
    sums are bit-identical across mbatch values.

    ``packed4_features``: the block arrives nibble-packed
    ([BS, ceil(F/2)] u8, ``tpu_bin_pack4`` — io/dataset.py pack4_matrix)
    and is unpacked here, inside the jitted block loop, so only one
    block's full width ever materializes while the HBM-resident matrix
    stays at half size. Fed by both the serving path and, since round 6,
    the pack4 TRAINING path (ops/compact.py segment_histogram with a
    ``RowLayout.packed4`` record layout).

    ``layout`` selects the Mosaic one-hot register layout
    (ops/pallas_histogram.py): "lane" keeps bins along lanes (channel-major
    output), "sublane" lays bins along sublanes for B <= 64 so the one-hot
    compare fills the register tile (tpu_hist_layout).

    ``acc_bits=16`` selects the narrowed quantized accumulation for integer
    channels (reference: GetHistBitsInLeaf): grad/hess and inbag/raw code
    pairs pack into ONE f32 channel each (exact below the packing radix,
    see narrow_chunk_rows), halving the contraction work; ``quant_max``
    must bound |code| (the trainer passes num_grad_quant_bins + 1).
    Results stay bit-identical int32.

    ``chunk_f``: feature count the XLA engines derive their row-chunk
    size from, when the call covers only a feature GROUP of a wider
    build (hist_overlap) — same chunk boundaries keep the f32 sums
    bit-identical to the full-width call."""
    if packed4_features:
        from .packed import unpack4
        binned = unpack4(binned, packed4_features)
    quantized = jnp.issubdtype(channels.dtype, jnp.integer)
    if acc_bits == 16 and quantized:
        # narrowed engine: packed f32 channels through the fp32-HIGHEST
        # contraction, exact int32 out (no Mosaic variant — the MXU's
        # int8 path already accumulates s32 natively, so narrowing buys
        # nothing there; this path wins where integer dots lack fast
        # kernels, e.g. the XLA CPU backend)
        return _xla_histogram_narrow(binned, channels, num_bins, quant_max,
                                     chunk_f=chunk_f)
    # resolve 'auto' from the FULL build width when this call covers only
    # a feature group (chunk_f): engine choice must match the ungrouped
    # call or the grouped sums lose bit-identity across the f32 engines
    impl = _resolve_impl(impl, num_bins, chunk_f or binned.shape[1])
    if impl == "pallas":
        from .pallas_histogram import pallas_histogram
        if quantized:
            return pallas_histogram(binned, channels, num_bins, mode="int8",
                                    mbatch=mbatch, hist_layout=layout)
        return pallas_histogram(binned, channels, num_bins, mbatch=mbatch,
                                hist_layout=layout)
    return _xla_histogram(binned, channels, num_bins, mbatch=mbatch,
                          chunk_f=chunk_f)


def overlap_groups(f: int, overlap: int):
    """Contiguous feature-group bounds for the async-collective overlap.

    Splits ``f`` features into ``overlap`` near-equal contiguous groups
    (empty tail groups dropped): the distributed histogram build issues
    one collective per group as soon as that group's contraction
    finishes, so group g's reduce rides under group g+1's MXU work."""
    g = max(1, int(overlap))
    per = -(-f // g)
    return [(lo, min(lo + per, f)) for lo in range(0, f, per)]


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "axis_name", "impl",
                                    "mbatch", "layout", "acc_bits",
                                    "quant_max", "overlap"))
def histogram(
    binned: jax.Array,      # [N, F] uint8/uint16/int32
    channels: jax.Array,    # [N, K] f32
    num_bins: int,          # B (static)
    axis_name: Optional[str] = None,
    impl: str = "auto",
    mbatch: int = 1,
    layout: str = "lane",
    acc_bits: int = 32,
    quant_max: int = 127,
    overlap: int = 0,
) -> jax.Array:             # [F, B, K] f32
    """Accumulate per-(feature, bin) sums of ``channels`` columns.

    ``overlap`` > 1 with an ``axis_name`` builds the histogram in that
    many contiguous feature groups with ONE psum per group, each issued
    while the next group still contracts (tpu_hist_overlap) — XLA's
    async scheduler hides the collective under the remaining MXU work.
    ``chunk_f`` pins the engines' row-chunk size to the full width, so
    the grouped sums are bit-identical to the ungrouped ones, and the
    per-element psum addends are unchanged — same bytes, same result."""
    if impl == "pallas":
        from ..engines.registry import on_tpu
        if not on_tpu():
            raise RuntimeError(
                "tpu_hist_impl=pallas requires a TPU backend; use 'xla'")
    f = binned.shape[1]
    if axis_name is not None and overlap > 1 and f > 1:
        parts = []
        for lo, hi in overlap_groups(f, overlap):
            part = histogram_block(
                binned[:, lo:hi], channels, num_bins, impl=impl,
                mbatch=mbatch, layout=layout, acc_bits=acc_bits,
                quant_max=quant_max, chunk_f=f)
            # the reduce of group g is independent of group g+1's
            # contraction: XLA issues it async (-start/-done twins)
            with span("collective_reduce"):
                parts.append(lax.psum(part, axis_name))
        return jnp.concatenate(parts, axis=0)
    hist = histogram_block(binned, channels, num_bins, impl=impl,
                           mbatch=mbatch, layout=layout, acc_bits=acc_bits,
                           quant_max=quant_max)

    if axis_name is not None:
        # distributed data-parallel: the reference reduce-scatters histograms over
        # its socket/MPI Network (src/treelearner/data_parallel_tree_learner.cpp:223-300);
        # on TPU the equivalent is a psum over the ICI mesh axis.
        with span("collective_reduce"):
            hist = lax.psum(hist, axis_name)
    return hist
