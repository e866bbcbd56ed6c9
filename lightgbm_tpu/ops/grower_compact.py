"""Leaf-wise tree growth over physically compacted row segments.

TPU-native re-design of the reference's single-device tree learner
(reference: CUDASingleGPUTreeLearner::Train,
src/treelearner/cuda/cuda_single_gpu_tree_learner.cpp:158-345 — the loop
ConstructHistogramForLeaf -> SubtractHistogramForLeaf -> FindBestSplitsForLeaf
-> FindBestFromAllSplits -> Split; CPU analogue SerialTreeLearner::Train,
src/treelearner/serial_tree_learner.cpp:179 with the smaller-child histogram
trick at :404).

This is the serial (single-chip) fast path. Where the masked grower
(ops/grower.py) streams ALL N rows per split — O(N * num_leaves) per tree —
this grower keeps every leaf's rows in a contiguous segment of a packed
row-record array (ops/compact.py):

  * each split streams only the parent's segment once to stably partition it
    (contiguous DMA + one-hot MXU compaction, no gathers/scatters);
  * the smaller child's histogram streams only that child's contiguous rows;
    the larger child is parent - smaller (histogram subtraction);
  * per-tree work is O(N * depth) instead of O(N * num_leaves) — at 255
    leaves that is a ~30-60x reduction, and it is what makes the
    Higgs-10.5M/255-leaf configuration tractable on one chip.

Carried ``extras`` columns (scores, label, weight) ride along through every
partition, so between trees all per-row state lives in the same permuted
order and nothing ever needs to be gathered back. The canonical (user-facing)
row order is only used at dataset construction and prediction time.

The whole tree grows inside one ``lax.fori_loop`` — zero host syncs per tree
(the CUDA learner ships one SplitInfo struct to host per split; we ship none).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ..obs.spans import span
from .compact import (RowLayout, partition_segment, segment_histogram,
                      segments_to_leaf_vectors)
from .fused_split import fused_split
from .grower import _RESCAN_FOLD_STRIDE, GrowerParams, TreeArrays, _NEG_INF
from .split import (apply_efb_bitset, best_split, child_output, depth_gate,
                    extend_hist_efb, leaf_output, left_rows_of_split)


class CompactState(NamedTuple):
    done: jnp.ndarray
    num_nodes: jnp.ndarray
    work: jnp.ndarray        # [N + pad, C] u8 row records (shard-local)
    scratch: jnp.ndarray     # [N + pad, C] u8 partition staging
    # per-leaf histograms are stored FLAT [L, F, B*4]: a trailing dim of 4
    # would be tiled to 128 lanes in HBM (f32 T(8,128) on the minor dims),
    # inflating the cache 32x — 17.7GB at F=529. Views reshape per split.
    leaf_hist: tuple         # per-leaf GLOBAL histograms, [L, F, B*4]; under
    #                          a mesh axis ([L, F, B*2] f32 sums, [L, F, B*2]
    #                          i32 counts) unless quantized
    leaf_hist_loc: jnp.ndarray  # [L, F, B*4] shard-local (data-parallel;
    #                             dummy [1,1,1] on the serial path)
    leaf_start: jnp.ndarray  # [L] i32 shard-local segment starts
    leaf_nrows: jnp.ndarray  # [L] i32 shard-local segment raw row counts
    leaf_nrows_g: jnp.ndarray  # [L] i32 GLOBAL raw row counts
    leaf_side: jnp.ndarray   # [L] i32 residency array of each segment
    #                          (0 = work, 1 = scratch; fused path only —
    #                          dual residency, ops/fused_split.py)
    # intermediate monotone method state (dummies when off; reference:
    # IntermediateLeafConstraints, monotone_constraints.hpp:516)
    leaf_in_mono: jnp.ndarray   # [L] bool: leaf under a monotone split
    node_parent: jnp.ndarray    # [L-1] i32 parent node (-1 = root)
    node_is_cat: jnp.ndarray    # [L-1] bool categorical split
    leaf_fmask: jnp.ndarray     # [L, F_scan] bool: scan-time feature masks
    #                             (rescans must reuse the original draw)
    # tree arrays under construction
    split_feature: jnp.ndarray
    split_bin: jnp.ndarray
    cat_bitset: jnp.ndarray    # [L-1, W] u32
    split_gain: jnp.ndarray
    default_left: jnp.ndarray
    left_child: jnp.ndarray
    right_child: jnp.ndarray
    leaf_parent: jnp.ndarray
    leaf_parent_side: jnp.ndarray
    leaf_depth: jnp.ndarray
    # per-internal-node aggregates
    node_grad: jnp.ndarray
    node_hess: jnp.ndarray
    node_cnt: jnp.ndarray
    # per-leaf aggregates
    leaf_grad: jnp.ndarray
    leaf_hess: jnp.ndarray
    leaf_cnt: jnp.ndarray
    # per-leaf cached best splits
    bs_gain: jnp.ndarray
    bs_feature: jnp.ndarray
    bs_bin: jnp.ndarray
    bs_default_left: jnp.ndarray
    bs_left_grad: jnp.ndarray
    bs_left_hess: jnp.ndarray
    bs_left_cnt: jnp.ndarray
    bs_left_rows: jnp.ndarray
    bs_bitset: jnp.ndarray     # [L, W] u32 cached categorical bitsets
    bs_cat_l2: jnp.ndarray     # [L] bool (sorted-cat split: l2 += cat_l2)
    leaf_out: jnp.ndarray      # [L] f32 outputs fixed at split time
    leaf_cmin: jnp.ndarray     # [L] f32 monotone output bounds
    leaf_cmax: jnp.ndarray     # [L] f32
    leaf_used: jnp.ndarray     # [L, F] bool path features (interaction)
    leaf_pout: jnp.ndarray     # [L] f32 smoothing context
    cegb_used: jnp.ndarray     # [F] bool (CEGB coupled costs paid once)


def reduce_over_shards(local: jnp.ndarray, axis_name: str,
                       scatter: bool = False) -> tuple:
    """A shard-local histogram [F, B, 4] summed over the mesh axis, as a
    tuple. The f32 one crosses as two arrays, (grad and hess sums
    [F, B, 2] f32, the two count channels [F, B, 2] int32): a shard's own
    counts are exact in f32 below 2^24 rows, their sum over the shards is
    not, so they are cast before the collective and summed as integers.
    An int32 one (quantized gradients) crosses whole. ``scatter``: every
    shard keeps the sum of its own F / S features (``lax.psum_scatter``)
    in place of the whole."""
    parts = ((local,) if jnp.issubdtype(local.dtype, jnp.integer)
             else (local[..., :2], local[..., 2:].astype(jnp.int32)))
    if scatter:
        return tuple(lax.psum_scatter(a, axis_name, scatter_dimension=0,
                                      tiled=True) for a in parts)
    return tuple(lax.psum(a, axis_name) for a in parts)


@functools.partial(jax.jit,
                   static_argnames=("layout", "params", "n_real"))
def grow_tree_compact(
    work: jnp.ndarray,        # [N + pad, C] u8 packed rows (current order)
    scratch: jnp.ndarray,     # [N + pad, C] u8
    num_bins_arr: jnp.ndarray,
    nan_bin_arr: jnp.ndarray,
    has_nan_arr: jnp.ndarray,
    is_cat_arr: jnp.ndarray,
    feat_mask: jnp.ndarray,
    layout: RowLayout,
    params: GrowerParams,
    n_real: int,
    mono_types: jnp.ndarray = None,
    inter_sets: jnp.ndarray = None,
    bynode_key: jnp.ndarray = None,
    cegb_coupled: jnp.ndarray = None,
    cegb_used0: jnp.ndarray = None,
    extra_key: jnp.ndarray = None,
    feature_contri: jnp.ndarray = None,
    efb=None,   # (col_of_ext, route_cat_ext, off_ext, nb_ext, dbin_ext,
    #              orig_of_ext) — see io/efb.py / gbdt._setup_efb
    quant_scales=None,   # (g_scale, h_scale) traced f32 (params.quant_hist)
    leaf_budget=None,    # i32 traced actual leaf budget (step_buckets)
    depth_budget=None,   # i32 traced actual depth bound (step_buckets)
):
    """Grow one tree; returns (TreeArrays, row_leaf [N], work', scratch',
    leaf_start [L], leaf_nrows [L]) — per-row outputs in the post-tree
    permuted row order. (Callers expand per-row leaf values themselves via
    segments_to_leaf_vectors once shrinkage/renewal are applied.)

    ``params.quant_hist``: the grad/hess row columns carry integer
    discretizer codes; every histogram accumulates int8 x int8 -> int32 on
    the MXU and stays int32 through caching/subtraction/reduction (exact
    integer arithmetic while global num_data * quant_bins < 2^31; the
    GBDT gates the path on that bound), dequantizing with
    ``quant_scales`` only at the split scan and the scalar leaf sums.

    ``params.hist_scatter`` = S > 1 (data-parallel): per-leaf histograms
    reduce with ``lax.psum_scatter`` over the feature axis — each shard
    owns the GLOBAL histogram of F/S features, scans its own slice, and
    the tiny winning candidates sync with an all-gather (the reference's
    ReduceScatter + SyncUpGlobalBestSplit protocol,
    data_parallel_tree_learner.cpp:223-300) — instead of all-reducing the
    full [F, B, 4] histogram to every shard. Requires efb_virtual == 0 and
    mono_intermediate off (their scans need cross-feature histogram
    access).

    Under a mesh axis every count that crosses shards is an int32: the
    reduced histogram is the pair (grad and hess sums [F, B, 2] f32, the
    two count channels [F, B, 2] i32), a shard's own counts (f32, exact
    below 2^24 rows a shard) cast to integers before the collective, and
    a leaf's and a node's count, the root's totals and what the scan
    holds against ``min_data_in_leaf`` stay integers to the tree's
    ``leaf_count`` and ``internal_count``: exact below 2^31 rows in all.
    Without an axis the histogram and its counts are one f32 array."""
    n = n_real
    L = params.num_leaves
    B = params.num_bins
    if params.step_buckets and leaf_budget is None:
        raise ValueError("params.step_buckets needs the traced leaf_budget "
                         "(the rung is the jit key, not the leaf count)")
    if params.step_buckets and params.max_depth > 0 and depth_budget is None:
        raise ValueError("params.step_buckets with the bounded depth "
                         "bucket needs the traced depth_budget (max_depth "
                         "is the bucket sentinel, not the actual bound)")
    dbudget = depth_budget if (params.step_buckets
                               and params.max_depth > 0) else None
    if layout.packed4 and B > 16:
        raise ValueError(
            f"RowLayout.packed4 needs every bin value to fit a nibble "
            f"(num_bins <= 16, got {B}) — tpu_bin_pack4 training is only "
            "eligible when all stored columns realize <= 16 bins")
    if bool(params.bin_pack4) != bool(layout.packed4):
        raise ValueError(
            "GrowerParams.bin_pack4 and RowLayout.packed4 disagree — the "
            "trainer must thread the pack4 decision through both (the "
            "layout drives the kernels, the param the analysis rules)")
    F = layout.num_features          # stored columns (histogram space)
    F_scan = F + params.efb_virtual  # + virtual EFB features (scan space)
    feat_info = (num_bins_arr, nan_bin_arr, has_nan_arr, is_cat_arr)
    sp_params = params.split_params()
    i32 = jnp.int32
    quant = params.quant_hist
    if quant and quant_scales is None:
        raise ValueError("params.quant_hist needs quant_scales=(g_s, h_s)")
    hdtype = jnp.float32
    if quant:
        hdtype = jnp.int32
        g_scale, h_scale = quant_scales

    def dq_g(x):    # dequantize scalar/array grad code sums
        return x.astype(jnp.float32) * g_scale if quant else x

    def dq_h(x):
        return x.astype(jnp.float32) * h_scale if quant else x

    W = params.bitset_words
    zero = jnp.asarray(0, i32)
    ax = params.axis_name
    # counts across shards are integers (docstring); a serial run's are
    # the f32 channels of its one histogram
    cdtype = i32 if ax else jnp.float32

    def dq_c(x):    # count channels: exact integer -> f32 cast
        return x.astype(jnp.float32) if quant and not ax else x

    if mono_types is None:
        mono_types = jnp.zeros((F_scan,), jnp.int8)
    if inter_sets is None:
        inter_sets = jnp.zeros((0, F_scan), bool)
    if bynode_key is None:
        bynode_key = jax.random.PRNGKey(0)
    if cegb_coupled is None:
        cegb_coupled = jnp.zeros((F_scan,), jnp.float32)
    if cegb_used0 is None:
        cegb_used0 = jnp.zeros((F_scan,), bool)
    if extra_key is None:
        extra_key = jax.random.PRNGKey(6)
    big = jnp.float32(3.4e38)

    # ---- feature-scattered histogram reduction (data-parallel) ----
    scatter = params.hist_scatter > 1
    if scatter and ax is None:
        raise ValueError("hist_scatter needs a data-parallel mesh axis")
    if scatter and (params.efb_virtual or params.mono_intermediate):
        raise ValueError("hist_scatter is incompatible with EFB bundles "
                         "and monotone_constraints_method=intermediate")
    if scatter:
        S_sc = params.hist_scatter
        F_loc = -(-F // S_sc)          # features owned per shard
        f_pad_sc = F_loc * S_sc - F
        shard_i = lax.axis_index(ax)

        def _pad_f(a, fill):
            return jnp.pad(a, (0, f_pad_sc), constant_values=fill) \
                if f_pad_sc else a

        # metadata slices for the shard's own features (pad features get
        # num_bins=1 + mask False, so they can never win a split)
        def _fslice(a):
            return lax.dynamic_slice_in_dim(a, shard_i * F_loc, F_loc)

        meta_sl = tuple(_fslice(_pad_f(a, fill)) for a, fill in (
            (num_bins_arr, 1), (nan_bin_arr, 0), (has_nan_arr, False),
            (is_cat_arr, False)))
        mono_sl = _fslice(_pad_f(mono_types, 0))
        contri_sl = (_fslice(_pad_f(feature_contri, 1.0))
                     if feature_contri is not None else None)
        F_h = F_loc                    # cached-histogram feature width
    else:
        F_h = F

    tmap = jax.tree_util.tree_map

    def hist_cat(parts):
        """Reduced histograms joined along the feature axis."""
        return tmap(lambda *a: jnp.concatenate(a, axis=0), *parts)

    def reduce_hist(local):
        """[F, B, 4] shard-local -> globally-summed histogram (full copy,
        or this shard's [F_loc, B, .] feature slice under hist_scatter)."""
        if not ax:
            return (local,)
        if scatter and f_pad_sc:
            local = jnp.pad(local, ((0, f_pad_sc), (0, 0), (0, 0)))
        return _reduce_group(local)

    def sync_split(sp):
        """All-gather the per-shard best-split candidates and return the
        global winner on every shard (reference: SyncUpGlobalBestSplit,
        parallel_tree_learner.h) — a few dozen bytes instead of the full
        histogram."""
        gains = lax.all_gather(sp.gain, ax)                 # [S]
        win = jnp.argmax(gains).astype(i32)
        return type(sp)(*(lax.all_gather(v, ax)[win] for v in sp))

    def leaf_best(hist, pg, ph, pc, depth, fm, cmn, cmx, po, cegb_pen=None,
                  ek=None):
        with span("split_scan"):
            return _leaf_best(hist, pg, ph, pc, depth, fm, cmn, cmx, po,
                              cegb_pen, ek)

    def _leaf_best(hist, pg, ph, pc, depth, fm, cmn, cmx, po, cegb_pen,
                   ek):
        if params.efb_virtual:
            # scan axis = stored columns + one virtual row per bundled
            # original feature (io/efb.py); exact in int32 when quantized
            hist = tmap(lambda a: extend_hist_efb(
                a, efb, params.efb_virtual, params.efb_bmax), hist)
        qs = quant_scales if quant else None
        # the reduced histogram's integer counts, where it has them
        counts = (None if not ax else hist[0][..., 2:] if quant
                  else hist[1])
        hist = hist[0]
        if scatter:
            sp = best_split(hist, pg, ph, pc, *meta_sl,
                            _fslice(_pad_f(fm, False)), sp_params,
                            mono_sl, cmn, cmx, po, depth,
                            (_fslice(_pad_f(cegb_pen, 0.0))
                             if cegb_pen is not None else None),
                            ek, contri_sl, quant_scales=qs, counts=counts)
            # local winner -> global feature id, then the tiny cross-shard
            # candidate exchange picks one winner bit-identically everywhere
            sp = sp._replace(feature=shard_i * F_loc + sp.feature)
            sp = sync_split(sp)
        else:
            sp = best_split(hist, pg, ph, pc, *feat_info, fm, sp_params,
                            mono_types, cmn, cmx, po, depth, cegb_pen, ek,
                            feature_contri, quant_scales=qs, counts=counts)
        if params.efb_virtual:
            # a bundled winner routes as a ready-made bitset on its column
            sp = apply_efb_bitset(sp, efb, F, B)
        return sp._replace(gain=depth_gate(sp.gain, depth, params.max_depth,
                                           dbudget))

    def seg_hist(work, start, count, cols=None):
        with span("hist_build"):
            return _seg_hist(work, start, count, cols)

    def _seg_hist(work, start, count, cols=None):
        # ``cols``: static stored-column subset of a hist_overlap feature
        # group; chunk_f pins the engines' row chunking to the full width
        # so the group build matches the ungrouped histogram bitwise
        chunk_f = F if cols is not None else 0

        def hist_with(acc_bits):
            def fn(args):
                w, s_, c_ = args
                return segment_histogram(
                    w, s_, c_, layout, B, params.hist_block,
                    params.hist_impl, quantized=quant,
                    mbatch=params.hist_mbatch, acc_bits=acc_bits,
                    quant_max=params.quant_max,
                    hist_layout=params.hist_layout,
                    feat_idx=cols, chunk_f=chunk_f)
            return fn

        if quant and params.quant_narrow:
            # per-leaf hist-bits renewal (reference: GetHistBitsInLeaf,
            # renewed as leaves shrink): narrow leaves take the packed-pair
            # 16-bit engine, wide leaves the int8/int32 engine — both
            # branches return identical int32 [F, B, 4] sums, so the cond
            # is a pure engine-selection with bit-identical results
            from .renew import hist_bits_in_leaf
            bits = hist_bits_in_leaf(count, params.quant_max)
            return lax.cond(bits == 16, hist_with(16), hist_with(32),
                            (work, start, count))
        return hist_with(32)((work, start, count))

    # ---- async histogram-collective overlap (tpu_hist_overlap) ----
    # Build the per-leaf histogram in G feature groups and reduce each
    # group with its OWN collective, issued while the next group's walk
    # still accumulates — XLA's async scheduler hides the psum/
    # psum_scatter under the remaining MXU contraction. Grouping never
    # changes which shard-local addends reach an element, so trees stay
    # bit-identical and total collective bytes are unchanged.
    G = params.hist_overlap if (ax and params.hist_overlap > 1) else 0
    if G:
        from .histogram import overlap_groups
        _gb = overlap_groups(F_h, G)      # bounds over the owned width
        if len(_gb) < 2:
            G = 0                          # one feature: nothing to group
    # the fused Mosaic kernel and packed4 walks produce the local
    # histogram whole — they keep the single build and group only the
    # reduction (collective-collective pipelining, no compute overlap)
    grouped_build = bool(G) and not params.fused_block \
        and not layout.packed4

    def _reduce_group(part):
        with span("collective_reduce"):
            return reduce_over_shards(part, ax, scatter)

    def _grouped_reduce(local):
        """reduce_hist with one collective per feature group (the
        precomputed-local path: fused kernel / packed4 walks)."""
        parts = []
        if scatter:
            padded = jnp.pad(local, ((0, f_pad_sc), (0, 0), (0, 0))) \
                if f_pad_sc else local
            resh = padded.reshape(S_sc, F_loc, B, 4)
            for lo, hi in _gb:
                parts.append(_reduce_group(
                    resh[:, lo:hi].reshape(S_sc * (hi - lo), B, 4)))
        else:
            for lo, hi in _gb:
                parts.append(_reduce_group(local[lo:hi]))
        return hist_cat(parts)

    def reduce_any(local):
        return _grouped_reduce(local) if G else reduce_hist(local)

    def seg_hist_reduced(work, start, count):
        """(local [F, B, 4], reduced [F_h, B, 4]) histogram of one leaf
        segment. Under hist_overlap each feature group's collective is
        constructed right after that group's streamed walk, dependence-
        free of the later groups — the overlap the reference gets from
        its socket ReduceScatter running beside the next group's kernel
        (data_parallel_tree_learner.cpp:223-300)."""
        if not grouped_build:
            loc = seg_hist(work, start, count)
            return loc, reduce_any(loc)
        parts_loc, parts_red, all_cols = [], [], []
        for lo, hi in _gb:
            if scatter:
                # group g owns sub-range [lo, hi) of EVERY shard's feature
                # slice, so the reassembled scatter output keeps the
                # ownership map (shard i <-> global [i*F_loc, (i+1)*F_loc))
                pos = [i * F_loc + t
                       for i in range(S_sc) for t in range(lo, hi)]
                cols = [p for p in pos if p < F]
            else:
                pos = cols = list(range(lo, hi))
            loc_g = seg_hist(work, start, count, cols=tuple(cols))
            part = loc_g
            if len(cols) < len(pos):
                # pad features (scatter rounding) carry zero histograms
                idx = [j for j, p in enumerate(pos) if p < F]
                part = jnp.zeros((len(pos), B, 4), loc_g.dtype) \
                    .at[jnp.asarray(idx, i32)].set(loc_g)
            parts_loc.append(loc_g)
            all_cols.extend(cols)
            parts_red.append(_reduce_group(part))
        loc_cat = jnp.concatenate(parts_loc, axis=0)
        if scatter:
            loc_full = jnp.zeros((F, B, 4), loc_cat.dtype) \
                .at[jnp.asarray(all_cols, i32)].set(loc_cat)
        else:
            loc_full = loc_cat
        return loc_full, hist_cat(parts_red)

    # ---- root ----
    if params.fused_block:
        # hist-only mode of the fused Mosaic kernel (ops/fused_split.py)
        with span("hist_build"):
            work, scratch, root_loc = fused_split(
                work, scratch, jnp.asarray(1, i32), zero,
                jnp.asarray(n, i32), zero, zero, zero, zero, zero, zero,
                jnp.zeros((W,), jnp.uint32), layout, B, params.fused_block,
                W, interpret=params.fused_interpret, dual=params.fused_dual,
                hist_debug=params.fused_hist_debug, num_rows=n, quant=quant,
                mbatch=params.hist_mbatch, hist_layout=params.hist_layout,
                name="fused_split_root")
        root_hist = reduce_any(root_loc)
    else:
        # data-parallel: histograms reduce over the mesh axis (reference:
        # the ReduceScatter of per-feature histograms,
        # data_parallel_tree_learner.cpp:223-300); split decisions then
        # replicate bit-identically
        root_loc, root_hist = seg_hist_reduced(
            work, jnp.asarray(0, i32), jnp.asarray(n, i32))
    # every feature's bins sum to the global totals (each row lands in
    # exactly one bin per feature), so feature 0 gives the root sums;
    # under hist_scatter the shard's slice may be all padding, so the
    # totals come from the LOCAL histogram + a tiny scalar psum instead
    if scatter:
        # a shard's own count is exact in its f32 channel; the shards'
        # counts are summed as integers
        root_g, root_h = lax.psum(jnp.stack(
            [root_loc[0, :, 0].sum(), root_loc[0, :, 1].sum()]), ax)
        root_c = lax.psum(root_loc[0, :, 2].sum().astype(i32), ax)
    else:
        root_g, root_h = (root_hist[0][0, :, ch].sum() for ch in (0, 1))
        root_c = (root_hist[-1][0, :, 0].sum() if ax and not quant
                  else root_hist[0][0, :, 2].sum())
    root_g, root_h, root_c = dq_g(root_g), dq_h(root_h), dq_c(root_c)
    from .grower import node_feature_mask
    root_fm = node_feature_mask(
        feat_mask, jnp.zeros((F_scan,), bool), inter_sets,
        jax.random.fold_in(bynode_key, 0), params)
    # path smoothing at the root smooths toward the root's own output
    # (reference: GetParentOutput, serial_tree_learner.cpp:1005-1016)
    root_out = leaf_output(root_g, root_h, sp_params)
    sp0 = leaf_best(root_hist, root_g, root_h, root_c, jnp.asarray(0, i32),
                    root_fm, -big, big, root_out,
                    cegb_coupled * jnp.logical_not(cegb_used0),
                    jax.random.fold_in(extra_key, 0))

    n_g = (n * lax.psum(jnp.asarray(1, i32), ax)) if ax \
        else jnp.asarray(n, i32)
    st = CompactState(
        done=jnp.asarray(False),
        num_nodes=jnp.asarray(0, i32),
        work=work,
        scratch=scratch,
        leaf_hist=tmap(lambda a: jnp.zeros((L, F_h, a[0].size), a.dtype)
                       .at[0].set(a.reshape(F_h, -1)), root_hist),
        leaf_hist_loc=(jnp.zeros((L, F, B * 4), hdtype).at[0]
                       .set(root_loc.reshape(F, B * 4)) if ax
                       else jnp.zeros((1, 1, 1), hdtype)),
        leaf_start=jnp.zeros((L,), i32),
        leaf_nrows=jnp.zeros((L,), i32).at[0].set(n),
        leaf_nrows_g=(jnp.zeros((L,), i32).at[0].set(n_g) if ax
                      else jnp.zeros((1,), i32)),
        leaf_side=jnp.zeros((L,), i32),
        leaf_in_mono=(jnp.zeros((L,), bool) if params.mono_intermediate
                      else jnp.zeros((1,), bool)),
        node_parent=(jnp.full((L - 1,), -1, i32) if params.mono_intermediate
                     else jnp.zeros((1,), i32)),
        node_is_cat=(jnp.zeros((L - 1,), bool) if params.mono_intermediate
                     else jnp.zeros((1,), bool)),
        leaf_fmask=(jnp.zeros((L, F_scan), bool).at[0].set(root_fm)
                    if params.mono_intermediate
                    else jnp.zeros((1, 1), bool)),
        split_feature=jnp.full((L - 1,), -1, i32),
        split_bin=jnp.zeros((L - 1,), i32),
        cat_bitset=jnp.zeros((L - 1, W), jnp.uint32),
        split_gain=jnp.zeros((L - 1,), jnp.float32),
        default_left=jnp.zeros((L - 1,), bool),
        left_child=jnp.full((L - 1,), -1, i32),
        right_child=jnp.full((L - 1,), -1, i32),
        leaf_parent=jnp.full((L,), -1, i32),
        leaf_parent_side=jnp.zeros((L,), i32),
        leaf_depth=jnp.zeros((L,), i32),
        node_grad=jnp.zeros((L - 1,), jnp.float32),
        node_hess=jnp.zeros((L - 1,), jnp.float32),
        node_cnt=jnp.zeros((L - 1,), cdtype),
        leaf_grad=jnp.zeros((L,), jnp.float32).at[0].set(root_g),
        leaf_hess=jnp.zeros((L,), jnp.float32).at[0].set(root_h),
        leaf_cnt=jnp.zeros((L,), cdtype).at[0].set(root_c),
        bs_gain=jnp.full((L,), _NEG_INF, jnp.float32).at[0].set(sp0.gain),
        bs_feature=jnp.zeros((L,), i32).at[0].set(sp0.feature),
        bs_bin=jnp.zeros((L,), i32).at[0].set(sp0.bin),
        bs_default_left=jnp.zeros((L,), bool).at[0].set(sp0.default_left),
        bs_left_grad=jnp.zeros((L,), jnp.float32).at[0].set(sp0.left_grad),
        bs_left_hess=jnp.zeros((L,), jnp.float32).at[0].set(sp0.left_hess),
        bs_left_cnt=jnp.zeros((L,), cdtype).at[0].set(sp0.left_count),
        bs_left_rows=jnp.zeros((L,), i32).at[0].set(
            sp0.left_rows.astype(i32)),
        bs_bitset=jnp.zeros((L, W), jnp.uint32).at[0].set(sp0.cat_bitset),
        bs_cat_l2=jnp.zeros((L,), bool).at[0].set(sp0.is_cat_l2),
        leaf_out=jnp.zeros((L,), jnp.float32).at[0].set(root_out),
        leaf_cmin=jnp.full((L,), -3.4e38, jnp.float32),
        leaf_cmax=jnp.full((L,), 3.4e38, jnp.float32),
        leaf_used=jnp.zeros((L, F_scan), bool),
        leaf_pout=jnp.zeros((L,), jnp.float32).at[0].set(root_out),
        cegb_used=cegb_used0,
    )

    def body(k, st: CompactState) -> CompactState:
        # ---- FindBestFromAllSplits (reference: cuda_best_split_finder.cu:2113) ----
        leaf_alive = jnp.arange(L) <= k
        gains = jnp.where(leaf_alive, st.bs_gain, _NEG_INF)
        best_leaf = jnp.argmax(gains).astype(i32)
        valid = gains[best_leaf] > 0.0
        if params.step_buckets:
            # rounds past the traced leaf budget are inert: the rung's
            # remaining iterations stream zero-trip partition/histogram
            # walks, exactly like a post-early-stop round
            valid = jnp.logical_and(valid, k < leaf_budget - 1)
        applied = jnp.logical_and(valid, jnp.logical_not(st.done))
        done = jnp.logical_or(st.done, jnp.logical_not(valid))

        node = k
        new_leaf = jnp.asarray(k + 1, i32)

        f_ = st.bs_feature[best_leaf]
        b_ = st.bs_bin[best_leaf]
        dl = st.bs_default_left[best_leaf]
        n_left = st.bs_left_rows[best_leaf]
        bits = st.bs_bitset[best_leaf]
        catl2 = st.bs_cat_l2[best_leaf]
        if params.efb_virtual:
            # EFB: the scan index translates to (stored column, routing
            # mode, original feature id); bundled winners carry a ready
            # bitset (apply_efb_bitset) and route like categorical splits
            f_col = efb[0][f_]
            f_cat = efb[1][f_]
            f_orig = efb[5][f_]
        else:
            f_col = f_
            f_cat = is_cat_arr[f_]
            f_orig = f_

        # ---- record split; wire tree structure ----
        split_feature = st.split_feature.at[node].set(
            jnp.where(applied, f_orig, -1))
        split_bin = st.split_bin.at[node].set(jnp.where(applied, b_, 0))
        cat_bitset = st.cat_bitset.at[node].set(jnp.where(applied, bits, 0))
        split_gain = st.split_gain.at[node].set(
            jnp.where(applied, st.bs_gain[best_leaf], 0.0))
        default_left = st.default_left.at[node].set(jnp.where(applied, dl, False))
        p = st.leaf_parent[best_leaf]
        side = st.leaf_parent_side[best_leaf]
        p_idx = jnp.maximum(p, 0)
        left_child = st.left_child.at[p_idx].set(
            jnp.where(applied & (p >= 0) & (side == 0), node,
                      st.left_child[p_idx]))
        right_child = st.right_child.at[p_idx].set(
            jnp.where(applied & (p >= 0) & (side == 1), node,
                      st.right_child[p_idx]))
        left_child = left_child.at[node].set(
            jnp.where(applied, -(best_leaf + 1), left_child[node]))
        right_child = right_child.at[node].set(
            jnp.where(applied, -(new_leaf + 1), right_child[node]))
        leaf_parent = st.leaf_parent.at[best_leaf].set(
            jnp.where(applied, node, st.leaf_parent[best_leaf]))
        leaf_parent = leaf_parent.at[new_leaf].set(
            jnp.where(applied, node, leaf_parent[new_leaf]))
        leaf_parent_side = st.leaf_parent_side.at[best_leaf].set(
            jnp.where(applied, 0, st.leaf_parent_side[best_leaf]))
        leaf_parent_side = leaf_parent_side.at[new_leaf].set(
            jnp.where(applied, 1, leaf_parent_side[new_leaf]))

        # ---- per-leaf aggregates for the two children ----
        lg, lh, lc = (st.bs_left_grad[best_leaf], st.bs_left_hess[best_leaf],
                      st.bs_left_cnt[best_leaf])
        pg, ph, pc = (st.leaf_grad[best_leaf], st.leaf_hess[best_leaf],
                      st.leaf_cnt[best_leaf])
        rg, rh, rc = pg - lg, ph - lh, pc - lc
        node_grad = st.node_grad.at[node].set(jnp.where(applied, pg, 0.0))
        node_hess = st.node_hess.at[node].set(jnp.where(applied, ph, 0.0))
        node_cnt = st.node_cnt.at[node].set(jnp.where(applied, pc, 0))
        d_child = st.leaf_depth[best_leaf] + 1
        leaf_grad = st.leaf_grad.at[best_leaf].set(jnp.where(applied, lg, pg))
        leaf_grad = leaf_grad.at[new_leaf].set(
            jnp.where(applied, rg, leaf_grad[new_leaf]))
        leaf_hess = st.leaf_hess.at[best_leaf].set(jnp.where(applied, lh, ph))
        leaf_hess = leaf_hess.at[new_leaf].set(
            jnp.where(applied, rh, leaf_hess[new_leaf]))
        leaf_cnt = st.leaf_cnt.at[best_leaf].set(jnp.where(applied, lc, pc))
        leaf_cnt = leaf_cnt.at[new_leaf].set(
            jnp.where(applied, rc, leaf_cnt[new_leaf]))
        leaf_depth = st.leaf_depth.at[best_leaf].set(
            jnp.where(applied, d_child, st.leaf_depth[best_leaf]))
        leaf_depth = leaf_depth.at[new_leaf].set(
            jnp.where(applied, d_child, leaf_depth[new_leaf]))
        l2_used = params.lambda_l2 + params.cat_l2 * catl2.astype(jnp.float32)
        cminp = st.leaf_cmin[best_leaf]
        cmaxp = st.leaf_cmax[best_leaf]
        poutp = st.leaf_pout[best_leaf]
        lw = child_output(lg, lh, lc, sp_params, l2_used, poutp, cminp, cmaxp)
        rw = child_output(rg, rh, rc, sp_params, l2_used, poutp, cminp, cmaxp)
        leaf_out = st.leaf_out.at[best_leaf].set(
            jnp.where(applied, lw, st.leaf_out[best_leaf]))
        leaf_out = leaf_out.at[new_leaf].set(
            jnp.where(applied, rw, leaf_out[new_leaf]))
        leaf_pout = st.leaf_pout.at[best_leaf].set(
            jnp.where(applied, lw, poutp))
        leaf_pout = leaf_pout.at[new_leaf].set(
            jnp.where(applied, rw, leaf_pout[new_leaf]))
        iscat_split = is_cat_arr[f_]
        if params.use_monotone:
            mt = mono_types[f_].astype(jnp.int32)
            act = applied & jnp.logical_not(iscat_split)
            if params.mono_intermediate:
                # intermediate method: children bound by the SIBLING's
                # actual output, not the midpoint (reference:
                # UpdateConstraintsWithOutputs, monotone_constraints
                # .hpp:546-560)
                cmax_l = jnp.where(act & (mt > 0),
                                   jnp.minimum(cmaxp, rw), cmaxp)
                cmin_l = jnp.where(act & (mt < 0),
                                   jnp.maximum(cminp, rw), cminp)
                cmin_r = jnp.where(act & (mt > 0),
                                   jnp.maximum(cminp, lw), cminp)
                cmax_r = jnp.where(act & (mt < 0),
                                   jnp.minimum(cmaxp, lw), cmaxp)
            else:
                mid = 0.5 * (lw + rw)
                cmax_l = jnp.where(act & (mt > 0),
                                   jnp.minimum(cmaxp, mid), cmaxp)
                cmin_l = jnp.where(act & (mt < 0),
                                   jnp.maximum(cminp, mid), cminp)
                cmin_r = jnp.where(act & (mt > 0),
                                   jnp.maximum(cminp, mid), cminp)
                cmax_r = jnp.where(act & (mt < 0),
                                   jnp.minimum(cmaxp, mid), cmaxp)
        else:
            cmax_l = cmax_r = cmaxp
            cmin_l = cmin_r = cminp
        leaf_cmin = st.leaf_cmin.at[best_leaf].set(
            jnp.where(applied, cmin_l, cminp))
        leaf_cmin = leaf_cmin.at[new_leaf].set(
            jnp.where(applied, cmin_r, leaf_cmin[new_leaf]))
        leaf_cmax = st.leaf_cmax.at[best_leaf].set(
            jnp.where(applied, cmax_l, cmaxp))
        leaf_cmax = leaf_cmax.at[new_leaf].set(
            jnp.where(applied, cmax_r, leaf_cmax[new_leaf]))
        used_child = st.leaf_used[best_leaf] | (jnp.arange(F_scan) == f_)
        leaf_used = st.leaf_used.at[best_leaf].set(
            jnp.where(applied, used_child, st.leaf_used[best_leaf]))
        leaf_used = leaf_used.at[new_leaf].set(
            jnp.where(applied, used_child, leaf_used[new_leaf]))
        cegb_used = st.cegb_used | (applied & (jnp.arange(F_scan) == f_))

        # ---- physical partition + children histograms + best splits ----
        # NO lax.cond around the heavy buffers: a cond output forces XLA to
        # copy the carried work/scratch arrays (~1.4 GB) every split. The
        # not-applied case instead zeroes the loop trip counts, so the same
        # program runs with empty partition/histogram walks.
        s_ = st.leaf_start[best_leaf]
        m_loc = st.leaf_nrows[best_leaf]
        if ax:
            # global split decision, LOCAL partition offsets: this shard's
            # left count comes from its own histogram (reference keeps
            # global_data_count_in_leaf_ beside the local partition,
            # data_parallel_tree_learner.cpp:300-340)
            m_g = st.leaf_nrows_g[best_leaf]
            parent_loc = st.leaf_hist_loc[best_leaf].reshape(F, B, 4)
            n_left_loc = left_rows_of_split(
                parent_loc, f_col, b_, dl, nan_bin_arr[f_], f_cat, bits)
        else:
            m_g = m_loc
            parent_loc = None
            n_left_loc = n_left
        n_right_g = m_g - n_left
        n_right_loc = m_loc - n_left_loc
        # the GLOBALLY smaller child is streamed on every shard, so the
        # psum-ed histograms all describe the same child
        left_smaller = n_left <= n_right_g
        m_eff = jnp.where(applied, m_loc, 0)
        n_left_eff = jnp.where(applied, n_left_loc, 0)

        # stable partition of the parent's contiguous segment
        # (reference: DataPartition::Split / cuda_data_partition.cu:907)
        side_p = st.leaf_side[best_leaf]
        if params.fused_block:
            # one fused Mosaic kernel: partition + smaller-child histogram
            # in a single streamed walk (ops/fused_split.py); the left child
            # stays in the parent's residency array, the right child lands
            # in the other one (dual residency — no copy-back pass)
            with span("partition"), span("hist_build"):
                work, scratch, hist_small_fused = fused_split(
                    st.work, st.scratch, jnp.asarray(0, i32), s_, m_eff,
                    n_left_eff, f_col, b_, dl, nan_bin_arr[f_], f_cat,
                    bits, layout, B, params.fused_block, W,
                    interpret=params.fused_interpret,
                    smaller_left=left_smaller.astype(i32), side=side_p,
                    dual=params.fused_dual,
                    hist_debug=params.fused_hist_debug,
                    num_rows=n, quant=quant, mbatch=params.hist_mbatch,
                    hist_layout=params.hist_layout, name="fused_split_step")
        else:
            with span("partition"):
                work, scratch = partition_segment(
                    st.work, st.scratch, s_, m_eff, n_left_eff, f_col, b_,
                    dl, nan_bin_arr[f_], f_cat, bits, params.part_block,
                    packed4=layout.packed4)
        leaf_start = st.leaf_start.at[best_leaf].set(
            jnp.where(applied, s_, st.leaf_start[best_leaf]))
        leaf_start = leaf_start.at[new_leaf].set(
            jnp.where(applied, s_ + n_left_loc, leaf_start[new_leaf]))
        leaf_nrows = st.leaf_nrows.at[best_leaf].set(
            jnp.where(applied, n_left_loc, st.leaf_nrows[best_leaf]))
        leaf_nrows = leaf_nrows.at[new_leaf].set(
            jnp.where(applied, n_right_loc, leaf_nrows[new_leaf]))
        if ax:
            leaf_nrows_g = st.leaf_nrows_g.at[best_leaf].set(
                jnp.where(applied, n_left, st.leaf_nrows_g[best_leaf]))
            leaf_nrows_g = leaf_nrows_g.at[new_leaf].set(
                jnp.where(applied, n_right_g, leaf_nrows_g[new_leaf]))
        else:
            leaf_nrows_g = st.leaf_nrows_g
        if params.fused_block and params.fused_dual:
            leaf_side = st.leaf_side.at[new_leaf].set(
                jnp.where(applied, 1 - side_p, st.leaf_side[new_leaf]))
        else:
            leaf_side = st.leaf_side

        # one streamed pass over the SMALLER child only; the larger child
        # is parent - smaller (reference: SubtractHistogramForLeaf,
        # cuda_histogram_constructor.cu:723); exact in int32 when quantized
        parent_hist = tmap(lambda a: a[best_leaf].reshape(F_h, B, -1),
                           st.leaf_hist)
        if params.fused_block:
            hist_small_loc = hist_small_fused
            hist_small = reduce_any(hist_small_loc)
        else:
            s_small = jnp.where(left_smaller, s_, s_ + n_left_loc)
            m_small = jnp.where(left_smaller, n_left_eff,
                                m_eff - n_left_eff)
            hist_small_loc, hist_small = seg_hist_reduced(
                work, s_small, m_small)
        hist_large = tmap(jnp.subtract, parent_hist, hist_small)
        hist_left = tmap(lambda a, b: jnp.where(left_smaller, a, b),
                         hist_small, hist_large)
        hist_right = tmap(lambda a, b: jnp.where(left_smaller, a, b),
                          hist_large, hist_small)
        leaf_hist = tmap(
            lambda cache, left, parent: cache.at[best_leaf].set(
                jnp.where(applied, left, parent).reshape(F_h, -1)),
            st.leaf_hist, hist_left, parent_hist)
        leaf_hist = tmap(
            lambda cache, right: cache.at[new_leaf].set(
                jnp.where(applied, right.reshape(F_h, -1), cache[new_leaf])),
            leaf_hist, hist_right)
        if ax:
            large_loc = parent_loc - hist_small_loc
            left_loc = jnp.where(left_smaller, hist_small_loc, large_loc)
            right_loc = jnp.where(left_smaller, large_loc, hist_small_loc)
            leaf_hist_loc = st.leaf_hist_loc.at[best_leaf].set(
                jnp.where(applied, left_loc, parent_loc)
                .reshape(F, B * 4))
            leaf_hist_loc = leaf_hist_loc.at[new_leaf].set(
                jnp.where(applied, right_loc.reshape(F, B * 4),
                          leaf_hist_loc[new_leaf]))
        else:
            leaf_hist_loc = st.leaf_hist_loc

        fm_l = node_feature_mask(
            feat_mask, used_child, inter_sets,
            jax.random.fold_in(bynode_key, 2 * k + 1), params)
        fm_r = node_feature_mask(
            feat_mask, used_child, inter_sets,
            jax.random.fold_in(bynode_key, 2 * k + 2), params)
        pen = cegb_coupled * jnp.logical_not(cegb_used)
        spl = leaf_best(hist_left, lg, lh, lc, d_child, fm_l,
                        cmin_l, cmax_l, lw, pen,
                        jax.random.fold_in(extra_key, 2 * k + 1))
        spr = leaf_best(hist_right, rg, rh, rc, d_child, fm_r,
                        cmin_r, cmax_r, rw, pen,
                        jax.random.fold_in(extra_key, 2 * k + 2))
        (bs_gain, bs_feature, bs_bin, bs_dl, bs_lg, bs_lh, bs_lc, bs_lr,
         bs_bits, bs_catl2) = (st.bs_gain, st.bs_feature, st.bs_bin,
                               st.bs_default_left, st.bs_left_grad,
                               st.bs_left_hess, st.bs_left_cnt,
                               st.bs_left_rows, st.bs_bitset, st.bs_cat_l2)
        for leaf, sp in ((best_leaf, spl), (new_leaf, spr)):
            bs_gain = bs_gain.at[leaf].set(
                jnp.where(applied, sp.gain, bs_gain[leaf]))
            bs_feature = bs_feature.at[leaf].set(
                jnp.where(applied, sp.feature, bs_feature[leaf]))
            bs_bin = bs_bin.at[leaf].set(
                jnp.where(applied, sp.bin, bs_bin[leaf]))
            bs_dl = bs_dl.at[leaf].set(
                jnp.where(applied, sp.default_left, bs_dl[leaf]))
            bs_lg = bs_lg.at[leaf].set(
                jnp.where(applied, sp.left_grad, bs_lg[leaf]))
            bs_lh = bs_lh.at[leaf].set(
                jnp.where(applied, sp.left_hess, bs_lh[leaf]))
            bs_lc = bs_lc.at[leaf].set(
                jnp.where(applied, sp.left_count, bs_lc[leaf]))
            bs_lr = bs_lr.at[leaf].set(
                jnp.where(applied, sp.left_rows.astype(i32), bs_lr[leaf]))
            bs_bits = bs_bits.at[leaf].set(
                jnp.where(applied, sp.cat_bitset, bs_bits[leaf]))
            bs_catl2 = bs_catl2.at[leaf].set(
                jnp.where(applied, sp.is_cat_l2, bs_catl2[leaf]))

        if params.mono_intermediate:
            # ---- intermediate monotone: tighten contiguous leaves ----
            # (reference: IntermediateLeafConstraints::Update +
            # GoUpToFindLeavesToUpdate / GoDownToFindLeavesToUpdate,
            # src/treelearner/monotone_constraints.hpp:560-858). Walk up
            # from the new split; at every monotone ancestor whose opposite
            # branch is still contiguous, walk down it and clamp each
            # contiguous leaf's bound against the new children's ACTUAL
            # outputs; leaves whose bounds changed get their cached best
            # split recomputed (it may now violate the tighter bound).
            mono_i32 = mono_types.astype(i32)
            mt_i = mono_i32[f_]
            in_mono_here = jnp.logical_or(mt_i != 0,
                                          st.leaf_in_mono[best_leaf])
            eff = jnp.logical_and(applied, in_mono_here)
            leaf_in_mono = st.leaf_in_mono.at[best_leaf].set(
                jnp.where(applied, in_mono_here,
                          st.leaf_in_mono[best_leaf]))
            leaf_in_mono = leaf_in_mono.at[new_leaf].set(
                jnp.where(applied, in_mono_here, leaf_in_mono[new_leaf]))
            node_parent = st.node_parent.at[node].set(
                jnp.where(applied, p, st.node_parent[node]))
            node_is_cat = st.node_is_cat.at[node].set(
                jnp.where(applied, iscat_split, st.node_is_cat[node]))
            leaf_fmask = st.leaf_fmask.at[best_leaf].set(
                jnp.where(applied, fm_l, st.leaf_fmask[best_leaf]))
            leaf_fmask = leaf_fmask.at[new_leaf].set(
                jnp.where(applied, fm_r, leaf_fmask[new_leaf]))

            arangeL = jnp.arange(L, dtype=i32)
            thr_split = b_
            lo_out = jnp.minimum(lw, rw)
            hi_out = jnp.maximum(lw, rw)

            def up_cond(c):
                return c[1] >= 0

            def up_body(c):
                (cur, par, d, n_pend, feats_u, thrs_u, wasr_u, pend_root,
                 pend_umax, pend_d) = c
                pf = split_feature[par]
                pt = split_bin[par]
                p_num = jnp.logical_not(node_is_cat[par])
                mt_p = mono_i32[pf]
                is_right = right_child[par] == cur
                # contiguity optimization: a second climb on the same side
                # of the same feature cannot reach new contiguous leaves
                clash = jnp.any((feats_u == pf) & (wasr_u == is_right)
                                & (arangeL < d))
                opp_should = p_num & jnp.logical_not(clash)
                do_pend = opp_should & (mt_p != 0)
                left_is_cur = left_child[par] == cur
                opp = jnp.where(left_is_cur, right_child[par],
                                left_child[par])
                umax = jnp.where(mt_p < 0, left_is_cur,
                                 jnp.logical_not(left_is_cur))
                ip = jnp.minimum(n_pend, L - 1)
                pend_root = pend_root.at[ip].set(
                    jnp.where(do_pend, opp, pend_root[ip]))
                pend_umax = pend_umax.at[ip].set(
                    jnp.where(do_pend, umax, pend_umax[ip]))
                pend_d = pend_d.at[ip].set(
                    jnp.where(do_pend, d, pend_d[ip]))
                n_pend = n_pend + do_pend.astype(i32)
                idx = jnp.minimum(d, L - 1)
                feats_u = feats_u.at[idx].set(
                    jnp.where(opp_should, pf, feats_u[idx]))
                thrs_u = thrs_u.at[idx].set(
                    jnp.where(opp_should, pt, thrs_u[idx]))
                wasr_u = wasr_u.at[idx].set(
                    jnp.where(opp_should, is_right, wasr_u[idx]))
                d = d + opp_should.astype(i32)
                return (par, node_parent[par], d, n_pend, feats_u, thrs_u,
                        wasr_u, pend_root, pend_umax, pend_d)

            up0 = (node, jnp.where(eff, p, jnp.asarray(-1, i32)),
                   jnp.asarray(0, i32), jnp.asarray(0, i32),
                   jnp.full((L,), -1, i32), jnp.zeros((L,), i32),
                   jnp.zeros((L,), bool), jnp.zeros((L,), i32),
                   jnp.zeros((L,), bool), jnp.zeros((L,), i32))
            (_, _, _, n_pend, feats_u, thrs_u, wasr_u, pend_root,
             pend_umax, pend_d) = lax.while_loop(up_cond, up_body, up0)

            def down_one(j, carry):
                lcm0, lcx0, rs0 = carry
                dj = pend_d[j]
                umax = pend_umax[j]
                mask_u = arangeL < dj

                def d_cond(s):
                    return s[0] > 0

                def d_body(s):
                    sp_, st_n, st_ul, st_ur, lcm, lcx, rs = s
                    sp_ = sp_ - 1
                    nd = st_n[sp_]
                    ul = st_ul[sp_]
                    ur = st_ur[sp_]
                    is_leaf = nd < 0
                    leafi = jnp.maximum(-(nd + 1), 0)
                    both = jnp.logical_and(ul, ur)
                    # update_max clamps with the SMALLER contiguous output,
                    # update_min with the larger (reference minmax pair)
                    bnd_max = jnp.where(both, lo_out, jnp.where(ur, rw, lw))
                    bnd_min = jnp.where(both, hi_out, jnp.where(ur, rw, lw))
                    gain_ok = bs_gain[leafi] > _NEG_INF / 2
                    newmax = jnp.minimum(lcx[leafi], bnd_max)
                    newmin = jnp.maximum(lcm[leafi], bnd_min)
                    chg = jnp.where(umax, newmax < lcx[leafi],
                                    newmin > lcm[leafi])
                    upd = is_leaf & gain_ok
                    lcx = lcx.at[leafi].set(
                        jnp.where(upd & umax, newmax, lcx[leafi]))
                    lcm = lcm.at[leafi].set(
                        jnp.where(upd & jnp.logical_not(umax), newmin,
                                  lcm[leafi]))
                    rs = rs.at[leafi].set(rs[leafi] | (upd & chg))
                    ndi = jnp.maximum(nd, 0)
                    nf_n = split_feature[ndi]
                    nt_n = split_bin[ndi]
                    n_num = jnp.logical_not(node_is_cat[ndi])
                    same = (feats_u == nf_n) & mask_u
                    kg_r = jnp.logical_not(jnp.any(
                        same & (nt_n >= thrs_u)
                        & jnp.logical_not(wasr_u))) | jnp.logical_not(n_num)
                    kg_l = jnp.logical_not(jnp.any(
                        same & (nt_n <= thrs_u) & wasr_u)) \
                        | jnp.logical_not(n_num)
                    ul4r = jnp.logical_not(n_num & (nf_n == f_)
                                           & (nt_n >= thr_split))
                    ur4l = jnp.logical_not(n_num & (nf_n == f_)
                                           & (nt_n <= thr_split))
                    push_l = jnp.logical_not(is_leaf) & kg_l
                    st_n = st_n.at[sp_].set(
                        jnp.where(push_l, left_child[ndi], st_n[sp_]))
                    st_ul = st_ul.at[sp_].set(
                        jnp.where(push_l, ul, st_ul[sp_]))
                    st_ur = st_ur.at[sp_].set(
                        jnp.where(push_l, ur & ur4l, st_ur[sp_]))
                    sp_ = sp_ + push_l.astype(i32)
                    push_r = jnp.logical_not(is_leaf) & kg_r
                    st_n = st_n.at[sp_].set(
                        jnp.where(push_r, right_child[ndi], st_n[sp_]))
                    st_ul = st_ul.at[sp_].set(
                        jnp.where(push_r, ul & ul4r, st_ul[sp_]))
                    st_ur = st_ur.at[sp_].set(
                        jnp.where(push_r, ur, st_ur[sp_]))
                    sp_ = sp_ + push_r.astype(i32)
                    return (sp_, st_n, st_ul, st_ur, lcm, lcx, rs)

                out = lax.while_loop(
                    d_cond, d_body,
                    (jnp.asarray(1, i32),
                     jnp.zeros((2 * L,), i32).at[0].set(pend_root[j]),
                     jnp.zeros((2 * L,), bool).at[0].set(True),
                     jnp.zeros((2 * L,), bool).at[0].set(True),
                     lcm0, lcx0, rs0))
                return out[4], out[5], out[6]

            leaf_cmin, leaf_cmax, resc = lax.fori_loop(
                0, n_pend, down_one,
                (leaf_cmin, leaf_cmax, jnp.zeros((L,), bool)))

            # rescan every leaf whose bounds tightened — its cached split
            # may now be invalid (reference: leaves_to_update_ re-entering
            # FindBestSplitsFromHistograms)
            pen_cur = cegb_coupled * jnp.logical_not(cegb_used)

            def rescan_body(i, carry):
                (g_a, f_a, b_a, d_a, lg_a, lh_a, lc_a, lr_a, bb_a,
                 cl_a, cmn_a, cmx_a) = carry

                def do(_):
                    sp = leaf_best(
                        tmap(lambda a: a[i].reshape(F_h, B, -1), leaf_hist),
                        leaf_grad[i],
                        leaf_hess[i], leaf_cnt[i], leaf_depth[i],
                        leaf_fmask[i], cmn_a[i], cmx_a[i], leaf_pout[i],
                        pen_cur,
                        # chained fold under a fixed domain separator:
                        # rescan draws must not depend on the leaf-array
                        # size, or a rung-padded program (step_buckets)
                        # would draw different extra_trees thresholds than
                        # the exact-keyed one; folding (separator, k, i)
                        # stepwise instead of a (3+k)*stride+i product
                        # keeps traced-i32 arithmetic in range at any
                        # num_leaves and cannot re-enter the node-draw
                        # fold domain (2k+2 < the separator)
                        jax.random.fold_in(jax.random.fold_in(
                            jax.random.fold_in(
                                extra_key, _RESCAN_FOLD_STRIDE), k), i))
                    return (sp.gain, sp.feature, sp.bin, sp.default_left,
                            sp.left_grad, sp.left_hess, sp.left_count,
                            sp.left_rows.astype(i32), sp.cat_bitset,
                            sp.is_cat_l2)

                def dont(_):
                    return (g_a[i], f_a[i], b_a[i], d_a[i], lg_a[i],
                            lh_a[i], lc_a[i], lr_a[i], bb_a[i], cl_a[i])

                vals = lax.cond(resc[i], do, dont, 0)
                return (g_a.at[i].set(vals[0]), f_a.at[i].set(vals[1]),
                        b_a.at[i].set(vals[2]), d_a.at[i].set(vals[3]),
                        lg_a.at[i].set(vals[4]), lh_a.at[i].set(vals[5]),
                        lc_a.at[i].set(vals[6]), lr_a.at[i].set(vals[7]),
                        bb_a.at[i].set(vals[8]), cl_a.at[i].set(vals[9]),
                        cmn_a, cmx_a)

            (bs_gain, bs_feature, bs_bin, bs_dl, bs_lg, bs_lh, bs_lc,
             bs_lr, bs_bits, bs_catl2, leaf_cmin, leaf_cmax) = lax.fori_loop(
                0, L, rescan_body,
                (bs_gain, bs_feature, bs_bin, bs_dl, bs_lg, bs_lh, bs_lc,
                 bs_lr, bs_bits, bs_catl2, leaf_cmin, leaf_cmax))
        else:
            leaf_in_mono = st.leaf_in_mono
            node_parent = st.node_parent
            node_is_cat = st.node_is_cat
            leaf_fmask = st.leaf_fmask

        return CompactState(
            done=done,
            num_nodes=st.num_nodes + jnp.where(applied, 1, 0).astype(i32),
            work=work,
            scratch=scratch,
            leaf_hist=leaf_hist,
            leaf_hist_loc=leaf_hist_loc,
            leaf_start=leaf_start,
            leaf_nrows=leaf_nrows,
            leaf_nrows_g=leaf_nrows_g,
            leaf_side=leaf_side,
            split_feature=split_feature,
            split_bin=split_bin,
            cat_bitset=cat_bitset,
            split_gain=split_gain,
            default_left=default_left,
            left_child=left_child,
            right_child=right_child,
            leaf_parent=leaf_parent,
            leaf_parent_side=leaf_parent_side,
            leaf_depth=leaf_depth,
            node_grad=node_grad,
            node_hess=node_hess,
            node_cnt=node_cnt,
            leaf_grad=leaf_grad,
            leaf_hess=leaf_hess,
            leaf_cnt=leaf_cnt,
            bs_gain=bs_gain,
            bs_feature=bs_feature,
            bs_bin=bs_bin,
            bs_default_left=bs_dl,
            bs_left_grad=bs_lg,
            bs_left_hess=bs_lh,
            bs_left_cnt=bs_lc,
            bs_left_rows=bs_lr,
            bs_bitset=bs_bits,
            bs_cat_l2=bs_catl2,
            leaf_out=leaf_out,
            leaf_cmin=leaf_cmin,
            leaf_cmax=leaf_cmax,
            leaf_used=leaf_used,
            leaf_pout=leaf_pout,
            cegb_used=cegb_used,
            leaf_in_mono=leaf_in_mono,
            node_parent=node_parent,
            node_is_cat=node_is_cat,
            leaf_fmask=leaf_fmask,
        )

    st = lax.fori_loop(0, L - 1, body, st)

    if params.fused_block and params.fused_dual:
        # dual residency: consolidate scratch-resident segments back into
        # work once per tree (the copy-back variant does this after EVERY
        # split, re-streaming the whole right child each time)
        _, row_side = segments_to_leaf_vectors(
            st.leaf_start, st.leaf_nrows, st.leaf_side.astype(jnp.float32), n)
        in_scratch = jnp.zeros((st.work.shape[0],), bool) \
            .at[:n].set(row_side > 0.5)
        st = st._replace(
            work=jnp.where(in_scratch[:, None], st.scratch, st.work))

    leaf_value = st.leaf_out
    tree = TreeArrays(
        split_feature=st.split_feature,
        split_bin=st.split_bin,
        cat_bitset=st.cat_bitset,
        split_gain=st.split_gain,
        default_left=st.default_left,
        left_child=st.left_child,
        right_child=st.right_child,
        leaf_value=leaf_value,
        leaf_weight=st.leaf_hess,
        leaf_count=st.leaf_cnt,
        leaf_parent=st.leaf_parent,
        leaf_depth=st.leaf_depth,
        internal_value=leaf_output(st.node_grad, st.node_hess, sp_params),
        internal_weight=st.node_hess,
        internal_count=st.node_cnt,
        num_leaves=st.num_nodes + 1,
        num_nodes=st.num_nodes,
    )
    row_leaf, _ = segments_to_leaf_vectors(
        st.leaf_start, st.leaf_nrows, leaf_value, n)
    return (tree, row_leaf, st.work, st.scratch, st.leaf_start,
            st.leaf_nrows)
