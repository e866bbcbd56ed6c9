"""Leaf-wise (best-first) tree growth, fully on device.

TPU-native re-design of the reference's device tree learner
(reference: CUDASingleGPUTreeLearner::Train,
src/treelearner/cuda/cuda_single_gpu_tree_learner.cpp:158-345 — the loop
ConstructHistogramForLeaf -> SubtractHistogramForLeaf -> FindBestSplitsForLeaf ->
FindBestFromAllSplits -> Split; CPU analogue SerialTreeLearner::Train,
src/treelearner/serial_tree_learner.cpp:179).

Design, by TPU constraints (static shapes, no atomics, no cheap host
round-trips):

  * The whole tree grows inside one ``jax.lax.fori_loop`` — zero host syncs per
    tree (the CUDA learner ships one SplitInfo struct to host per split; we
    ship none).
  * Row->leaf assignment is a dense ``[N]`` int vector updated by masked where,
    instead of the reference's index-partition scatter
    (cuda_data_partition.cu:288 GenDataToLeftBitVectorKernel + prefix sums).
    The split column is read from a transposed ``[F, N]`` bin matrix so the
    per-split partition is one contiguous dynamic row slice, not a strided
    gather over the whole ``[N, F]`` matrix.
  * Per-leaf histograms stay resident in HBM (``[L, F, B, 3]``) and each split
    builds only the SMALLER child's histogram with one masked pass; the larger
    child is parent − smaller — the reference's histogram-subtraction trick
    (serial_tree_learner.cpp:404, cuda_histogram_constructor.cu:723
    SubtractHistogramKernel).
  * Early stop (no leaf with positive gain) becomes a ``done`` flag that turns
    remaining iterations into no-ops via ``lax.cond`` (skipping the histogram
    work), since ``fori_loop`` has a static trip count.

The same function runs under GSPMD sharding for data-parallel training: rows
are sharded, per-leaf histograms are ``psum``-ed over the mesh axis (replacing
the reference's socket/MPI ReduceScatter in data_parallel_tree_learner.cpp:
223-300), and every shard then takes identical split decisions.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..obs.spans import span
from .histogram import histogram
from .split import (SplitParams, SplitResult, best_split, child_output,
                    depth_gate, go_left_pred, leaf_output)

_NEG_INF = -1e30

# rescan PRNG domain separator (compact grower's monotone-intermediate
# rescan): a fixed first fold keeps the extra_trees rescan draws
# independent of the leaf-array size — a rung-padded program
# (step_buckets) draws the same thresholds as the exact-keyed one — and
# out of the node-draw fold domain (direct folds stay <= 2*num_leaves+2
# < this for every legal num_leaves)
_RESCAN_FOLD_STRIDE = 1 << 20


def leaf_rung(num_leaves: int) -> int:
    """Power-of-two leaf-count rung of the bucketed step ladder.

    The grower's per-leaf state arrays (histogram cache, best-split cache,
    segment table) and its ``fori_loop`` trip count are sized by the jit
    key's ``num_leaves``; keying on the RUNG instead of the exact count
    means every ``num_leaves`` in (rung/2, rung] lowers the same program —
    inactive leaves are masked segments with zero-weight histograms, and
    the actual budget rides as a traced scalar (``leaf_budget``)."""
    r = 2
    while r < num_leaves:
        r *= 2
    return r


def depth_rung(max_depth: int) -> int:
    """Depth bucket of the step-ladder key.

    Training programs carry no depth-dependent shapes (depth only gates
    candidate gains), so the depth axis of the ladder collapses to two
    buckets: -1 = unlimited (the gate compiles away), +1 = bounded (the
    actual bound is the traced ``depth_budget``). That is the <= O(log
    max_depth) end of the compile-budget contract — one bounded-depth
    program per leaf rung, not one per max_depth value."""
    return -1 if max_depth <= 0 else 1


class GrowerParams(NamedTuple):
    """Static tree-growth hyper-parameters (hashable; part of the jit key)."""
    num_leaves: int = 31
    max_depth: int = -1
    num_bins: int = 256
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_data_in_leaf: float = 20.0
    min_sum_hessian_in_leaf: float = 1e-3
    min_gain_to_split: float = 0.0
    max_delta_step: float = 0.0
    # categorical-split knobs (reference: config.h:480-501)
    max_cat_threshold: int = 32
    cat_l2: float = 10.0
    cat_smooth: float = 10.0
    max_cat_to_onehot: int = 4
    min_data_per_group: float = 100.0
    any_cat: bool = True     # static: dataset has categorical features
    # voting-parallel (PV-Tree): per-shard top-k feature vote caps the
    # histogram reduction at 2k features (0 = off; reference: top_k config)
    voting_k: int = 0
    voting_shards: int = 0
    # constraints / per-node sampling (statics; defaults compile away)
    use_monotone: bool = False
    monotone_penalty: float = 0.0
    # intermediate monotone method (reference: IntermediateLeafConstraints,
    # monotone_constraints.hpp:516) — compact grower only; the masked
    # grower keeps the basic method
    mono_intermediate: bool = False
    path_smooth: float = 0.0
    use_interaction: bool = False
    bynode_fraction: float = 1.0
    use_cegb: bool = False
    cegb_split_pen: float = 0.0
    extra_trees: bool = False
    axis_name: Optional[str] = None
    hist_impl: str = "auto"  # auto | xla | pallas (ops/histogram.py dispatch)
    # compact-grower streaming block sizes (ops/grower_compact.py)
    part_block: int = 2048
    hist_block: int = 16384
    # fused per-split Mosaic kernel (ops/fused_split.py): 0 = off, else the
    # kernel's streaming block size (multiple of 32)
    fused_block: int = 0
    fused_interpret: bool = False   # Pallas interpret mode (CPU tests)
    # dual-residency segments (round 4). False = copy-back variant: all
    # segments stay in work, rights re-stream through scratch — slower, but
    # immune to the open dual+EFB TPU fault (ops/fused_split.py docstring)
    fused_dual: bool = True
    # timing bisect only (LGBM_TPU_FUSED_HIST_DEBUG=off|assembly|matmul):
    # disable all hist work / run channel assembly only / run one-hot
    # matmuls with constant channels — results are INVALID, timings
    # decompose the fused kernel's histogram cost
    fused_hist_debug: str = ""

    # EFB (io/efb.py): the scan axis extends past the stored columns with
    # one virtual feature per bundled original (0 = bundling off)
    efb_virtual: int = 0
    efb_bmax: int = 0

    # quantized-gradient integer histograms (compact grower): grad/hess
    # columns carry int8 discretizer codes, histograms accumulate
    # int8 x int8 -> int32 on the MXU and dequantize at the split scan
    # (reference: gradient_discretizer.cpp + cuda_histogram_constructor
    # .cu:249-524); the per-iteration scales ride as traced args
    quant_hist: bool = False
    # narrowed (16-bit) quantized accumulation (reference:
    # GetHistBitsInLeaf, gradient_discretizer.cpp): leaves whose code sums
    # fit the packing radix take the packed-pair engine — grad/hess and
    # inbag/raw pairs share one f32 channel each, HALF the contraction
    # work, bit-identical int32 sums (ops/histogram.py
    # _xla_histogram_narrow); bits renew per split as leaves shrink
    # (ops/renew.py hist_bits_in_leaf). XLA engine only — the MXU's int8
    # dot accumulates s32 natively, so Mosaic paths gain nothing
    quant_narrow: bool = False
    # static |code| bound for the narrowed engine's packing radix
    # (num_grad_quant_bins + 1; 127 = the raw int8 bound)
    quant_max: int = 127
    # 4-bit nibble-packed bin columns in the compact row records
    # (tpu_bin_pack4 training; RowLayout.packed4 is the operative static
    # key — this mirror keeps the knob visible on the params pytree)
    bin_pack4: bool = False
    # Mosaic one-hot register layout (tpu_hist_layout): "lane" = bins
    # along lanes (channel-major output), "sublane" = bins along
    # sublanes for B <= 64 (ops/pallas_histogram.py
    # _hist_kernel_sublane, ops/fused_split.py hist_contract)
    hist_layout: str = "lane"
    # batched-M histogram depth (env/param tpu_hist_mbatch): K row blocks
    # per one-hot contraction fill M = 8K of the 128 MXU rows. The depth
    # the run's histogram engine runs, as the engine registry resolved
    # it: the Mosaic kernel's window partition and the XLA engine's
    # chunk widening want 8 (their default and their sweep's usual
    # winner); the fused kernel's pending ring wants 1 or 2 — at 8 it
    # costs a fused split ten times its time on the chip
    # (ops/fused_split.py docstring), so under a fused entry this field
    # reads engines/registry.py FUSED_MBATCH (2) unless the user or the
    # environment named a depth. The ring multiplies histogram-side VMEM residency by K
    # (ops/fused_split.py fused_block_cap)
    hist_mbatch: int = 8
    # data-parallel histogram reduction: 0 = all-reduce (lax.psum) of the
    # full [F, B, 4] histogram; S > 0 = reduce-scatter over the feature
    # axis across S shards (lax.psum_scatter) + an all-gather of the tiny
    # per-shard best-split candidate — the reference's actual protocol
    # (ReduceScatter + SyncUpGlobalBestSplit,
    # data_parallel_tree_learner.cpp:223-300)
    hist_scatter: int = 0
    # bucketed step ladder (tpu_step_buckets): ``num_leaves`` holds the
    # power-of-two LEAF RUNG (leaf_rung) and ``max_depth`` the DEPTH
    # BUCKET (depth_rung: -1 unlimited / +1 bounded); the actual budgets
    # arrive as the traced scalars (leaf_budget, depth_budget), so one
    # program serves every (num_leaves, max_depth) in the rung
    step_buckets: bool = False
    # async histogram-collective overlap (tpu_hist_overlap): > 1 = build
    # the local histogram in that many feature groups and reduce each
    # group separately, issuing group g's psum_scatter/all-reduce while
    # group g+1 still accumulates (double-buffered hist slots) — comm
    # hides under the contraction, collective bytes unchanged
    hist_overlap: int = 0

    def split_params(self) -> SplitParams:
        return SplitParams(
            lambda_l1=self.lambda_l1,
            lambda_l2=self.lambda_l2,
            min_data_in_leaf=self.min_data_in_leaf,
            min_sum_hessian_in_leaf=self.min_sum_hessian_in_leaf,
            min_gain_to_split=self.min_gain_to_split,
            max_delta_step=self.max_delta_step,
            max_cat_threshold=self.max_cat_threshold,
            cat_l2=self.cat_l2,
            cat_smooth=self.cat_smooth,
            max_cat_to_onehot=self.max_cat_to_onehot,
            min_data_per_group=self.min_data_per_group,
            enable_sorted_cat=self.any_cat,
            use_monotone=self.use_monotone,
            monotone_penalty=self.monotone_penalty,
            path_smooth=self.path_smooth,
            use_cegb=self.use_cegb,
            cegb_split_pen=self.cegb_split_pen,
            extra_trees=self.extra_trees,
        )

    @property
    def bitset_words(self) -> int:
        return -(-self.num_bins // 32)


class TreeArrays(NamedTuple):
    """Struct-of-arrays tree (reference: Tree, include/LightGBM/tree.h:26).

    Nodes are indexed 0..num_leaves-2 in creation order; child pointers >= 0
    reference internal nodes, negative values ~leaf (i.e. -(leaf_idx+1))
    reference leaves — same convention as the reference's Tree arrays.
    """
    split_feature: jax.Array   # [L-1] i32 (-1 = unused node)
    split_bin: jax.Array       # [L-1] i32 threshold bin (numerical: left is bin <= t)
    cat_bitset: jax.Array      # [L-1, W] u32 bin bitset for categorical splits
    split_gain: jax.Array      # [L-1] f32
    default_left: jax.Array    # [L-1] bool
    left_child: jax.Array      # [L-1] i32
    right_child: jax.Array     # [L-1] i32
    leaf_value: jax.Array      # [L] f32
    leaf_weight: jax.Array     # [L] f32 (sum of hessians)
    leaf_count: jax.Array      # [L] f32 (weighted row count)
    leaf_parent: jax.Array     # [L] i32 node whose child the leaf is
    leaf_depth: jax.Array      # [L] i32
    internal_value: jax.Array  # [L-1] f32 output the node would emit as a leaf
    internal_weight: jax.Array  # [L-1] f32 hessian sum at the node
    internal_count: jax.Array  # [L-1] f32 row count at the node
    num_leaves: jax.Array      # scalar i32: actual number of leaves
    num_nodes: jax.Array       # scalar i32: actual number of internal nodes


class GrowerState(NamedTuple):
    done: jax.Array
    num_nodes: jax.Array
    row_leaf: jax.Array
    # per-leaf histograms resident in HBM [L, F, B, K]
    leaf_hist: jax.Array
    # tree arrays under construction
    split_feature: jax.Array
    split_bin: jax.Array
    cat_bitset: jax.Array      # [L-1, W] u32
    split_gain: jax.Array
    default_left: jax.Array
    left_child: jax.Array
    right_child: jax.Array
    leaf_parent: jax.Array
    leaf_parent_side: jax.Array
    leaf_depth: jax.Array
    # per-internal-node aggregates (for model export / plotting)
    node_grad: jax.Array
    node_hess: jax.Array
    node_cnt: jax.Array
    # per-leaf aggregates
    leaf_grad: jax.Array
    leaf_hess: jax.Array
    leaf_cnt: jax.Array
    # lazy CEGB charged-rows bitmap [F, N] (dummy [1, 1] when off)
    cegb_charged: jax.Array
    # per-leaf cached best splits
    bs_gain: jax.Array
    bs_feature: jax.Array
    bs_bin: jax.Array
    bs_default_left: jax.Array
    bs_left_grad: jax.Array
    bs_left_hess: jax.Array
    bs_left_cnt: jax.Array
    bs_bitset: jax.Array       # [L, W] u32 cached categorical bitsets
    bs_cat_l2: jax.Array       # [L] bool: cached split uses lambda_l2+cat_l2
    # per-leaf outputs fixed at split time (reference stores left_output/
    # right_output in SplitInfo; sorted-categorical splits use l2+cat_l2)
    leaf_out: jax.Array        # [L] f32
    # monotone output bounds per leaf (reference: BasicConstraintEntry)
    leaf_cmin: jax.Array       # [L] f32
    leaf_cmax: jax.Array       # [L] f32
    # features used on the path to each leaf (interaction constraints)
    leaf_used: jax.Array       # [L, F] bool
    # output of the parent at leaf creation (path smoothing context)
    leaf_pout: jax.Array       # [L] f32
    # features already used by any split (CEGB coupled costs paid once)
    cegb_used: jax.Array       # [F] bool


def _leaf_best_split(hist3, pg, ph, pc, feat_info, feat_mask, depth,
                     params: GrowerParams, mono_types=None, cmin=None,
                     cmax=None, pout=0.0, cegb_pen=None, extra_key=None,
                     feature_contri=None, depth_budget=None):
    num_bins_arr, nan_bin_arr, has_nan_arr, is_cat_arr = feat_info
    with span("split_scan"):
        sp = best_split(
            hist3, pg, ph, pc,
            num_bins_arr, nan_bin_arr, has_nan_arr, is_cat_arr, feat_mask,
            params.split_params(), mono_types, cmin, cmax, pout, depth,
            cegb_pen, extra_key, feature_contri,
        )
    return sp._replace(gain=depth_gate(sp.gain, depth, params.max_depth,
                                       depth_budget))


def node_feature_mask(feat_mask, used, inter_sets, key, params):
    """Per-node allowed features: interaction constraints restrict to the
    union of constraint sets containing every feature already used on the
    path (reference: ColSampler::GetByNode, col_sampler.hpp), then
    feature_fraction_bynode Bernoulli-samples the survivors (documented
    deviation: the reference draws an exact-count sample)."""
    fm = feat_mask
    if params.use_interaction:
        subset = jnp.logical_not(
            jnp.any(used[None, :] & jnp.logical_not(inter_sets), axis=1))
        allowed = jnp.any(subset[:, None] & inter_sets, axis=0)
        fm = fm & allowed
    if params.bynode_fraction < 1.0:
        keep = jax.random.uniform(key, fm.shape) < params.bynode_fraction
        keep = jnp.where(jnp.any(keep & fm), keep, True)
        fm = fm & keep
    return fm


@functools.partial(jax.jit, static_argnames=("params",))
def grow_tree(
    binned: jax.Array,        # [N, F] uint8/uint16
    grad: jax.Array,          # [N] f32 (already multiplied by sample weights/mask)
    hess: jax.Array,          # [N] f32 (already multiplied by sample weights/mask)
    cnt_weight: jax.Array,    # [N] f32 in {0,1}: bagging mask (row counts)
    num_bins_arr: jax.Array,  # [F] i32
    nan_bin_arr: jax.Array,   # [F] i32
    has_nan_arr: jax.Array,   # [F] bool
    is_cat_arr: jax.Array,    # [F] bool
    feat_mask: jax.Array,     # [F] bool
    params: GrowerParams,
    mono_types: Optional[jax.Array] = None,   # [F] i8 (use_monotone)
    inter_sets: Optional[jax.Array] = None,   # [S, F] bool (use_interaction)
    bynode_key: Optional[jax.Array] = None,   # PRNG key (bynode_fraction<1)
    cegb_coupled: Optional[jax.Array] = None,  # [F] tradeoff*coupled costs
    cegb_used0: Optional[jax.Array] = None,    # [F] bool (persisted model-level)
    extra_key: Optional[jax.Array] = None,     # PRNG key (extra_trees)
    feature_contri: Optional[jax.Array] = None,  # [F] gain multipliers
    forced: Optional[tuple] = None,   # (leaf[J], feature[J], bin[J]) arrays
    cegb_lazy: Optional[jax.Array] = None,     # [F] tradeoff*lazy costs
    cegb_charged0: Optional[jax.Array] = None,  # [F, N] bool (persisted)
    leaf_budget: Optional[jax.Array] = None,   # i32 actual leaf budget
    depth_budget: Optional[jax.Array] = None,  # i32 actual depth bound
):
    """Grow one tree; returns (TreeArrays, row_leaf [N] i32), plus the
    updated [F, N] charged-rows bitmap when ``cegb_lazy`` is set (lazy
    feature penalties persist per (row, feature) across the whole model —
    reference: feature_used_in_data_, cost_effective_gradient_boosting
    .hpp:62,125).

    ``params.step_buckets``: ``params.num_leaves`` is the power-of-two
    rung and ``leaf_budget``/``depth_budget`` carry the ACTUAL budgets as
    traced scalars — rounds past the leaf budget are masked no-ops and
    the padded leaves stay zero-weight segments, so the grown tree is
    bit-identical to the exact-keyed program while the jit key stays on
    (rung, depth bucket, mode, dtype)."""
    n, f = binned.shape
    L = params.num_leaves
    if params.step_buckets and leaf_budget is None:
        raise ValueError("params.step_buckets needs the traced leaf_budget "
                         "(the rung is the jit key, not the leaf count)")
    if params.step_buckets and params.max_depth > 0 and depth_budget is None:
        raise ValueError("params.step_buckets with the bounded depth "
                         "bucket needs the traced depth_budget (max_depth "
                         "is the bucket sentinel, not the actual bound)")
    dbudget = depth_budget if (params.step_buckets
                               and params.max_depth > 0) else None
    use_lazy = cegb_lazy is not None
    if use_lazy and cegb_charged0 is None:
        cegb_charged0 = jnp.zeros((f, n), bool)
    B = params.num_bins
    ax = params.axis_name
    feat_info = (num_bins_arr, nan_bin_arr, has_nan_arr, is_cat_arr)

    grad = grad.astype(jnp.float32)
    hess = hess.astype(jnp.float32)
    cnt_weight = cnt_weight.astype(jnp.float32)
    # contiguous per-feature rows for the split partition (one dynamic row
    # slice per split instead of a strided column gather from [N, F])
    binned_t = binned.T

    # voting with 2k >= F elects every feature — the vote is a no-op, so
    # the grower must run the data-parallel program EXACTLY (same
    # histogram chunking, same parent-minus-smaller subtraction): the
    # fresh-both-children voting variant rounds its f32 sums differently
    # and the last-ulp gain noise flips split tie-breaks vs the data
    # learner (the pre-PR-8 tier-1 voting-parity failure)
    voting_live = (params.voting_k > 0 and params.voting_shards > 1
                   and min(2 * params.voting_k, f) < f)

    def hist3(mask):
        with span("hist_build"):
            chans = jnp.stack(
                [grad * mask, hess * mask, cnt_weight * mask], axis=1)
            if voting_live:
                from ..parallel.voting import voting_histogram
                return voting_histogram(
                    binned, chans, B, params.voting_shards,
                    params.voting_k, params.split_params(),
                    impl=params.hist_impl,
                    mbatch=params.hist_mbatch,
                    layout=params.hist_layout,
                    overlap=params.hist_overlap)
            return histogram(binned, chans, B, ax, impl=params.hist_impl,
                             mbatch=params.hist_mbatch,
                             layout=params.hist_layout,
                             overlap=params.hist_overlap)

    if mono_types is None:
        mono_types = jnp.zeros((f,), jnp.int8)
    if inter_sets is None:
        inter_sets = jnp.zeros((0, f), bool)
    if bynode_key is None:
        bynode_key = jax.random.PRNGKey(0)
    if cegb_coupled is None:
        cegb_coupled = jnp.zeros((f,), jnp.float32)
    if cegb_used0 is None:
        cegb_used0 = jnp.zeros((f,), bool)
    if extra_key is None:
        extra_key = jax.random.PRNGKey(6)
    big = jnp.float32(3.4e38)

    # batched best-split over the two fresh children (one fused scan);
    # cegb_pen is per-child [2, F] (lazy costs differ between children)
    def two_best_splits(h2, pg2, ph2, pc2, fm2, depth, cmin2, cmax2, pout2,
                        cegb_pen2, ek2):
        fn = lambda h, pg, ph, pc, fm, cmn, cmx, po, pen, ek: \
            _leaf_best_split(
                h, pg, ph, pc, feat_info, fm, depth, params, mono_types,
                cmn, cmx, po, pen, ek, feature_contri, dbudget)
        return jax.vmap(fn)(h2, pg2, ph2, pc2, fm2, cmin2, cmax2, pout2,
                            cegb_pen2, ek2)

    # ---- root ----
    root_g = grad.sum()
    root_h = hess.sum()
    root_c = cnt_weight.sum()
    if ax is not None:
        with span("collective_reduce"):
            root_g = lax.psum(root_g, ax)
            root_h = lax.psum(root_h, ax)
            root_c = lax.psum(root_c, ax)
    root_hist = hist3(jnp.ones_like(cnt_weight))
    root_fm = node_feature_mask(
        feat_mask, jnp.zeros((f,), bool), inter_sets,
        jax.random.fold_in(bynode_key, 0), params)
    # path smoothing at the root smooths toward the root's own output
    # (reference: GetParentOutput, serial_tree_learner.cpp:1005-1016)
    root_out = leaf_output(root_g, root_h, params.split_params())
    bag = (cnt_weight != 0.0).astype(jnp.float32)
    if use_lazy:
        # on-demand (lazy) feature costs: penalty * bagged rows of the leaf
        # not yet charged for the feature (reference:
        # CalculateOndemandCosts, cost_effective_gradient_boosting.hpp:139)
        u_root = jnp.logical_not(cegb_charged0).astype(jnp.float32) @ bag
        pen_root = (cegb_coupled * jnp.logical_not(cegb_used0)
                    + cegb_lazy * u_root)
    else:
        pen_root = cegb_coupled * jnp.logical_not(cegb_used0)
    sp0 = _leaf_best_split(
        root_hist, root_g, root_h, root_c, feat_info, root_fm,
        jnp.asarray(0, jnp.int32), params, mono_types,
        -big, big, root_out, pen_root,
        jax.random.fold_in(extra_key, 0), feature_contri, dbudget,
    )

    i32 = jnp.int32
    W = params.bitset_words
    leaf_hist0 = jnp.zeros((L, f, B, 3), jnp.float32).at[0].set(root_hist)
    st = GrowerState(
        done=jnp.asarray(False),
        cegb_charged=(cegb_charged0 if use_lazy
                      else jnp.zeros((1, 1), bool)),
        num_nodes=jnp.asarray(0, i32),
        row_leaf=jnp.zeros((n,), i32),
        leaf_hist=leaf_hist0,
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
        node_cnt=jnp.zeros((L - 1,), jnp.float32),
        leaf_grad=jnp.zeros((L,), jnp.float32).at[0].set(root_g),
        leaf_hess=jnp.zeros((L,), jnp.float32).at[0].set(root_h),
        leaf_cnt=jnp.zeros((L,), jnp.float32).at[0].set(root_c),
        bs_gain=jnp.full((L,), _NEG_INF, jnp.float32).at[0].set(sp0.gain),
        bs_feature=jnp.zeros((L,), i32).at[0].set(sp0.feature),
        bs_bin=jnp.zeros((L,), i32).at[0].set(sp0.bin),
        bs_default_left=jnp.zeros((L,), bool).at[0].set(sp0.default_left),
        bs_left_grad=jnp.zeros((L,), jnp.float32).at[0].set(sp0.left_grad),
        bs_left_hess=jnp.zeros((L,), jnp.float32).at[0].set(sp0.left_hess),
        bs_left_cnt=jnp.zeros((L,), jnp.float32).at[0].set(sp0.left_count),
        bs_bitset=jnp.zeros((L, W), jnp.uint32).at[0].set(sp0.cat_bitset),
        bs_cat_l2=jnp.zeros((L,), bool).at[0].set(sp0.is_cat_l2),
        leaf_out=jnp.zeros((L,), jnp.float32).at[0].set(root_out),
        leaf_cmin=jnp.full((L,), -3.4e38, jnp.float32),
        leaf_cmax=jnp.full((L,), 3.4e38, jnp.float32),
        leaf_used=jnp.zeros((L, f), bool),
        leaf_pout=jnp.zeros((L,), jnp.float32).at[0].set(root_out),
        cegb_used=cegb_used0,
    )

    def body(k, st: GrowerState) -> GrowerState:
        # ---- FindBestFromAllSplits (reference: cuda_best_split_finder.cu:2113) ----
        leaf_alive = jnp.arange(L) <= k
        gains = jnp.where(leaf_alive, st.bs_gain, _NEG_INF)
        best_leaf = jnp.argmax(gains).astype(i32)
        valid = gains[best_leaf] > 0.0
        if params.step_buckets:
            # rounds past the traced leaf budget are inert — the rung's
            # remaining iterations run the same program with zero trip
            # counts, exactly like a post-early-stop round
            valid = jnp.logical_and(valid, k < leaf_budget - 1)
        applied = jnp.logical_and(valid, jnp.logical_not(st.done))
        done = jnp.logical_or(st.done, jnp.logical_not(valid))

        node = k
        new_leaf = jnp.asarray(k + 1, i32)

        f_ = st.bs_feature[best_leaf]
        b_ = st.bs_bin[best_leaf]
        dl = st.bs_default_left[best_leaf]
        bits = st.bs_bitset[best_leaf]
        catl2 = st.bs_cat_l2[best_leaf]
        if forced is not None:
            # the first len(forced) splits are dictated by the user's JSON
            # tree (reference: SerialTreeLearner::ForceSplits,
            # serial_tree_learner.cpp:620 — forced splits apply before the
            # gain-driven growth). The target leaf ids were precomputed on
            # the host from the creation-order convention.
            fleaf, ffeat, fbin = forced
            j_forced = fleaf.shape[0]
            is_forced = k < j_forced
            if params.step_buckets:
                # forced splits must respect the traced budget too: the
                # rung loop runs rounds the exact-keyed num_leaves-1 loop
                # never had, and an ungated is_forced would re-enable
                # `applied` past leaf_budget (e.g. a forced schedule
                # parsed under a larger pre-reset_parameter num_leaves)
                is_forced = jnp.logical_and(is_forced, k < leaf_budget - 1)
            kf = jnp.minimum(k, j_forced - 1)
            best_leaf = jnp.where(is_forced, fleaf[kf], best_leaf)
            f_ = jnp.where(is_forced, ffeat[kf], f_)
            b_ = jnp.where(is_forced, fbin[kf], b_)
            dl = jnp.where(is_forced, False, dl)
            bits = jnp.where(is_forced, 0, bits)
            catl2 = jnp.where(is_forced, False, catl2)
            # sums for the forced (feature, bin): one feature row sliced
            # from the leaf's histogram, then a single-bin cumulative read
            frow = lax.dynamic_slice_in_dim(
                st.leaf_hist[best_leaf], f_, 1, axis=0)[0]   # [B, K]
            cum = jnp.cumsum(frow, axis=0)
            flg = cum[b_, 0]
            flh = cum[b_, 1]
            flc = cum[b_, 2]
            applied = jnp.logical_or(applied, is_forced)
            done = jnp.where(is_forced, False, done)

        # ---- record split; wire tree structure ----
        split_feature = st.split_feature.at[node].set(jnp.where(applied, f_, -1))
        split_bin = st.split_bin.at[node].set(jnp.where(applied, b_, 0))
        cat_bitset = st.cat_bitset.at[node].set(jnp.where(applied, bits, 0))
        gain_rec = st.bs_gain[best_leaf]
        if forced is not None:
            # the cached candidate gain belongs to a different (feature,
            # bin); record 0 for forced nodes (reference reports the forced
            # SplitInfo's own gain, which we do not evaluate)
            gain_rec = jnp.where(is_forced, 0.0, gain_rec)
        split_gain = st.split_gain.at[node].set(
            jnp.where(applied, gain_rec, 0.0))
        default_left = st.default_left.at[node].set(jnp.where(applied, dl, False))
        p = st.leaf_parent[best_leaf]
        side = st.leaf_parent_side[best_leaf]
        p_idx = jnp.maximum(p, 0)
        left_child = st.left_child.at[p_idx].set(
            jnp.where(applied & (p >= 0) & (side == 0), node, st.left_child[p_idx]))
        right_child = st.right_child.at[p_idx].set(
            jnp.where(applied & (p >= 0) & (side == 1), node, st.right_child[p_idx]))
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

        # ---- partition rows (reference: CUDADataPartition::SplitInner) ----
        with span("partition"):
            fcol = lax.dynamic_slice_in_dim(
                binned_t, f_, 1, axis=0)[0].astype(i32)
            nb = nan_bin_arr[f_]
            iscat = is_cat_arr[f_]
            go_left = go_left_pred(fcol, b_, dl, nb, iscat, bits)
            row_leaf = jnp.where(
                applied & (st.row_leaf == best_leaf)
                & jnp.logical_not(go_left),
                new_leaf,
                st.row_leaf,
            )

        # ---- per-leaf aggregates for the two children ----
        lg, lh, lc = (st.bs_left_grad[best_leaf], st.bs_left_hess[best_leaf],
                      st.bs_left_cnt[best_leaf])
        if forced is not None:
            lg = jnp.where(is_forced, flg, lg)
            lh = jnp.where(is_forced, flh, lh)
            lc = jnp.where(is_forced, flc, lc)
        pg, ph, pc = (st.leaf_grad[best_leaf], st.leaf_hess[best_leaf],
                      st.leaf_cnt[best_leaf])
        rg, rh, rc = pg - lg, ph - lh, pc - lc
        node_grad = st.node_grad.at[node].set(jnp.where(applied, pg, 0.0))
        node_hess = st.node_hess.at[node].set(jnp.where(applied, ph, 0.0))
        node_cnt = st.node_cnt.at[node].set(jnp.where(applied, pc, 0.0))
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
        # child outputs fixed now, under the parent leaf's monotone bounds
        # and smoothing context (reference: SplitInfo left/right_output)
        sp_ = params.split_params()
        l2_used = params.lambda_l2 + params.cat_l2 * catl2.astype(jnp.float32)
        cminp = st.leaf_cmin[best_leaf]
        cmaxp = st.leaf_cmax[best_leaf]
        poutp = st.leaf_pout[best_leaf]
        lw = child_output(lg, lh, lc, sp_, l2_used, poutp, cminp, cmaxp)
        rw = child_output(rg, rh, rc, sp_, l2_used, poutp, cminp, cmaxp)
        leaf_out = st.leaf_out.at[best_leaf].set(
            jnp.where(applied, lw, st.leaf_out[best_leaf]))
        leaf_out = leaf_out.at[new_leaf].set(
            jnp.where(applied, rw, leaf_out[new_leaf]))
        leaf_pout = st.leaf_pout.at[best_leaf].set(
            jnp.where(applied, lw, poutp))
        leaf_pout = leaf_pout.at[new_leaf].set(
            jnp.where(applied, rw, leaf_pout[new_leaf]))

        # monotone bound propagation, basic method (reference:
        # BasicLeafConstraints::Update — children bounded by the midpoint)
        iscat_split = is_cat_arr[f_]
        if params.use_monotone:
            mt = mono_types[f_].astype(jnp.int32)
            mid = 0.5 * (lw + rw)
            act = applied & jnp.logical_not(iscat_split)
            cmax_l = jnp.where(act & (mt > 0), jnp.minimum(cmaxp, mid), cmaxp)
            cmin_l = jnp.where(act & (mt < 0), jnp.maximum(cminp, mid), cminp)
            cmin_r = jnp.where(act & (mt > 0), jnp.maximum(cminp, mid), cminp)
            cmax_r = jnp.where(act & (mt < 0), jnp.minimum(cmaxp, mid), cmaxp)
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

        used_child = st.leaf_used[best_leaf] | (jnp.arange(f) == f_)
        leaf_used = st.leaf_used.at[best_leaf].set(
            jnp.where(applied, used_child, st.leaf_used[best_leaf]))
        leaf_used = leaf_used.at[new_leaf].set(
            jnp.where(applied, used_child, leaf_used[new_leaf]))
        cegb_used = st.cegb_used | (applied & (jnp.arange(f) == f_))
        if use_lazy:
            # charge every bagged row of the parent for the split feature
            # (reference: UpdateLeafBestSplits runs BEFORE the partition,
            # serial_tree_learner.cpp:768 — the parent's full row set)
            in_parent = ((row_leaf == best_leaf) | (row_leaf == new_leaf)) \
                & (cnt_weight != 0.0)
            cegb_charged = st.cegb_charged.at[f_].set(
                st.cegb_charged[f_] | (applied & in_parent))
        else:
            cegb_charged = st.cegb_charged

        # ---- children histograms + best splits (skipped when done) ----
        bs_arrays = (st.leaf_hist, st.bs_gain, st.bs_feature, st.bs_bin,
                     st.bs_default_left, st.bs_left_grad, st.bs_left_hess,
                     st.bs_left_cnt, st.bs_bitset, st.bs_cat_l2)

        def compute_children(bs):
            (leaf_hist, bs_gain, bs_feature, bs_bin, bs_dl, bs_lg, bs_lh,
             bs_lc, bs_bits, bs_catl2) = bs
            if voting_live:
                # voting elects a DIFFERENT feature subset per histogram
                # (unvoted features are zeroed), so parent-minus-smaller
                # subtraction would mix inconsistent elected sets — build
                # both children fresh instead (the reference's voting
                # learner re-elects per FindBestSplits round too,
                # voting_parallel_tree_learner.cpp:151)
                hist_left = hist3((row_leaf == best_leaf).astype(jnp.float32))
                hist_right = hist3((row_leaf == new_leaf).astype(jnp.float32))
            else:
                # one masked pass over the SMALLER child only; the larger
                # child is parent − smaller (reference:
                # SubtractHistogramForLeaf, cuda_histogram_constructor.cu:723)
                parent_hist = leaf_hist[best_leaf]
                left_smaller = lc <= rc
                small_id = jnp.where(left_smaller, best_leaf, new_leaf)
                m = (row_leaf == small_id).astype(jnp.float32)
                hist_small = hist3(m)
                hist_large = parent_hist - hist_small
                hist_left = jnp.where(left_smaller, hist_small, hist_large)
                hist_right = jnp.where(left_smaller, hist_large, hist_small)
            leaf_hist = leaf_hist.at[best_leaf].set(hist_left)
            leaf_hist = leaf_hist.at[new_leaf].set(hist_right)

            h2 = jnp.stack([hist_left, hist_right])
            fm_l = node_feature_mask(
                feat_mask, used_child, inter_sets,
                jax.random.fold_in(bynode_key, 2 * k + 1), params)
            fm_r = node_feature_mask(
                feat_mask, used_child, inter_sets,
                jax.random.fold_in(bynode_key, 2 * k + 2), params)
            pen_base = cegb_coupled * jnp.logical_not(cegb_used)
            if use_lazy:
                unch = jnp.logical_not(cegb_charged).astype(jnp.float32)
                bagm = cnt_weight != 0.0
                u_l = unch @ ((row_leaf == best_leaf) & bagm) \
                    .astype(jnp.float32)
                u_r = unch @ ((row_leaf == new_leaf) & bagm) \
                    .astype(jnp.float32)
                pen2 = jnp.stack([pen_base + cegb_lazy * u_l,
                                  pen_base + cegb_lazy * u_r])
            else:
                pen2 = jnp.stack([pen_base, pen_base])
            sp = two_best_splits(
                h2, jnp.stack([lg, rg]), jnp.stack([lh, rh]),
                jnp.stack([lc, rc]), jnp.stack([fm_l, fm_r]), d_child,
                jnp.stack([cmin_l, cmin_r]), jnp.stack([cmax_l, cmax_r]),
                jnp.stack([lw, rw]), pen2,
                jnp.stack([jax.random.fold_in(extra_key, 2 * k + 1),
                           jax.random.fold_in(extra_key, 2 * k + 2)]))
            bs_gain = bs_gain.at[best_leaf].set(sp.gain[0]).at[new_leaf].set(sp.gain[1])
            bs_feature = bs_feature.at[best_leaf].set(sp.feature[0]).at[new_leaf].set(sp.feature[1])
            bs_bin = bs_bin.at[best_leaf].set(sp.bin[0]).at[new_leaf].set(sp.bin[1])
            bs_dl = bs_dl.at[best_leaf].set(sp.default_left[0]).at[new_leaf].set(sp.default_left[1])
            bs_lg = bs_lg.at[best_leaf].set(sp.left_grad[0]).at[new_leaf].set(sp.left_grad[1])
            bs_lh = bs_lh.at[best_leaf].set(sp.left_hess[0]).at[new_leaf].set(sp.left_hess[1])
            bs_lc = bs_lc.at[best_leaf].set(sp.left_count[0]).at[new_leaf].set(sp.left_count[1])
            bs_bits = bs_bits.at[best_leaf].set(sp.cat_bitset[0]) \
                .at[new_leaf].set(sp.cat_bitset[1])
            bs_catl2 = bs_catl2.at[best_leaf].set(sp.is_cat_l2[0]) \
                .at[new_leaf].set(sp.is_cat_l2[1])
            return (leaf_hist, bs_gain, bs_feature, bs_bin, bs_dl, bs_lg,
                    bs_lh, bs_lc, bs_bits, bs_catl2)

        bs_arrays = lax.cond(applied, compute_children, lambda bs: bs, bs_arrays)
        (leaf_hist, bs_gain, bs_feature, bs_bin, bs_dl, bs_lg, bs_lh,
         bs_lc, bs_bits, bs_catl2) = bs_arrays

        return GrowerState(
            done=done,
            cegb_charged=cegb_charged,
            num_nodes=st.num_nodes + jnp.where(applied, 1, 0).astype(i32),
            row_leaf=row_leaf,
            leaf_hist=leaf_hist,
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
            bs_bitset=bs_bits,
            bs_cat_l2=bs_catl2,
            leaf_out=leaf_out,
            leaf_cmin=leaf_cmin,
            leaf_cmax=leaf_cmax,
            leaf_used=leaf_used,
            leaf_pout=leaf_pout,
            cegb_used=cegb_used,
        )

    st = lax.fori_loop(0, L - 1, body, st)

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
        internal_value=leaf_output(st.node_grad, st.node_hess,
                                   params.split_params()),
        internal_weight=st.node_hess,
        internal_count=st.node_cnt,
        num_leaves=st.num_nodes + 1,
        num_nodes=st.num_nodes,
    )
    if use_lazy:
        return tree, st.row_leaf, st.cegb_charged
    return tree, st.row_leaf
