"""Best-split search over histograms.

TPU-native re-design of the reference's per-feature threshold scan
(reference: FeatureHistogram::FindBestThresholdSequentially
src/treelearner/feature_histogram.hpp:832 and the CUDA variant
src/treelearner/cuda/cuda_best_split_finder.cu:772 FindBestSplitsForLeafKernel).

Where the reference scans bins sequentially per feature (one OpenMP task or CUDA
block per feature), here the scan is a vectorized cumulative sum over the bin
axis of the whole ``[F, B]`` histogram, followed by a masked gain computation and
a single argmax — one fused XLA op chain, no per-feature loop.

Both missing-value default directions are evaluated (the reference's two-direction
scan): "missing right" is the plain left-cumulative scan (the NaN bin is the last
bin), "missing left" re-adds the NaN-bin mass to the left side for thresholds
below the NaN bin.

Categorical features use one-hot splits (left = {bin == b}); the reference's
sorted many-category scan (feature_histogram.hpp categorical branch) is a later
addition.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax.numpy as jnp
from jax import lax

_NEG_INF = -1e30
_EPS = 1e-15


class SplitParams(NamedTuple):
    """Static split hyper-parameters (subset of reference Config)."""
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
    # static gate: skip the sorted-categorical machinery entirely when the
    # dataset has no categorical features (set from the dataset by the GBDT)
    enable_sorted_cat: bool = True
    # monotone constraints, basic method (reference:
    # BasicLeafConstraints, monotone_constraints.hpp:465) + split-gain
    # penalty (:357); static gate keeps the unconstrained path unchanged
    use_monotone: bool = False
    monotone_penalty: float = 0.0
    # path smoothing (reference: CalculateSplittedLeafOutput USE_SMOOTHING,
    # feature_histogram.hpp: w*(n/s)/(n/s+1) + parent/(n/s+1))
    path_smooth: float = 0.0
    # cost-effective gradient boosting (reference:
    # cost_effective_gradient_boosting.hpp DeltaGain — per-split data cost +
    # one-time coupled feature-acquisition cost, both scaled by tradeoff)
    use_cegb: bool = False
    cegb_split_pen: float = 0.0    # tradeoff * cegb_penalty_split
    # extremely randomized trees: each feature evaluates ONE random
    # threshold instead of the full scan (reference: USE_RAND branch of
    # FindBestThresholdSequentially, rand_threshold)
    extra_trees: bool = False


class SplitResult(NamedTuple):
    """Best split of one leaf (reference: SplitInfo, src/treelearner/split_info.hpp)."""
    gain: jnp.ndarray          # shifted gain; > 0 means valid split
    feature: jnp.ndarray       # i32
    bin: jnp.ndarray           # i32 threshold bin (numerical: left is bin <= t)
    default_left: jnp.ndarray  # bool
    left_grad: jnp.ndarray
    left_hess: jnp.ndarray
    left_count: jnp.ndarray    # weighted (in-bag) row count
    left_rows: jnp.ndarray     # raw row count (drives the physical partition)
    # categorical splits: left = {bins whose bit is set}; [W] u32 with
    # W = ceil(B/32) (reference: SplitInfo::cat_threshold bitset)
    cat_bitset: jnp.ndarray
    # True when the winning split is a sorted-many-category split (leaf
    # outputs then use lambda_l2 + cat_l2 — reference: l2 += cat_l2)
    is_cat_l2: jnp.ndarray


def threshold_l1(s: jnp.ndarray, l1: float) -> jnp.ndarray:
    """Soft-threshold by the L1 regularization (reference:
    feature_histogram.hpp ThresholdL1)."""
    return jnp.sign(s) * jnp.maximum(jnp.abs(s) - l1, 0.0)


def leaf_output(sum_grad, sum_hess, p: SplitParams, l2: Optional[float] = None):
    """Optimal leaf value -ThL1(G)/(H + l2), clipped by max_delta_step
    (reference: FeatureHistogram::CalculateSplittedLeafOutput). ``l2``
    overrides lambda_l2 (sorted-categorical splits add cat_l2)."""
    if l2 is None:
        l2 = p.lambda_l2
    out = -threshold_l1(sum_grad, p.lambda_l1) / (sum_hess + l2 + _EPS)
    if p.max_delta_step > 0.0:
        out = jnp.clip(out, -p.max_delta_step, p.max_delta_step)
    return out

def leaf_gain(sum_grad, sum_hess, p: SplitParams, l2: Optional[float] = None):
    """Gain contribution of a leaf: ThL1(G)^2 / (H + l2)
    (reference: FeatureHistogram::GetLeafGain)."""
    if l2 is None:
        l2 = p.lambda_l2
    if p.max_delta_step > 0.0:
        # with clipped output the gain is -(2*G*w + (H+l2)*w^2)... evaluated at w
        w = leaf_output(sum_grad, sum_hess, p, l2)
        return -(2.0 * sum_grad * w + (sum_hess + l2) * w * w) \
            - 2.0 * p.lambda_l1 * jnp.abs(w)
    t = threshold_l1(sum_grad, p.lambda_l1)
    return (t * t) / (sum_hess + l2 + _EPS)


def gain_given_output(sum_grad, sum_hess, w, p: SplitParams, l2=None):
    """Leaf gain at a FIXED output (reference: GetLeafGainGivenOutput) —
    used when constraints/smoothing move the output off the optimum."""
    if l2 is None:
        l2 = p.lambda_l2
    sg = threshold_l1(sum_grad, p.lambda_l1)
    return -(2.0 * sg * w + (sum_hess + l2) * w * w)


def child_output(sum_grad, sum_hess, cnt, p: SplitParams, l2=None,
                 parent_output=0.0, cmin=None, cmax=None):
    """Constrained/smoothed child output (reference:
    CalculateSplittedLeafOutput with USE_SMOOTHING + BasicConstraint clip)."""
    w = leaf_output(sum_grad, sum_hess, p, l2)
    if p.path_smooth > 0.0:
        ratio = cnt / p.path_smooth
        w = w * ratio / (ratio + 1.0) + parent_output / (ratio + 1.0)
    if p.use_monotone and cmin is not None:
        w = jnp.clip(w, cmin, cmax)
    return w


def depth_gate(gain, depth, max_depth: int, depth_budget=None):
    """Mask a split candidate's gain by the tree-depth limit.

    The exact-keyed path bakes the static ``max_depth`` into the program
    (the unlimited case compiles away entirely). Under the bucketed step
    ladder (``GrowerParams.step_buckets``) the jit key carries only the
    DEPTH BUCKET — ``max_depth`` is -1 (unlimited) or +1 (bounded) — and
    the actual bound rides as the traced scalar ``depth_budget``, so one
    program serves every bounded depth at a given leaf rung."""
    if depth_budget is not None:
        ok = depth < depth_budget
    else:
        ok = jnp.logical_or(max_depth <= 0, depth < max_depth)
    return jnp.where(ok, gain, _NEG_INF)


def monotone_penalty_factor(depth, penalty: float):
    """(reference: ComputeMonotoneSplitGainPenalty,
    monotone_constraints.hpp:357)"""
    d = depth.astype(jnp.float32)
    small = 1.0 - penalty / jnp.exp2(d) + _EPS
    large = 1.0 - jnp.exp2(penalty - 1.0 - d) + _EPS
    out = jnp.where(penalty <= 1.0, small, large)
    return jnp.where(penalty >= d + 1.0, _EPS, out)


def pack_bin_bitset(mask: jnp.ndarray) -> jnp.ndarray:
    """[B] bool bin-membership -> [ceil(B/32)] u32 bitset words."""
    b = mask.shape[0]
    w = -(-b // 32)
    pad = w * 32 - b
    m = jnp.pad(mask.astype(jnp.uint32), (0, pad)).reshape(w, 32)
    return (m << jnp.arange(32, dtype=jnp.uint32)[None, :]).sum(
        axis=1, dtype=jnp.uint32)


def bitset_contains(words: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Vectorized membership test: is bit ``idx`` set in the [W] u32 bitset?

    Avoids a table gather (slow on TPU): the word is selected with W
    compare+select lanes, then shifted — all elementwise.
    """
    w = words.shape[0]
    word_id = (idx // 32).astype(jnp.uint32)
    sel = jnp.zeros_like(idx, dtype=jnp.uint32)
    for j in range(w):
        sel = jnp.where(word_id == j, words[j].astype(jnp.uint32), sel)
    return ((sel >> (idx.astype(jnp.uint32) % 32)) & 1) != 0


def go_left_pred(col: jnp.ndarray, bin_: jnp.ndarray, default_left,
                 nan_bin, is_cat, cat_bitset: jnp.ndarray) -> jnp.ndarray:
    """THE left-child routing predicate, shared by the masked grower, the
    compact partition, and prediction routing — it must agree bit-for-bit
    with the histogram cumulative semantics above (reference: Tree::Decision/
    Tree::CategoricalDecision, include/LightGBM/tree.h)."""
    col = col.astype(jnp.int32)
    return jnp.where(
        is_cat,
        bitset_contains(cat_bitset, col),
        (col <= bin_) | (default_left & (col == nan_bin)),
    )


def left_rows_of_split(hist: jnp.ndarray, feature, bin_, default_left,
                       nan_bin, is_cat, cat_bitset) -> jnp.ndarray:
    """Raw rows routed left by an already-decided split, recovered from a
    histogram's raw-count channel (every row of a bin routes identically).

    The data-parallel compact grower uses this to derive the SHARD-LOCAL
    left count from the shard-local histogram while the split decision
    itself comes from the psum-ed global histogram (reference:
    DataParallelTreeLearner keeps global_data_count_in_leaf_ beside the
    local partition, data_parallel_tree_learner.cpp:300-340)."""
    raw = hist[feature, :, 3]                                  # [B]
    bins = jnp.arange(hist.shape[1], dtype=jnp.int32)
    gl = go_left_pred(bins, bin_, default_left, nan_bin, is_cat, cat_bitset)
    return jnp.sum(raw * gl).astype(jnp.int32)


def extend_hist_efb(hist: jnp.ndarray, efb, n_virtual: int, bmax: int
                    ) -> jnp.ndarray:
    """Append virtual per-feature histogram rows for EFB-bundled features.

    ``hist`` is [C, B, K] over STORED columns (passthrough features and
    bundle columns). Each bundled original feature's non-default bins live
    at ``offset+1 .. offset+nb`` of its bundle column; its default-bin mass
    is the leaf total minus the range sum (reference: FixHistogram /
    sum_of_hessian bookkeeping, include/LightGBM/bin.h). The scan then
    treats virtual rows as ordinary numerical features.
    """
    col_of_ext, off_ext, nb_ext, dbin_ext = efb[0], efb[2], efb[3], efb[4]
    C, B, K = hist.shape
    bcol = col_of_ext[C:]                  # [Fb]
    off = off_ext[C:]
    nb = nb_ext[C:]
    dbin = dbin_ext[C:]
    j = jnp.arange(bmax, dtype=jnp.int32)[None, :]          # [1, Bmax]
    idx = jnp.minimum(off[:, None] + 1 + j, B - 1)
    gathered = hist[bcol[:, None], idx, :]                  # [Fb, Bmax, K]
    gathered = gathered * (j < nb[:, None])[:, :, None]
    totals = hist[0].sum(axis=0)                            # [K] leaf totals
    default = totals[None, :] - gathered.sum(axis=1)        # [Fb, K]
    virtual = gathered.at[jnp.arange(n_virtual), dbin].add(default)
    virtual = jnp.pad(virtual, ((0, 0), (0, B - bmax), (0, 0)))
    return jnp.concatenate([hist, virtual], axis=0)


def apply_efb_bitset(sp: "SplitResult", efb, n_cols: int, B: int
                     ) -> "SplitResult":
    """Translate a winning split on a VIRTUAL (bundled) feature into a
    bundle-column bitset so every router (partition, fused kernel,
    route_one_tree) treats it as a ready-made categorical-style split:
    left = {v in (off, off+1+t]} | {v outside the member's range, when the
    member's default bin <= t}."""
    off_ext, nb_ext, dbin_ext = efb[2], efb[3], efb[4]
    f = sp.feature
    bundled = f >= n_cols
    o = off_ext[f]
    nb = nb_ext[f]
    d = dbin_ext[f]
    v = jnp.arange(B, dtype=jnp.int32)
    in_r = jnp.logical_and(v > o, v <= o + nb)
    left = jnp.logical_or(
        jnp.logical_and(in_r, v <= o + 1 + sp.bin),
        jnp.logical_and(jnp.logical_not(in_r), d <= sp.bin))
    bits = pack_bin_bitset(left)
    return sp._replace(
        cat_bitset=jnp.where(bundled, bits, sp.cat_bitset))


def go_left_scalar_np(col: int, bin_: int, default_left: bool, nan_bin: int,
                      is_cat: bool, cat_bitset) -> bool:
    """Numpy scalar twin of go_left_pred for host-side consumers (TreeSHAP);
    MUST mirror go_left_pred bit-for-bit."""
    if is_cat:
        w = int(cat_bitset[col // 32]) if col // 32 < len(cat_bitset) else 0
        return bool((w >> (col % 32)) & 1)
    return col <= bin_ or (default_left and col == nan_bin)


def best_split(
    hist: jnp.ndarray,        # [F, B, K>=3] (grad, hess, count-weight[, raw-count])
    parent_grad: jnp.ndarray,
    parent_hess: jnp.ndarray,
    parent_count: jnp.ndarray,
    num_bins: jnp.ndarray,    # [F] i32
    nan_bin: jnp.ndarray,     # [F] i32 (bin NaN maps to; == num_bins-1 iff MissingType::NaN)
    has_nan_bin: jnp.ndarray, # [F] bool
    is_cat: jnp.ndarray,      # [F] bool
    feat_mask: jnp.ndarray,   # [F] bool: features allowed at this node
    p: SplitParams,
    mono_types: Optional[jnp.ndarray] = None,   # [F] i8 in {-1, 0, +1}
    cmin: Optional[jnp.ndarray] = None,         # scalar: leaf output bounds
    cmax: Optional[jnp.ndarray] = None,
    parent_output: float = 0.0,                 # for path smoothing
    depth: Optional[jnp.ndarray] = None,        # for the monotone penalty
    cegb_pen: Optional[jnp.ndarray] = None,     # [F] remaining coupled costs
    extra_key: Optional[jnp.ndarray] = None,    # PRNG key (extra_trees)
    feature_contri: Optional[jnp.ndarray] = None,  # [F] gain multipliers
    quant_scales: Optional[tuple] = None,       # (g_scale, h_scale) f32
    counts: Optional[jnp.ndarray] = None,       # [F, B, 2] i32 (count-weight, raw-count)
) -> SplitResult:
    """Find the best (feature, threshold, direction) for one leaf.

    ``counts``: the two count channels as int32, in the histogram's place
    (which may then hold grad and hess alone). ``parent_count`` is an
    int32 too, and every count below stays one: prefix sums, the other
    child's count, ``left_count`` and ``left_rows`` are exact at any row
    count below 2^31, where an f32 holds integers only below 2^24 (the
    data-parallel compact grower sums counts across shards as integers).

    ``quant_scales``: the histogram holds int32 quantized-gradient code sums
    (ops/histogram.py int8 path); the per-bin sums dequantize HERE — leaf
    scale multiply on the grad/hess channels — before any gain computation,
    so the scan/gain machinery below is dtype-blind (reference: the int
    histogram is unpacked with grad_scale/hess_scale inside the best-split
    kernel, cuda_best_split_finder.cu)."""
    if quant_scales is not None:
        from .histogram import dequantize_hist
        hist = dequantize_hist(hist, quant_scales[0], quant_scales[1])
    f, b, k = hist.shape
    g = hist[:, :, 0]
    h = hist[:, :, 1]
    if counts is not None:
        c, r = counts[:, :, 0], counts[:, :, 1]
    else:
        c = hist[:, :, 2]
        # raw (unweighted) row counts drive the compact grower's physical
        # partition; histograms without the channel fall back to the
        # weighted one
        r = hist[:, :, 3] if k > 3 else c
    cg = jnp.cumsum(g, axis=1)
    ch = jnp.cumsum(h, axis=1)
    cc = jnp.cumsum(c, axis=1)
    cr = jnp.cumsum(r, axis=1)

    t_iota = jnp.arange(b, dtype=jnp.int32)[None, :]        # [1, B]
    is_cat_b = is_cat[:, None]

    # numerical: left = bins <= t (cumulative); categorical one-hot: left = {bin == t}
    left_g1 = jnp.where(is_cat_b, g, cg)
    left_h1 = jnp.where(is_cat_b, h, ch)
    left_c1 = jnp.where(is_cat_b, c, cc)
    left_r1 = jnp.where(is_cat_b, r, cr)

    # direction 2 ("missing left"): move the NaN-bin mass to the left side for
    # thresholds strictly below the NaN bin. Only for numerical features with NaN.
    nan_g = jnp.take_along_axis(g, nan_bin[:, None], axis=1)
    nan_h = jnp.take_along_axis(h, nan_bin[:, None], axis=1)
    nan_c = jnp.take_along_axis(c, nan_bin[:, None], axis=1)
    nan_r = jnp.take_along_axis(r, nan_bin[:, None], axis=1)
    below = t_iota < nan_bin[:, None]
    left_g2 = cg + jnp.where(below, nan_g, 0.0)
    left_h2 = ch + jnp.where(below, nan_h, 0.0)
    left_c2 = cc + jnp.where(below, nan_c, 0)
    left_r2 = cr + jnp.where(below, nan_r, 0)

    parent_gain = leaf_gain(parent_grad, parent_hess, p)
    gain_shift = parent_gain + p.min_gain_to_split

    constrained = p.use_monotone or p.path_smooth > 0.0

    def dir_score(lg, lh, lc, extra_valid):
        rg = parent_grad - lg
        rh = parent_hess - lh
        rc = parent_count - lc
        valid = (
            extra_valid
            & feat_mask[:, None]
            & (lc >= p.min_data_in_leaf)
            & (rc >= p.min_data_in_leaf)
            & (lh >= p.min_sum_hessian_in_leaf)
            & (rh >= p.min_sum_hessian_in_leaf)
        )
        if constrained:
            # outputs move off the optimum (clip/smooth), so gains are
            # evaluated at the realized outputs (reference: GetSplitGains ->
            # GetSplitGainsGivenOutputs path)
            lw = child_output(lg, lh, lc, p, None, parent_output, cmin, cmax)
            rw = child_output(rg, rh, rc, p, None, parent_output, cmin, cmax)
            gain = gain_given_output(lg, lh, lw, p) \
                + gain_given_output(rg, rh, rw, p) - gain_shift
            if p.use_monotone and mono_types is not None:
                mt = mono_types[:, None].astype(jnp.int32)
                valid &= jnp.logical_not((mt > 0) & (lw > rw))
                valid &= jnp.logical_not((mt < 0) & (lw < rw))
                if p.monotone_penalty > 0.0:
                    pen = monotone_penalty_factor(depth, p.monotone_penalty)
                    gain = jnp.where(mt != 0, gain * pen, gain)
        else:
            gain = leaf_gain(lg, lh, p) + leaf_gain(rg, rh, p) - gain_shift
        if p.use_cegb and cegb_pen is not None:
            # (reference: CostEfficientGradientBoosting::DeltaGain)
            gain = gain - cegb_pen[:, None] \
                - p.cegb_split_pen * parent_count
        if feature_contri is not None:
            # per-feature split-gain scaling (reference: config.h
            # feature_contri / feature_histogram.hpp meta_->penalty)
            gain = jnp.where(gain > 0, gain * feature_contri[:, None], gain)
        return jnp.where(valid, gain, _NEG_INF)

    # categorical one-hot splits (only for low-cardinality features,
    # reference: use_onehot = num_bin <= max_cat_to_onehot) may use any bin
    # (incl. last) as the "left" category; numerical thresholds must leave
    # the last bin on the right
    onehot_ok = is_cat_b & (num_bins[:, None] <= p.max_cat_to_onehot)
    cat_tmask = jnp.where(is_cat_b, onehot_ok & (t_iota < num_bins[:, None]),
                          t_iota < num_bins[:, None] - 1)
    if p.extra_trees and extra_key is not None:
        # one random candidate threshold per feature (reference: USE_RAND
        # rand_threshold per feature in FindBestThresholdSequentially)
        import jax as _jax
        # numerical thresholds live in [0, num_bins-1); one-hot categorical
        # candidates may use any bin incl. the last
        hi = jnp.where(is_cat, num_bins, num_bins - 1)
        rnd = _jax.random.randint(extra_key, (f,), 0, jnp.maximum(hi, 1))
        cat_tmask = cat_tmask & (t_iota == rnd[:, None])
        below_rand = (t_iota == rnd[:, None])
    else:
        below_rand = None
    score1 = dir_score(left_g1, left_h1, left_c1, cat_tmask)
    dir2_ok = (~is_cat_b) & has_nan_bin[:, None] & below \
        & (t_iota < num_bins[:, None] - 1)
    if below_rand is not None:
        dir2_ok = dir2_ok & below_rand
    score2 = dir_score(left_g2, left_h2, left_c2, dir2_ok)

    scores = jnp.stack([score1, score2], axis=-1)            # [F, B, 2]
    flat = scores.reshape(-1)
    best = jnp.argmax(flat)
    best_gain = flat[best]
    best_f = (best // (b * 2)).astype(jnp.int32)
    best_b = ((best // 2) % b).astype(jnp.int32)
    best_dir2 = (best % 2).astype(bool)

    lg = jnp.where(best_dir2, left_g2[best_f, best_b], left_g1[best_f, best_b])
    lh = jnp.where(best_dir2, left_h2[best_f, best_b], left_h1[best_f, best_b])
    lc = jnp.where(best_dir2, left_c2[best_f, best_b], left_c1[best_f, best_b])
    lr = jnp.where(best_dir2, left_r2[best_f, best_b], left_r1[best_f, best_b])

    # ---- sorted many-category splits -------------------------------------
    # (reference: FindBestThresholdCategoricalInner's sorted branch,
    # src/treelearner/feature_histogram.cpp:243-339 — categories sorted by
    # grad/(hess+cat_smooth), prefix scans from both ends, l2 += cat_l2.)
    # Vectorized over features; the stateful min_data_per_group gating runs
    # as a lax.scan over the <= max_cat_threshold prefix positions. The
    # reference estimates per-bin counts from hessians (cnt_factor); exact
    # counts from the histogram's count channel are used here instead.
    sorted_any = bool(b > 1) and p.enable_sorted_cat
    cs, cbest = _sorted_cat_split(
        g, h, c, r, is_cat, num_bins, feat_mask, parent_grad, parent_hess,
        parent_count, gain_shift, p, parent_output, cmin,
        cmax, cegb_pen, extra_key, feature_contri) \
        if sorted_any else (None, None)
    if cs is not None:
        use_sorted = cbest["gain"] > best_gain
    else:
        use_sorted = jnp.asarray(False)

    w = -(-b // 32)
    # bitset for the numerical/one-hot winner: one-hot cat -> single bin bit
    best_is_cat = is_cat[best_f]
    onehot_mask = (jnp.arange(b) == best_b) & best_is_cat
    bitset_a = pack_bin_bitset(onehot_mask)

    if cs is not None:
        gain_ = jnp.where(use_sorted, cbest["gain"], best_gain)
        feat_ = jnp.where(use_sorted, cbest["feature"], best_f)
        bin_ = jnp.where(use_sorted, 0, best_b)
        dl_ = jnp.where(use_sorted, False, best_dir2)
        lg = jnp.where(use_sorted, cbest["left_grad"], lg)
        lh = jnp.where(use_sorted, cbest["left_hess"], lh)
        lc = jnp.where(use_sorted, cbest["left_count"], lc)
        lr = jnp.where(use_sorted, cbest["left_rows"], lr)
        bitset = jnp.where(use_sorted, cbest["bitset"], bitset_a)
    else:
        gain_, feat_, bin_, dl_ = best_gain, best_f, best_b, best_dir2
        bitset = bitset_a

    return SplitResult(
        gain=gain_,
        feature=feat_,
        bin=bin_,
        default_left=dl_,
        left_grad=lg,
        left_hess=lh,
        left_count=lc,
        left_rows=lr,
        cat_bitset=bitset,
        is_cat_l2=use_sorted,
    )


def _sorted_cat_split(g, h, c, r, is_cat, num_bins, feat_mask, parent_grad,
                      parent_hess, parent_count, gain_shift, p: SplitParams,
                      parent_output=0.0, cmin=None, cmax=None, cegb_pen=None,
                      extra_key=None, feature_contri=None):
    """Best sorted-many-category split over all features; returns
    (True, dict) or (None, None) when no feature qualifies statically."""
    f, b = g.shape
    if not bool(is_cat.shape):  # pragma: no cover - shape guard
        return None, None
    mct = int(min(p.max_cat_threshold, b))
    if mct <= 0:
        return None, None
    l2c = p.lambda_l2 + p.cat_l2

    sort_mode = is_cat & (num_bins > p.max_cat_to_onehot) & feat_mask  # [F]
    elig = sort_mode[:, None] & (c >= p.cat_smooth)                    # [F, B]
    used_bin = elig.sum(axis=1).astype(jnp.int32)                      # [F]
    ratio = jnp.where(elig, g / (h + p.cat_smooth), jnp.inf)
    order = jnp.argsort(ratio, axis=1, stable=True)                    # [F, B]
    sg = jnp.take_along_axis(g, order, axis=1)
    sh = jnp.take_along_axis(h, order, axis=1)
    sc = jnp.take_along_axis(c, order, axis=1)
    sr = jnp.take_along_axis(r, order, axis=1)
    def csum0(x):     # [F, B+1] prefix sums from 0, in x's own dtype
        return jnp.pad(jnp.cumsum(x, axis=1), ((0, 0), (1, 0)))

    cg, ch, cc, cr = csum0(sg), csum0(sh), csum0(sc), csum0(sr)

    tot_idx = used_bin[:, None]                                       # [F, 1]
    max_num_cat = jnp.minimum(mct, (used_bin + 1) // 2)               # [F]

    # prefix tensors for all candidate set sizes t in 1..mct at once:
    # forward = first t sorted categories; reverse = last t eligible ones
    ts = jnp.arange(1, mct + 1, dtype=jnp.int32)                      # [T]
    idx_fwd = jnp.minimum(ts[None, :], b)                             # [F?,T]
    idx_fwd = jnp.broadcast_to(idx_fwd, (f, mct))
    idx_rev = jnp.maximum(tot_idx - ts[None, :], 0)                   # [F, T]

    def pref(csum):
        top = jnp.take_along_axis(csum, tot_idx, axis=1)              # [F, 1]
        fwd = jnp.take_along_axis(csum, idx_fwd, axis=1)              # [F, T]
        rev = top - jnp.take_along_axis(csum, idx_rev, axis=1)        # [F, T]
        return jnp.stack([fwd, rev], axis=2)                          # [F, T, 2]

    lg_t = pref(cg)
    lh_t = pref(ch)
    lc_t = pref(cc)
    lr_t = pref(cr)
    in_range = ((ts[None, :] <= used_bin[:, None])
                & (ts[None, :] <= max_num_cat[:, None])
                & sort_mode[:, None])                                 # [F, T]
    step_cnt = jnp.diff(lc_t, axis=1, prepend=0)                      # [F, T, 2]

    # stateful gating scan over t (cnt_cur_group accumulation + break flags)
    def gate(state, inputs):
        grp, dead = state                                             # [F, 2]
        sc_t, lct, lht, ok_t = inputs
        grp = grp + sc_t
        left_ok = (lct >= p.min_data_in_leaf) & \
            (lht >= p.min_sum_hessian_in_leaf)
        rc = parent_count - lct
        rh = parent_hess - lht
        brk = (rc < p.min_data_in_leaf) | (rc < p.min_data_per_group) | \
            (rh < p.min_sum_hessian_in_leaf)
        alive = jnp.logical_not(dead) & ok_t[:, None]
        evald = alive & left_ok & jnp.logical_not(brk) & \
            (grp >= p.min_data_per_group)
        grp = jnp.where(evald, 0, grp)
        dead = dead | (alive & brk)
        return (grp, dead), evald

    state0 = (jnp.zeros((f, 2), c.dtype), jnp.zeros((f, 2), bool))
    _, evald = lax.scan(
        gate, state0,
        (jnp.moveaxis(step_cnt, 1, 0), jnp.moveaxis(lc_t, 1, 0),
         jnp.moveaxis(lh_t, 1, 0), jnp.moveaxis(in_range, 1, 0)))
    evald = jnp.moveaxis(evald, 0, 1)                                 # [F, T, 2]

    rg_t = parent_grad - lg_t
    rh_t = parent_hess - lh_t
    if p.use_monotone or p.path_smooth > 0.0:
        # gains at realized (clipped/smoothed) outputs so they stay
        # comparable with the numerical candidates' constrained gains
        # (reference: GetSplitGains with constraints in the cat branch)
        rc_t = parent_count - lc_t
        lw_t = child_output(lg_t, lh_t, lc_t, p, l2c, parent_output,
                            cmin, cmax)
        rw_t = child_output(rg_t, rh_t, rc_t, p, l2c, parent_output,
                            cmin, cmax)
        gains = gain_given_output(lg_t, lh_t, lw_t, p, l2c) \
            + gain_given_output(rg_t, rh_t, rw_t, p, l2c) - gain_shift
    else:
        gains = leaf_gain(lg_t, lh_t, p, l2c) + leaf_gain(rg_t, rh_t, p, l2c) \
            - gain_shift
    if p.use_cegb and cegb_pen is not None:
        gains = gains - cegb_pen[:, None, None] \
            - p.cegb_split_pen * parent_count
    if feature_contri is not None:
        gains = jnp.where(gains > 0,
                          gains * feature_contri[:, None, None], gains)
    if p.extra_trees and extra_key is not None:
        # one random prefix size per feature (reference: USE_RAND
        # rand_threshold in the categorical branch)
        import jax as _jax
        rnd_t = _jax.random.randint(
            _jax.random.fold_in(extra_key, 1), (f,), 0,
            jnp.maximum(max_num_cat, 1))
        gains = jnp.where(
            (jnp.arange(mct)[None, :, None] == rnd_t[:, None, None]),
            gains, _NEG_INF)
    gains = jnp.where(evald, gains, _NEG_INF)

    flatc = gains.reshape(-1)
    cb = jnp.argmax(flatc)
    cgain = flatc[cb]
    cf = (cb // (mct * 2)).astype(jnp.int32)
    ct = ((cb // 2) % mct).astype(jnp.int32)          # t-1
    cdir_rev = (cb % 2).astype(bool)

    # chosen category set -> bin bitset
    pos = jnp.arange(b, dtype=jnp.int32)
    t_best = ct + 1
    ub = used_bin[cf]
    pos_mask = jnp.where(cdir_rev,
                         (pos >= ub - t_best) & (pos < ub),
                         pos < t_best)
    bin_mask = jnp.zeros((b,), bool).at[order[cf]].set(pos_mask)
    bitset = pack_bin_bitset(bin_mask)

    sel = (cf, ct, jnp.where(cdir_rev, 1, 0))
    cbest = {
        "gain": cgain,
        "feature": cf,
        "left_grad": lg_t[sel],
        "left_hess": lh_t[sel],
        "left_count": lc_t[sel],
        "left_rows": lr_t[sel],
        "bitset": bitset,
    }
    return True, cbest
