"""Quantized-gradient training (LightGBM ``use_quantized_grad``), plainly.

Float64 and int64 numpy, no kernels, nothing on a device (the gain of a
split is ``forest.py``'s formula, plain arithmetic): what the tests hold the
program's discretizer, integer histograms, split choice and leaf renewal
to (``tests/test_quantized_reference.py``). It follows Shi, Ke, Chen,
Zheng and Liu, "Quantized Training of Gradient Boosting Decision Trees"
(NeurIPS 2022, arXiv:2207.09682) and the reference implementation's
``GradientDiscretizer::DiscretizeGradients`` (``gradient_discretizer.cpp``)
as ``SURVEY.md`` describes it:

* an iteration's gradients are scaled by ``max|g| / (bins // 2)`` and its
  hessians by ``max h / bins`` (a constant hessian by ``max h``: every code
  is 1), and a row's code is the scaled value rounded stochastically: a
  uniform draw in [0, 1) is added, away from zero, and the sum truncated
  toward zero, so that the code's expectation is the scaled value;
* histograms are sums of the integer codes; a split's gain is computed
  from the code sums times the scales;
* ``quant_train_renew_leaf``: once a tree has its structure, every leaf's
  value is computed anew from the true gradients of its rows.

Departures, each on purpose:

* The uniforms are an argument. The reference implementation draws them
  once for all rows and starts each iteration at a random offset; here a
  caller passes the draws it wants the codes of, or 0.5 everywhere for
  rounding to nearest (``stochastic_rounding=false``).
* Code sums are int64 whatever the leaf's size. The reference
  implementation picks 8-, 16- or 32-bit histograms by the leaf's row
  count (``GetHistBitsInLeaf``); the width changes no sum that fits it.
* Dense rows, numerical features, no missing values, no sampling, one
  tree an iteration: what the benchmark's cells hold
  (``reference/forest.py``).
* The gain leaves out the constant the reference implementation's
  ``kEpsilon`` adds to a hessian sum.

The comparison that decides a benchmark cell's ``correct`` does not read
this file: it holds a renewed leaf to the true float32 gradients
(``correct.py``, ``reference/binary.py``).
"""
import numpy as np

from .forest import split_gain      # the gain from [.., (count, G, H)] sums


def scales(g, h, bins, const_hess=False):
    """``(g_scale, h_scale)`` of one iteration's gradients and hessians."""
    g = np.asarray(g, np.float64)
    h = np.asarray(h, np.float64)
    g_scale = np.max(np.abs(g)) / (bins // 2)
    h_scale = np.max(np.abs(h)) / (1 if const_hess else bins)
    return float(g_scale), float(h_scale)


def discretize(g, h, bins, u_g, u_h, const_hess=False):
    """``(code_g, code_h, g_scale, h_scale)``: int64 codes of every row.

    ``u_g`` and ``u_h`` are the uniforms in [0, 1) that round a row's
    scaled gradient and hessian (arrays, or the scalar 0.5 for rounding to
    nearest). ``|code_g| <= bins // 2`` and ``0 <= code_h <= bins``."""
    g = np.asarray(g, np.float64)
    h = np.asarray(h, np.float64)
    g_scale, h_scale = scales(g, h, bins, const_hess)
    code_g = np.trunc(g / g_scale + np.sign(g) * u_g).astype(np.int64)
    code_h = np.trunc(h / h_scale + u_h).astype(np.int64)
    return code_g, code_h, g_scale, h_scale


def histogram(binned, code_g, code_h, num_bins):
    """Integer histograms of the rows given: ``[F, num_bins, 3]`` int64,
    per feature and bin the row count, the sum of gradient codes and the
    sum of hessian codes (``forest.py``'s order of a leaf's sums).
    ``binned`` is ``[rows, F]``, a row's bin per feature."""
    binned = np.asarray(binned)
    out = np.zeros((binned.shape[1], num_bins, 3), np.int64)
    ones = np.ones(binned.shape[0], np.int64)
    for f in range(binned.shape[1]):
        for k, v in enumerate((ones, code_g, code_h)):
            out[f, :, k] = np.bincount(binned[:, f], weights=v,
                                       minlength=num_bins)[:num_bins]
    return out


def best_split(hist, g_scale, h_scale, feature_bins, params):
    """The best ``(gain, feature, threshold bin)`` of one leaf from its
    integer histogram, or None where no split is allowed. Rows whose bin
    is at most the threshold go left; a feature's last bin stays right.
    The code sums are accumulated as integers and dequantised once."""
    l2 = float(params.get("lambda_l2", 0.0))
    min_rows = int(params.get("min_data_in_leaf", 20))
    min_hess = float(params.get("min_sum_hessian_in_leaf", 1e-3))
    min_gain = float(params.get("min_gain_to_split", 0.0))
    below = np.cumsum(hist, axis=1)                     # [F, B, 3] int64
    above = below[0, -1][None, None, :] - below
    dequantise = np.array([1.0, g_scale, h_scale])
    below, above = below * dequantise, above * dequantise
    with np.errstate(divide="ignore", invalid="ignore"):  # an empty side
        gain = split_gain(below, above, l2) - min_gain
    ok = ((below[..., 0] >= min_rows) & (above[..., 0] >= min_rows)
          & (below[..., 2] >= min_hess) & (above[..., 2] >= min_hess)
          & (np.arange(hist.shape[1])[None, :]
             < np.asarray(feature_bins)[:, None] - 1))
    gain = np.where(ok, gain, -np.inf)
    f, t = np.unravel_index(np.argmax(gain), gain.shape)
    if not gain[f, t] > 0:
        return None
    return float(gain[f, t]), int(f), int(t)


def grow_tree(binned, code_g, code_h, g_scale, h_scale, feature_bins,
              params):
    """One tree, leaf-wise: the leaf whose best split gains most is split
    next, until ``num_leaves`` or no split is left. A split's left child
    keeps the leaf's number and its right child takes the next unused one,
    as in the model text. Returns ``split_feature``, ``threshold_bin``,
    ``left_child``, ``right_child`` (a negative child is the leaf
    ``~child``), ``leaf_count``, ``leaf_of_row`` and the root's integer
    ``root_hist``."""
    binned = np.asarray(binned)
    num_bins = int(np.max(feature_bins))
    rows = {0: np.arange(binned.shape[0])}

    def candidate(leaf):
        r = rows[leaf]
        hist = histogram(binned[r], code_g[r], code_h[r], num_bins)
        return hist, best_split(hist, g_scale, h_scale, feature_bins,
                                params)

    root_hist, first = candidate(0)
    found = {0: first}
    where = {0: None}           # leaf -> (its parent node, which side)
    feature, threshold, left, right = [], [], [], []
    for node in range(int(params["num_leaves"]) - 1):
        open_ = {leaf: s for leaf, s in found.items() if s is not None}
        if not open_:
            break
        leaf = max(open_, key=lambda i: (open_[i][0], -i))
        _, f, t = open_[leaf]
        new = node + 1
        if where[leaf] is not None:
            parent, side = where[leaf]
            (left if side == 0 else right)[parent] = node
        feature.append(f)
        threshold.append(t)
        left.append(~leaf)
        right.append(~new)
        goes_left = binned[rows[leaf], f] <= t
        rows[leaf], rows[new] = (rows[leaf][goes_left],
                                 rows[leaf][~goes_left])
        where[leaf], where[new] = (node, 0), (node, 1)
        for child in (leaf, new):
            found[child] = candidate(child)[1]
    leaf_of_row = np.empty(binned.shape[0], np.int64)
    for leaf, r in rows.items():
        leaf_of_row[r] = leaf
    return {"split_feature": np.array(feature, np.int64),
            "threshold_bin": np.array(threshold, np.int64),
            "left_child": np.array(left, np.int64),
            "right_child": np.array(right, np.int64),
            "leaf_count": np.array([len(rows[i]) for i in range(len(rows))],
                                   np.int64),
            "leaf_of_row": leaf_of_row, "root_hist": root_hist}


def leaf_sums(values, leaf_of_row, num_leaves):
    return np.bincount(leaf_of_row, weights=np.asarray(values, np.float64),
                       minlength=num_leaves)


def quantized_leaf_values(code_g, code_h, g_scale, h_scale, leaf_of_row,
                          num_leaves, learning_rate, l2=0.0):
    """What a leaf holds without renewal: ``-lr G / (H + l2)`` of its
    rows' dequantised code sums."""
    G = leaf_sums(code_g, leaf_of_row, num_leaves) * g_scale
    H = leaf_sums(code_h, leaf_of_row, num_leaves) * h_scale
    return -learning_rate * G / (H + l2)


def renewed_leaf_values(g, h, leaf_of_row, num_leaves, learning_rate,
                        l2=0.0):
    """``quant_train_renew_leaf``: ``-lr G / (H + l2)`` of the true
    gradients and hessians of a leaf's rows."""
    G = leaf_sums(g, leaf_of_row, num_leaves)
    H = leaf_sums(h, leaf_of_row, num_leaves)
    return -learning_rate * G / (H + l2)
