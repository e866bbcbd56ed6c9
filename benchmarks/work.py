"""The work a training window did, counted from the trees it produced.

Nothing here looks at how the program grows a tree: a later kernel change
cannot make the count stale. Per tree, from ``internal_count`` and
``leaf_count`` in the model text:

``hist_rows``  the root's rows plus, for every split, the smaller child's
               rows: the rows whose histograms have to be built when the
               larger child's is taken by subtraction.
``part_rows``  the sum over splits of the parent's rows: the rows that
               have to be moved (or marked) when a leaf is split.
"""
import numpy as np


def tree_rows(tree):
    n_int = tree["num_leaves"] - 1
    if n_int <= 0:
        return {"hist_rows": 0, "part_rows": 0}

    def rows(child):
        return (tree["leaf_count"][~child] if child < 0
                else tree["internal_count"][child])

    smaller = sum(min(rows(tree["left_child"][i]),
                      rows(tree["right_child"][i])) for i in range(n_int))
    return {"hist_rows": int(tree["internal_count"][0] + smaller),
            "part_rows": int(np.sum(tree["internal_count"]))}


def window_work(trees, num_features, num_bins):
    """Semantic work of growing ``trees``: ``flops`` is the histogram
    contraction, 2 x hist_rows x F x B x 2 (a multiply-add per row, feature
    and bin, for the gradient and the hessian sums); ``bytes`` is
    (hist_rows + 2 x part_rows) x (F + 8): the bin bytes and two f32 a
    row, read for a histogram, read and written for a partition."""
    rows = [tree_rows(t) for t in trees]
    hist = sum(r["hist_rows"] for r in rows)
    part = sum(r["part_rows"] for r in rows)
    return {"hist_rows": hist, "part_rows": part,
            "flops": 2.0 * hist * num_features * num_bins * 2,
            "bytes": float(hist + 2 * part) * (num_features + 8)}


def least_seconds(work, peak):
    """The least time the chip could take for ``work``, and which peak
    bounds it."""
    t_flop = work["flops"] / peak["bf16_flops_per_s"]
    t_byte = work["bytes"] / peak["hbm_bytes_per_s"]
    return max(t_flop, t_byte), ("flops" if t_flop >= t_byte else "bytes")
