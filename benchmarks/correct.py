"""The comparison that decides ``correct`` for a training window.

What the window produced (its trees, as model text, and the training score
at its end, in the dataset's row order) is held against the plain
reference (reference/): the forest is replayed on the raw rows, and each
of the first ``CHECKED_TREES`` trees the window grew is held, leaf by
leaf, against what the reference's own gradients say the tree should
hold. Numbers compared, each with a limit of its own (the cell's workload
file; PERF.md gives the readings each limit was set from):

``trees_missing``      window iterations that left no tree behind
``leaf_count_wrong``   leaves, over all trees, whose row count is not the
                       replay's (the partition; exact)
``score_gap``          worst row of |program's score - replayed score|
``leaf_value_gap``     worst leaf of |leaf_value - (-lr G / (H + l2))|
``leaf_hessian_gap``   worst leaf of |leaf_weight - H|
``split_gain_gap``     worst split of |split_gain - gain from G, H sums|
``root_gain_short``    how far the root split's gain falls short of the
                       best split on the reference's own grid

A gap is measured against the reference's value for that leaf, or the
median leaf's where that is larger: some leaves are all but zero.
"""
import importlib

import jax.numpy as jnp
import numpy as np

from .modeltext import children_sums, parse_trees
from .reference import forest

CHECKED_TREES = 3
GRID_SAMPLE_ROWS = 100_000


def relative_gap(got, want):
    """Worst entry of |got - want| over max(|want|, median |want|)."""
    want = np.asarray(want, np.float64)
    scale = np.maximum(np.abs(want), np.median(np.abs(want)))
    return float(np.max(np.abs(np.asarray(got, np.float64) - want)
                        / np.maximum(scale, 1e-300)))


def tree_readings(tree, sums, params):
    """One checked tree against the reference's per-leaf [count, G, H]."""
    lr = float(params.get("learning_rate", 0.1))
    l2 = float(params.get("lambda_l2", 0.0))
    want_value = -lr * sums[:, 1] / (sums[:, 2] + l2)
    left, right, _ = children_sums(tree, sums)
    want_gain = forest.split_gain(left, right, l2)
    return {
        "leaf_value_gap": relative_gap(tree["leaf_value"], want_value),
        "leaf_hessian_gap": relative_gap(tree["leaf_weight"], sums[:, 2]),
        "split_gain_gap": relative_gap(tree["split_gain"], want_gain),
    }, float(want_gain[0])


def best_on_grid(XT, grid, g, h, total, params):
    """The best gain over the reference's own thresholds at the root;
    ``total`` is the root's [count, G, H]."""
    below = np.asarray(forest.threshold_sums(XT, grid, g, h), np.float64)
    above = total[None, None, :] - below
    gain = forest.split_gain(below, above, float(params.get("lambda_l2", 0)))
    min_rows = float(params.get("min_data_in_leaf", 20))
    min_hess = float(params.get("min_sum_hessian_in_leaf", 1e-3))
    ok = ((below[..., 0] >= min_rows) & (above[..., 0] >= min_rows)
          & (below[..., 2] >= min_hess) & (above[..., 2] >= min_hess))
    return float(np.max(np.where(ok, gain, -np.inf)))


def reference_readings(produced, data, config, objective=None,
                       precision=jnp.float32):
    """Every number compared, from what the window produced.

    ``produced``: ``model_text``, ``train_score`` ([N] float32, dataset
    order), ``first_window_tree``, ``window_iterations``. ``data``: raw
    ``XT`` [F, N] float32, ``label``, ``group`` (or None). ``precision``
    below float32 is the control: the reference's gradients and leaf sums
    taken in it, in the program's place."""
    params = config["params"]
    trees = parse_trees(produced["model_text"])
    first = produced["first_window_tree"]
    n_window = produced["window_iterations"]
    readings = {"trees_missing": max(0, first + n_window - len(trees))}
    if objective is None:
        objective = importlib.import_module(
            f"{__package__}.reference.{params['objective']}")
    state = objective.prepare(data["label"], data.get("group"), params)
    XT = jnp.asarray(data["XT"])
    max_leaves = int(params["num_leaves"])
    rng = np.random.default_rng(produced.get("seed", 0))
    sample = rng.choice(XT.shape[1], min(GRID_SAMPLE_ROWS, XT.shape[1]),
                        replace=False)
    grid = jnp.asarray(forest.own_grid(data["XT"][:, np.sort(sample)].T,
                                       int(params["max_bin"]) - 1))

    score = jnp.zeros((XT.shape[1],), jnp.float32)
    zeros = jnp.zeros_like(score)
    wrong = 0
    checked = {"leaf_value_gap": 0.0, "leaf_hessian_gap": 0.0,
               "split_gain_gap": 0.0, "root_gain_short": -np.inf}
    for t, tree in enumerate(trees):
        leaf = forest.leaf_of_rows(XT, *forest.padded_nodes(tree, max_leaves))
        check = (first <= t < first + CHECKED_TREES
                 and tree["num_leaves"] > 1)
        g, h = (objective.gradients(state, score, precision) if check
                else (zeros, zeros))
        parts = forest.leaf_sums(leaf, g, h, max_leaves,
                                 precision if check else jnp.float32)
        sums = np.asarray(parts, np.float64).sum(axis=0)
        sums = sums[:tree["num_leaves"]]
        if check:
            got, root_gain = tree_readings(tree, sums, params)
            best = best_on_grid(XT, grid, g, h, sums.sum(axis=0), params)
            got["root_gain_short"] = (best - root_gain) / best
            for key, value in got.items():
                checked[key] = max(checked[key], value)
        wrong += int(np.sum(np.rint(sums[:, 0]) != tree["leaf_count"]))
        wrong += int(np.sum(children_sums(
            tree, tree["leaf_count"][:, None].astype(np.float64))[2][:, 0]
            != tree["internal_count"]))
        score = forest.add_tree(score, leaf, jnp.asarray(
            tree["leaf_value"], jnp.float32))
    readings["leaf_count_wrong"] = wrong
    readings["score_gap"] = relative_gap(produced["train_score"],
                                         np.asarray(score))
    readings.update(checked)
    return readings


def judge(readings, limits):
    """(correct, [(name, reading, limit)]) over the limits in the cell's
    file: a limit with no reading fails; a reading with no limit is not
    compared (the harness prints it on an earlier line)."""
    rows, ok = [], True
    for name, limit in limits.items():
        value = readings.get(name)
        rows.append((name, value, limit))
        if value is None or not np.isfinite(value) or value > limit:
            ok = False
    return ok, rows
