"""Replaying a forest on raw rows, and what each of its trees should hold.

``XT`` is the raw feature matrix transposed, [F, N] float32, so that one
feature's column is contiguous. A tree is replayed split by split in node
order: every row carries the node it sits in, and node i sends its rows
to its left child where ``x[feature_i] <= threshold_i`` and to its right
child otherwise (dense data, no missing values).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..modeltext import floor_f32

ROW_BLOCK = 1 << 15
HIGHEST = jax.lax.Precision.HIGHEST


def padded_nodes(tree, max_leaves):
    """The tree's split arrays padded to ``max_leaves - 1`` nodes, so one
    compiled replay serves every tree. No row ever sits in a pad node."""
    n_int, width = tree["num_leaves"] - 1, max_leaves - 1

    def pad(a, dtype):
        out = np.zeros((width,), dtype)
        out[:n_int] = a
        return jnp.asarray(out)

    return (pad(tree["split_feature"], np.int32),
            pad(floor_f32(tree["threshold"]), np.float32),
            pad(tree["left_child"], np.int32),
            pad(tree["right_child"], np.int32))


@jax.jit
def leaf_of_rows(XT, feature, threshold, left, right):
    """Leaf index of every row, [N] int32."""
    def split(i, node):
        x = jax.lax.dynamic_index_in_dim(XT, feature[i], 0, keepdims=False)
        child = jnp.where(x <= threshold[i], left[i], right[i])
        return jnp.where(node == i, child, node)

    start = jnp.zeros((XT.shape[1],), jnp.int32)
    node = jax.lax.fori_loop(0, feature.shape[0], split, start)
    # a single-leaf tree has no node 0: every row is in leaf 0
    return jnp.where(node < 0, -node - 1, 0)


def _blocks(a, fill):
    n = a.shape[0]
    pad = (-n) % ROW_BLOCK
    return jnp.pad(a, (0, pad), constant_values=fill).reshape(-1, ROW_BLOCK)


@functools.partial(jax.jit, static_argnames=("num_leaves", "dtype"))
def leaf_sums(leaf, g, h, num_leaves, dtype=jnp.float32):
    """Per leaf [count, sum g, sum h], as per-block partial sums
    [blocks, num_leaves, 3] for the host to add up in float64. ``dtype``
    is the precision the rows enter the sum in (the control lowers it)."""
    def block(_, x):
        lf, gb, hb = x
        onehot = (lf[:, None] == jnp.arange(num_leaves)[None, :])
        vals = jnp.stack([jnp.ones_like(gb), gb, hb], axis=1)
        if dtype == jnp.float32:
            part = jnp.dot(onehot.astype(jnp.float32).T, vals,
                           precision=HIGHEST)
        else:
            part = jnp.dot(onehot.astype(dtype).T, vals.astype(dtype),
                           preferred_element_type=jnp.float32)
        return None, part

    _, parts = jax.lax.scan(block, None,
                            (_blocks(leaf, -1), _blocks(g, 0), _blocks(h, 0)))
    return parts


@jax.jit
def add_tree(score, leaf, leaf_value):
    return score + leaf_value[leaf]


@jax.jit
def threshold_sums(XT, grid, g, h):
    """For every feature f and grid threshold j, [count, sum g, sum h] of
    the rows with x[f] <= grid[f, j]: shape [F, J, 3]."""
    gb, hb = _blocks(g, 0), _blocks(h, 0)
    ok = _blocks(jnp.ones_like(g), 0)
    vals = jnp.stack([ok, gb, hb], axis=2)                  # [blocks, R, 3]

    def feature(args):
        x, cuts = args

        def block(acc, xs):
            xb, vb = xs
            below = (xb[:, None] <= cuts[None, :]).astype(jnp.float32)
            return acc + jnp.dot(below.T, vb, precision=HIGHEST), None

        acc, _ = jax.lax.scan(block, jnp.zeros((cuts.shape[0], 3)),
                              (_blocks(x, jnp.inf), vals))
        return acc

    return jax.lax.map(feature, (XT, grid))


def own_grid(X_sample, num_cuts):
    """The reference's own candidate thresholds: per feature, midpoints
    between neighbouring quantiles of a sample of rows. [F, num_cuts]."""
    qs = np.quantile(X_sample.astype(np.float64),
                     np.linspace(0, 1, num_cuts + 2)[1:-1], axis=0).T
    return floor_f32(qs)


def split_gain(left, right, lambda_l2=0.0):
    """LightGBM's gain of a split from [.., (count, G, H)] sums."""
    def score(s):
        return s[..., 1] ** 2 / (s[..., 2] + lambda_l2)
    return score(left) + score(right) - score(left + right)
