"""Trees out of a LightGBM model text, as plain numpy arrays.

The yardstick's own reader: the benchmark takes the trained model from the
program only as the text ``Booster.model_to_string()`` returns, and reads
the trees here, so that the work counted (work.py) and the comparison
(correct.py) rest on what the window produced and on nothing inside the
program.
"""
import numpy as np

_INT = ("split_feature", "left_child", "right_child", "leaf_count",
        "internal_count", "decision_type")
_FLOAT = ("threshold", "split_gain", "leaf_value", "leaf_weight",
          "internal_value", "internal_weight")


def parse_trees(text):
    """List of dicts, one per tree in the text, in boosting order."""
    body = text.split("end of trees", 1)[0]
    trees = []
    for block in body.split("\nTree=")[1:]:
        kv = dict(line.split("=", 1) for line in block.splitlines()[1:]
                  if "=" in line)
        tree = {"num_leaves": int(kv["num_leaves"]),
                "shrinkage": float(kv.get("shrinkage", 1.0))}
        for key in _INT:
            tree[key] = np.array(kv.get(key, "").split(), dtype=np.int64)
        for key in _FLOAT:
            tree[key] = np.array(kv.get(key, "").split(), dtype=np.float64)
        if int(kv.get("num_cat", 0)):
            raise ValueError("categorical splits are not read here")
        n_int = tree["num_leaves"] - 1
        for key in ("split_feature", "threshold", "left_child",
                    "right_child", "split_gain", "internal_count"):
            if len(tree[key]) != n_int:
                raise ValueError(f"tree field {key} has {len(tree[key])} "
                                 f"entries, {n_int} expected")
        # a split's node is made before its children's: replaying nodes
        # in index order visits parents first
        for child in (tree["left_child"], tree["right_child"]):
            inner = child[child >= 0]
            if np.any(inner <= np.nonzero(child >= 0)[0]):
                raise ValueError("a child node precedes its parent")
        trees.append(tree)
    return trees


def floor_f32(t):
    """Largest float32 <= t (t: float64 array). For a float32 x,
    ``x <= t`` in exact arithmetic is ``x <= floor_f32(t)``."""
    t = np.asarray(t, np.float64)
    t32 = t.astype(np.float32)
    over = t32.astype(np.float64) > t
    return np.where(over, np.nextafter(t32, np.float32(-np.inf)), t32)


def children_sums(tree, leaf_vals):
    """Per internal node, the sum of ``leaf_vals`` (shape [num_leaves, k])
    over the leaves under its left child, its right child and itself."""
    n_int = tree["num_leaves"] - 1
    k = leaf_vals.shape[1]
    node = np.zeros((n_int, k))
    left = np.zeros((n_int, k))
    right = np.zeros((n_int, k))
    for i in range(n_int - 1, -1, -1):      # children have larger indices
        for side, child in ((left, tree["left_child"][i]),
                            (right, tree["right_child"][i])):
            side[i] = leaf_vals[~child] if child < 0 else node[child]
        node[i] = left[i] + right[i]
    return left, right, node
