"""work.py and modeltext.py on a hand-written three-leaf tree."""
import numpy as np
import pytest

from benchmarks import modeltext, work

TEXT = """tree
version=v4

Tree=0
num_leaves=3
num_cat=0
split_feature=1 0
split_gain=10 4
threshold=0.5 -0.25
decision_type=0 0
left_child=1 -1
right_child=-2 -3
leaf_value=0.1 -0.2 0.3
leaf_weight=15 10 5
leaf_count=60 40 20
internal_value=0 0.05
internal_weight=30 20
internal_count=120 80
is_linear=0
shrinkage=0.1

end of trees
"""


def test_three_leaf_tree():
    tree, = modeltext.parse_trees(TEXT)
    assert tree["num_leaves"] == 3
    # root 120 rows; its smaller child is leaf 1 (40); node 1 (80 rows)
    # splits into 60 and 20: smaller 20
    rows = work.tree_rows(tree)
    assert rows == {"hist_rows": 120 + 40 + 20, "part_rows": 120 + 80}
    w = work.window_work([tree, tree], num_features=4, num_bins=8)
    assert w["flops"] == 2.0 * 360 * 4 * 8 * 2
    assert w["bytes"] == (360 + 2 * 400) * (4 + 8)
    least, bound = work.least_seconds(
        w, {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e6})
    assert bound == "flops" and least == pytest.approx(w["flops"] / 1e3)


def test_children_sums_follow_the_tree():
    tree, = modeltext.parse_trees(TEXT)
    counts = tree["leaf_count"][:, None].astype(float)
    left, right, node = modeltext.children_sums(tree, counts)
    assert node[:, 0].tolist() == [120, 80]
    assert left[:, 0].tolist() == [80, 60]
    assert right[:, 0].tolist() == [40, 20]


def test_floor_f32_keeps_the_comparison_exact():
    t = np.array([0.1, 1.0, -0.3, 1e-40])
    f = modeltext.floor_f32(t)
    assert f.dtype == np.float32
    assert np.all(f.astype(np.float64) <= t)
    assert np.all(np.nextafter(f, np.float32(np.inf)).astype(np.float64) > t)
