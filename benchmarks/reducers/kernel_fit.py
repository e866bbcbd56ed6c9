"""What one call of the fused split kernel costs, by least squares over
the step calls of the traced window:

    device seconds of a call = a + b x parent rows + c x smaller-child rows

``a`` is the fixed cost of a call (``coefficient`` ``call``, in us), ``b``
the cost of a partitioned row (``part_row``, ns), ``c`` of a histogram row
(``hist_row``, ns). The seconds are the trace's, one event a call
(``step_pattern``: the compact grower names that call site
``fused_split_step``); the rows are the model text's: internal node *i*
of a tree is its *i*-th split, ``internal_count[i]`` rows are
partitioned, the smaller child's are histogrammed (work.py counts the
same). The loop makes ``num_leaves - 1`` calls a tree whatever the tree
grew; a call past the tree's last split moves no rows. A tree starts at
its root call (``root_pattern``: hist-only over all rows), which is held
out of the fit: ``fit`` returns what the fit predicts for it beside what
it took, as a check of ``c``. The squares summed are those of the
relative residuals. Silent where the trace shows no such calls
or their count does not match the trees'."""
import re

import numpy as np

from ..modeltext import parse_trees
from ..trace import clipped

UNITS = {"call": 1e6, "part_row": 1e9, "hist_row": 1e9}


def split_rows(tree):
    """[(parent rows, smaller child's rows)] per split, in split order."""
    def rows(child):
        return (tree["leaf_count"][~child] if child < 0
                else tree["internal_count"][child])
    return [(int(tree["internal_count"][i]),
             int(min(rows(tree["left_child"][i]),
                     rows(tree["right_child"][i]))))
            for i in range(tree["num_leaves"] - 1)]


def fit(run, step_pattern="^fused_split_step", root_pattern="^fused_split_root"):
    profile, produced = run.get("profile"), run.get("produced")
    if not profile or not profile["devices"] or not produced:
        return None
    first = produced["first_window_tree"]
    trees = parse_trees(produced["model_text"])[
        first:first + run["iterations"]]
    step, root = re.compile(step_pattern), re.compile(root_pattern)
    events = sorted(clipped(next(iter(profile["devices"].values())),
                            profile["window"]), key=lambda ev: ev[1])
    calls, roots = [], []           # per tree: its step calls' seconds
    for name, s, e in events:
        if root.search(name):
            roots.append(e - s)
            calls.append([])
        elif step.search(name) and calls:
            calls[-1].append(e - s)
    if not trees or len(calls) != len(trees):
        return None
    x, y = [], []
    for tree, took in zip(trees, calls):
        rows = split_rows(tree)
        if len(took) < len(rows):
            return None
        rows += [(0, 0)] * (len(took) - len(rows))
        x += [(1.0, part, hist) for part, hist in rows]
        y += took
    x, y = np.array(x), np.array(y)
    if len(y) < 3:
        return None
    # relative residuals: a call's time scatters in proportion to its
    # length, and the many short calls late in a tree are what fixes a
    coef, *_ = np.linalg.lstsq(x / y[:, None], np.ones_like(y), rcond=None)
    resid = y - x @ coef
    total = float(np.sum((y - y.mean()) ** 2))
    n_rows = [int(t["internal_count"][0]) for t in trees]
    return {
        "call": float(coef[0]), "part_row": float(coef[1]),
        "hist_row": float(coef[2]), "calls": len(y),
        "r2": 1.0 - float(np.sum(resid ** 2)) / total if total else None,
        "residual_rms_s": float(np.sqrt(np.mean(resid ** 2))),
        "residual_max_s": float(np.max(np.abs(resid))),
        "residual_rms_relative": float(np.sqrt(np.mean((resid / y) ** 2))),
        "mean_call_s": float(y.mean()),
        "root_s": roots,
        "root_predicted_s": [float(coef[0] + coef[2] * n) for n in n_rows],
    }


def reduce(run, coefficient, **patterns):
    out = fit(run, **patterns)
    return None if out is None else out[coefficient] * UNITS[coefficient]
