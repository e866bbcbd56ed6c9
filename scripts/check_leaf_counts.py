"""Invariant check for the fused+EFB shape: the scan's per-leaf row counts
(recorded in the model as leaf_count) must equal an independent re-routing
of the training data through the saved tree.

If the split scan's n_left ever disagrees with the kernel's routing, the
partition writes drift — in dual-residency mode that drift becomes
out-of-bounds DMA (the open TPU fault); in copy-back mode it would show up
here as count mismatches.

Usage: REPRO_ROWS=120000 python scripts/check_leaf_counts.py

Run it directly (through the chip tool on the TPU): the chip belongs to one
process, so never start it from a parent that has already touched JAX.
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ROWS = int(os.environ.get("REPRO_ROWS", 120_000))
FEATS = int(os.environ.get("REPRO_FEATS", 4228))
LEAVES = int(os.environ.get("REPRO_LEAVES", 255))
ITERS = int(os.environ.get("REPRO_ITERS", 2))

from bench import make_allstate_like  # noqa: E402
import lightgbm_tpu as lgb  # noqa: E402
from lightgbm_tpu.analysis.guards import (  # noqa: E402
    checkout_cache_dir, configure_compile_cache)

configure_compile_cache(checkout_cache_dir())

params = {
    "objective": "binary", "num_leaves": LEAVES, "max_bin": 255,
    "learning_rate": 0.1, "min_data_in_leaf": 100, "verbosity": -1,
    "stop_check_freq": 10_000, "bin_construct_sample_cnt": 20_000,
}
X, y = make_allstate_like(ROWS, FEATS)
ds = lgb.Dataset(X, label=y, params=params)
ds.construct()
print(f"[check] construct done, cols={ds._inner.binned.shape[1]}", flush=True)
bst = lgb.Booster(params, ds)
for i in range(ITERS):
    bst.update()
bst._gbdt._flush_trees()

leaves = bst.predict(X, pred_leaf=True)          # [N, T] raw-space routing
bad = 0
for t, m in enumerate(bst._gbdt.models):
    counts = np.bincount(leaves[:, t], minlength=m.num_leaves)
    model_counts = np.asarray(m.leaf_count[: m.num_leaves]).astype(np.int64)
    if not np.array_equal(counts[: m.num_leaves], model_counts):
        diff = counts[: m.num_leaves] - model_counts
        nz = np.nonzero(diff)[0]
        print(f"[check] tree {t}: MISMATCH at leaves {nz[:10]} "
              f"(delta {diff[nz][:10]}, total |delta| {np.abs(diff).sum()})",
              flush=True)
        bad += 1
print(f"[check] {'FAIL' if bad else 'OK'}: {bad}/{len(bst._gbdt.models)} "
      f"trees with count mismatches", flush=True)
