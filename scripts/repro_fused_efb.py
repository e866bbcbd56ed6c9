"""Repro / bisect harness for the fused+EFB TPU worker fault (round 4).

Known-failing shape: allstate-like one-hot data, 4228 raw features (EFB
bundles to ~532 stored columns), 255 leaves, ~120k rows, 3 iterations.
Round 3's copy-back kernel ran this; round 4's dual-residency kernel
faults the TPU worker.

Usage: REPRO_ROWS=120000 REPRO_LEAVES=255 REPRO_ITERS=3 \
       LGBM_TPU_FORCE_FUSED_EFB=1 python scripts/repro_fused_efb.py
Prints REPRO_OK as the last line when training survives.

Run it directly (through the chip tool on the TPU): the chip belongs to one
process, so never start it from a parent that has already touched JAX.
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ROWS = int(os.environ.get("REPRO_ROWS", 120_000))
FEATS = int(os.environ.get("REPRO_FEATS", 4228))
LEAVES = int(os.environ.get("REPRO_LEAVES", 255))
ITERS = int(os.environ.get("REPRO_ITERS", 3))

from bench import make_allstate_like  # noqa: E402
import lightgbm_tpu as lgb  # noqa: E402
from lightgbm_tpu.analysis.guards import (  # noqa: E402
    checkout_cache_dir, configure_compile_cache)

configure_compile_cache(checkout_cache_dir())

params = {
    "objective": "binary",
    "num_leaves": LEAVES,
    "max_bin": 255,
    "learning_rate": 0.1,
    "min_data_in_leaf": 100,
    "verbosity": 1,
    "stop_check_freq": 10_000,
    "bin_construct_sample_cnt": 20_000,
}
for k in ("tpu_fused_block", "tpu_grower", "tpu_fused"):
    if os.environ.get(f"REPRO_{k.upper()}"):
        v = os.environ[f"REPRO_{k.upper()}"]
        params[k] = int(v) if v.lstrip("-").isdigit() else v

print(f"[repro] rows={ROWS} feats={FEATS} leaves={LEAVES} iters={ITERS} "
      f"params={params}", flush=True)
t0 = time.time()
X, y = make_allstate_like(ROWS, FEATS)
print(f"[repro] datagen {time.time() - t0:.1f}s", flush=True)
t0 = time.time()
ds = lgb.Dataset(X, label=y, params=params)
ds.construct()
print(f"[repro] construct {time.time() - t0:.1f}s "
      f"cols={ds._inner.binned.shape[1]}", flush=True)
bst = lgb.Booster(params, ds)
for i in range(ITERS):
    t0 = time.time()
    bst.update()
    bst._gbdt._flush_trees()
    print(f"[repro] iter {i} done {time.time() - t0:.1f}s", flush=True)
print("REPRO_OK", flush=True)
