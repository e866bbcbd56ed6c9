"""Head-to-head: lightgbm_tpu (one TPU chip) vs the REAL LightGBM (CPU).

Same synthetic data, same config, held-out quality + wall-clock for both
sides (VERDICT r3 item 4: turn the accuracy and speed claims into
measurements). The reference build comes from /root/reference compiled into
.refsrc/lib_lightgbm.so (see tests/golden/README.md); it runs on THIS host's
CPU — note the core count in the output when comparing against the
28-thread numbers in BASELINE.md (docs/Experiments.rst).

Shapes (reference: Experiments.rst:113-121 table):
  higgs    dense 28-feature binary        (10.5M rows full size)
  sparse   one-hot wide binary, EFB territory (4228 raw features)
  ranking  lambdarank, 137 features, 50-doc queries

Writes BENCH_COMPARE.json and prints one line per (shape, side).

Env knobs: H2H_ROWS / H2H_SPARSE_ROWS / H2H_RANK_ROWS, H2H_ITERS,
H2H_SHAPES=higgs,sparse,ranking
"""
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, ".refpkg"))
sys.path.insert(0, ROOT)

ITERS = int(os.environ.get("H2H_ITERS", 15))
LEAVES = 255
BINS = 255


def _auc(y, p):
    from sklearn.metrics import roc_auc_score
    return float(roc_auc_score(y, p))


def _ndcg10(y, p, qsize):
    n = (len(y) // qsize) * qsize
    rel = y[:n].reshape(-1, qsize)
    sc = p[:n].reshape(-1, qsize)
    order = np.argsort(-sc, axis=1)
    g = np.take_along_axis(2.0 ** rel - 1, order, axis=1)[:, :10]
    disc = 1.0 / np.log2(np.arange(2, 12))
    dcg = (g * disc).sum(axis=1)
    ig = np.sort(2.0 ** rel - 1, axis=1)[:, ::-1][:, :10]
    idcg = np.maximum((ig * disc).sum(axis=1), 1e-12)
    return float((dcg / idcg).mean())


def _higgs_data(n, holdout):
    rng = np.random.RandomState(42)
    tot = n + holdout
    X = rng.randn(tot, 28).astype(np.float32)
    w = rng.randn(28) * 0.4
    logits = X @ w + 0.8 * np.sin(X[:, 0] * X[:, 1]) + 0.5 * rng.randn(tot)
    y = (logits > 0).astype(np.float64)
    return X[:n], y[:n], X[n:], y[n:]


def _sparse_data(n, holdout, groups=528, card=8, dense=4):
    rng = np.random.RandomState(7)
    tot = n + holdout
    cats = rng.randint(0, card, size=(tot, groups))
    X = np.zeros((tot, groups * card + dense), np.float32)
    for g in range(groups):
        X[np.arange(tot), g * card + cats[:, g]] = 1.0
    X[:, groups * card:] = rng.randn(tot, dense).astype(np.float32)
    w = rng.randn(X.shape[1]) * 0.3
    y = ((X @ w + 0.6 * rng.randn(tot)) > 0).astype(np.float64)
    return X[:n], y[:n], X[n:], y[n:]


def _rank_data(n, holdout, f=137, qsize=50):
    rng = np.random.RandomState(11)
    tot = (n + holdout) // qsize * qsize
    X = rng.randn(tot, f).astype(np.float32)
    w = rng.randn(f) * 0.3
    score = X @ w + rng.randn(tot)
    rel = np.clip(np.digitize(score, [-1.5, 0.0, 1.5, 2.5]), 0, 4)
    y = rel.astype(np.float64)
    n = n // qsize * qsize
    return X[:n], y[:n], X[n:], y[n:], qsize


def _train(side, shape, params, Xtr, ytr, Xho, group=None):
    if side == "ref":
        import lightgbm as lgb
    else:
        import lightgbm_tpu as lgb
    ds = lgb.Dataset(Xtr, label=ytr, group=group)
    t0 = time.perf_counter()
    bst = lgb.train(params, ds, 2)            # warmup / compile
    warm = time.perf_counter() - t0
    t0 = time.perf_counter()
    bst = lgb.train(params, ds, ITERS)
    dt = time.perf_counter() - t0
    pred = bst.predict(Xho)
    return bst, ITERS / dt, warm, pred


def _flush(out):
    # write after every shape: a crash mid-run must not lose completed
    # measurements
    path = os.path.join(ROOT, "BENCH_COMPARE.json")
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(out, fh, indent=1, sort_keys=True)
    os.replace(tmp, path)


def main():
    from lightgbm_tpu.analysis.guards import (checkout_cache_dir,
                                              configure_compile_cache)
    configure_compile_cache(checkout_cache_dir())
    shapes = os.environ.get("H2H_SHAPES", "higgs,sparse,ranking").split(",")
    out = {"host_cpus": os.cpu_count(), "leaves": LEAVES,
           "bins": BINS, "shapes": {}}
    path = os.path.join(ROOT, "BENCH_COMPARE.json")
    if os.path.exists(path):
        try:
            with open(path) as fh:
                prev = json.load(fh)
            out["shapes"].update(prev.get("shapes", {}))
        except ValueError:
            pass  # truncated file from a crashed run; start fresh
    base = {"objective": "binary", "num_leaves": LEAVES, "max_bin": BINS,
            "learning_rate": 0.1, "verbose": -1, "min_data_in_leaf": 100}

    if "higgs" in shapes:
        n = int(float(os.environ.get("H2H_ROWS", 10_500_000)))
        Xtr, ytr, Xho, yho = _higgs_data(n, 500_000)
        res = {}
        for side in ("tpu", "ref"):
            _, ips, warm, pred = _train(side, "higgs", dict(base), Xtr, ytr,
                                        Xho)
            res[side] = {"iters_per_sec": round(ips, 4),
                         "warmup_s": round(warm, 1),
                         "holdout_auc": round(_auc(yho, pred), 6)}
            print(f"higgs {side}: {res[side]}", flush=True)
        res["auc_delta"] = round(res["tpu"]["holdout_auc"]
                                 - res["ref"]["holdout_auc"], 6)
        out["shapes"]["higgs"] = {"rows": n, "features": 28,
                                   "iters": ITERS, **res}
        _flush(out)

    if "sparse" in shapes:
        n = int(float(os.environ.get("H2H_SPARSE_ROWS", 500_000)))
        Xtr, ytr, Xho, yho = _sparse_data(n, 100_000)
        res = {}
        for side in ("tpu", "ref"):
            _, ips, warm, pred = _train(side, "sparse", dict(base), Xtr, ytr,
                                        Xho)
            res[side] = {"iters_per_sec": round(ips, 4),
                         "warmup_s": round(warm, 1),
                         "holdout_auc": round(_auc(yho, pred), 6)}
            print(f"sparse {side}: {res[side]}", flush=True)
        res["auc_delta"] = round(res["tpu"]["holdout_auc"]
                                 - res["ref"]["holdout_auc"], 6)
        out["shapes"]["sparse"] = {"rows": n, "features": Xtr.shape[1],
                                   "iters": ITERS, **res}
        _flush(out)

    if "ranking" in shapes:
        n = int(float(os.environ.get("H2H_RANK_ROWS", 2_270_000)))
        Xtr, ytr, Xho, yho, qsize = _rank_data(n, 250_000)
        rp = {"objective": "lambdarank", "num_leaves": LEAVES,
              "max_bin": BINS, "learning_rate": 0.1, "verbose": -1,
              "min_data_in_leaf": 50, "lambdarank_truncation_level": 30}
        grp = np.full(len(ytr) // qsize, qsize, np.int64)
        res = {}
        for side in ("tpu", "ref"):
            _, ips, warm, pred = _train(side, "ranking", dict(rp), Xtr, ytr,
                                        Xho, group=grp)
            res[side] = {"iters_per_sec": round(ips, 4),
                         "warmup_s": round(warm, 1),
                         "holdout_ndcg10": round(_ndcg10(yho, pred, qsize),
                                                 6)}
            print(f"ranking {side}: {res[side]}", flush=True)
        res["ndcg_delta"] = round(res["tpu"]["holdout_ndcg10"]
                                  - res["ref"]["holdout_ndcg10"], 6)
        out["shapes"]["ranking"] = {"rows": len(ytr),
                                    "features": Xtr.shape[1],
                                    "iters": ITERS, **res}
        _flush(out)

    _flush(out)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
