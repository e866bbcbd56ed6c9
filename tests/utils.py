"""Shared test fixtures/generators.

Mirrors the reference's tests/python_package_test/utils.py (memoized dataset
loaders, make_synthetic_regression, make_ranking) at a smaller scale so the
XLA-on-CPU test path stays fast.
"""
from __future__ import annotations

import functools
import threading

import numpy as np
from sklearn.datasets import make_blobs, make_classification, make_regression

from lightgbm_tpu.obs import flight

# small defaults: CPU XLA histograms are the slow path; TPU is the target
FAST_PARAMS = {"max_bin": 31, "min_data_in_leaf": 5, "num_leaves": 15,
               "verbosity": -1}


@functools.lru_cache(maxsize=None)
def binary_data(n=600, f=10, seed=42):
    X, y = make_classification(
        n_samples=n, n_features=f, n_informative=max(2, f // 2),
        random_state=seed)
    return X, y.astype(np.float64)


@functools.lru_cache(maxsize=None)
def regression_data(n=600, f=10, seed=42):
    X, y = make_regression(n_samples=n, n_features=f, noise=5.0,
                           random_state=seed)
    return X, y


@functools.lru_cache(maxsize=None)
def multiclass_data(n=600, f=10, k=3, seed=42):
    X, y = make_blobs(n_samples=n, n_features=f, centers=k,
                      cluster_std=6.0, random_state=seed)
    return X, y.astype(np.float64)


def make_ranking(n_queries=40, docs_per_query=20, f=8, seed=42):
    """Relevance in {0,1,2}; returns X, y, group sizes
    (reference: utils.py make_ranking)."""
    rng = np.random.RandomState(seed)
    n = n_queries * docs_per_query
    X = rng.randn(n, f)
    w = rng.randn(f)
    scores = X @ w + 0.5 * rng.randn(n)
    y = np.zeros(n)
    for q in range(n_queries):
        s = scores[q * docs_per_query:(q + 1) * docs_per_query]
        r = np.argsort(np.argsort(s))
        y[q * docs_per_query:(q + 1) * docs_per_query] = np.where(
            r >= docs_per_query - 3, 2, np.where(r >= docs_per_query - 8, 1, 0))
    group = np.full(n_queries, docs_per_query)
    return X, y, group


def train_test_split_simple(X, y, test_frac=0.25, seed=0):
    rng = np.random.RandomState(seed)
    n = len(X)
    idx = rng.permutation(n)
    cut = int(n * (1 - test_frac))
    tr, te = idx[:cut], idx[cut:]
    return X[tr], y[tr], X[te], y[te]


class OwnThreadRing(flight.FlightRecorder):
    """A flight ring that keeps only what the thread that made it records.
    The process-wide ring is shared with whatever the xdist worker ran
    before: ``tests/test_checkpoint.py`` abandons a watchdog thread inside
    an injected 120 s hang, which wakes up in a later test's time,
    finishes its ``Booster.update()`` and writes that booster's
    ``iteration`` event (a third tick in the driver's run of PR 38)."""

    def __init__(self):
        super().__init__()
        self._owner = threading.get_ident()

    def record(self, event, **fields):
        if threading.get_ident() == self._owner:
            super().record(event, **fields)
