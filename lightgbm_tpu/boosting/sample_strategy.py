"""Row-sampling strategies: bagging and GOSS, computed on device.

TPU-native re-design of the reference's SampleStrategy
(reference: include/LightGBM/sample_strategy.h:31, BaggingSampleStrategy
src/boosting/bagging.hpp:14, GOSSStrategy src/boosting/goss.hpp:18, factory
src/boosting/sample_strategy.cpp).

The reference materializes compacted ``bag_data_indices`` and copies gradients;
with static shapes on TPU a dense ``[N]`` {0,1} mask is multiplied into
grad/hess/count channels instead — no compaction, no copies, and the same mask
flows straight into the histogram contraction (ops/histogram.py).

Sampling is Bernoulli per row at rate ``bagging_fraction`` (the reference draws
an exact count without replacement — bagging.hpp; the expected in-bag count is
identical and the draw stays on device).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


class SampleStrategy:
    """Produces the per-iteration in-bag mask [N] (float {0,1})."""

    is_hessian_change = False
    # True when the last bag_mask() call drew a NEW bag (vs reusing a cached
    # one) — the compact grower stores reused bags in its permuted row records
    last_fresh = False

    def __init__(self, config, num_data: int, metadata=None):
        self.config = config
        self.num_data = num_data
        self.metadata = metadata

    def samples(self, iter_num: int) -> bool:
        """Whether ``bag_mask(iter_num, ...)`` draws or reuses a bag (False:
        it returns None, 'use all rows'). The update loop enters its
        ``bag`` span only then."""
        return False

    def bag_mask(self, iter_num: int, grad, hess) -> Optional[jax.Array]:
        """Return in-bag mask for this iteration, or None for 'use all rows'.
        ``grad``/``hess`` are [K, N] (needed by GOSS only)."""
        return None

    def scale_grad_hess(self, mask, grad, hess):
        """GOSS amplifies sampled small-gradient rows; bagging does not."""
        return grad, hess


class BaggingStrategy(SampleStrategy):
    """(reference: BaggingSampleStrategy, src/boosting/bagging.hpp:14)"""

    def __init__(self, config, num_data: int, metadata=None):
        super().__init__(config, num_data, metadata)
        self.fraction = float(config.get("bagging_fraction", 1.0))
        self.pos_fraction = float(config.get("pos_bagging_fraction", 1.0))
        self.neg_fraction = float(config.get("neg_bagging_fraction", 1.0))
        self.freq = int(config.get("bagging_freq", 0))
        self.seed = int(config.get("bagging_seed", 3))
        self.by_query = bool(config.get("bagging_by_query", False))
        self.balanced = self.pos_fraction < 1.0 or self.neg_fraction < 1.0
        self.enabled = self.freq > 0 and (self.fraction < 1.0 or self.balanced)
        self._cached = None
        self._label01 = None
        self._row_query = None
        if self.enabled and self.balanced and metadata is not None \
                and metadata.label is not None:
            self._label01 = jnp.asarray(np.asarray(metadata.label) > 0)
        if self.enabled and self.by_query and metadata is not None \
                and metadata.query_boundaries is not None:
            qb = np.asarray(metadata.query_boundaries)
            rq = np.zeros(num_data, dtype=np.int32)
            for i in range(len(qb) - 1):
                rq[qb[i]:qb[i + 1]] = i
            self._row_query = jnp.asarray(rq)
            self._num_queries = len(qb) - 1

    def samples(self, iter_num):
        return self.enabled

    def bag_mask(self, iter_num, grad, hess):
        self.last_fresh = False
        if not self.samples(iter_num):
            return None
        if iter_num % self.freq != 0 and self._cached is not None:
            return self._cached
        self.last_fresh = True
        key = jax.random.PRNGKey(self.seed + iter_num // max(self.freq, 1))
        if self.by_query and self._row_query is not None:
            qkeep = jax.random.uniform(key, (self._num_queries,)) < self.fraction
            mask = qkeep[self._row_query].astype(jnp.float32)
        elif self.balanced and self._label01 is not None:
            u = jax.random.uniform(key, (self.num_data,))
            rate = jnp.where(self._label01, self.pos_fraction, self.neg_fraction)
            mask = (u < rate).astype(jnp.float32)
        else:
            u = jax.random.uniform(key, (self.num_data,))
            mask = (u < self.fraction).astype(jnp.float32)
        self._cached = mask
        return mask


class GOSSStrategy(SampleStrategy):
    """Gradient-based one-side sampling (reference: GOSSStrategy,
    src/boosting/goss.hpp:18): keep the top ``top_rate`` rows by gradient
    magnitude, Bernoulli-sample the rest at ``other_rate/(1-top_rate)`` and
    amplify their grad/hess by ``(1-top_rate)/other_rate``."""

    is_hessian_change = True

    def __init__(self, config, num_data: int, metadata=None):
        super().__init__(config, num_data, metadata)
        self.top_rate = float(config.get("top_rate", 0.2))
        self.other_rate = float(config.get("other_rate", 0.1))
        self.seed = int(config.get("bagging_seed", 3))
        self.learning_rate = float(config.get("learning_rate", 0.1))
        self._amplify = None

    def samples(self, iter_num):
        # warm-up: no sampling for the first 1/learning_rate iterations
        # (reference: goss.hpp Bagging's early return)
        return iter_num >= int(1.0 / max(self.learning_rate, 1e-12))

    def bag_mask(self, iter_num, grad, hess):
        self.last_fresh = False
        if not self.samples(iter_num):
            self._amplify = None
            return None
        self.last_fresh = True
        # multiclass: magnitude summed over class rows (reference sums |g|*h)
        mag = jnp.sum(jnp.abs(grad) * hess, axis=0)
        thresh = jnp.quantile(mag, 1.0 - self.top_rate)
        is_top = mag >= thresh
        key = jax.random.PRNGKey(self.seed + iter_num)
        keep_rate = self.other_rate / max(1.0 - self.top_rate, 1e-12)
        u = jax.random.uniform(u_key := key, (self.num_data,))
        sampled = (~is_top) & (u < keep_rate)
        mask = (is_top | sampled).astype(jnp.float32)
        amp = (1.0 - self.top_rate) / max(self.other_rate, 1e-12)
        self._amplify = jnp.where(sampled, amp, 1.0)
        return mask

    def scale_grad_hess(self, mask, grad, hess):
        if self._amplify is None:
            return grad, hess
        a = self._amplify[None, :]
        return grad * a, hess * a


def create_sample_strategy(config, num_data: int, metadata=None) -> SampleStrategy:
    """(reference: SampleStrategy::CreateSampleStrategy,
    src/boosting/sample_strategy.cpp)"""
    strategy = str(config.get("data_sample_strategy", "bagging")).lower()
    boosting = str(config.get("boosting", "gbdt")).lower()
    if strategy == "goss" or boosting == "goss":
        return GOSSStrategy(config, num_data, metadata)
    return BaggingStrategy(config, num_data, metadata)
