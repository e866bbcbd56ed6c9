"""Objective functions (gradient/hessian kernels), computed on device.

TPU-native re-design of the reference's objective layer
(reference: include/LightGBM/objective_function.h, factory
ObjectiveFunction::CreateObjectiveFunction src/objective/objective_function.cpp:12-130,
families in src/objective/{regression,binary,multiclass,rank,xentropy}_objective.hpp
and their CUDA mirrors src/objective/cuda/*).

Where the reference launches per-row CUDA kernels, here every objective is a pure
jnp function over the score vector — XLA fuses the elementwise math into the
surrounding training step, and the same code runs under ``shard_map`` for
data-parallel training (per-query ranking reductions become segment ops over
padded query blocks).

Interface mirrors the reference's (objective_function.h):
  * ``get_gradients(score) -> (grad, hess)``     (GetGradients, :37)
  * ``boost_from_score(class_id)``               (BoostFromScore)
  * ``convert_output(raw)``                      (ConvertOutput, :81)
  * ``renew_tree_output`` percentile/leaf renewal (RenewTreeOutput, :57)
  * ``num_model_per_iteration``                  (multiclass: num_class trees/iter)
"""
from __future__ import annotations

import contextlib
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .obs.spans import span

_EPS = 1e-15


def _np(x):
    return np.asarray(x)


class Objective:
    """Base objective (reference: ObjectiveFunction, objective_function.h)."""

    name = "custom"
    is_constant_hessian = False
    num_model_per_iteration = 1
    # leaves renewed after growth (reference: RegressionL1loss::RenewTreeOutput)
    renew_leaves = False
    is_ranking = False
    # gradients depend only on this row's (label, weight, scores) — required
    # by the compact grower, whose rows live in a per-tree permuted order
    row_elementwise = True

    def __init__(self, config):
        self.config = config

    def init(self, metadata, num_data: int) -> None:
        self.num_data = num_data
        self.label = jnp.asarray(metadata.label, jnp.float32)
        self.weight = (
            jnp.asarray(metadata.weight, jnp.float32)
            if metadata.weight is not None else None
        )
        self.metadata = metadata

    def _weighted(self, grad, hess):
        if self.weight is not None:
            return grad * self.weight, hess * self.weight
        return grad, hess

    def get_gradients(self, score: jax.Array) -> Tuple[jax.Array, jax.Array]:
        raise NotImplementedError

    def boost_from_score(self, class_id: int = 0) -> float:
        return 0.0

    def convert_output(self, raw: jax.Array) -> jax.Array:
        return raw

    def renew_tree_output(self, score, residual_fn=None):
        raise NotImplementedError

    def _avg_label(self) -> float:
        lbl = _np(self.label).astype(np.float64)
        if self.weight is not None:
            w = _np(self.weight).astype(np.float64)
            return float((lbl * w).sum() / max(w.sum(), _EPS))
        return float(lbl.mean())


# ---------------------------------------------------------------------------
# Regression family (reference: src/objective/regression_objective.hpp)
# ---------------------------------------------------------------------------
class RegressionL2(Objective):
    """L2 loss (reference: RegressionL2loss, regression_objective.hpp:93)."""

    name = "regression"
    is_constant_hessian = True

    def __init__(self, config):
        super().__init__(config)
        self.sqrt = bool(config.get("reg_sqrt", False))

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if self.sqrt:
            lbl = self.label
            self.label = jnp.sign(lbl) * jnp.sqrt(jnp.abs(lbl))

    def get_gradients(self, score):
        grad = score - self.label
        hess = jnp.ones_like(score)
        return self._weighted(grad, hess)

    def boost_from_score(self, class_id: int = 0) -> float:
        return self._avg_label()

    def convert_output(self, raw):
        if self.sqrt:
            return jnp.sign(raw) * raw * raw
        return raw


class RegressionL1(RegressionL2):
    """L1 loss; leaf outputs renewed to the per-leaf weighted median of residuals
    (reference: RegressionL1loss, regression_objective.hpp:165)."""

    name = "regression_l1"
    is_constant_hessian = True
    renew_leaves = True
    renew_alpha = 0.5

    def get_gradients(self, score):
        diff = score - self.label
        grad = jnp.sign(diff)
        hess = jnp.ones_like(score)
        return self._weighted(grad, hess)


class RegressionHuber(RegressionL2):
    """Huber loss (reference: RegressionHuberLoss, regression_objective.hpp:234)."""

    name = "huber"
    is_constant_hessian = True

    def __init__(self, config):
        super().__init__(config)
        self.alpha = float(config.get("alpha", 0.9))

    def get_gradients(self, score):
        diff = score - self.label
        grad = jnp.where(jnp.abs(diff) <= self.alpha, diff,
                         jnp.sign(diff) * self.alpha)
        hess = jnp.ones_like(score)
        return self._weighted(grad, hess)


class RegressionFair(RegressionL2):
    """Fair loss (reference: RegressionFairLoss, regression_objective.hpp:290)."""

    name = "fair"
    is_constant_hessian = False

    def __init__(self, config):
        super().__init__(config)
        self.c = float(config.get("fair_c", 1.0))

    def get_gradients(self, score):
        diff = score - self.label
        c = self.c
        grad = c * diff / (jnp.abs(diff) + c)
        hess = c * c / ((jnp.abs(diff) + c) ** 2)
        return self._weighted(grad, hess)


class RegressionPoisson(RegressionL2):
    """Poisson regression on log-link scores
    (reference: RegressionPoissonLoss, regression_objective.hpp:341)."""

    name = "poisson"
    is_constant_hessian = False

    def __init__(self, config):
        super().__init__(config)
        self.max_delta = float(config.get("poisson_max_delta_step", 0.7))

    def get_gradients(self, score):
        ex = jnp.exp(score)
        grad = ex - self.label
        hess = jnp.exp(score + self.max_delta)
        return self._weighted(grad, hess)

    def boost_from_score(self, class_id: int = 0) -> float:
        return float(np.log(max(self._avg_label(), _EPS)))

    def convert_output(self, raw):
        return jnp.exp(raw)


class RegressionQuantile(RegressionL2):
    """Quantile (pinball) loss with per-leaf quantile renewal
    (reference: RegressionQuantileloss, regression_objective.hpp:417)."""

    name = "quantile"
    is_constant_hessian = True
    renew_leaves = True

    def __init__(self, config):
        super().__init__(config)
        self.alpha = float(config.get("alpha", 0.9))
        self.renew_alpha = self.alpha

    def get_gradients(self, score):
        diff = score - self.label
        grad = jnp.where(diff >= 0, 1.0 - self.alpha, -self.alpha)
        hess = jnp.ones_like(score)
        return self._weighted(grad, hess)


class RegressionMAPE(RegressionL2):
    """MAPE loss (reference: RegressionMAPELOSS, regression_objective.hpp:498)."""

    name = "mape"
    is_constant_hessian = True
    renew_leaves = True
    renew_alpha = 0.5

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        # label_weight = 1 / max(1, |label|), folded into the row weight
        lw = 1.0 / jnp.maximum(1.0, jnp.abs(self.label))
        self.weight = lw if self.weight is None else self.weight * lw

    def get_gradients(self, score):
        diff = score - self.label
        grad = jnp.sign(diff)
        hess = jnp.ones_like(score)
        return self._weighted(grad, hess)


class RegressionGamma(RegressionPoisson):
    """Gamma deviance on log-link scores
    (reference: RegressionGammaLoss, regression_objective.hpp:578)."""

    name = "gamma"

    def get_gradients(self, score):
        e = jnp.exp(-score)
        grad = 1.0 - self.label * e
        hess = self.label * e
        return self._weighted(grad, hess)


class RegressionTweedie(RegressionPoisson):
    """Tweedie deviance on log-link scores
    (reference: RegressionTweedieLoss, regression_objective.hpp:612)."""

    name = "tweedie"

    def __init__(self, config):
        super().__init__(config)
        self.rho = float(config.get("tweedie_variance_power", 1.5))

    def get_gradients(self, score):
        rho = self.rho
        e1 = jnp.exp((1.0 - rho) * score)
        e2 = jnp.exp((2.0 - rho) * score)
        grad = -self.label * e1 + e2
        hess = -self.label * (1.0 - rho) * e1 + (2.0 - rho) * e2
        return self._weighted(grad, hess)


# ---------------------------------------------------------------------------
# Binary (reference: src/objective/binary_objective.hpp:21 BinaryLogloss)
# ---------------------------------------------------------------------------
class BinaryLogloss(Objective):
    name = "binary"

    def __init__(self, config):
        super().__init__(config)
        self.sigmoid = float(config.get("sigmoid", 1.0))
        self.is_unbalance = bool(config.get("is_unbalance", False))
        self.scale_pos_weight = float(config.get("scale_pos_weight", 1.0))

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        lbl = _np(metadata.label)
        uniq = np.unique(lbl)
        if not np.all(np.isin(uniq, [0.0, 1.0])):
            raise ValueError("binary objective requires labels in {0, 1}")
        if metadata.weight is not None:
            w = _np(metadata.weight).astype(np.float64)
            pos = float(w[lbl > 0].sum())
            neg = float(w.sum() - pos)
        else:
            pos = float((lbl > 0).sum())
            neg = float(len(lbl) - pos)
        self.label01 = jnp.asarray(lbl > 0, jnp.float32)
        # class weighting (reference: binary_objective.hpp:60-86 — the
        # MINORITY class is upweighted to majority/minority, the other stays 1)
        if self.is_unbalance and pos > 0 and neg > 0:
            if pos > neg:
                self.label_weights = (pos / neg, 1.0)   # (neg_w, pos_w)
            else:
                self.label_weights = (1.0, neg / pos)
        else:
            self.label_weights = (1.0, self.scale_pos_weight)
        self._pos, self._neg = pos, neg

    def get_gradients(self, score):
        sig = self.sigmoid
        # derived inline from self.label: the compact grower rebinds label
        # per-tree (rows live in a permuted order), so gradients may depend
        # only on self.label / self.weight (see Objective.row_elementwise)
        y = (self.label > 0).astype(jnp.float32)
        p = jax.nn.sigmoid(sig * score)
        neg_w, pos_w = self.label_weights
        w = jnp.where(y > 0, pos_w, neg_w)
        grad = (p - y) * sig * w
        hess = p * (1.0 - p) * sig * sig * w
        return self._weighted(grad, hess)

    def boost_from_score(self, class_id: int = 0) -> float:
        # sigmoid^-1 of weighted positive rate (reference: binary_objective.hpp:94-108)
        if self.weight is not None:
            w = _np(self.weight).astype(np.float64)
            lbl = _np(self.label01).astype(np.float64)
            pavg = float((lbl * w).sum() / max(w.sum(), _EPS))
        else:
            pavg = self._pos / max(self._pos + self._neg, 1.0)
        pavg = min(max(pavg, 1e-15), 1.0 - 1e-15)
        return float(np.log(pavg / (1.0 - pavg)) / self.sigmoid)

    def convert_output(self, raw):
        return jax.nn.sigmoid(self.sigmoid * raw)


# ---------------------------------------------------------------------------
# Multiclass (reference: src/objective/multiclass_objective.hpp)
# ---------------------------------------------------------------------------
class MulticlassSoftmax(Objective):
    """Softmax over num_class score rows (reference: MulticlassSoftmax,
    multiclass_objective.hpp:24). One tree per class per iteration."""

    name = "multiclass"

    def __init__(self, config):
        super().__init__(config)
        self.num_class = int(config.get("num_class", 1))
        if self.num_class <= 1:
            raise ValueError("multiclass objective requires num_class > 1")
        self.num_model_per_iteration = self.num_class

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        lbl = _np(metadata.label).astype(np.int32)
        if lbl.min() < 0 or lbl.max() >= self.num_class:
            raise ValueError(
                f"multiclass labels must be in [0, {self.num_class}); "
                f"got range [{lbl.min()}, {lbl.max()}]")
        self._class_counts = np.bincount(lbl, minlength=self.num_class)

    def get_gradients(self, score):
        # score: [K, N]; one-hot derived inline from self.label (see
        # Objective.row_elementwise — the compact grower rebinds label)
        p = jax.nn.softmax(score, axis=0)                   # [K, N]
        classes = jnp.arange(self.num_class, dtype=jnp.float32)
        y = (self.label[None, :] == classes[:, None]).astype(jnp.float32)
        grad = p - y
        factor = self.num_class / (self.num_class - 1.0)
        hess = factor * p * (1.0 - p)
        if self.weight is not None:
            grad = grad * self.weight[None, :]
            hess = hess * self.weight[None, :]
        return grad, hess

    def boost_from_score(self, class_id: int = 0) -> float:
        # reference inits multiclass scores at 0 (softmax handles normalization)
        return 0.0

    def convert_output(self, raw):
        # raw: [..., K] -> probabilities
        return jax.nn.softmax(raw, axis=-1)


class MulticlassOVA(Objective):
    """One-vs-all: num_class independent sigmoid losses
    (reference: MulticlassOVA, multiclass_objective.hpp:186)."""

    name = "multiclassova"

    def __init__(self, config):
        super().__init__(config)
        self.num_class = int(config.get("num_class", 1))
        if self.num_class <= 1:
            raise ValueError("multiclassova requires num_class > 1")
        self.num_model_per_iteration = self.num_class
        self.sigmoid = float(config.get("sigmoid", 1.0))

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        lbl = _np(metadata.label).astype(np.int32)
        self._class_rates = (
            np.bincount(lbl, minlength=self.num_class) / max(len(lbl), 1))

    def get_gradients(self, score):
        sig = self.sigmoid
        classes = jnp.arange(self.num_class, dtype=jnp.float32)
        y = (self.label[None, :] == classes[:, None]).astype(jnp.float32)
        p = jax.nn.sigmoid(sig * score)
        grad = (p - y) * sig
        hess = p * (1.0 - p) * sig * sig
        if self.weight is not None:
            grad = grad * self.weight[None, :]
            hess = hess * self.weight[None, :]
        return grad, hess

    def boost_from_score(self, class_id: int = 0) -> float:
        pavg = min(max(float(self._class_rates[class_id]), 1e-15), 1 - 1e-15)
        return float(np.log(pavg / (1.0 - pavg)) / self.sigmoid)

    def convert_output(self, raw):
        return jax.nn.sigmoid(self.sigmoid * raw)


# ---------------------------------------------------------------------------
# Cross-entropy on continuous labels in [0,1]
# (reference: src/objective/xentropy_objective.hpp:44,:185)
# ---------------------------------------------------------------------------
class CrossEntropy(Objective):
    name = "cross_entropy"

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        lbl = _np(metadata.label)
        if lbl.min() < 0 or lbl.max() > 1:
            raise ValueError("cross_entropy labels must lie in [0, 1]")

    def get_gradients(self, score):
        p = jax.nn.sigmoid(score)
        grad = p - self.label
        hess = p * (1.0 - p)
        return self._weighted(grad, hess)

    def boost_from_score(self, class_id: int = 0) -> float:
        pavg = min(max(self._avg_label(), 1e-15), 1 - 1e-15)
        return float(np.log(pavg / (1.0 - pavg)))

    def convert_output(self, raw):
        return jax.nn.sigmoid(raw)


class CrossEntropyLambda(Objective):
    """Alternative parametrization (reference: CrossEntropyLambda,
    xentropy_objective.hpp:185)."""

    name = "cross_entropy_lambda"

    def get_gradients(self, score):
        # z = log1p(exp(score)); loss = (1-y)*score ... reference parametrization
        epf = jnp.exp(score)
        hhat = jnp.log1p(epf)
        z = 1.0 - jnp.exp(-hhat)
        enf = jnp.exp(-score)
        grad = (1.0 - self.label / jnp.maximum(z, _EPS)) / (1.0 + enf)
        c = 1.0 / (1.0 - jnp.exp(-epf))
        hess = epf / ((1.0 + epf) ** 2) * (
            1.0 + self.label * (1.0 - c + epf * c * c) / jnp.maximum(z * z, _EPS) * z)
        # guard numerical blowups near score -> -inf
        grad = jnp.nan_to_num(grad, nan=0.0, posinf=0.0, neginf=0.0)
        hess = jnp.clip(jnp.nan_to_num(hess, nan=1.0, posinf=1.0, neginf=_EPS),
                        _EPS, None)
        return self._weighted(grad, hess)

    def boost_from_score(self, class_id: int = 0) -> float:
        avg = max(self._avg_label(), 1e-15)
        return float(np.log(np.expm1(avg)) if avg < 30 else avg)

    def convert_output(self, raw):
        return jnp.log1p(jnp.exp(raw))


# ---------------------------------------------------------------------------
# Ranking (reference: src/objective/rank_objective.hpp — LambdarankNDCG :138,
# RankXENDCG :378; CUDA mirror cuda_rank_objective.cu)
# ---------------------------------------------------------------------------
def _padded_index(starts: np.ndarray, sizes: np.ndarray) -> np.ndarray:
    """[Q, M] int32 row index of queries that start at ``starts`` and hold
    ``sizes`` rows, M the longest (at least 1), padded with -1 —
    vectorized (no O(total rows) Python loop)."""
    m = max(int(sizes.max()), 1) if len(sizes) else 1
    pos = np.arange(m, dtype=np.int32)[None, :]
    idx = np.asarray(starts)[:, None].astype(np.int32) + pos
    return np.where(pos < np.asarray(sizes)[:, None], idx, -1)


def _pad_queries(boundaries: np.ndarray) -> Tuple[np.ndarray, int]:
    """Build a [Q, M] row-index matrix (padded with -1) from query
    boundaries."""
    idx = _padded_index(boundaries[:-1], np.diff(boundaries))
    return idx, idx.shape[1]


#: queries are grouped by length into classes of this width up to
#: ``_CLASS_LINEAR_TOP`` documents and of doubling width beyond it: the
#: pair block of a class costs its queries times its longest query, so a
#: class of width 128 pads a query by 64 documents on average whatever
#: its length (a fifth at the few hundred documents real result lists
#: hold; powers of two would pad by a third), and the doubling keeps the
#: number of classes, each a sort and a pair block of its own in the one
#: gradient program, at 8 + log2(longest / 1024)
_CLASS_WIDTH = 128
_CLASS_LINEAR_TOP = 1024


def _length_class(sizes: np.ndarray) -> np.ndarray:
    """The class of each query length: see ``_CLASS_WIDTH``."""
    sizes = np.maximum(np.asarray(sizes, np.int64), 1)
    linear = -(-sizes // _CLASS_WIDTH)
    beyond = _CLASS_LINEAR_TOP // _CLASS_WIDTH + np.ceil(np.log2(
        np.maximum(sizes, _CLASS_LINEAR_TOP) / _CLASS_LINEAR_TOP)
    ).astype(np.int64)
    return np.where(sizes <= _CLASS_LINEAR_TOP, linear, beyond)


class _QueryClass(NamedTuple):
    """The queries of one length class, padded to the longest of them."""
    gain: jax.Array           # [Qc, Mc] float32 label gain, 0 in the pad
    inv_max_dcg: jax.Array    # [Qc] float32
    length: jax.Array         # [Qc] int32 documents; the slots after are pad
    queries: np.ndarray       # [Qc] the queries' numbers, ascending


def _queries_by_length(boundaries: np.ndarray, num_data: int):
    """Group the queries into length classes. Returns ``[(queries [Qc],
    index [Qc, Mc] padded with -1)]`` in ascending class order, and
    ``row_slot`` [num_data] int32: where each row sits in the classes'
    slots laid end to end, to read per-slot results back by one gather; a
    row of no query (the sharded learner's pad rows) points one past the
    last slot. Queries of one length make one class, which is
    ``_pad_queries``' matrix."""
    boundaries = np.asarray(boundaries, np.int64)
    sizes = np.diff(boundaries)
    cls = _length_class(sizes)
    row_slot = np.zeros(num_data, np.int32)
    out, base = [], 0
    for c in np.unique(cls):
        qs = np.flatnonzero(cls == c)
        idx = _padded_index(boundaries[qs], sizes[qs])
        valid = idx >= 0
        row_slot[idx[valid]] = base + np.flatnonzero(valid.reshape(-1))
        out.append((qs, idx))
        base += idx.size
    if len(boundaries):
        row_slot[int(boundaries[-1]):] = base
    return out, row_slot


def _read_both(grad, hess, index):
    """``grad[index], hess[index]`` by one gather of two-wide rows: a TPU
    gathers an index at a time, and reads an index's two floats for less
    than one float twice (PERF.md section 6, PR 36)."""
    both = jnp.stack([grad, hess], axis=1)[index]
    return both[:, 0], both[:, 1]


class LambdarankNDCG(Objective):
    """LambdaRank with |ΔNDCG| weighting (reference: LambdarankNDCG,
    rank_objective.hpp:138-320).

    The queries are grouped by length into classes (``_CLASS_WIDTH``), a
    class's queries padded to its longest: the class's slots, [Qc, Mc].
    The classes' slots laid end to end, and one more for the rows of no
    query, are slot order, in which the gradients are computed
    (``slot_gradients``): per class a sort of each query by score that
    carries the label gains, the [T, Mc] pair block of the truncation
    level's top positions against every position, and a sort back that
    carries the gradients. Rows reach the slots and the gradients come
    back by ``row_slot``, composed with whatever order the caller holds
    its rows in (``gradients_in_order``)."""

    row_elementwise = False
    name = "lambdarank"
    is_ranking = True

    def __init__(self, config):
        super().__init__(config)
        self.sigmoid = float(config.get("sigmoid", 2.0))
        self.norm = bool(config.get("lambdarank_norm", True))
        trunc = int(config.get("lambdarank_truncation_level", 30))
        self.truncation_level = trunc
        self.label_gain = config.get("label_gain", None)
        # position-bias correction (reference: RankingObjective pos_biases_,
        # rank_objective.hpp:56-98 + UpdatePositionBiasFactors :296)
        self.bias_reg = float(config.get(
            "lambdarank_position_bias_regularization", 0.0))
        self.bias_lr = float(config.get("learning_rate", 0.1))

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if metadata.query_boundaries is None:
            raise ValueError("ranking objective requires query groups (set_group)")
        lbl = _np(metadata.label).astype(np.int32)
        max_label = int(lbl.max()) if len(lbl) else 0
        if self.label_gain is None:
            gains = (2.0 ** np.arange(max(max_label + 1, 2))) - 1.0
        else:
            gains = np.asarray(self.label_gain, dtype=np.float64)
            if len(gains) <= max_label:
                raise ValueError("label_gain shorter than max label + 1")
        self._label_gain_table = gains
        # per-row gain values, padded gather-safe
        row_gain = gains[lbl]
        self.row_gain = jnp.asarray(row_gain, jnp.float32)
        self.row_label = jnp.asarray(lbl, jnp.int32)
        with span("rank_layout"):
            self._layout_queries(metadata.query_boundaries, row_gain,
                                 num_data)
        # per-position bias state (updated every iteration -> the gradient
        # fn must not be jit-frozen; see is_stochastic)
        self.positions = None
        if metadata.position is not None:
            pos = np.asarray(metadata.position).astype(np.int32)
            if len(pos) != num_data:
                raise ValueError("position length != num_data")
            self.positions = jnp.asarray(pos)
            self.num_position_ids = int(pos.max()) + 1
            self.pos_biases = jnp.zeros((self.num_position_ids,), jnp.float32)
            # padding rows carry zero weight (gbdt._pad_metadata) and must
            # not count toward the per-position regularizer
            wts = (np.asarray(metadata.weight, np.float64)
                   if metadata.weight is not None else np.ones(num_data))
            self._pos_counts = jnp.asarray(
                np.bincount(pos, weights=(wts > 0).astype(np.float64),
                            minlength=self.num_position_ids)
                .astype(np.float32))
            self.is_stochastic = True  # stateful bias updates each call

    def _layout_queries(self, boundaries, row_gain, num_data):
        """The queries by length class (``_queries_by_length``), each
        class with its inverse max DCG per query (reference:
        lambdarank_ndcg init); the two indexes between row order and
        slot order, the row weights in slot order, and the counters of
        the layout."""
        classes, row_slot = _queries_by_length(boundaries, num_data)
        self.query_classes = []
        slots = 0
        for qs, idx in classes:
            valid = idx >= 0
            gain = np.where(valid, row_gain[np.maximum(idx, 0)], 0.0)
            top = -np.sort(-np.where(valid, gain, -np.inf), axis=1)
            k = min(idx.shape[1], self.truncation_level)
            disc = 1.0 / np.log2(np.arange(k) + 2.0)
            mdcg = np.sum(np.where(np.isfinite(top[:, :k]), top[:, :k], 0.0)
                          * disc[None, :], axis=1)
            inv = np.where(mdcg > 0, 1.0 / np.maximum(mdcg, 1e-300), 0.0)
            self.query_classes.append(_QueryClass(
                jnp.asarray(gain, jnp.float32), jnp.asarray(inv, jnp.float32),
                jnp.asarray(valid.sum(axis=1), jnp.int32), qs))
            chunk, n_chunks = self._chunks(idx.shape[0])
            slots += chunk * n_chunks * idx.shape[1]
        self.row_slot = jnp.asarray(row_slot)
        # the row each slot holds: -1 in a class's pad and in the last
        # slot, which is every row's of no query
        slot_row = np.concatenate(
            [idx.reshape(-1) for _, idx in classes]
            + [np.full(1, -1, np.int32)])
        self.slot_row = jnp.asarray(slot_row)
        self.slot_weight = None
        if self.weight is not None:
            self.slot_weight = jnp.asarray(np.where(
                slot_row >= 0, _np(self.weight)[np.maximum(slot_row, 0)],
                0.0), jnp.float32)
        docs = int(boundaries[-1])
        #: what the gradient program computes against what there is: the
        #: update's ``iteration`` event carries these (gbdt)
        self.rank_counters = {
            "rank_slots": slots, "rank_docs": docs,
            "rank_slots_per_doc": slots / docs if docs else 0.0,
            "rank_classes": len(self.query_classes)}

    def layout_arrays(self):
        """The layout's device arrays that ``gradients_in_order`` reads,
        as one pytree, for a jitted caller to hand in as an argument
        (``bound_layout``)."""
        return ([(c.gain, c.inv_max_dcg, c.length)
                 for c in self.query_classes], self.row_slot,
                self.slot_weight)

    @contextlib.contextmanager
    def bound_layout(self, arrays):
        """``gradients_in_order`` inside reads ``arrays``
        (``layout_arrays``' pytree, traced) in place of the arrays held.
        Held arrays traced into a program are its constants: tens of MB
        in its text, and another program, compiled anew, for every order
        the same queries come in."""
        classes, row_slot, slot_weight = arrays
        held = self.query_classes, self.row_slot, self.slot_weight
        self.query_classes = [
            c._replace(gain=g, inv_max_dcg=d, length=n)
            for c, (g, d, n) in zip(held[0], classes)]
        self.row_slot, self.slot_weight = row_slot, slot_weight
        try:
            yield
        finally:
            self.query_classes, self.row_slot, self.slot_weight = held

    # queries processed in chunks of this many per pair-tensor block; the
    # block is [CHUNK, T, M] floats — memory stays bounded for MS-LTR-scale
    # datasets (the old formulation materialized [Q, M, M])
    _QUERY_CHUNK = 256

    @classmethod
    def _chunks(cls, q: int) -> Tuple[int, int]:
        """(queries a chunk, chunks) for a class of ``q`` queries: as few
        chunks as ``_QUERY_CHUNK`` allows, of even size, so that fewer
        padding queries than chunks are computed."""
        n_chunks = max(1, -(-q // cls._QUERY_CHUNK))
        return -(-q // n_chunks), n_chunks

    def _query_chunk_grads(self, s, g, length, inv_max_dcg):
        """Lambda gradients for one chunk of padded queries [Qc, M]:
        ``s`` holds -inf and ``g`` 0 past each query's ``length``.

        The reference enumerates pairs (i, j) over SORTED positions with
        i < truncation_level and j > i (rank_objective.hpp:222-257) — a
        [T, M] pair block per query, not [M, M]."""
        qc, m = s.shape
        t = min(self.truncation_level, m)
        sig = self.sigmoid

        # one stable sort by descending score carries the gains along and
        # yields each sorted position's document (``jnp.argsort`` is this
        # sort of (key, iota); a gather by its result costs the TPU an
        # element at a time). The pad's -inf sorts last.
        pos = jax.lax.broadcasted_iota(jnp.int32, (qc, m), 1)
        neg_s, g_s, order = jax.lax.sort(
            (-s, g, pos), dimension=1, is_stable=True, num_keys=1)
        s_s = -neg_s
        m_s = pos < length[:, None]
        disc = 1.0 / jnp.log2(jnp.arange(m, dtype=jnp.float32) + 2.0)  # [M]

        # pair block [Qc, T, M]: i = sorted position < T, j = any position > i
        # (the top T cut first: a slice and a new axis in one index is a
        # gather to jnp)
        s_i = s_s[:, :t][:, :, None]
        s_j = s_s[:, None, :]
        g_i = g_s[:, :t][:, :, None]
        g_j = g_s[:, None, :]
        d_i = disc[:t][None, :, None]
        d_j = disc[None, None, :]
        upper = jnp.arange(t)[:, None] < jnp.arange(m)[None, :]
        pair_valid = (m_s[:, :t][:, :, None] & m_s[:, None, :]
                      & (g_i != g_j) & upper[None])
        delta_ndcg = jnp.abs((g_i - g_j) * (d_i - d_j)) \
            * inv_max_dcg[:, None, None]
        # lambda applies to the HIGHER-labeled doc of the pair
        i_high = g_i > g_j
        ds_high = jnp.where(i_high, s_i - s_j, s_j - s_i)
        if self.norm:
            # score-distance regularization (reference: "regular the
            # delta_pair_NDCG by score distance",
            # rank_objective.hpp:242-244): applied when the query's best
            # and worst scores differ
            best = s_s[:, 0]
            worst = jnp.min(jnp.where(m_s, s_s, jnp.inf), axis=1)
            delta_ndcg = jnp.where(
                (best != worst)[:, None, None],
                delta_ndcg / (0.01 + jnp.abs(ds_high)), delta_ndcg)
        p = jax.nn.sigmoid(sig * ds_high)
        lam_h = sig * (p - 1.0) * delta_ndcg           # <= 0, on higher doc
        hes = sig * sig * p * (1.0 - p) * delta_ndcg
        lam_h = jnp.where(pair_valid, lam_h, 0.0)
        hes = jnp.where(pair_valid, hes, 0.0)

        lam_i = jnp.where(i_high, lam_h, -lam_h)       # contribution @ pos i
        pad_t = ((0, 0), (0, m - t))
        grad_sorted = jnp.pad(lam_i.sum(axis=2), pad_t) - lam_i.sum(axis=1)
        hess_sorted = jnp.pad(hes.sum(axis=2), pad_t) + hes.sum(axis=1)

        if self.norm:
            # reference norm_ (rank_objective.hpp:259-263)
            sum_lambdas = 2.0 * (-lam_h).sum(axis=(1, 2))
            scale = jnp.where(
                sum_lambdas > 0,
                jnp.log2(1.0 + sum_lambdas) / jnp.maximum(sum_lambdas, _EPS),
                1.0)
            grad_sorted = grad_sorted * scale[:, None]
            hess_sorted = hess_sorted * scale[:, None]

        # back to document order within the query: sorted by the document
        # of each position, both ride along (no two keys are equal: a
        # stable sort would carry a fourth operand to break ties)
        _, grad_q, hess_q = jax.lax.sort(
            (order, grad_sorted, hess_sorted), dimension=1, is_stable=False,
            num_keys=1)
        return grad_q, hess_q

    def _class_grads(self, s, cls):
        """Per-slot lambda gradients of one length class, [Qc * Mc] each,
        from its slots' scores [Qc, Mc], in chunks of ``_QUERY_CHUNK``
        queries."""
        q, m = s.shape
        g, length, imd = cls.gain, cls.length, cls.inv_max_dcg
        chunk, n_chunks = self._chunks(q)
        q_pad = chunk * n_chunks - q
        if q_pad:
            s = jnp.pad(s, ((0, q_pad), (0, 0)), constant_values=-jnp.inf)
            g = jnp.pad(g, ((0, q_pad), (0, 0)))
            length = jnp.pad(length, (0, q_pad))
            imd = jnp.pad(imd, (0, q_pad))

        grad_q, hess_q = jax.lax.map(
            lambda args: self._query_chunk_grads(*args),
            (s.reshape(n_chunks, chunk, m), g.reshape(n_chunks, chunk, m),
             length.reshape(n_chunks, chunk), imd.reshape(n_chunks, chunk)))
        return (grad_q.reshape(-1, m)[:q].reshape(-1),
                hess_q.reshape(-1, m)[:q].reshape(-1))

    def slot_gradients(self, s_slots):
        """Gradients in slot order, [slots + 1] each, row weights applied,
        from the scores in slot order: each class a static slice, -inf in
        its pad; the last slot, of the rows of no query, gets zeros
        whatever it holds."""
        per_class, base = [], 0
        for c in self.query_classes:
            q, m = c.gain.shape
            per_class.append(self._class_grads(
                s_slots[base:base + q * m].reshape(q, m), c))
            base += q * m
        zero = jnp.zeros((1,), s_slots.dtype)
        grad = jnp.concatenate([g for g, _ in per_class] + [zero])
        hess = jnp.concatenate([h for _, h in per_class] + [zero])
        if self.slot_weight is not None:
            grad, hess = grad * self.slot_weight, hess * self.slot_weight
        return grad, hess

    def gradients_in_order(self, score, rows):
        """``get_gradients`` for a caller that holds its rows in another
        order (the compact grower's): ``score[i]`` is the score of row
        ``rows[i]``, every row once, and so are the gradients returned.
        One composed index carries scores to the slots and both gradients
        back; row order is never made. Position-bias state lives in row
        order and is not read here."""
        slot = self.row_slot[rows]
        s_slots = jnp.full(self.slot_row.shape, -jnp.inf, score.dtype).at[
            slot].set(score)
        return _read_both(*self.slot_gradients(s_slots), slot)

    def get_gradients(self, score):
        if self.positions is not None:
            # ranking math sees position-debiased scores (reference:
            # rank_objective.hpp:70 score + pos_biases_[positions_[j]])
            score = score + self.pos_biases[self.positions]
        # rows are in dataset order: each slot reads its row, and each
        # row its slot's gradients (already times the row's weight)
        s_slots = jnp.where(self.slot_row >= 0,
                            score[jnp.maximum(self.slot_row, 0)], -jnp.inf)
        grad, hess = _read_both(*self.slot_gradients(s_slots), self.row_slot)
        if self.positions is not None:
            # Newton step on the per-position bias factors (reference:
            # UpdatePositionBiasFactors, rank_objective.hpp:296-331, fed the
            # weight-multiplied lambdas)
            p_ids = self.positions
            d1 = jnp.zeros((self.num_position_ids,)).at[p_ids].add(-grad)
            d2 = jnp.zeros((self.num_position_ids,)).at[p_ids].add(-hess)
            d1 = d1 - self.pos_biases * self.bias_reg * self._pos_counts
            d2 = d2 - self.bias_reg * self._pos_counts
            self.pos_biases = self.pos_biases + \
                self.bias_lr * d1 / (jnp.abs(d2) + 0.001)
        return grad, hess


class RankXENDCG(Objective):
    """Listwise cross-entropy surrogate for NDCG
    (reference: RankXENDCG, rank_objective.hpp:378)."""

    name = "rank_xendcg"
    is_ranking = True
    row_elementwise = False
    # draws fresh gamma noise each iteration — must not be jit-frozen
    is_stochastic = True

    def __init__(self, config):
        super().__init__(config)
        self.seed = int(config.get("objective_seed", 5) or 5)

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if metadata.query_boundaries is None:
            raise ValueError("ranking objective requires query groups (set_group)")
        idx, m = _pad_queries(metadata.query_boundaries)
        self.query_index = jnp.asarray(idx)
        self.query_mask = jnp.asarray(idx >= 0)
        lbl = _np(metadata.label).astype(np.float64)
        phi = (2.0 ** lbl) - 1.0
        self.row_phi = jnp.asarray(phi, jnp.float32)
        self._key = jax.random.PRNGKey(self.seed)

    def get_gradients(self, score):
        idx = self.query_index
        mask = self.query_mask
        safe_idx = jnp.maximum(idx, 0)
        s = jnp.where(mask, score[safe_idx], -jnp.inf)
        phi = jnp.where(mask, self.row_phi[safe_idx], 0.0)
        # gumbel-perturbed relevance target (reference draws per-doc gammas)
        self._key, sub = jax.random.split(self._key)
        gam = jax.random.gamma(sub, 1.0, shape=phi.shape)
        rho_raw = phi / jnp.maximum(gam, _EPS)
        denom = jnp.where(mask, rho_raw, 0.0).sum(axis=1, keepdims=True)
        t = rho_raw / jnp.maximum(denom, _EPS)       # target distribution
        p = jax.nn.softmax(s, axis=1)
        p = jnp.where(mask, p, 0.0)
        grad_q = p - jnp.where(mask, t, 0.0)
        hess_q = p * (1.0 - p)
        grad = jnp.zeros_like(score).at[safe_idx.reshape(-1)].add(
            jnp.where(mask, grad_q, 0.0).reshape(-1))
        hess = jnp.zeros_like(score).at[safe_idx.reshape(-1)].add(
            jnp.where(mask, hess_q, 0.0).reshape(-1))
        hess = jnp.maximum(hess, _EPS)
        return self._weighted(grad, hess)


# ---------------------------------------------------------------------------
# Factory (reference: ObjectiveFunction::CreateObjectiveFunction,
# src/objective/objective_function.cpp:12-130)
# ---------------------------------------------------------------------------
_OBJECTIVES = {
    "regression": RegressionL2,
    "regression_l2": RegressionL2,
    "l2": RegressionL2,
    "mean_squared_error": RegressionL2,
    "mse": RegressionL2,
    "l2_root": RegressionL2,
    "root_mean_squared_error": RegressionL2,
    "rmse": RegressionL2,
    "regression_l1": RegressionL1,
    "l1": RegressionL1,
    "mean_absolute_error": RegressionL1,
    "mae": RegressionL1,
    "huber": RegressionHuber,
    "fair": RegressionFair,
    "poisson": RegressionPoisson,
    "quantile": RegressionQuantile,
    "mape": RegressionMAPE,
    "mean_absolute_percentage_error": RegressionMAPE,
    "gamma": RegressionGamma,
    "tweedie": RegressionTweedie,
    "binary": BinaryLogloss,
    "multiclass": MulticlassSoftmax,
    "softmax": MulticlassSoftmax,
    "multiclassova": MulticlassOVA,
    "multiclass_ova": MulticlassOVA,
    "ova": MulticlassOVA,
    "ovr": MulticlassOVA,
    "cross_entropy": CrossEntropy,
    "xentropy": CrossEntropy,
    "cross_entropy_lambda": CrossEntropyLambda,
    "xentlambda": CrossEntropyLambda,
    "lambdarank": LambdarankNDCG,
    "rank_xendcg": RankXENDCG,
    "xendcg": RankXENDCG,
    "xe_ndcg": RankXENDCG,
    "xe_ndcg_mart": RankXENDCG,
    "xendcg_mart": RankXENDCG,
}


def create_objective(name: str, config) -> Optional[Objective]:
    """Create an objective by (aliased) name; None for 'custom'/'none'."""
    if name is None or name in ("custom", "none", "null", "na"):
        return None
    key = str(name).lower()
    if key not in _OBJECTIVES:
        raise ValueError(f"Unknown objective: {name}")
    return _OBJECTIVES[key](config)
