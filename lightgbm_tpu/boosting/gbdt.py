"""GBDT training driver.

TPU-native re-design of the reference's boosting layer
(reference: GBDT, src/boosting/gbdt.cpp — Init :53, TrainOneIter :344-452,
Boosting [gradient compute] :220, UpdateScore :491, RollbackOneIter :454,
BoostFromAverage :319; ScoreUpdater src/boosting/score_updater.hpp:21 and its
CUDA variant src/boosting/cuda/cuda_score_updater.cu).

Layout decisions (vs the reference):
  * scores are a device-resident ``[K, N]`` array (K = trees per iteration,
    i.e. num_class for multiclass) — the reference keeps a flat K*N buffer;
  * gradients/hessians never leave HBM between the objective kernel and the
    histogram contraction (same contract as the CUDA path, §3.3 of SURVEY);
  * the in-bag mask is a dense {0,1} vector multiplied into grad/hess/count
    channels instead of compacted ``bag_data_indices`` (static shapes);
  * trees are stored as host numpy struct-of-arrays (models are tiny) and
    re-stacked to device arrays for batch prediction.
"""
from __future__ import annotations

import contextlib
import functools
import json
import os
import threading
import time
import weakref

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..io.dataset import BinnedDataset
from ..metrics import Metric
from ..objectives import Objective
from ..obs.spans import (SlowUpdates, bump, span, update_counters,
                         update_phases)
from ..ops.compact import RowLayout, pack_rows, segments_to_leaf_vectors
from ..ops.grower import (GrowerParams, TreeArrays, depth_rung, grow_tree,
                          leaf_rung)
from ..ops.grower_compact import grow_tree_compact
from ..ops.predict import (DEFAULT_LEVEL_DEPTH_CAP, StackedTrees,
                           bucket_rows, build_level_layout, depth_bucket,
                           early_stop_tbatch, parse_bucket_ladder,
                           predict_leaf_batched, predict_leaf_level,
                           predict_raw_batched, predict_raw_level,
                           predict_raw_scan, quantize_leaves,
                           route_one_tree, tree_bucket)
from ..parallel.multihost import to_host as _to_host
from ..ops.record_write import record_write
from ..ops.renew import renew_leaf_quantile
from ..utils import log
from ..utils.rwlock import Mutex
from .sample_strategy import GOSSStrategy, create_sample_strategy

_EPS = 1e-35

#: boosters whose UNWIND-table cache is live — probed (entry count) by the
#: resource witness; WeakSet so a dropped booster stops being counted
_shap_table_boosters: "weakref.WeakSet" = weakref.WeakSet()
_shap_probe_lock = threading.Lock()
_shap_probe_registered = False


def _register_shap_table_probe(booster) -> None:
    """R012 hook: the UNWIND-table cache is a keyed retained-data cache,
    so its live entry count feeds ``guards.resource_witness``'s
    jit_cache delta (one module-level probe, registered on first use)."""
    global _shap_probe_registered
    with _shap_probe_lock:
        _shap_table_boosters.add(booster)
        if _shap_probe_registered:
            return
        from ..analysis import guards
        guards.register_witness_cache_probe(
            lambda: sum(len(getattr(b, "_shap_tables_cache", None) or {})
                        for b in list(_shap_table_boosters)))
        _shap_probe_registered = True


def _bound_gradients(obj, k_total: int, scores, label, weight):
    """Objective gradients with label/weight rebound to the compact grower's
    current row order (the objective's stored arrays are in the original
    order; see Objective.row_elementwise)."""
    old_l, old_w = obj.label, obj.weight
    obj.label, obj.weight = label, weight
    try:
        with span("gradient"):
            if k_total == 1:
                g, h = obj.get_gradients(scores[0])
                return g[None, :], h[None, :]
            return obj.get_gradients(scores)
    finally:
        obj.label, obj.weight = old_l, old_w


def _parse_monotone(value, num_features: int, feature_names) -> Optional[np.ndarray]:
    """monotone_constraints -> [F] int8 (list, comma string, or name dict)."""
    if value is None:
        return None
    if isinstance(value, str):
        value = [int(v) for v in value.replace("(", "").replace(")", "")
                 .split(",") if v.strip()]
    if isinstance(value, dict):
        out = np.zeros(num_features, np.int8)
        for name, v in value.items():
            out[list(feature_names).index(name)] = int(v)
        return out if out.any() else None
    arr = np.asarray(list(value), np.int8)
    if arr.size != num_features:
        raise ValueError(
            f"monotone_constraints has {arr.size} entries for "
            f"{num_features} features")
    return arr if arr.any() else None


def _parse_interactions(value, num_features: int) -> Optional[np.ndarray]:
    """interaction_constraints -> [S, F] bool masks (list of index lists or
    the reference's "[0,1],[2,3]" string form)."""
    if value in (None, "", []):
        return None
    if isinstance(value, str):
        import json as _json
        value = _json.loads("[" + value + "]")
    sets = np.zeros((len(value), num_features), bool)
    for i, group in enumerate(value):
        sets[i, np.asarray(list(group), np.int64)] = True
    return sets


def _discretize_gradients(grad, hess, key, num_bins: int, stochastic: bool,
                          const_hess: bool, axis_name=None):
    """Gradient discretization (reference:
    GradientDiscretizer::DiscretizeGradients, gradient_discretizer.cpp):
    gradients snap to num_grad_quant_bins levels of max|g|/(bins/2) with
    stochastic rounding. Returns ``(qg, qh, g_scale, h_scale)`` — the CODE
    arrays (integer-valued f32: |qg| <= bins/2, 0 <= qh <= bins, so they
    cast exactly to int8 for bins <= 127) plus the per-iteration scales.
    The int-histogram pipeline consumes the codes directly; the masked
    grower's shim multiplies them back (``_quantize_gradients``).

    ``axis_name``: under shard_map the max-abs scale must be GLOBAL (pmax)
    — per-shard scales would make the psum-ed int histograms sum codes on
    different grids."""
    with span("quant_discretize"):
        gmax = jnp.max(jnp.abs(grad))
        hmax = jnp.max(jnp.abs(hess))
        if axis_name is not None:
            gmax = jax.lax.pmax(gmax, axis_name)
            hmax = jax.lax.pmax(hmax, axis_name)
        g_scale = jnp.maximum(gmax / (num_bins // 2), 1e-30)
        h_scale = jnp.maximum(
            hmax if const_hess else hmax / num_bins, 1e-30)
        if stochastic:
            kg, kh = jax.random.split(key)
            ug = jax.random.uniform(kg, grad.shape)
            uh = jax.random.uniform(kh, hess.shape)
            qg = jnp.trunc(grad / g_scale + jnp.sign(grad) * ug)
            qh = jnp.trunc(hess / h_scale + uh)
        else:
            qg = jnp.trunc(grad / g_scale + jnp.sign(grad) * 0.5)
            qh = jnp.trunc(hess / h_scale + 0.5)
    return qg, qh, g_scale, h_scale


def _quantize_gradients(grad, hess, key, num_bins: int, stochastic: bool,
                        const_hess: bool):
    """Dequantized-f32 shim over ``_discretize_gradients`` for the masked
    grower: codes multiply straight back by their scales (exact integer
    multiples), so that histogram pipeline is unchanged while the training
    statistics match the reference's coarse-gradient regularization. The
    compact grower skips this shim and feeds the codes to the int8 MXU
    histogram path instead (ops/grower_compact.py quant_hist)."""
    qg, qh, g_scale, h_scale = _discretize_gradients(
        grad, hess, key, num_bins, stochastic, const_hess)
    return qg * g_scale, qh * h_scale


def _tree_used_features(tree, nf: int, used: jax.Array) -> jax.Array:
    """OR the tree's split features into the model-level CEGB used set."""
    idx = jnp.where(tree.split_feature >= 0, tree.split_feature, nf)
    return used | jnp.zeros((nf + 1,), bool).at[idx].set(True)[:nf]


def _forced_split_schedule(path: str, mappers, num_leaves: int):
    """Precompute the (leaf, feature, bin) schedule for a forced-splits JSON
    tree (reference: forcedsplits_filename, SerialTreeLearner::ForceSplits
    serial_tree_learner.cpp:620 — BFS order). Leaf ids follow the grower's
    creation-order convention (left keeps the parent's leaf id, the right
    child becomes leaf k+1)."""
    import json as _json
    from collections import deque
    with open(path) as fh:
        root = _json.load(fh)
    leaves, feats, bins = [], [], []
    queue = deque([(root, 0)])
    k = 0
    while queue and k < num_leaves - 1:
        node, leaf = queue.popleft()
        if node is None or "feature" not in node:
            continue
        f = int(node["feature"])
        thr = float(node["threshold"])
        m = mappers[f]
        if m.is_categorical:
            raise ValueError(
                "forced splits on categorical features are not supported")
        b = int(m.value_to_bin(np.array([thr]))[0])
        leaves.append(leaf)
        feats.append(f)
        bins.append(b)
        k += 1
        if node.get("left"):
            queue.append((node["left"], leaf))
        if node.get("right"):
            queue.append((node["right"], k))
    if not leaves:
        return None
    return (jnp.asarray(leaves, jnp.int32), jnp.asarray(feats, jnp.int32),
            jnp.asarray(bins, jnp.int32))


def _clamp_block(block: int, n: int, floor: int = 128) -> int:
    """Shrink a streaming block size toward the data size (power-of-two)."""
    while block // 2 >= max(n, floor) and block > floor:
        block //= 2
    return max(block, floor)


def bucketed_tree_shape(step_buckets: bool, num_leaves: int,
                        max_depth: int) -> Tuple[int, int]:
    """(num_leaves, max_depth) as they enter the GrowerParams jit key:
    the (leaf rung, depth bucket) pair under the step ladder, the exact
    values on the ``tpu_step_buckets=off`` escape hatch."""
    if step_buckets:
        return leaf_rung(num_leaves), depth_rung(max_depth)
    return num_leaves, max_depth


class HostTree:
    """Host-side copy of one grown tree (numpy struct-of-arrays)."""

    __slots__ = ("split_feature", "split_bin", "cat_bitset", "split_gain",
                 "default_left", "left_child", "right_child", "leaf_value",
                 "leaf_weight", "leaf_count", "leaf_parent", "leaf_depth",
                 "internal_value", "internal_weight", "internal_count",
                 "num_leaves", "num_nodes", "shrinkage",
                 # linear leaves (boosting/linear.py)
                 "is_linear", "leaf_const", "leaf_features", "leaf_coeff")

    def __init__(self, tree: TreeArrays, shrinkage: float = 1.0):
        self.split_feature = np.asarray(tree.split_feature)
        self.split_bin = np.asarray(tree.split_bin)
        self.cat_bitset = np.asarray(tree.cat_bitset)
        self.split_gain = np.asarray(tree.split_gain)
        self.default_left = np.asarray(tree.default_left)
        self.left_child = np.asarray(tree.left_child)
        self.right_child = np.asarray(tree.right_child)
        self.leaf_value = np.asarray(tree.leaf_value)
        self.leaf_weight = np.asarray(tree.leaf_weight)
        self.leaf_count = np.asarray(tree.leaf_count)
        self.leaf_parent = np.asarray(tree.leaf_parent)
        self.leaf_depth = np.asarray(tree.leaf_depth)
        self.internal_value = np.asarray(tree.internal_value)
        self.internal_weight = np.asarray(tree.internal_weight)
        self.internal_count = np.asarray(tree.internal_count)
        self.num_leaves = int(tree.num_leaves)
        self.num_nodes = int(tree.num_nodes)
        self.shrinkage = shrinkage
        self.is_linear = False

    def scale(self, factor: float) -> None:
        """(reference: Tree::Shrinkage, tree.h:185)"""
        self.leaf_value = self.leaf_value * factor
        self.internal_value = self.internal_value * factor
        self.shrinkage *= factor

    def add_bias(self, bias: float) -> None:
        """(reference: Tree::AddBias, called from gbdt.cpp:417)"""
        self.leaf_value = self.leaf_value + bias


def stack_trees(models: Sequence[HostTree], max_nodes: int, max_leaves: int,
                cat_w: Optional[int] = None, pad_to: Optional[int] = None
                ) -> StackedTrees:
    """Stack host trees into device arrays for batch prediction.

    ``pad_to`` pads the leading T axis (on host, before the transfer) up
    to a tree-count bucket: padding entries are all-constant trees
    (num_nodes == 0, leaf_value 0) that contribute exactly nothing, so
    the padded stack predicts identically while the jit key stays on the
    bucket. ``cat_w`` forces the categorical-bitset width (the bucketed
    cache appends new trees into existing padded arrays, so widths must
    match across fills)."""
    t = len(models)
    t_pad = max(t, pad_to or t)

    def pad2(getter, fill, dtype, width):
        out = np.full((t_pad, width), fill, dtype=dtype)
        for i, m in enumerate(models):
            a = getter(m)
            out[i, : len(a)] = a
        return jnp.asarray(out)

    cat_w = max(cat_w or 1,
                max((m.cat_bitset.shape[1] for m in models), default=1))
    cat = np.zeros((t_pad, max_nodes, cat_w), np.uint32)
    for i, m in enumerate(models):
        cb = m.cat_bitset
        cat[i, : cb.shape[0], : cb.shape[1]] = cb
    nn = np.zeros(t_pad, np.int32)
    nn[:t] = [m.num_nodes for m in models]
    return StackedTrees(
        split_feature=pad2(lambda m: m.split_feature, -1, np.int32, max_nodes),
        split_bin=pad2(lambda m: m.split_bin, 0, np.int32, max_nodes),
        cat_bitset=jnp.asarray(cat),
        default_left=pad2(lambda m: m.default_left, False, bool, max_nodes),
        left_child=pad2(lambda m: m.left_child, -1, np.int32, max_nodes),
        right_child=pad2(lambda m: m.right_child, -1, np.int32, max_nodes),
        leaf_value=pad2(lambda m: m.leaf_value, 0.0, np.float32, max_leaves),
        num_nodes=jnp.asarray(nn),
    )


def _pad_metadata(md, n_padded: int):
    """Shallow metadata clone with label/weight zero-padded to the sharded
    row count (padding rows carry zero weight and are masked out of every
    histogram/gradient by the valid-row mask)."""
    from ..io.dataset import Metadata
    out = Metadata(n_padded)
    if md.label is not None:
        out.label = np.pad(np.asarray(md.label), (0, n_padded - len(md.label)))
    # padding rows get explicit zero weight so objective label statistics
    # (boost_from_average, class balance) never count them
    n_real = len(md.label) if md.label is not None else n_padded
    w = np.ones(n_padded, np.float32) if md.weight is None \
        else np.pad(np.asarray(md.weight, np.float32), (0, n_padded - n_real))
    w[n_real:] = 0.0
    out.weight = w
    out.init_score = md.init_score
    out.group = md.group
    out.query_boundaries = md.query_boundaries
    out.position = (np.pad(np.asarray(md.position),
                           (0, n_padded - n_real))
                    if md.position is not None else None)
    return out


def _init_score_matrix(init_score, k: int, n: int) -> np.ndarray:
    """Normalize user init_score into [K, N] f32.

    Accepts [N] (k=1), 2-D [N, K] (the reference Python API's layout), or a
    flat class-major [K*N] vector (the reference Metadata's internal layout,
    src/io/metadata.cpp init_score_)."""
    arr = np.asarray(init_score, np.float32)
    if arr.ndim == 2:
        if arr.shape == (n, k):
            return arr.T
        if arr.shape == (k, n):
            return arr
        raise ValueError(f"init_score shape {arr.shape} does not match "
                         f"(num_data={n}, num_class={k})")
    if arr.size != k * n:
        raise ValueError(f"init_score size {arr.size} != num_class*num_data "
                         f"({k * n})")
    return arr.reshape(k, n)


def _device_put_like(arr, like):
    """Place a host snapshot array back on the device(s) of an existing
    array, preserving its sharding. ``make_array_from_callback`` hands each
    process only the shards it addresses, so the same global host array
    restores correctly on 1 chip, a mesh, or a multi-host pod."""
    arr = np.asarray(arr)
    if isinstance(like, jax.Array):
        return jax.make_array_from_callback(
            arr.shape, like.sharding, lambda idx: arr[idx])
    return jnp.asarray(arr)


@jax.jit
def _add_leaf_outputs(score_row, leaf_value, row_leaf):
    return score_row + leaf_value[row_leaf]


@jax.jit
def _sub_leaf_outputs(score_row, leaf_value, row_leaf):
    return score_row - leaf_value[row_leaf]


class _ValidSet:
    """Cached raw scores for one validation set
    (reference: ScoreUpdater per valid set, gbdt.cpp valid_score_updater_)."""

    def __init__(self, dataset: BinnedDataset, num_class: int, name: str,
                 mesh=None):
        self.dataset = dataset
        self.name = name
        self.n_real = dataset.num_data
        binned_np = dataset.binned
        pad = 0
        if mesh is not None:
            from ..parallel.mesh import (class_row_sharding, mesh_axis_sizes,
                                         pad_rows, row_sharding_2d)
            pad = pad_rows(self.n_real, mesh_axis_sizes(mesh)[0])
            if pad:
                binned_np = np.pad(binned_np, ((0, pad), (0, 0)))
            self.binned = jax.device_put(binned_np, row_sharding_2d(mesh))
        else:
            self.binned = jnp.asarray(binned_np)
        n = self.n_real + pad
        score0 = np.zeros((num_class, n), np.float32)
        if dataset.metadata is not None and dataset.metadata.init_score is not None:
            score0[:, : self.n_real] += _init_score_matrix(
                dataset.metadata.init_score, num_class, self.n_real)
        if mesh is not None:
            self.score = jax.device_put(score0, class_row_sharding(mesh))
        else:
            self.score = jnp.asarray(score0)
        self.metrics: List[Metric] = []


class GBDT:
    """Gradient Boosted Decision Trees (reference: class GBDT, gbdt.h)."""

    _supports_lazy_cegb = True

    boosting_type = "gbdt"
    # RF overrides: average outputs instead of summing
    average_output = False

    def __init__(
        self,
        config,
        train_set: Optional[BinnedDataset] = None,
        objective: Optional[Objective] = None,
    ):
        self.config = config
        self.objective = objective
        self.train_set = train_set
        self.models: List[HostTree] = []
        self._dev_trees: List[Tuple[TreeArrays, float]] = []
        # batched stop-check / host-materialization cadence (TPU extension;
        # 1 == reference behavior of checking every iteration)
        self.stop_check_freq = max(1, int(config.get("stop_check_freq", 1) or 1))
        self.iter_ = 0
        self.learning_rate = float(config.get("learning_rate", 0.1))
        # per-iteration shrinkage; DART re-computes this each iter
        # (reference: shrinkage_rate_, gbdt.cpp / dart.hpp DroppingTrees)
        self.shrinkage_rate = self.learning_rate
        self.num_class = int(config.get("num_class", 1))
        if objective is not None:
            self.num_tree_per_iteration = objective.num_model_per_iteration
        else:
            self.num_tree_per_iteration = self.num_class
        self.max_leaves = int(config.get("num_leaves", 31))
        self._init_scores = [0.0] * self.num_tree_per_iteration
        self.valid_sets: List[_ValidSet] = []
        self.train_metrics: List[Metric] = []
        self.best_iteration = -1
        # bucketed device-tree cache (see _device_trees_batched): per
        # tbatch, stacked trees padded to the tree-count bucket plus fill
        # metadata. APPENDED trees extend a slot in place; the cache is
        # set to None only where existing models are mutated or removed
        # (rollback, DART drops/normalization, RF vote scaling, reload)
        self._device_trees_cache: Optional[Dict[int, Dict[str, Any]]] = None
        # serializes the pending-tree flush and the device-tree cache fill,
        # so concurrent Booster.predict readers (basic.py read lock) never
        # interleave _flush_trees' models/_dev_trees mutation; re-entrant
        # because predict_raw_binned -> device_trees -> _flush_trees nests,
        # and deepcopy-safe so users can still snapshot trained models
        self._trees_mu = Mutex()
        self._comm_hlo: Dict[str, str] = {}
        self._comm_hlo_history: Dict[str, List[str]] = {}
        self._comm_hlo_sigs: Dict[str, List[tuple]] = {}
        self._comm_jitted: Dict[str, Any] = {}
        self._comm_abstract: Dict[str, tuple] = {}
        self._use_compact = False
        self._compact = None
        self.tree_learner = "serial"
        # defaults for boosters constructed without a train set (model
        # load); _setup_train overwrites them from the config
        self._step_buckets = False
        self._max_depth_cfg = int(config.get("max_depth", -1))
        # engine-registry context (engines/registry.py): the dataset
        # shape class + resolution from _setup_train, and the compact
        # record-width clamp context — reset_parameter re-resolves
        # through these so a mid-run change never leaves a stale engine
        self._engine_shape = None
        self._engine_resolution = None
        self._fused_clamp_ctx = None
        # persistent XLA compilation cache (tpu_compile_cache_dir): armed
        # before the first jit of this booster so training AND predict-only
        # programs can skip their backend compiles on a warm cache
        cache_dir = config.get("tpu_compile_cache_dir", "")
        if cache_dir:
            from ..analysis.guards import configure_compile_cache
            configure_compile_cache(cache_dir)
        # telemetry plane (lightgbm_tpu/obs): flight-ring capacity, the
        # global phase-keyed compile listener, and the per-iteration
        # metrics stream when tpu_metrics_path is set
        from .. import obs as _obs
        self._metrics_stream = _obs.configure(config)
        self._slow_updates = SlowUpdates()

        if train_set is not None:
            self._setup_train(train_set)

    # -- training setup ------------------------------------------------------
    def _setup_train(self, train_set: BinnedDataset) -> None:
        cfg = self.config
        from ..parallel.mesh import (class_row_sharding, make_mesh,
                                     mesh_axis_sizes, pad_rows, parse_mesh_shape,
                                     replicated, row_feature_sharding,
                                     row_sharding, row_sharding_2d)
        # multi-host bootstrap before any device queries (reference:
        # Network::Init from config, src/network/linkers_socket.cpp)
        if int(cfg.get("num_machines", 1) or 1) > 1:
            from ..parallel.multihost import init_distributed
            init_distributed(cfg)
        tree_learner = str(cfg.get("tree_learner", "serial")).lower()
        tree_learner = {"data_parallel": "data", "voting_parallel": "voting",
                        "feature_parallel": "feature"}.get(
                            tree_learner, tree_learner)
        distributed = tree_learner in ("data", "voting", "feature") \
            and len(jax.devices()) > 1
        self.tree_learner = tree_learner
        mesh_shape = parse_mesh_shape(cfg.get("tpu_mesh_shape", ""))
        self.mesh = make_mesh(mesh_shape=mesh_shape) if distributed else None
        self._multiproc = jax.process_count() > 1
        if self.mesh is not None and mesh_axis_sizes(self.mesh)[1] > 1:
            # 2-D rows x features: the masked GSPMD growers shard the bin
            # matrix over both axes; learners with a physical row layout
            # (compact's shard_map partitions, feature-parallel's
            # feature-axis placement) stay row-mesh only
            if self.tree_learner == "feature":
                raise ValueError(
                    "tpu_mesh_shape=RxC (2-D rows x features) does not "
                    "compose with tree_learner=feature — the feature "
                    "learner already owns the feature axis; use a 1-D "
                    "mesh or tree_learner=data/voting")
            if self._multiproc:
                raise ValueError(
                    "tpu_mesh_shape=RxC is single-process only for now; "
                    "multi-host runs keep the 1-D row mesh")
        if self._multiproc:
            # each process holds only its LOCAL row shard; the global array
            # is assembled below from the per-process pieces (reference:
            # pre_partition=true rank-local loading, dataset_loader.cpp:203)
            if tree_learner != "data":
                raise ValueError(
                    "multi-host training supports tree_learner=data")
            n_loc = train_set.num_data
            d_loc = len(jax.local_devices())
            if n_loc % d_loc:
                raise ValueError(
                    f"multi-host: each process's rows ({n_loc}) must divide "
                    f"its local device count ({d_loc}); pad or re-partition "
                    "the local shard")
            self._n_real = n_loc * jax.process_count()
            pad = 0
        else:
            self._n_real = train_set.num_data
            pad = pad_rows(self._n_real, mesh_axis_sizes(self.mesh)[0]) \
                if self.mesh else 0
        self._pad = pad
        self.num_data = self._n_real + pad

        # per-rank runtime attribution (obs/ranks.py): sampled step /
        # collective-wait timers + rank-0 straggler aggregation over the
        # coordination-service KV. Constructed HERE (not lazily) so the
        # collective-arrival probe compiles outside the steady-state
        # region; off-sample iterations touch none of it.
        self._rank_stats = None
        rs_every = int(cfg.get("tpu_rank_stats_every", 0) or 0)
        if rs_every > 0:
            from ..obs.ranks import RankStats
            self._rank_stats = RankStats(
                every=rs_every,
                straggler_factor=float(
                    cfg.get("tpu_straggler_factor", 3.0) or 3.0),
                mesh=self.mesh,
                deadline_s=float(
                    cfg.get("tpu_collective_deadline_s", 0.0) or 0.0),
                stream=self._metrics_stream)

        # EFB: configurations the bundle-space growers can't serve unbundle
        # HERE, before any device placement, so every learner's layout logic
        # below sees a plain dense matrix (bundling is lossless)
        self._efb_precheck(train_set, cfg, tree_learner)

        # span `to_device` below: the binned matrix's first move to the
        # device. Host seconds to hand it over; the copy may still be in
        # flight when the span closes, nothing here waits for it
        binned_np = train_set.binned
        if pad:
            binned_np = np.pad(binned_np, ((0, pad), (0, 0)))
        # feature-parallel shards the feature axis; pad it to the mesh size
        # with trivial (never-selectable) features
        self._f_pad = 0
        if self.mesh is not None and self.tree_learner == "feature":
            self._f_pad = (-binned_np.shape[1]) % len(
                self.mesh.devices.ravel())
            if self._f_pad:
                binned_np = np.pad(binned_np, ((0, 0), (0, self._f_pad)))
            # feature-parallel: data replicated, split finding partitioned by
            # feature (reference: feature_parallel_tree_learner.cpp — every
            # rank holds full data; GSPMD shards the [F, B] histogram/scan
            # over features and all-gathers the tiny best-split argmax, the
            # analogue of SyncUpGlobalBestSplit)
            from ..parallel.mesh import feature_sharding_2d
            with span("to_device"):
                self.binned = jax.device_put(
                    binned_np, feature_sharding_2d(self.mesh))
            ones = np.ones(self.num_data, np.float32)
            if pad:
                ones[self._n_real:] = 0.0
            self._valid_row_mask = jax.device_put(
                ones, replicated(self.mesh)) if pad else None
        elif self.mesh is not None:
            # rows sharded over the mesh: the reference's row partitioning
            # across machines (data_parallel_tree_learner.cpp BeforeTrain)
            if self._multiproc:
                # assemble the global array from per-process local shards
                with span("to_device"):
                    self.binned = jax.make_array_from_process_local_data(
                        row_sharding_2d(self.mesh), binned_np)
                self._valid_row_mask = None
            else:
                s_feat = mesh_axis_sizes(self.mesh)[1]
                if s_feat > 1:
                    # 2-D mesh: the feature axis shards too — pad it with
                    # trivial (never-selectable) columns like the
                    # feature-parallel learner does
                    self._f_pad = (-binned_np.shape[1]) % s_feat
                    if self._f_pad:
                        binned_np = np.pad(binned_np,
                                           ((0, 0), (0, self._f_pad)))
                with span("to_device"):
                    self.binned = jax.device_put(
                        binned_np, row_feature_sharding(self.mesh))
                ones = np.ones(self.num_data, np.float32)
                if pad:
                    ones[self._n_real:] = 0.0
                self._valid_row_mask = jax.device_put(
                    ones, row_sharding(self.mesh))
        else:
            with span("to_device"):
                self.binned = jnp.asarray(binned_np)
            self._valid_row_mask = None
        def fpad(arr, fill):
            if self._f_pad:
                return np.concatenate(
                    [np.asarray(arr),
                     np.full(self._f_pad, fill, np.asarray(arr).dtype)])
            return np.asarray(arr)

        self.num_bins_arr = jnp.asarray(
            fpad(train_set.feature_num_bins(), 1))
        self.nan_bin_arr = jnp.asarray(fpad(train_set.feature_nan_bins(), 0))
        self.has_nan_arr = jnp.asarray(fpad(
            np.array([m.missing_type == 2 and not m.is_categorical
                      for m in train_set.mappers], dtype=bool), False))
        self.is_cat_arr = jnp.asarray(fpad(
            train_set.feature_is_categorical(), False))
        self.base_feat_mask = fpad(np.array(
            [not m.is_trivial for m in train_set.mappers], dtype=bool), False)
        # inference-engine flags: prediction inputs arrive in ORIGINAL
        # feature space, so categorical presence and 4-bit-pack
        # eligibility come from the raw mappers (ops/predict.py engine)
        self._pred_any_cat = bool(np.any(train_set.feature_is_categorical()))
        from ..io.dataset import pack4_eligible
        want_pack4 = bool(cfg.get("tpu_bin_pack4", False))
        self._pred_pack4 = want_pack4 and pack4_eligible(train_set.mappers)
        if want_pack4 and not self._pred_pack4:
            log.warning("tpu_bin_pack4=true needs every feature to have "
                        "<= 16 bins (max_bin <= 15); serving the u8 matrix")

        nf = train_set.num_total_features
        mono_np = _parse_monotone(cfg.get("monotone_constraints"), nf,
                                  train_set.feature_names)
        inter_np = _parse_interactions(
            cfg.get("interaction_constraints"), nf)
        self._mono_types = (jnp.asarray(fpad(mono_np, 0))
                            if mono_np is not None else None)
        mono_method = str(cfg.get("monotone_constraints_method", "basic"))
        self._mono_intermediate = (mono_np is not None
                                   and mono_method in ("intermediate",
                                                       "advanced"))
        if mono_np is not None and mono_method == "advanced":
            log.warning(
                "monotone_constraints_method='advanced' is not implemented; "
                "using the 'intermediate' method")
        if inter_np is not None and self._f_pad:
            inter_np = np.pad(inter_np, ((0, 0), (0, self._f_pad)))
        self._inter_sets = (jnp.asarray(inter_np) if inter_np is not None
                            else None)
        self._bynode_key = jax.random.PRNGKey(
            int(cfg.get("feature_fraction_seed", 2)))
        # CEGB (reference: cost_effective_gradient_boosting.hpp): coupled
        # feature costs are paid once per model, so the used-feature set
        # persists across trees
        tradeoff = float(cfg.get("cegb_tradeoff", 1.0))

        def _vec(v):
            # config files / CLI deliver vector params as comma strings
            if isinstance(v, str):
                return [float(t) for t in v.split(",") if t.strip()]
            return list(v)

        coupled = cfg.get("cegb_penalty_feature_coupled")
        split_pen = float(cfg.get("cegb_penalty_split", 0.0))
        self._use_cegb = split_pen > 0.0 or coupled is not None
        lazy = cfg.get("cegb_penalty_feature_lazy")
        if lazy is not None and not self._supports_lazy_cegb:
            # RF (and any other subclass that opts out) must decline BEFORE
            # the bitmap size check / EFB precheck act on the parameter
            log.warning("cegb_penalty_feature_lazy is not supported with "
                        f"boosting={self.boosting_type}; the lazy penalty "
                        "is ignored")
            lazy = None
        if lazy is not None:
            lz = np.asarray(_vec(lazy), np.float32)
            if lz.size != nf:
                raise ValueError(
                    "cegb_penalty_feature_lazy must have one entry per "
                    f"feature ({nf}), got {lz.size}")
            # on-demand (lazy) per-row feature costs: the [F, N] bool
            # bitmap plus its transient f32 cast in the per-split matvec
            # cost ~5 bytes per element on device — bound well inside HBM
            nf_pad = nf + self._f_pad
            if nf_pad * self.num_data > (1 << 30):
                raise ValueError(
                    "cegb_penalty_feature_lazy needs an [F, N] charged-rows "
                    f"bitmap (~5 bytes/element transient); "
                    f"{nf_pad}x{self.num_data} exceeds the supported size "
                    "(2^30 elements)")
            self._cegb_lazy = jnp.asarray(
                fpad(tradeoff * lz, 0.0)) if self._f_pad else \
                jnp.asarray(tradeoff * lz)
            self._use_cegb = True
        else:
            self._cegb_lazy = None
        self._cegb_charged = None  # lazily a [F, N] bool device array
        if coupled is not None:
            cp = np.asarray(_vec(coupled), np.float32)
            if cp.size != nf:
                raise ValueError(
                    "cegb_penalty_feature_coupled must have one entry per "
                    f"feature ({nf}), got {cp.size}")
            self._cegb_coupled = jnp.asarray(
                fpad(tradeoff * cp, 0.0)) if self._f_pad else \
                jnp.asarray(tradeoff * cp)
        else:
            self._cegb_coupled = None
        self._cegb_split_pen = tradeoff * split_pen
        self._cegb_used = None  # lazily a [F] bool device array
        # quantized-gradient training (reference: gradient_discretizer.cpp)
        self._linear = bool(cfg.get("linear_tree", False)) \
            and self.mesh is None and self.boosting_type == "gbdt"
        if bool(cfg.get("linear_tree", False)) \
                and self.boosting_type != "gbdt":
            log.warning(f"linear_tree is not supported with "
                        f"boosting={self.boosting_type}; training constant "
                        "leaves")
        if self._linear and train_set.raw_data is None:
            raise ValueError(
                "linear_tree=true needs raw feature values; construct the "
                "Dataset with the linear_tree parameter set (or "
                "free_raw_data=False) so they are retained")
        if bool(cfg.get("linear_tree", False)) and self.mesh is not None:
            log.warning("linear_tree is not supported with distributed "
                        "tree learners; training constant leaves")
        self._use_quant = bool(cfg.get("use_quantized_grad", False))
        self._quant_bins = int(cfg.get("num_grad_quant_bins", 4))
        self._quant_renew = bool(cfg.get("quant_train_renew_leaf", False))
        self._quant_stochastic = bool(cfg.get("stochastic_rounding", True))
        self._quant_key = jax.random.PRNGKey(
            int(cfg.get("seed", 0) or 0) + 1337)
        self._extra_key = jax.random.PRNGKey(int(cfg.get("extra_seed", 6)))
        fs_path = str(cfg.get("forcedsplits_filename", "") or "")
        if fs_path and self.mesh is not None and self.tree_learner == "voting":
            # voted histograms zero un-elected features, so forced child
            # sums would be wrong (grower reads them from leaf_hist)
            log.warning("forcedsplits_filename is not supported with "
                        "tree_learner=voting; ignoring it")
            fs_path = ""
        self._forced_splits = _forced_split_schedule(
            fs_path, train_set.mappers, self.max_leaves) if fs_path else None
        fc = cfg.get("feature_contri")
        if fc is not None:
            fcv = np.asarray(list(fc), np.float32)
            if fcv.size != nf:
                raise ValueError("feature_contri needs one entry per feature")
            self._feature_contri = jnp.asarray(
                fpad(fcv, 1.0)) if self._f_pad else jnp.asarray(fcv)
        else:
            self._feature_contri = None
        # serial-learner row storage: the compact grower physically
        # partitions rows into per-leaf segments — O(N*depth) per tree
        # instead of the masked grower's O(N*num_leaves) (see
        # ops/grower_compact.py). It requires row-elementwise gradients
        # (the rows live in a per-tree permuted order).
        grower = str(cfg.get("tpu_grower", "auto")).lower()
        # data-parallel: the compact grower runs per shard under shard_map,
        # with shard-local partitions and psum-ed histograms (reference:
        # DataParallelTreeLearner, data_parallel_tree_learner.cpp:223-300);
        # voting/feature learners keep the masked GSPMD path
        mesh_compact_ok = (
            self.mesh is None
            or (self.tree_learner == "data"
                and mesh_axis_sizes(self.mesh)[1] == 1
                and not (self.objective is not None
                         and self.objective.renew_leaves)))
        # exact-count ceiling: a shard's own histogram count channels ride
        # f32, exact for integers < 2^24, and drive its partition offsets
        # (n_left_loc), so the bound applies per shard, not globally. The
        # counts that cross shards (a leaf's and a node's global count, the
        # root's totals, what min_data_in_leaf is held against) are summed
        # as int32 (ops/grower_compact.py): the model text's leaf_count and
        # internal_count are exact below 2^31 rows, which the benchmark's
        # replay holds them to (benchmarks/correct.py, leaf_count_wrong).
        n_shards = (mesh_axis_sizes(self.mesh)[0]
                    if self.mesh is not None and self.tree_learner == "data"
                    else 1)
        # non-row-elementwise objectives (lambdarank: gradients couple rows
        # of a query) still run compact when K == 1: gradients compute
        # on-device in ORIGINAL row order (scatter by the carried row-id
        # column) and feed the step externally — see _rank_grads_fn
        obj_re = (getattr(self.objective, "row_elementwise", True)
                  if self.objective is not None else False)
        goss = (str(cfg.get("data_sample_strategy", "bagging")).lower()
                == "goss"
                or str(cfg.get("boosting", "gbdt")).lower() == "goss")
        self._ext_grads = (
            not obj_re and int(cfg.get("num_class", 1) or 1) == 1
            and not goss and not bool(cfg.get("use_quantized_grad", False)))
        can_compact = (
            mesh_compact_ok
            and self.objective is not None
            and (obj_re or self._ext_grads)
            and not getattr(self.objective, "is_stochastic", False)
            and int(train_set.max_num_bins) <= 256
            and -(-self.num_data // n_shards) < (1 << 24)
            # balanced / by-query bagging index rows in the original order
            and float(cfg.get("pos_bagging_fraction", 1.0)) >= 1.0
            and float(cfg.get("neg_bagging_fraction", 1.0)) >= 1.0
            and not bool(cfg.get("bagging_by_query", False))
            # lazy CEGB tracks charged rows in ORIGINAL row order; the
            # compact grower permutes rows, so it runs masked
            and (cfg.get("cegb_penalty_feature_lazy") is None
                 or not self._supports_lazy_cegb)
        )
        if grower == "compact" and not can_compact:
            log.warning("tpu_grower=compact requires a serial learner and a "
                        "row-elementwise objective; using masked grower")
        # linear leaves fit against raw rows in the ORIGINAL order; the
        # compact grower permutes rows, so linear mode uses the masked path;
        # forced splits are implemented in the masked grower only
        can_compact = can_compact and not self._linear \
            and self._forced_splits is None
        self._use_compact = can_compact and (
            grower == "compact"
            # bundled datasets always prefer the compact grower: the
            # bundle-space scan/routing lives there, and the masked grower
            # would otherwise unbundle back to the dense width
            or (grower == "auto"
                and (self._n_real >= 65536
                     or getattr(train_set, "bundle_info", None) is not None)))
        self._compact = None          # lazy _CompactTrainState
        # THE engine-registry callsite (lightgbm_tpu/engines/registry.py):
        # one resolve populates every engine knob of GrowerParams —
        # {fused, pallas, xla} x layout x batched-M x ladder x overlap —
        # user > env > what platform and shape decide
        from ..engines import registry as engine_registry
        shape = engine_registry.DatasetShape(
            rows=int(self._n_real),
            # STORED columns (post-EFB): the width the histogram engines
            # actually stream
            features=int(train_set.binned.shape[1]),
            num_bins=int(train_set.max_num_bins),
            mode=(self.tree_learner if self.mesh is not None
                  or self._multiproc else "serial"),
            quant=bool(cfg.get("use_quantized_grad", False)),
            pack4=bool(cfg.get("tpu_bin_pack4", False)),
            # the masked grower under a mesh is partitioned by GSPMD,
            # which cannot partition a Mosaic call (registry.DatasetShape)
            gspmd=self.mesh is not None and not self._use_compact,
            compact=self._use_compact)

        self._engine_shape = shape
        resolved = engine_registry.resolve(cfg, shape=shape)
        self._engine_resolution = resolved
        if shape.gspmd and engine_registry.on_tpu():
            log.info("engine registry: this step is partitioned by GSPMD "
                     "(masked grower under a mesh); histograms take the XLA "
                     "einsum — a Mosaic kernel cannot be partitioned "
                     "automatically")

        # bucketed step ladder (the compile-once training contract): the
        # jit key carries (leaf rung, depth bucket), the actual budgets
        # ride as traced scalars through _step_budget_args()
        self._step_buckets = resolved.step_buckets
        self._max_depth_cfg = int(cfg.get("max_depth", -1))
        key_leaves, key_depth = bucketed_tree_shape(
            self._step_buckets, self.max_leaves, self._max_depth_cfg)
        self.grower_params = GrowerParams(
            num_leaves=key_leaves,
            max_depth=key_depth,
            step_buckets=self._step_buckets,
            hist_overlap=resolved.hist_overlap,
            num_bins=int(train_set.max_num_bins),
            lambda_l1=float(cfg.get("lambda_l1", 0.0)),
            lambda_l2=float(cfg.get("lambda_l2", 0.0)),
            min_data_in_leaf=float(cfg.get("min_data_in_leaf", 20)),
            min_sum_hessian_in_leaf=float(cfg.get("min_sum_hessian_in_leaf", 1e-3)),
            min_gain_to_split=float(cfg.get("min_gain_to_split", 0.0)),
            max_delta_step=float(cfg.get("max_delta_step", 0.0)),
            max_cat_threshold=int(cfg.get("max_cat_threshold", 32)),
            cat_l2=float(cfg.get("cat_l2", 10.0)),
            cat_smooth=float(cfg.get("cat_smooth", 10.0)),
            max_cat_to_onehot=int(cfg.get("max_cat_to_onehot", 4)),
            min_data_per_group=float(cfg.get("min_data_per_group", 100)),
            any_cat=bool(np.any(train_set.feature_is_categorical())),
            use_monotone=mono_np is not None,
            monotone_penalty=float(cfg.get("monotone_penalty", 0.0)),
            mono_intermediate=self._mono_intermediate,
            path_smooth=float(cfg.get("path_smooth", 0.0)),
            use_interaction=inter_np is not None,
            bynode_fraction=float(cfg.get("feature_fraction_bynode", 1.0)),
            use_cegb=self._use_cegb,
            cegb_split_pen=self._cegb_split_pen,
            extra_trees=bool(cfg.get("extra_trees", False)),
            voting_k=(int(cfg.get("top_k", 20))
                      if self.mesh is not None
                      and self.tree_learner == "voting" else 0),
            voting_shards=(mesh_axis_sizes(self.mesh)[0]
                           if self.mesh is not None
                           and self.tree_learner == "voting" else 0),
            hist_impl=resolved.hist_impl,
            part_block=_clamp_block(
                int(cfg.get("tpu_part_block", 2048)), self._n_real),
            hist_block=_clamp_block(
                int(cfg.get("tpu_hist_block", 16384)), self._n_real),
            fused_block=resolved.fused_block,
            fused_interpret=bool(cfg.get("tpu_fused_interpret", False)),
            hist_mbatch=resolved.hist_mbatch,
            hist_layout=resolved.hist_layout,
        )

        if self._mono_intermediate and not self._use_compact:
            log.warning(
                "monotone_constraints_method='intermediate' runs on the "
                "compact grower only; this configuration uses the masked "
                "grower with the 'basic' method")
            self.grower_params = self.grower_params._replace(
                mono_intermediate=False)
        self._setup_efb(train_set)
        md = train_set.metadata if not pad else _pad_metadata(
            train_set.metadata, self.num_data)
        if self._multiproc:
            # label/weight/... become the host-side GLOBAL arrays on every
            # process (metrics, averages and objectives are global state)
            from ..parallel.multihost import gather_metadata
            md = gather_metadata(train_set.metadata, train_set.num_data)
        self._global_md = md if self._multiproc else None
        if self.objective is not None:
            self.objective.init(md, self.num_data)

        k, n = self.num_tree_per_iteration, self.num_data
        score0 = np.zeros((k, n), np.float32)
        if md.init_score is not None:
            init = _init_score_matrix(md.init_score, k, self._n_real)
            score0[:, : self._n_real] += init
            self._has_init_score = True
        else:
            self._has_init_score = False
        if self.mesh is not None and self.tree_learner != "feature":
            self.train_score = jax.device_put(
                score0, class_row_sharding(self.mesh))
        elif self.mesh is not None:
            self.train_score = jax.device_put(score0, replicated(self.mesh))
        else:
            self.train_score = jnp.asarray(score0)

        self.sample_strategy = create_sample_strategy(cfg, self.num_data, md)
        self.feature_fraction = float(cfg.get("feature_fraction", 1.0))
        self._feat_rng = np.random.RandomState(
            int(cfg.get("feature_fraction_seed", 2)))
        self.row_weight = (
            jnp.asarray(md.weight, jnp.float32)
            if md.weight is not None else None)
        self._grad_fn = None
        self._step_fn = None
        self._comm_hlo = {}
        self._comm_hlo_history = {}
        self._comm_hlo_sigs = {}
        self._comm_jitted = {}
        self._comm_abstract = {}

    def _step_budget_args(self) -> Tuple[jax.Array, jax.Array]:
        """(leaf_budget, depth_budget) — the ACTUAL tree budgets as traced
        i32 scalars for the bucketed step ladder. Device scalars are cached
        per value so steady-state iterations re-feed the same arrays
        (passed on the exact-keyed path too, where the growers ignore them
        — dead args keep one call signature per mode)."""
        vals = (int(self.max_leaves), int(self._max_depth_cfg))
        cached = getattr(self, "_budget_cache", None)
        if cached is None or cached[0] != vals:
            self._budget_cache = (vals, (jnp.asarray(vals[0], jnp.int32),
                                         jnp.asarray(vals[1], jnp.int32)))
        return self._budget_cache[1]

    def _note_quant_path(self, int_hist: bool, renew: bool) -> None:
        """What every ``iteration`` event of a quantized run carries
        beside the update's counters: ``quant_hist`` (1 where the step
        histograms the int8 codes into int32 sums, 0 where it takes the
        dequantising f32 shim), ``quant_bins`` and ``quant_renew`` (1
        where leaves are renewed from the true gradients). Host integers,
        noted when the step is built; nothing for an f32 run."""
        self._quant_counters = {
            "quant_hist": int(int_hist), "quant_bins": self._quant_bins,
            "quant_renew": int(renew)} if self._use_quant else {}

    def _note_fused_path(self, fused: bool) -> None:
        """``hist_levels``, ``carry_bytes`` and ``record_write`` of every
        ``iteration`` event, noted when the step is built. ``hist_levels``:
        2 where the step's fused kernel contracts a two-level one-hot (bin
        = 64 hi + lo: more than 64 bins a feature), 1 where its one-hot
        spans the whole stride, 0 where the fused kernel is off
        (ops/fused_split.hist_levels). ``carry_bytes``: the bytes of VMEM
        a carried row's byte takes in the kernel's ring carries, 1 where
        they hold packed bytes (4: one byte an i32 word), 0 where the
        fused kernel is off (ops/fused_split.carry_bytes).
        ``record_write``: 1 where the ``record_write`` kernel writes the
        per-row columns into the records (ops/record_write.py, wherever
        the fused kernel runs), 0 where XLA's lane-slice update does."""
        from ..ops.fused_split import carry_bytes, hist_levels
        gp = self.grower_params
        layout = self._compact["layout"] if fused else None
        self._hist_counters = {
            "hist_levels": hist_levels(layout.num_features, gp.num_bins,
                                       gp.hist_layout) if fused else 0,
            "carry_bytes": carry_bytes(gp.fused_block, layout.num_cols)
            if fused else 0,
            "record_write": int(fused)}

    def _build_step_fn(self):
        """One fused, jitted train step per tree: mask gradients, grow, renew,
        shrink, update the train score — a single XLA program, zero host syncs
        (the contract of the reference's CUDA path, SURVEY §3.3)."""
        obj = self.objective
        renew = obj is not None and obj.renew_leaves
        row_weight = self.row_weight
        grower_params = self.grower_params
        num_bins_arr = self.num_bins_arr
        nan_bin_arr = self.nan_bin_arr
        has_nan_arr = self.has_nan_arr
        is_cat_arr = self.is_cat_arr
        # leaf-array length of the grown trees: the RUNG under the step
        # ladder (renew scatters and liveness masks must match the
        # grower's padded leaf arrays, not the user's leaf count)
        max_leaves = self.grower_params.num_leaves

        mono_types = self._mono_types
        inter_sets = self._inter_sets
        cegb_coupled = self._cegb_coupled
        use_cegb = self._use_cegb
        use_quant = self._use_quant
        quant_renew = use_quant and self._quant_renew
        quant_bins = self._quant_bins
        quant_stoch = self._quant_stochastic
        const_hess = bool(getattr(obj, "is_constant_hessian", False))
        feature_contri = self._feature_contri
        # the masked grower histograms the dequantised codes in f32
        self._note_quant_path(False, quant_renew)
        self._note_fused_path(False)

        def step(binned, score_k, grad_k, hess_k, mask, feat_mask,
                 shrinkage, bynode_key, cegb_used, true_grad_k, true_hess_k,
                 extra_key, cegb_charged, leaf_budget, depth_budget):
            # binned is an argument, not a closure: multi-process global
            # arrays cannot be captured as jit constants
            # grad_k/hess_k arrive already quantized when use_quantized_grad
            # (once per iteration over all classes, like the reference's
            # GradientDiscretizer); true_* carry the originals for renewal
            g = grad_k * mask
            h = hess_k * mask
            if use_lazy:
                tree, row_leaf, cegb_charged = grow_tree(
                    binned, g, h, mask, num_bins_arr, nan_bin_arr,
                    has_nan_arr, is_cat_arr, feat_mask, grower_params,
                    mono_types, inter_sets, bynode_key, cegb_coupled,
                    cegb_used, extra_key, feature_contri,
                    self._forced_splits, cegb_lazy=self._cegb_lazy,
                    cegb_charged0=cegb_charged, leaf_budget=leaf_budget,
                    depth_budget=depth_budget)
            else:
                tree, row_leaf = grow_tree(
                    binned, g, h, mask, num_bins_arr, nan_bin_arr,
                    has_nan_arr, is_cat_arr, feat_mask, grower_params,
                    mono_types, inter_sets, bynode_key, cegb_coupled,
                    cegb_used, extra_key, feature_contri,
                    self._forced_splits, leaf_budget=leaf_budget,
                    depth_budget=depth_budget)
            if use_cegb:
                cegb_used = _tree_used_features(tree, binned.shape[1],
                                                cegb_used)
            if quant_renew:
                # re-fit leaf outputs from the TRUE gradient sums
                # (reference: RenewIntGradTreeOutput, gbdt.cpp)
                with span("quant_renew"):
                    tg = true_grad_k * mask
                    th = true_hess_k * mask
                    sums_g = jnp.zeros((max_leaves,)).at[row_leaf].add(tg)
                    sums_h = jnp.zeros((max_leaves,)).at[row_leaf].add(th)
                    from ..ops.split import leaf_output as _lo
                    live = jnp.arange(max_leaves) < tree.num_leaves
                    tree = tree._replace(leaf_value=jnp.where(
                        live,
                        _lo(sums_g, sums_h, grower_params.split_params()),
                        tree.leaf_value))
            if renew:
                residual = obj.label - score_k
                w = mask if row_weight is None else mask * row_weight
                renewed = renew_leaf_quantile(
                    residual, w, row_leaf, max_leaves, float(obj.renew_alpha))
                live = jnp.arange(max_leaves) < tree.num_leaves
                tree = tree._replace(
                    leaf_value=jnp.where(live, renewed, tree.leaf_value))
            # a no-split tree contributes nothing (reference: AsConstantTree 0,
            # gbdt.cpp:433) — zeroing here lets the host defer its stop check
            # without score corruption (no per-iteration device->host sync)
            lv = jnp.where(tree.num_nodes > 0, tree.leaf_value, 0.0)
            tree = tree._replace(
                leaf_value=lv * shrinkage,
                internal_value=tree.internal_value * shrinkage)
            new_score = score_k + tree.leaf_value[row_leaf]
            return tree, row_leaf, new_score, cegb_used, cegb_charged

        use_lazy = self._cegb_lazy is not None
        jitted = jax.jit(step)
        if os.environ.get("LGBM_TPU_COMM_ACCOUNTING", "") == "1":
            return self._comm_capture(jitted, "step")
        return jitted

    # comm-volume accounting (dryrun_multichip) and the hlo_check contract
    # gate: compiled-HLO text of the train-step programs, captured when
    # LGBM_TPU_COMM_ACCOUNTING=1 so the collectives XLA actually inserted
    # can be parsed back out (analysis/hlo.py)
    _comm_hlo: Dict[str, str]

    def _comm_capture(self, jitted, key):
        """Wrap a jitted step for LGBM_TPU_COMM_ACCOUNTING=1 runs.

        Records the compiled HLO text under ``key`` on the first call and
        re-lowers whenever the abstract argument signature changes, so
        ``analysis/hlo_check.py`` can both verify the steady-state program
        against its contract and prove it stable across iterations — a
        recompile detector at the HLO level, not just the event counter
        (``_comm_hlo_history[key]`` holds one text per distinct signature;
        length 1 == the step never re-lowered)."""
        key_of = key if callable(key) else (lambda kwargs: key)

        def capture(*args, **kwargs):
            k = key_of(kwargs)
            sig = tuple(
                (tuple(x.shape), str(x.dtype))
                for x in jax.tree_util.tree_leaves((args, kwargs))
                if hasattr(x, "shape"))
            seen = self._comm_hlo_sigs.setdefault(k, [])
            if sig not in seen:
                seen.append(sig)
                # AOT re-lowering hook (analysis/spmd_check.py): the jitted
                # callable + the abstract (shape/dtype/sharding) argument
                # signature — enough to re-lower this program at a DIFFERENT
                # row count without data (ShapeDtypeStructs hold no buffers,
                # so donated args are not retained)
                self._comm_jitted[k] = jitted
                self._comm_abstract[k] = (
                    [self._abstractify(a) for a in args],
                    {kk: self._abstractify(v) for kk, v in kwargs.items()})
                text = jitted.lower(*args, **kwargs).compile().as_text()
                self._comm_hlo.setdefault(k, text)
                self._comm_hlo_history.setdefault(k, []).append(text)
                # flight-recorder accounting: the collectives XLA actually
                # inserted into this program, in bytes per step — a dead
                # run's dump carries its own comm inventory
                try:
                    from ..analysis.hlo import collective_bytes
                    from ..obs import flight
                    bts = collective_bytes(text)
                    flight.note("collective_program", key=k,
                                bytes={kk: v for kk, v in bts.items()
                                       if kk not in ("total", "count")
                                       and v},
                                total=bts.get("total", 0),
                                count=bts.get("count", 0),
                                relowered=len(self._comm_hlo_history[k]) - 1)
                except Exception:  # noqa: BLE001 - accounting best-effort
                    pass
            return jitted(*args, **kwargs)
        return capture

    @staticmethod
    def _abstractify(x):
        """jax.Array leaves -> sharded ShapeDtypeStructs (AOT signature).

        Only NAMED (mesh) shardings are pinned: a single-device placement
        on an auxiliary arg (e.g. an uncommitted bag vector) must stay
        unconstrained, or relowering under the mesh reports an
        incompatible-devices conflict the real call never had."""
        from jax.sharding import NamedSharding

        def leaf(v):
            if isinstance(v, jax.Array):
                sh = v.sharding if isinstance(v.sharding, NamedSharding) \
                    else None
                return jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sh)
            return v
        return jax.tree_util.tree_map(leaf, x)

    def aot_lower_program(self, key: str, dim_map: Optional[Dict[int, int]]
                          = None):
        """AOT-relower a comm-captured step program at rewritten row dims.

        The spmd flight check's scaling hook: a tiny training run under
        ``LGBM_TPU_COMM_ACCOUNTING=1`` records the jitted step and its
        abstract argument signature; this re-lowers the SAME program with
        every dimension in ``dim_map`` rewritten (e.g. the padded tiny
        row count -> the full Allstate row count) — shapes only, no data
        is materialized, so a 13.2M-row program lowers on this CPU host
        in compile time, not memory. Shardings ride the recorded
        ShapeDtypeStructs, so the mesh placement is the captured run's.
        Returns the ``jax.stages.Lowered`` (call ``.compile()`` for the
        partitioned per-chip HLO text).
        """
        if key not in self._comm_jitted:
            raise KeyError(
                f"program {key!r} was not comm-captured (have "
                f"{sorted(self._comm_jitted)}); train at least one "
                "iteration with LGBM_TPU_COMM_ACCOUNTING=1 first")
        args, kwargs = self._comm_abstract[key]

        def resize(x):
            if isinstance(x, jax.ShapeDtypeStruct) and dim_map:
                shape = tuple(dim_map.get(d, d) for d in x.shape)
                if shape != tuple(x.shape):
                    return jax.ShapeDtypeStruct(shape, x.dtype,
                                                sharding=x.sharding)
            return x

        args = [jax.tree_util.tree_map(resize, a) for a in args]
        kwargs = {k: jax.tree_util.tree_map(resize, v)
                  for k, v in kwargs.items()}
        return self._comm_jitted[key].lower(*args, **kwargs)

    def flight_row_dims(self, n_rows: int) -> Dict[int, int]:
        """``dim_map`` for :meth:`aot_lower_program`: every captured
        row-proportional dimension -> its value at ``n_rows`` real rows.

        Two row dims exist: the mesh-padded global row count
        (``num_data``) and, for the compact grower, the work/scratch row
        count ``S * (n/S + pad_rows)`` (each shard's rows plus its own
        block-overrun pad — see ``_setup_compact_state``)."""
        from ..parallel.mesh import mesh_axis_sizes, pad_rows
        s_rows = (mesh_axis_sizes(self.mesh)[0]
                  if self.mesh is not None else 1)
        n_pad = n_rows + pad_rows(n_rows, s_rows)
        dim_map = {int(self.num_data): int(n_pad)}
        c = getattr(self, "_compact", None)
        if c and c.get("work") is not None:
            new_rows = c["S"] * (n_pad // c["S"] + c["pad_rows"])
            dim_map[int(c["work"].shape[0])] = int(new_rows)
        return dim_map

    def aot_lower_sharded_predict(self, n_rows: int):
        """AOT-lower the GSPMD row-sharded serving dispatch (the
        ``predict_raw_device`` oversize branch) at ``n_rows`` rows over
        the training mesh — the spmd flight check's serving program.
        Abstract input only: nothing is featurized or transferred."""
        if self.mesh is None:
            raise ValueError(
                "sharded predict needs a training mesh (tree_learner="
                "data/voting/feature on >1 device)")
        from ..parallel.mesh import (mesh_axis_sizes, predict_shard_pad,
                                     replicated, row_sharding_2d)
        tb_cfg, ladder, _engine = self._predict_cfg()
        nan_a, cat_a = self._pred_route_args()
        st, t_real, depth = self._device_trees_batched(None, 0, tb_cfg)
        if t_real == 0:
            raise ValueError("no trees to lower (train first)")
        num_shards = mesh_axis_sizes(self.mesh)[0]
        n_pad = predict_shard_pad(n_rows, num_shards, ladder)
        if n_pad is None:
            # per-shard share above the ladder: lower at the top rung —
            # the program the slicing fallback would run per slice
            n_pad = ladder[-1] * num_shards
        packed = self._pred_pack4
        f = self.train_set.num_total_features
        cols = (f + 1) // 2 if packed else f
        rep = replicated(self.mesh)
        shaped = self._abstractify
        rep_abs = jax.tree_util.tree_map(
            lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=rep)
            if isinstance(v, jax.ShapeDtypeStruct) else v, shaped(
                (st, nan_a, cat_a)))
        st_a, nan_abs, cat_abs = rep_abs
        k = self.num_tree_per_iteration
        ab = jax.ShapeDtypeStruct(
            (n_pad, cols), self.train_set.binned.dtype,
            sharding=row_sharding_2d(self.mesh))
        kk = jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)
        return predict_raw_batched.lower(
            ab, st_a, nan_abs, cat_abs, kk, num_class=k,
            depth=depth_bucket(depth), tbatch=tb_cfg,
            any_cat=self._pred_any_cat, packed=packed)

    def aot_lower_serving(self, engine: str, n_rows: Optional[int] = None):
        """AOT-lower one serving engine's predict program ("walk" or
        "level") at a ladder rung with abstract inputs — the
        serving-contract harness (analysis/hlo_check
        verify_serving_contracts). Nothing is featurized or
        transferred; returns the ``jax.stages.Lowered``."""
        tb_cfg, ladder, _ = self._predict_cfg()
        nan_a, cat_a = self._pred_route_args()
        st, t_real, depth, c = self._device_trees_entry(None, 0, tb_cfg)
        if t_real == 0:
            raise ValueError("no trees to lower (train first)")
        rung = int(ladder[0]) if n_rows is None \
            else bucket_rows(n_rows, ladder)
        packed = self._pred_pack4
        f = self.train_set.num_total_features
        cols = (f + 1) // 2 if packed else f
        ab = jax.ShapeDtypeStruct((rung, cols), self.train_set.binned.dtype)
        kk = jax.ShapeDtypeStruct((), jnp.int32)
        k = self.num_tree_per_iteration
        if engine == "level":
            lvt_a, lv_a = self._abstractify(
                (self._level_state(c, depth), st.leaf_value))
            return predict_raw_level.lower(
                ab, lvt_a, lv_a, kk, num_class=k, depth=max(1, depth),
                tbatch=tb_cfg, any_cat=self._pred_any_cat, packed=packed)
        if engine != "walk":
            raise ValueError(f"unknown serving engine {engine!r} "
                             "(walk|level)")
        st_a, nan_abs, cat_abs = self._abstractify((st, nan_a, cat_a))
        return predict_raw_batched.lower(
            ab, st_a, nan_abs, cat_abs, kk, num_class=k,
            depth=depth_bucket(depth), tbatch=tb_cfg,
            any_cat=self._pred_any_cat, packed=packed)

    # -- compact (physically partitioned) serial path ------------------------
    def _setup_compact_state(self) -> None:
        """Build the packed row-record arrays for the compact grower
        (ops/grower_compact.py). Extras carried through every partition:
        [scores(K), objective label, objective weight?, original row id]."""
        obj = self.objective
        n = self.num_data
        n_shards = (len(self.mesh.devices.ravel())
                    if self.mesh is not None else 1)
        rows_a_shard = -(-n // n_shards)
        if rows_a_shard >= (1 << 24):
            # what is still f32: the shard-local raw-count histograms that
            # drive the partition offsets, exact only below 2^24 rows a
            # shard (ops/compact.py). Row ids and the counts that cross
            # shards are integers and bound nothing below 2^31.
            raise RuntimeError(
                f"tpu_grower=compact supports up to 2^24 - 1 rows a shard "
                f"(f32 shard-local counts drive the partition offsets); "
                f"this run holds {rows_a_shard} a shard over "
                f"{n_shards}. Use tree_learner=data over more devices or "
                "tpu_grower=masked")
        k = self.num_tree_per_iteration
        has_w = obj.weight is not None
        # extras: [scores(K), grads(K-1 extra pairs for multiclass), label,
        # weight?, rowid]. For K>1 the per-class gradients are computed once
        # per iteration (reference: GBDT::Boosting before the class-tree
        # loop, gbdt.cpp:220) and must ride the permutations of earlier
        # same-iteration trees, so they live in carried columns.
        self._cx_grads = k if k > 1 else None
        gcols = 2 * k if k > 1 else 0
        e = k + gcols + 1 + (1 if has_w else 0) + 1
        # pack4 TRAINING (reference: the 4-bit dense bin store,
        # src/io/dense_bin.hpp DenseBin<true>): when every STORED column
        # realizes <= 16 bins AND the shape-stable histogram width fits a
        # nibble, the work/scratch bin columns nibble-pack — the streamed
        # bin bytes (the fused kernel's dominant HBM traffic) halve, and
        # every consumer unpacks per block/nibble at its read site
        pack4_train = False
        if bool(self.config.get("tpu_bin_pack4", False)):
            from ..io.dataset import pack4_train_eligible
            nb_max = int(np.asarray(self.num_bins_arr).max())
            if pack4_train_eligible(np.asarray(self.num_bins_arr),
                                    int(self.grower_params.num_bins)):
                pack4_train = True
            else:
                log.warning(
                    "tpu_bin_pack4=true: training keeps u8 bin columns — "
                    "nibble packing needs every stored column to realize "
                    f"<= 16 bins and max_bin <= 15 (histogram width "
                    f"{int(self.grower_params.num_bins)}, widest column "
                    f"{nb_max})")
        layout = RowLayout(num_features=int(self.binned.shape[1]),
                           num_extra=e, packed4=pack4_train)
        self._cx_label = k + gcols
        self._cx_weight = k + gcols + 1 if has_w else None
        self._cx_rowid = e - 1
        gp = self.grower_params
        if pack4_train != gp.bin_pack4:
            gp = gp._replace(bin_pack4=pack4_train)
            self.grower_params = gp
        force_efb_fused = os.environ.get("LGBM_TPU_FORCE_FUSED_EFB", "") == "1"
        if os.environ.get("LGBM_TPU_FUSED_DUAL", "") == "0":
            gp = gp._replace(fused_dual=False)
            self.grower_params = gp
        if os.environ.get("LGBM_TPU_FUSED_HIST_DEBUG", ""):
            hd = os.environ["LGBM_TPU_FUSED_HIST_DEBUG"]
            log.warning(f"LGBM_TPU_FUSED_HIST_DEBUG={hd}: fused kernel "
                        "histogram work altered - results are INVALID "
                        "(timing bisect)")
            gp = gp._replace(fused_hist_debug=hd)
            self.grower_params = gp
        if gp.fused_block and gp.efb_virtual and gp.fused_dual \
                and not force_efb_fused:
            # HISTORY: through round 4 the dual-residency kernel faulted
            # the TPU worker on EFB-bundled deep trees (F=532 bundle
            # columns, bs=64, 255 leaves). Round 5's in-kernel DMA-base
            # clamps fixed the fault — the hardened dual path now trains
            # the repro shape to completion with leaf counts exactly
            # matching an independent re-routing (scripts/
            # check_leaf_counts.py) — but bundled data stays on the
            # copy-back variant (round-3 design, ~1/3 more DMA per split,
            # measured within noise of dual at this shape) for one more
            # round of soak. LGBM_TPU_FORCE_FUSED_EFB=1 opts into dual.
            log.info("EFB-bundled dataset: using the copy-back fused "
                     "kernel variant")
            gp = gp._replace(fused_dual=False)
            self.grower_params = gp
        # record-width context for the registry's scoped-VMEM clamp:
        # kept so reset_parameter can re-run the SAME clamp when a
        # mid-run config change re-resolves the engine knobs
        from ..engines import registry as engine_registry
        self._fused_clamp_ctx = {
            "num_cols": layout.num_cols,
            "num_features": layout.num_features,
            "num_bins": int(self.grower_params.num_bins),
        }
        if gp.fused_block:
            # kernel scoped-VMEM buffers scale with block_size * num_cols,
            # the batched-M pending ring with hist_mbatch * block_size,
            # and the histogram accumulator with num_cols * num_bins; the
            # registry-owned clamp scales the block down for wide records
            # / deep rings / many feature groups (trading the depth
            # for the block where nobody named a depth) and falls back to
            # the XLA walk when the histogram alone would blow the ~16MB
            # scoped limit
            resolved_bs, resolved_depth = engine_registry.fit_fused_flush(
                self._engine_resolution, layout.num_cols,
                int(self.grower_params.num_bins), layout.num_features,
                env_override=os.environ.get("LGBM_TPU_FUSED_BS", ""))
            if not resolved_bs:
                # the XLA walk's segment_histogram is a standalone
                # engine: it keeps their depth, not the fused kernel's
                resolved_depth = engine_registry.resolve_mbatch(self.config)
            if (resolved_bs, resolved_depth) != (gp.fused_block,
                                                gp.hist_mbatch):
                gp = gp._replace(fused_block=resolved_bs,
                                 hist_mbatch=resolved_depth)
                self.grower_params = gp
        # the fused kernel's aligned block writes may overrun a segment end
        # by up to one block + one alignment tile
        pad = max(gp.part_block, gp.hist_block, gp.fused_block + 32)
        # padded rows (mesh row-count alignment) start permanently out of
        # bag: zero count weight, zero gradients
        valid = getattr(self, "_valid_row_mask", None)

        def pack(binned, scores, label, weight, cnt, first_row):
            """One device's rows as packed records, in place on that
            device: [rows + pad, C] u8. The carried row id is the row's
            index in the dataset, an int32 from ``first_row`` up."""
            rows = binned.shape[0]
            zeros = jnp.zeros((rows,), jnp.float32)
            parts = [scores]
            if gcols:
                parts.append(jnp.zeros((gcols, rows), jnp.float32))
            parts.append(label[None, :])
            if weight is not None:
                parts.append(weight[None, :])
            if cnt is None:
                cnt = jnp.ones((rows,), jnp.float32)
            rid = first_row + jnp.arange(rows, dtype=jnp.int32)
            return pack_rows(binned, zeros, zeros, cnt,
                             jnp.concatenate(parts, axis=0), layout,
                             pad_rows=pad, row_id=rid)

        if self.mesh is not None:
            # per-shard layout: each shard's rows sit in a contiguous block
            # followed by its own `pad` overrun rows, so the per-shard
            # partition walks never touch a neighbour shard. Each device
            # packs the rows it already holds: no array of all rows is
            # ever on one device.
            from jax.sharding import PartitionSpec as P
            from ..parallel.mesh import DATA_AXIS, row_sharding
            S = n_shards
            nl = n // S
            row1, row2 = P(DATA_AXIS), P(DATA_AXIS, None)

            def pack_shard(binned, scores, label, weight, cnt):
                work = pack(binned, scores, label, weight, cnt,
                            jax.lax.axis_index(DATA_AXIS) * nl)
                return work, jnp.zeros_like(work)

            rows_of = functools.partial(jax.device_put,
                                        device=row_sharding(self.mesh))
            with span("shard_rows"):
                work, scratch = jax.jit(jax.shard_map(
                    pack_shard, mesh=self.mesh,
                    in_specs=(row2, P(None, DATA_AXIS), row1,
                              row1 if has_w else None,
                              row1 if valid is not None else None),
                    out_specs=(row2, row2), check_vma=False))(
                    self.binned, self.train_score, rows_of(obj.label),
                    rows_of(obj.weight) if has_w else None, valid)
            shards = {"S": S, "nl": nl, "pad_rows": pad}
        else:
            work = pack(self.binned, self.train_score, obj.label,
                        obj.weight, valid, 0)
            scratch = jnp.zeros_like(work)
            shards = {"S": 1, "nl": n, "pad_rows": pad}
        self._compact = {
            "layout": layout,
            "work": work,
            "scratch": scratch,
            "step": None,
            "epoch": 0,        # bumped per grown tree; keys the perm cache
            "perm_epoch": -1,
            "perm": None,
            **shards,
        }

    def _rank_grads_fn(self):
        """Jitted: bounded objective gradients for non-row-elementwise
        objectives (lambdarank), returned in the compact grower's CURRENT
        permuted row order, by the carried row-id column — no host round
        trip (reference: the rank objective always sees original
        query-contiguous rows, rank_objective.hpp:25)."""
        c = self._compact
        if c.get("rank_grad_fn") is None:
            obj = self.objective
            # position-bias objectives update host state (pos_biases) inside
            # get_gradients — run those eagerly, never under jit
            eager = (getattr(obj, "is_stochastic", False)
                     or getattr(obj, "positions", None) is not None)
            # an objective that computes in an order of its own takes the
            # carried row ids and composes them with its index; one with
            # state in row order (position biases) or no order of its own
            # (rank_xendcg) gets its rows scattered to row order and its
            # gradients gathered back
            in_order = not eager and hasattr(obj, "gradients_in_order")

            def fn(work, scores_cur, by_length=None):
                rid = self._compact_row_ids(work)
                if in_order:
                    with (obj.bound_layout(by_length)
                          if by_length is not None
                          else contextlib.nullcontext()):
                        return obj.gradients_in_order(scores_cur[0], rid)
                s_orig = jnp.zeros_like(scores_cur).at[:, rid].set(scores_cur)
                g, h = obj.get_gradients(s_orig[0])
                return g[rid], h[rid]

            c["rank_grad_fn"] = fn if eager else jax.jit(fn)
            # the jitted program takes the objective's layout (the queries
            # by length class) as an argument, not as its constants
            c["rank_grad_layout"] = obj.layout_arrays() if in_order else None
            if in_order:
                self._count_rank_moves(c)
        return c["rank_grad_fn"]

    def _count_rank_moves(self, c) -> None:
        """What the gradient program moves by index, read from its jaxpr
        when it is built (``analysis/jaxpr.indexed_moves``; the trace is
        the one the first call would make, jit keeps it): the objective's
        ``rank_counters``, which every ``iteration`` event carries, gain
        the program's gathers, scatters and sorts and the indexed accesses
        a document."""
        from ..analysis.jaxpr import indexed_moves
        counters = self.objective.rank_counters
        moves = indexed_moves(jax.make_jaxpr(c["rank_grad_fn"])(
            c["work"], self.train_score, c["rank_grad_layout"]))
        docs = counters["rank_docs"]
        moved = sum(m["accesses"] for m in moves)
        counters.update(
            {f"rank_{op}s": sum(m["op"] == op for m in moves)
             for op in ("gather", "scatter", "sort")},
            rank_moved_per_doc=moved / docs if docs else 0.0)

    def _compact_rows(self, work):
        """The row records in current order, per-shard pad rows stripped."""
        c = self._compact
        S, nl, pr = c["S"], c["nl"], c["pad_rows"]
        if S > 1:
            return work.reshape(S, nl + pr, -1)[:, :nl].reshape(S * nl, -1)
        return work[:self.num_data]

    def _compact_extra(self, work, i):
        """The four bytes of extra column ``i`` of the row records in
        current order, [N, 4] u8, per-shard pad rows stripped (the columns
        are cut first: the records whole are gigabytes)."""
        c = self._compact
        S, nl, pr = c["S"], c["nl"], c["pad_rows"]
        off = c["layout"].extra_off + 4 * i
        col = work[:, off:off + 4]
        if S > 1:
            return col.reshape(S, nl + pr, 4)[:, :nl].reshape(S * nl, 4)
        return col[:self.num_data]

    def _compact_row_ids(self, work):
        """The carried row ids in current order, [N] int32: each row's
        index in the dataset (the record's last extra column)."""
        from ..ops.compact import _u8_to_i32
        return _u8_to_i32(self._compact_extra(work, self._cx_rowid))

    def _compact_cols(self, work, *extra_idx):
        """Unpack selected extra f32 columns from the work array."""
        from ..ops.compact import _u8_to_f32
        return [_u8_to_f32(self._compact_extra(work, i)) for i in extra_idx]

    def _build_compact_step_fn(self):
        """One fused jitted step per tree on the compact path: recompute
        gradients in the current row order, write the per-tree columns, grow
        (partitioning rows), renew/shrink leaves, and update scores — a
        single XLA program, zero host syncs. The work/scratch buffers are
        donated (updated in place)."""
        from jax import lax
        from ..ops.compact import _f32_to_u8, _u8_to_f32, _u8_to_i32

        obj = self.objective
        renew = obj.renew_leaves
        layout = self._compact["layout"]
        gp = self.grower_params
        mesh = self.mesh
        if mesh is not None:
            from ..parallel.mesh import DATA_AXIS
            gp = gp._replace(axis_name=DATA_AXIS)
            # data-parallel histogram reduction: reduce-scatter over the
            # feature axis + tiny best-split all-gather instead of
            # all-reducing the full [F, B, 4] histogram (the reference's
            # actual protocol — ReduceScatter + SyncUpGlobalBestSplit,
            # data_parallel_tree_learner.cpp:223-300). EFB bundles and the
            # intermediate monotone method scan across features a shard
            # would not own, so they keep the all-reduce.
            sc_cfg = os.environ.get(
                "LGBM_TPU_HIST_SCATTER",
                str(self.config.get("tpu_hist_scatter", "auto"))).lower()
            n_sh = len(mesh.devices.ravel())
            sc_able = (n_sh > 1 and gp.efb_virtual == 0
                       and not gp.mono_intermediate)
            if sc_cfg in ("on", "1", "true") and not sc_able:
                why = ("a single-shard mesh has nothing to scatter"
                       if n_sh <= 1 else
                       "EFB bundles / monotone intermediate need "
                       "cross-feature histogram access")
                log.warning(f"tpu_hist_scatter=on: {why}; using the "
                            "full histogram all-reduce")
            if sc_cfg not in ("off", "0", "false") and sc_able:
                gp = gp._replace(hist_scatter=n_sh)
        k_total = self.num_tree_per_iteration
        # per-shard rows derive from the work buffer's SHAPE at trace
        # time (rows = work.shape[0] - the static block-overrun pad), not
        # from a baked closure int: the spmd flight check AOT-relowers
        # this same step at the full pod row count (aot_lower_program),
        # and every row-proportional quantity must follow the abstract
        # argument shapes
        pr = self._compact["pad_rows"]   # per-shard overrun pad (static)
        n_real_g = self._n_real
        rid_off = (self._compact["layout"].extra_off + 4 * self._cx_rowid)
        # rung-sized leaf arrays under the step ladder (see _build_step_fn)
        max_leaves = gp.num_leaves
        num_bins_arr = self.num_bins_arr
        nan_bin_arr = self.nan_bin_arr
        has_nan_arr = self.has_nan_arr
        is_cat_arr = self.is_cat_arr
        mono_types = self._mono_types
        inter_sets = self._inter_sets
        cegb_coupled = self._cegb_coupled
        use_cegb = self._use_cegb
        use_quant = self._use_quant
        quant_renew = use_quant and self._quant_renew
        if quant_renew and k_total > 1:
            # multiclass renewal needs iteration-start gradients, which are
            # not carried post-permutation; masked grower supports it
            log.warning("quant_train_renew_leaf with num_class>1 is only "
                        "supported by tpu_grower=masked; skipping renewal")
            quant_renew = False
        quant_bins = self._quant_bins
        quant_stoch = self._quant_stochastic
        # quantized-gradient INT histogram path (the int8 MXU speed lever):
        # grad/hess columns carry integer codes, histograms accumulate
        # int8 x int8 -> int32 and dequantize at the split scan. Requires
        # codes that survive the {0,1} bag multiply as integers — GOSS
        # amplifies sampled rows' gradients by a non-integer factor, and
        # multiclass carries per-class gradients whose shared scale would
        # need cross-step plumbing; both keep the dequantized-f32 shim.
        # Overflow bound: |hess code| <= quant_bins and the cross-shard
        # psum sums over GLOBAL rows, so a near-constant feature's root
        # bin holds up to num_data * quant_bins — that must stay inside
        # int32 (the per-shard 2^24 row cap alone does not bound the
        # reduced sums on many shards).
        quant_int = (use_quant and k_total == 1 and quant_bins <= 127
                     and self.num_data * quant_bins < (1 << 31)
                     and not isinstance(self.sample_strategy, GOSSStrategy))
        if use_quant and k_total == 1 and not quant_int \
                and self.num_data * quant_bins >= (1 << 31):
            log.warning(
                f"use_quantized_grad: num_data*num_grad_quant_bins = "
                f"{self.num_data}*{quant_bins} exceeds the int32 histogram "
                "range; using the dequantized-f32 histogram path")
        if quant_int:
            gp = gp._replace(quant_hist=True, quant_max=quant_bins + 1)
            # per-leaf bit-width narrowing (reference: GetHistBitsInLeaf,
            # gradient_discretizer.cpp — renewed as leaves shrink): leaves
            # whose code sums fit the packing radix take the packed-pair
            # engine at HALF the contraction work, selected per leaf by a
            # lax.cond in the compact grower (ops/grower_compact.py
            # seg_hist). It rides the XLA segment-histogram walk — the
            # fused Mosaic kernel histograms in-kernel on the int8 MXU
            # path, where s32 accumulation is native and narrowing buys
            # nothing.
            from ..ops.histogram import narrow_chunk_rows
            bits_cfg = int(self.config.get("tpu_quant_hist_bits", 0) or 0)
            if bits_cfg not in (0, 16, 32):
                log.warning(f"tpu_quant_hist_bits={bits_cfg} is not one of "
                            "0 (auto) | 16 | 32; using 32-bit accumulation")
                bits_cfg = 32
            narrow_able = (narrow_chunk_rows(quant_bins + 1) > 0
                           and gp.fused_block == 0)
            if bits_cfg == 16 and not narrow_able:
                log.warning(
                    "tpu_quant_hist_bits=16 needs the XLA segment-"
                    "histogram walk (tpu_fused=off) and a "
                    "num_grad_quant_bins small enough for the packing "
                    "radix; keeping 32-bit accumulation")
            if bits_cfg == 16 and narrow_able:
                gp = gp._replace(quant_narrow=True)
            # auto (bits_cfg == 0) stays on the int8 -> int32 engine: the
            # packed-pair engine's exactness radix caps its row chunks at
            # narrow_chunk_rows (a few hundred), and the measured CPU
            # sweep (BENCH_SHAPES layout_sweep) shows the chunking
            # overhead eats the halved channel count at B <= 64 while
            # int8 already beats the f32 einsum outright. Narrow is the
            # measured opt-in until a backend's sweep row says otherwise.
        # the booster's GrowerParams say what the step runs (an engine
        # note reads quant_hist there), and every iteration event too
        self.grower_params = self.grower_params._replace(
            quant_hist=gp.quant_hist, quant_max=gp.quant_max,
            quant_narrow=gp.quant_narrow)
        self._note_quant_path(quant_int, quant_renew)
        self._note_fused_path(gp.fused_block > 0)
        const_hess = bool(getattr(obj, "is_constant_hessian", False))
        feature_contri = self._feature_contri
        efb = self._efb
        sc_off = layout.extra_off            # K score columns live first
        lbl_off = layout.extra_off + 4 * self._cx_label
        w_off = (layout.extra_off + 4 * self._cx_weight
                 if self._cx_weight is not None else None)

        def col(work, off):                  # [n] f32 from 4 u8 columns
            return _u8_to_f32(work[:work.shape[0] - pr, off:off + 4])

        def scores_of(work):                 # [K, n] f32
            nn = work.shape[0] - pr
            raw = work[:nn, sc_off:sc_off + 4 * k_total]
            return _u8_to_f32(raw.reshape(nn, k_total, 4)).T

        gx_off = (layout.extra_off + 4 * self._cx_grads
                  if self._cx_grads is not None else None)

        ext_grads = getattr(self, "_ext_grads", False)

        def step(work, scratch, scores, bag_w, use_stored_bag, feat_mask,
                 shrinkage, bynode_key, cegb_used, quant_key, extra_key,
                 leaf_budget, depth_budget, ext_g=None, ext_h=None, *, k):
            n = work.shape[0] - pr           # per-shard rows (trace-static)
            pad_n = pr

            w_col = jnp.where(use_stored_bag, col(work, layout.cnt_off),
                              bag_w)
            if mesh is not None and self.num_data > n_real_g:
                # mesh row-count padding: pad rows (row id >= n_real) must
                # stay permanently out of bag even when a fresh bag draws
                # them — their label/score bytes are meaningless
                w_col = w_col * (_u8_to_i32(
                    work[:n, rid_off:rid_off + 4]) < n_real_g)
            label = col(work, lbl_off)
            weight = col(work, w_off) if w_off is not None else None
            class_grads = []
            quant_scales = None
            if ext_grads:
                # gradients arrive pre-computed in the CURRENT row order
                # (lambdarank couples rows of a query; _rank_grads_fn)
                g_k, h_k = ext_g, ext_h
            elif k_total == 1:
                g, h = _bound_gradients(obj, k_total, scores, label, weight)
                if quant_int:
                    # integer-code path: the grad/hess columns carry the
                    # discretizer CODES (exact small ints in f32 lanes) and
                    # the per-iteration scales flow to the split scan as
                    # traced scalars — the histogram pipeline runs
                    # int8 x int8 -> int32 end to end
                    qk = quant_key
                    if gp.axis_name is not None:
                        # shard-independent stochastic rounding draws
                        qk = jax.random.fold_in(
                            qk, lax.axis_index(gp.axis_name))
                    qg, qh, g_s, h_s = _discretize_gradients(
                        g, h, qk, quant_bins, quant_stoch, const_hess,
                        axis_name=gp.axis_name)
                    g, h = qg, qh
                    quant_scales = (g_s, h_s)
                elif use_quant:
                    g, h = _quantize_gradients(
                        g, h, quant_key, quant_bins, quant_stoch, const_hess)
                g_k, h_k = g[0], h[0]
            elif k == 0:
                # all K class gradients once per iteration, from the
                # iteration-start scores (reference: GBDT::Boosting runs
                # before the per-class tree loop, gbdt.cpp:220); stored in
                # carried columns so later trees see them permutation-aligned
                g, h = _bound_gradients(obj, k_total, scores, label, weight)
                if use_quant:
                    g, h = _quantize_gradients(
                        g, h, quant_key, quant_bins, quant_stoch, const_hess)
                g_k, h_k = g[0], h[0]
                class_grads = ([g[j] for j in range(k_total)]
                               + [h[j] for j in range(k_total)])
            else:
                g_k = col(work, gx_off + 4 * k)
                h_k = col(work, gx_off + 4 * (k_total + k))
            # grad/hess/cnt, the K score columns, and (at k=0) the per-class
            # gradient columns are CONTIGUOUS lanes, written once a tree.
            # As XLA's lane-slice update of the u8 records the write cost
            # 35 ms an iteration at 10.5M rows (a row-major copy of the
            # packed operand, a select, an update of the whole array: 2.5
            # ns a row, ten times its roofline). Where the step runs the
            # fused kernel, record_write streams each record through VMEM
            # once and writes the same bytes in place (PERF.md, PR 40)
            cols = [g_k * w_col, h_k * w_col, w_col]
            # scores are authoritative outside the work array; write all K
            # columns fresh so they ride the partition correctly
            cols += [scores[j] for j in range(k_total)]
            cols += class_grads
            if gp.fused_block:
                work = record_write(
                    work, jnp.stack([jnp.pad(v, (0, pad_n)) for v in cols]),
                    layout.grad_off, interpret=gp.fused_interpret)
            else:
                packed = jnp.concatenate(
                    [_f32_to_u8(jnp.pad(v, (0, pad_n))) for v in cols],
                    axis=1)
                work = work.at[:, layout.grad_off:
                               layout.grad_off + 4 * len(cols)].set(packed)

            (tree, row_leaf, work, scratch, leaf_start,
             leaf_nrows) = grow_tree_compact(
                work, scratch, num_bins_arr, nan_bin_arr, has_nan_arr,
                is_cat_arr, feat_mask, layout, gp, n,
                mono_types, inter_sets, bynode_key, cegb_coupled, cegb_used,
                extra_key, feature_contri, efb, quant_scales=quant_scales,
                leaf_budget=leaf_budget, depth_budget=depth_budget)
            if use_cegb:
                cegb_used = _tree_used_features(tree, layout.num_features,
                                                cegb_used)

            leaf_value = tree.leaf_value
            if renew:
                residual = col(work, lbl_off) - scores_of(work)[k]
                wts = (col(work, layout.cnt_off) != 0.0).astype(jnp.float32)
                if w_off is not None:
                    wts = wts * col(work, w_off)
                renewed = renew_leaf_quantile(
                    residual, wts, row_leaf, max_leaves,
                    float(obj.renew_alpha))
                live = jnp.arange(max_leaves) < tree.num_leaves
                leaf_value = jnp.where(live, renewed, leaf_value)

            if quant_renew:
                # TRUE gradients from carried label/score columns, summed
                # per contiguous leaf segment via cumsum differences
                # (reference: RenewIntGradTreeOutput)
                with span("quant_renew"):
                    tg, th = _bound_gradients(
                        obj, k_total, scores_of(work),
                        col(work, lbl_off),
                        col(work, w_off) if w_off is not None else None)
                    wq = col(work, layout.cnt_off)
                    tgk = tg[k] * wq
                    thk = th[k] * wq
                    csg = jnp.concatenate([jnp.zeros(1), jnp.cumsum(tgk)])
                    csh = jnp.concatenate([jnp.zeros(1), jnp.cumsum(thk)])
                    ends = jnp.minimum(leaf_start + leaf_nrows, n)
                    sums_g = csg[ends] - csg[jnp.minimum(leaf_start, n)]
                    sums_h = csh[ends] - csh[jnp.minimum(leaf_start, n)]
                    if mesh is not None:
                        from ..parallel.mesh import DATA_AXIS
                        sums_g = jax.lax.psum(sums_g, DATA_AXIS)
                        sums_h = jax.lax.psum(sums_h, DATA_AXIS)
                    from ..ops.split import leaf_output as _lo
                    live = jnp.arange(max_leaves) < tree.num_leaves
                    leaf_value = jnp.where(
                        live, _lo(sums_g, sums_h, gp.split_params()),
                        leaf_value)
            lv = jnp.where(tree.num_nodes > 0, leaf_value, 0.0) * shrinkage
            tree = tree._replace(
                leaf_value=lv,
                internal_value=tree.internal_value * shrinkage)
            _, row_delta = segments_to_leaf_vectors(
                leaf_start, leaf_nrows, lv, n)
            sc = scores_of(work).at[k].add(row_delta)
            return tree, work, scratch, sc, cegb_used

        if mesh is None:
            jitted = jax.jit(step, donate_argnums=(0, 1),
                             static_argnames=("k",))
            if os.environ.get("LGBM_TPU_COMM_ACCOUNTING", "") == "1":
                # same key scheme as the mesh dispatch below so hlo_check
                # addresses the serial/compact step uniformly
                return self._comm_capture(
                    jitted, lambda kw: f"compact_step_k{kw.get('k', 0)}")
            return jitted

        # data-parallel: the whole per-tree step runs per shard under
        # shard_map — shard-local partitions, psum-ed histograms inside
        # grow_tree_compact. Trees replicate bit-identically because every
        # shard scans the same psum-ed histograms (reference: all ranks apply
        # the same SyncUpGlobalBestSplit decision, parallel_tree_learner.h)
        from jax.sharding import PartitionSpec as P
        from ..parallel.mesh import DATA_AXIS
        row2 = P(DATA_AXIS, None)
        krow = P(None, DATA_AXIS)
        rep = P()
        in_specs = (row2, row2, krow, P(DATA_AXIS), rep, rep, rep, rep,
                    rep, rep, rep, rep, rep)
        if ext_grads:
            in_specs = in_specs + (P(DATA_AXIS), P(DATA_AXIS))
        # outputs: (tree pytree — replicated, work, scratch, scores,
        # cegb_used); specs are pytree prefixes
        out_specs = (rep, row2, row2, krow, rep)
        fns = {}

        def dispatch(*args, k):
            if k not in fns:
                jitted = jax.jit(
                    jax.shard_map(functools.partial(step, k=k), mesh=mesh,
                                  in_specs=in_specs, out_specs=out_specs,
                                  check_vma=False),
                    donate_argnums=(0, 1))
                if os.environ.get("LGBM_TPU_COMM_ACCOUNTING", "") == "1":
                    jitted = self._comm_capture(jitted, f"compact_step_k{k}")
                elif k == 0:
                    self._count_collectives(jitted, args)
                fns[k] = jitted
            return fns[k](*args)

        return dispatch

    def _count_collectives(self, jitted, args) -> None:
        """What every ``iteration`` event of a data-parallel run carries
        beside the update's counters (``_obs_iteration_tick``): the
        shards, a shard's rows, and the collective instructions of the
        step's compiled text with their bytes as
        ``analysis/hlo.collective_bytes`` counts them, each instruction
        once whatever loop it stands in. Read once, when the step is
        built: the compile is the one the first call would make (jit
        keeps the lowering and its executable), the rest host integers."""
        from ..analysis.hlo import collective_bytes
        if any(isinstance(a, jax.core.Tracer)
               for a in jax.tree_util.tree_leaves(args)):
            return          # the step traced into a caller's program
        found = collective_bytes(jitted.lower(*args).compile().as_text())
        c = self._compact
        self._mesh_counters = {
            "shards": c["S"], "rows_per_shard": c["nl"],
            "collectives": found["count"],
            "collective_bytes": found["total"]}

    def _compact_perm(self) -> np.ndarray:
        """Current row permutation (original index per position), cached per
        grown tree — used to reorder host-side metric arrays."""
        c = self._compact
        if c["perm_epoch"] != c["epoch"]:
            c["perm"] = np.asarray(
                self._compact_row_ids(c["work"])).astype(np.int64)
            c["perm_epoch"] = c["epoch"]
        return c["perm"]

    def _step_state0(self, x: jax.Array) -> jax.Array:
        """Initial value of a small state array the train step carries
        from one iteration to the next. Under a mesh the step hands it
        back replicated over the mesh, so it starts there too: fed
        uncommitted, the second iteration's differently-typed input
        re-lowers (and on the chip re-compiles) the whole step program."""
        if self.mesh is None:
            return x
        from ..parallel.mesh import replicated
        return jax.device_put(x, replicated(self.mesh))

    def _cegb_state(self) -> jax.Array:
        if self._cegb_used is None:
            self._cegb_used = self._step_state0(jnp.zeros(
                (int(self.binned.shape[1])
                 + self.grower_params.efb_virtual,), bool))
        return self._cegb_used

    def _cegb_charged_state(self) -> jax.Array:
        """Lazy-penalty charged-rows bitmap, persisted across the whole
        model (reference: feature_used_in_data_ is filled once and never
        reset, cost_effective_gradient_boosting.hpp:62)."""
        if self._cegb_charged is None:
            if self._cegb_lazy is None:
                # unused placeholder the step passes through
                self._cegb_charged = self._step_state0(
                    jnp.zeros((1, 1), bool))
            else:
                self._cegb_charged = jnp.zeros(
                    (int(self.binned.shape[1]),
                     int(self.binned.shape[0])), bool)
        return self._cegb_charged

    def _compact_gradients(self):
        """Gradients in the current (permuted) row order, for GOSS ranking."""
        c = self._compact
        if c.get("grad_fn") is None:
            obj = self.objective
            k_total = self.num_tree_per_iteration

            def fn(scores, label, weight):
                return _bound_gradients(obj, k_total, scores, label, weight)

            c["grad_fn"] = jax.jit(fn) \
                if not getattr(self.objective, "is_stochastic", False) else fn
        label, = self._compact_cols(c["work"], self._cx_label)
        weight = (self._compact_cols(c["work"], self._cx_weight)[0]
                  if self._cx_weight is not None else None)
        return c["grad_fn"](self.train_score, label, weight)

    def _compact_shared_args(self, strat, mask):
        """What the trees of one compact iteration share among the step's
        arguments: (bag mask over the work rows, whether the bag is
        fresh, feature mask)."""
        c = self._compact
        # fresh == the strategy actually drew a new bag this iteration; a
        # reused (cached) bag must come from the stored sample-weight column,
        # which rode the partitions and is in the current row order — the
        # host-cached vector is not
        fresh = getattr(strat, "last_fresh", mask is not None)
        if mask is None:
            n = self.num_data  # bag vectors align with work rows (incl. pad)
            if self.mesh is None:
                mask = jnp.ones((n,), jnp.float32)
            else:
                # every row in bag: made once, a shard's rows on its own
                # device (a fresh [n] array an iteration would be made on
                # one device and sent round the mesh every time)
                if c.get("all_in_bag") is None:
                    from ..parallel.mesh import row_sharding
                    c["all_in_bag"] = jnp.ones(
                        (n,), jnp.float32, device=row_sharding(self.mesh))
                mask = c["all_in_bag"]
            fresh = self.iter_ == 0 or fresh
        if getattr(strat, "_amplify", None) is not None:
            mask = mask * strat._amplify
        return mask, fresh, self._feature_mask()

    def _train_one_iter_compact(self) -> bool:
        """Compact-path iteration (same contract as train_one_iter)."""
        self._boost_from_average()
        c = self._compact
        if c["step"] is None:
            with span("build_step"):
                c["step"] = self._build_compact_step_fn()
        strat = self.sample_strategy

        # GOSS ranks rows by gradient magnitude; compute in current order
        g = h = None
        if strat.is_hessian_change:
            g, h = self._dispatch("gradient", self._compact_gradients)
        # span `bag`: only where the strategy draws or reuses a bag
        with (span("bag") if strat.samples(self.iter_)
              else contextlib.nullcontext()):
            mask = strat.bag_mask(self.iter_, g, h)
        ext_args = ()
        if getattr(self, "_ext_grads", False):
            # lambdarank-style coupled gradients: computed once per
            # iteration in original query order, permuted to current order
            rank_grads = self._rank_grads_fn()
            ext_args = tuple(self._dispatch(
                "rank_grads", rank_grads, c["work"], self.train_score,
                c["rank_grad_layout"]))
        first_iter = self.num_total_trees < self.num_tree_per_iteration
        k_total = self.num_tree_per_iteration
        for k in range(k_total):
            # span `step_args`, one per tree: everything the host does
            # for the step's arguments, so that `step_dispatch` times the
            # call alone (the first tree's holds the iteration's shared
            # ones: the bag's mask and the feature mask)
            with span("step_args"):
                if k == 0:
                    mask, fresh, feat_mask = self._compact_shared_args(
                        strat, mask)
                # trees after the first in an iteration reuse the stored
                # bag (same bag for all trees of one iteration, like the
                # reference)
                use_stored = not (fresh and k == 0)
                args = (
                    c["work"], c["scratch"], self.train_score, mask,
                    jnp.asarray(use_stored), feat_mask,
                    jnp.float32(self.shrinkage_rate),
                    jax.random.fold_in(self._bynode_key,
                                       self.num_total_trees),
                    self._cegb_state(),
                    jax.random.fold_in(self._quant_key, self.iter_),
                    jax.random.fold_in(self._extra_key,
                                       self.num_total_trees),
                    *self._step_budget_args(), *ext_args)
            (tree, work, scratch, scores,
             self._cegb_used) = self._dispatch(
                "step_dispatch", c["step"], *args, k=k)
            c["work"], c["scratch"] = work, scratch
            c["epoch"] += 1
            self.train_score = scores
            self._update_valid_scores(tree, k)
            if first_iter and abs(self._init_scores[k]) > 1e-10:
                tree = tree._replace(
                    leaf_value=tree.leaf_value + self._init_scores[k])
            self._dev_trees.append((tree, self.shrinkage_rate))
            # NOTE: appends do NOT invalidate the device-tree cache — the
            # bucketed cache append-pads new trees in (mid-train predict
            # used to re-stack the whole model every iteration)
            # the pending list holds the tree now; without this frame's
            # reference the flush releases its device arrays under
            # `decode_trees`, not unnamed on the way out of here
            del tree

        self.iter_ += 1
        if len(self._dev_trees) >= k_total * self.stop_check_freq:
            return self._flush_trees()
        return False

    def add_valid(self, valid_set: BinnedDataset, name: str,
                  metrics: Sequence[Metric]) -> None:
        # the valid matrix must be in the SAME column space the booster
        # routes in: a bundle-layout mismatch (e.g. the valid rows hit a
        # feature conflict and stayed dense, or the train side unbundled)
        # would silently corrupt validation scores
        vb = getattr(valid_set, "bundle_info", None)
        if self._efb is not None:
            if vb is None or (valid_set.binned.shape[1]
                              != int(self.binned.shape[1])):
                raise ValueError(
                    f"validation set '{name}' is not in the training data's "
                    "EFB bundle layout (a feature conflict outside the "
                    "training rows?); rebuild both with enable_bundle=false")
        elif vb is not None:
            from ..io.efb import unbundle
            log.warning(f"validation set '{name}': unbundling to match the "
                        "unbundled training layout")
            dbins = np.array([m.default_bin for m in valid_set.mappers],
                             np.int32)
            valid_set.binned = unbundle(
                np.asarray(valid_set.binned), vb, dbins,
                valid_set.feature_num_bins())
            valid_set.bundle_info = None
        vs = _ValidSet(valid_set, self.num_tree_per_iteration, name,
                       mesh=self.mesh if self.tree_learner != "feature"
                       else None)
        if self._linear and valid_set.raw_data is None:
            raise ValueError(
                "linear_tree validation sets need raw data; create them "
                "from the training Dataset (create_valid) with "
                "free_raw_data=False or the linear_tree param set")
        for m in metrics:
            m.init(valid_set.metadata, valid_set.num_data)
        vs.metrics = list(metrics)
        self.valid_sets.append(vs)

    def set_train_metrics(self, metrics: Sequence[Metric]) -> None:
        for m in metrics:
            # multi-host: metrics need the GLOBAL gathered metadata
            m.init(getattr(self, "_global_md", None)
                   or self.train_set.metadata, self._n_real)
        self.train_metrics = list(metrics)

    # -- one boosting iteration ---------------------------------------------
    def _boost_from_average(self) -> None:
        """(reference: GBDT::BoostFromAverage, gbdt.cpp:319)"""
        if self.num_total_trees == 0 and not self._has_init_score \
                and self.objective is not None \
                and bool(self.config.get("boost_from_average", True)):
            for k in range(self.num_tree_per_iteration):
                init = self.objective.boost_from_score(k)
                if abs(init) > 1e-10:
                    self._init_scores[k] = init
                    self.train_score = self.train_score.at[k].add(init)
                    for vs in self.valid_sets:
                        vs.score = vs.score.at[k].add(init)
                    log.info(f"Start training from score {init:.6f}")

    def _gradients(self) -> Tuple[jax.Array, jax.Array]:
        """(reference: GBDT::Boosting, gbdt.cpp:220)"""
        if self._grad_fn is None:
            base = self.objective.get_gradients

            def named(*a, **kw):
                # span at trace time: the gradient program carries its
                # phase name into the device trace
                with span("gradient"):
                    return base(*a, **kw)

            fn = named
            if not getattr(self.objective, "is_stochastic", False):
                fn = jax.jit(named)
            self._grad_fn = fn
        score = self.train_score
        if self.num_tree_per_iteration == 1:
            g, h = self._grad_fn(score[0])
            return g[None, :], h[None, :]
        return self._grad_fn(score)

    def _efb_precheck(self, train_set, cfg, tree_learner) -> None:
        """Unbundle an EFB dataset when this configuration won't use the
        bundle-space compact grower (mirrors the can_compact conditions in
        _setup_train plus the bundle-incompatible knobs). Runs BEFORE device
        placement so every learner sees a plain dense matrix."""
        binfo = getattr(train_set, "bundle_info", None)
        if binfo is None:
            return
        obj = self.objective
        grower = str(cfg.get("tpu_grower", "auto")).lower()
        compact_possible = (
            tree_learner in ("serial", "data")
            and not self._multiproc
            and obj is not None
            and getattr(obj, "row_elementwise", True)
            and not getattr(obj, "is_stochastic", False)
            and int(train_set.max_num_bins) <= 256
            and float(cfg.get("pos_bagging_fraction", 1.0)) >= 1.0
            and float(cfg.get("neg_bagging_fraction", 1.0)) >= 1.0
            and not bool(cfg.get("bagging_by_query", False))
            and train_set.metadata.query_boundaries is None
            and not bool(cfg.get("linear_tree", False))
            and not str(cfg.get("forcedsplits_filename", "") or "")
            and grower != "masked"
            # a bundled dataset always routes to the compact grower under
            # grower=auto (see _setup_train), at any row count
            and grower in ("compact", "auto")
            and not (self.mesh is not None and obj.renew_leaves))
        knobs_ok = (
            cfg.get("monotone_constraints") is None
            and cfg.get("interaction_constraints") is None
            and cfg.get("feature_contri") is None
            and float(cfg.get("cegb_penalty_split", 0) or 0) == 0.0
            and cfg.get("cegb_penalty_feature_coupled") is None
            and (cfg.get("cegb_penalty_feature_lazy") is None
                 or not self._supports_lazy_cegb))
        if compact_possible and knobs_ok:
            return
        log.warning(
            "EFB bundles are not supported by this configuration; "
            "unbundling the dataset (set enable_bundle=false to skip "
            "bundling entirely)")
        from ..io.efb import unbundle
        dbins = np.array([m.default_bin for m in train_set.mappers],
                         np.int32)
        train_set.binned = unbundle(
            np.asarray(train_set.binned), binfo, dbins,
            train_set.feature_num_bins())
        train_set.bundle_info = None

    def _setup_efb(self, train_set: BinnedDataset) -> None:
        """Wire an EFB-bundled dataset (io/efb.py) into the learner.

        Scan space = stored columns + one VIRTUAL feature per bundled
        original (its histogram is synthesized from its bundle column's bin
        range, ops/split.py extend_hist_efb); routing space = stored columns
        (bundled splits carry a ready bitset). Tree arrays record ORIGINAL
        feature ids, so model text and raw-data prediction never see bundles
        (reference analogue: FeatureGroup keeps group bins while SplitInfo
        carries the real feature, include/LightGBM/feature_group.h)."""
        self._efb = None
        binfo = getattr(train_set, "bundle_info", None)
        if binfo is None:
            return
        if self.mesh is not None and self.tree_learner not in ("data",):
            raise ValueError(
                "EFB-bundled datasets support the serial and data-parallel "
                "learners; construct the Dataset with enable_bundle=false "
                f"for tree_learner={self.tree_learner}")
        bad = [name for flag, name in (
            (self._mono_types is not None, "monotone_constraints"),
            (self._inter_sets is not None, "interaction_constraints"),
            (self._use_cegb, "cegb penalties"),
            (self._feature_contri is not None, "feature_contri"),
            (self._forced_splits is not None, "forcedsplits"),
            (self._linear, "linear_tree"),
        ) if flag]
        if bad or not self._use_compact:
            # graceful fallback: bundling is lossless, so reconstruct the
            # dense binned matrix and train unbundled (reference analogue:
            # EFB is construction-time there too, but its learners all read
            # FeatureGroups; ours only the compact grower does)
            why = ", ".join(bad) if bad else "the masked grower"
            log.warning(f"EFB bundles are not supported with {why}; "
                        "unbundling the dataset (set enable_bundle=false to "
                        "skip bundling entirely)")
            from ..io.efb import unbundle
            dbins = np.array([m.default_bin for m in train_set.mappers],
                             np.int32)
            dense = unbundle(np.asarray(train_set.binned), binfo, dbins,
                             train_set.feature_num_bins())
            train_set.binned = dense
            train_set.bundle_info = None
            # rebuild the device matrix exactly as _setup_train placed it
            if self._pad:
                dense = np.pad(dense, ((0, self._pad), (0, 0)))
            if self.mesh is not None:
                from ..parallel.mesh import row_sharding_2d
                if self._multiproc:
                    self.binned = jax.make_array_from_process_local_data(
                        row_sharding_2d(self.mesh), dense)
                else:
                    self.binned = jax.device_put(dense,
                                                 row_sharding_2d(self.mesh))
            else:
                self.binned = jnp.asarray(dense)
            return
        C = binfo.n_columns
        mappers = train_set.mappers
        orig_nb = train_set.feature_num_bins()
        orig_nan = train_set.feature_nan_bins()
        orig_cat = train_set.feature_is_categorical()
        orig_has_nan = np.array(
            [m.missing_type == 2 and not m.is_categorical for m in mappers],
            bool)
        orig_dbin = np.array([m.default_bin for m in mappers], np.int32)
        nontrivial = np.array([not m.is_trivial for m in mappers], bool)
        bundled = np.nonzero(binfo.offset_of >= 0)[0]
        passthrough = np.nonzero(binfo.offset_of < 0)[0]
        Fb = len(bundled)

        def colv(vals, fill):
            vals = np.asarray(vals)
            v = np.full(C, fill, vals.dtype)
            v[binfo.col_of[passthrough]] = vals[passthrough]
            return v

        self.num_bins_arr = jnp.asarray(np.concatenate(
            [binfo.num_column_bins, orig_nb[bundled]]).astype(np.int32))
        self.nan_bin_arr = jnp.asarray(np.concatenate(
            [colv(orig_nan, 0), orig_nan[bundled]]).astype(np.int32))
        self.has_nan_arr = jnp.asarray(np.concatenate(
            [colv(orig_has_nan, False), np.zeros(Fb, bool)]))
        self.is_cat_arr = jnp.asarray(np.concatenate(
            [colv(orig_cat, False), np.zeros(Fb, bool)]))
        # bundle columns themselves never win a split
        self.base_feat_mask = np.concatenate(
            [colv(nontrivial, False), np.ones(Fb, bool)])
        orig_of_col = np.full(C, -1, np.int32)
        orig_of_col[binfo.col_of[passthrough]] = passthrough
        self._efb = tuple(jnp.asarray(a) for a in (
            np.concatenate([np.arange(C, dtype=np.int32),
                            binfo.col_of[bundled]]),          # col_of_ext
            np.concatenate([colv(orig_cat, False),
                            np.ones(Fb, bool)]),              # route_cat_ext
            np.concatenate([np.full(C, -1, np.int32),
                            binfo.offset_of[bundled]]),       # off_ext
            np.concatenate([np.zeros(C, np.int32),
                            orig_nb[bundled]]),               # nb_ext
            np.concatenate([np.zeros(C, np.int32),
                            orig_dbin[bundled]]),             # dbin_ext
            np.concatenate([orig_of_col,
                            bundled.astype(np.int32)]),       # orig_of_ext
        ))
        # per-ORIGINAL routing (valid scoring / DART / rollback replay)
        # and plain per-original arrays for prediction (prediction rows are
        # binned per ORIGINAL feature, never bundled)
        self._orig_nan_arr = jnp.asarray(orig_nan.astype(np.int32))
        self._orig_cat_arr = jnp.asarray(orig_cat)
        self._route_nan = self._orig_nan_arr
        self._route_cat = jnp.asarray(orig_cat | (binfo.offset_of >= 0))
        self._route_col = jnp.asarray(binfo.col_of.astype(np.int32))
        self._num_orig_features = train_set.num_total_features
        self.grower_params = self.grower_params._replace(
            efb_virtual=Fb, efb_bmax=int(orig_nb[bundled].max()))

    def _route_args(self):
        """(nan_bin, is_cat[, col_of]) arrays for route_one_tree."""
        if self._efb is not None:
            return (self._route_nan, self._route_cat, self._route_col)
        return (self.nan_bin_arr, self.is_cat_arr)

    def _feature_mask(self) -> jnp.ndarray:
        """Per-tree column sampling (reference: ColSampler, col_sampler.hpp)."""
        mask = self.base_feat_mask.copy()
        if self.feature_fraction < 1.0:
            used = np.where(mask)[0]
            keep = max(1, int(np.ceil(len(used) * self.feature_fraction)))
            chosen = self._feat_rng.choice(used, size=keep, replace=False)
            mask = np.zeros_like(mask)
            mask[chosen] = True
        return jnp.asarray(mask)

    def _masked_shared_args(self, mask, grad, hess):
        """What the trees of one masked-path iteration share among the
        step's arguments: (bag mask, feature mask, the gradients the
        grower histograms, the true gradients)."""
        if mask is None:
            mask = jnp.ones((self.num_data,), jnp.float32)
        if self._valid_row_mask is not None:
            mask = mask * self._valid_row_mask
        feat_mask = self._feature_mask()
        true_grad, true_hess = grad, hess
        if self._use_quant:
            # one global-scale quantization per iteration over all classes
            # (reference: DiscretizeGradients on the full k*N buffer)
            grad, hess = _quantize_gradients(
                grad, hess,
                jax.random.fold_in(self._quant_key, self.iter_),
                self._quant_bins, self._quant_stochastic,
                bool(getattr(self.objective, "is_constant_hessian", False)))
        return mask, feat_mask, grad, hess, true_grad, true_hess

    def train_one_iter(
        self,
        gradients: Optional[np.ndarray] = None,
        hessians: Optional[np.ndarray] = None,
    ) -> bool:
        """Train trees for one iteration; True when training should stop
        (reference: GBDT::TrainOneIter, gbdt.cpp:344)."""
        k = self.num_tree_per_iteration
        if self._use_compact:
            if gradients is not None or hessians is not None:
                if self._compact is not None:
                    raise RuntimeError(
                        "cannot switch to caller-supplied gradients after "
                        "compact training started; set tpu_grower=masked")
                # caller-supplied gradients arrive in the original row order
                self._use_compact = False
                # the masked grower does not fuse: the standalone depth
                from ..engines import registry as engine_registry
                self.grower_params = self.grower_params._replace(
                    hist_mbatch=engine_registry.resolve_mbatch(self.config))
                if self._engine_shape is not None:
                    self._engine_shape = self._engine_shape._replace(
                        compact=False)
            else:
                if self._compact is None:
                    with span("compact_setup"):
                        self._setup_compact_state()
                return self._train_one_iter_compact()
        if gradients is None or hessians is None:
            self._boost_from_average()
            grad, hess = self._dispatch("gradient", self._gradients)
        else:
            g_np = np.asarray(gradients, np.float32).reshape(k, self._n_real)
            h_np = np.asarray(hessians, np.float32).reshape(k, self._n_real)
            if self._pad:
                g_np = np.pad(g_np, ((0, 0), (0, self._pad)))
                h_np = np.pad(h_np, ((0, 0), (0, self._pad)))
            grad, hess = jnp.asarray(g_np), jnp.asarray(h_np)

        if self._valid_row_mask is not None:
            # zero padding-row gradients before GOSS ranks them
            grad = grad * self._valid_row_mask[None, :]
            hess = hess * self._valid_row_mask[None, :]
        if self._step_fn is None:
            with span("build_step"):
                self._step_fn = self._build_step_fn()
        strat = self.sample_strategy
        # span `bag`: only where the strategy draws or reuses a bag
        with (span("bag") if strat.samples(self.iter_)
              else contextlib.nullcontext()):
            mask = strat.bag_mask(self.iter_, grad, hess)
            grad, hess = strat.scale_grad_hess(mask, grad, hess)
        first_iter = self.num_total_trees < self.num_tree_per_iteration
        for cur_tree_id in range(k):
            # span `step_args`, one per tree (the first tree's holds what
            # the iteration's trees share), as on the compact path
            with span("step_args"):
                if cur_tree_id == 0:
                    (mask, feat_mask, grad, hess, true_grad,
                     true_hess) = self._masked_shared_args(mask, grad, hess)
                args = (
                    self.binned, self.train_score[cur_tree_id],
                    grad[cur_tree_id], hess[cur_tree_id], mask, feat_mask,
                    jnp.float32(self.shrinkage_rate),
                    jax.random.fold_in(self._bynode_key,
                                       self.num_total_trees),
                    self._cegb_state(),
                    true_grad[cur_tree_id], true_hess[cur_tree_id],
                    jax.random.fold_in(self._extra_key,
                                       self.num_total_trees),
                    self._cegb_charged_state(), *self._step_budget_args())
            (tree, row_leaf, new_score, self._cegb_used,
             self._cegb_charged) = self._dispatch(
                "step_dispatch", self._step_fn, *args)
            if self._linear:
                split_ok = self._linear_tree_iter(
                    tree, row_leaf, true_grad[cur_tree_id],
                    true_hess[cur_tree_id], mask, cur_tree_id, first_iter)
                self._linear_any_split = (
                    getattr(self, "_linear_any_split", False) or split_ok)
                continue
            self.train_score = self.train_score.at[cur_tree_id].set(new_score)
            # valid scores got the init at _boost_from_average already, so the
            # tree must be pushed through them BEFORE the bias fold
            self._update_valid_scores(tree, cur_tree_id)
            if first_iter and abs(self._init_scores[cur_tree_id]) > 1e-10:
                # fold the init score into the first tree's leaves, on device
                # (reference: Tree::AddBias, gbdt.cpp:417; also covers the
                # constant first tree, AsConstantTree(init), gbdt.cpp:430)
                tree = tree._replace(
                    leaf_value=tree.leaf_value + self._init_scores[cur_tree_id])
            self._dev_trees.append((tree, self.shrinkage_rate))

        self.iter_ += 1
        if self._linear:
            # all-constant iteration ends training (reference gbdt.cpp:440)
            if not getattr(self, "_linear_any_split", False):
                # same accounting as _flush_trees (reference gbdt.cpp:440):
                # pop the failed iteration unless it is the very first
                if len(self.models) > k:
                    del self.models[-k:]
                    # removal, not append: a cached stack may hold the
                    # popped trees (append-pad cannot repair deletions)
                    self._invalidate_device_trees()
                self.iter_ -= 1
                log.warning("Stopped training because there are no more "
                            "leaves that meet the split requirements")
                return True
            self._linear_any_split = False
            return False
        # stop-check + host materialization, batched to bound device->host
        # syncs (reference checks every iter, gbdt.cpp:440; one sync per
        # `stop_check_freq` iters here — each one drains the dispatch queue)
        if len(self._dev_trees) >= k * self.stop_check_freq:
            return self._flush_trees()
        return False

    @staticmethod
    def _dispatch(site, program, *args, **kwargs):
        """One call of one of the booster's jitted programs from the
        update loop, under the host span ``site`` (``step_dispatch``,
        ``gradient``, ``rank_grads``) and counted into the update's
        ``dispatches``. The
        span times the dispatch (and, on a first call, the trace and
        compile inside it), not the device's work: nothing here waits."""
        with span(site):
            bump("dispatches")
            return program(*args, **kwargs)

    def _obs_iteration_tick(self, seconds: float) -> None:
        """Per-update telemetry tick (called from Booster.update, inside
        its ``iteration`` span): one flight-ring event and, when
        ``tpu_metrics_path`` is armed, one JSONL record carrying
        CUMULATIVE phase-keyed compile counts and persistent-cache
        counters — host-only reads (python ints and the wall clock), so
        the steady-state 0-d2h guard holds with telemetry fully enabled.

        ``iteration`` is the count of completed updates (absolute, so
        resumed runs line up; the update's spans carry the ``iter_`` it
        started from, one less). ``seconds`` is the update's wall on the
        host: dispatch time, unless something in the update blocked on
        the device — ``flush_trees`` does every ``stop_check_freq``-th
        update, and then ``seconds`` holds the step's device time too.
        ``dispatches``, ``host_syncs`` and ``d2h_bytes`` are the update's
        counters (obs/spans.py); ``t1`` is now on the spans' clock.
        ``phase_s`` holds the seconds of every span closed inside this
        update, by name, and ``cpu_s`` the CPU seconds the thread was
        given in it (``spans.update_phases``). An update far slower than
        the booster's recent ones writes a ``slow_iteration`` record and
        a warning beside its event (``spans.SlowUpdates``)."""
        from ..analysis import guards
        from ..obs import flight
        counters = update_counters()
        phase_s, cpu_s = update_phases()
        slow = self._slow_updates.check(
            self.iter_, seconds, counters["host_syncs"], phase_s, cpu_s)
        # what a ranking objective's layout by query length makes the
        # gradient program compute (objectives.py): fixed at init
        counters.update(getattr(self.objective, "rank_counters", {}))
        # a data-parallel step's shards and collectives: fixed when the
        # step is built (_count_collectives)
        counters.update(getattr(self, "_mesh_counters", {}))
        # which histogram path a quantized step runs, and whether it
        # renews its leaves: fixed when the step is built
        counters.update(getattr(self, "_quant_counters", {}))
        # how many levels the fused kernel's one-hot has (0: kernel off),
        # the bytes its ring carries take a row byte, and whether
        # record_write writes the step's per-row columns
        counters.update(getattr(self, "_hist_counters", {}))
        flight.note("iteration", iteration=self.iter_,
                    seconds=round(seconds, 6), t1=time.perf_counter(),
                    phase_s=phase_s, cpu_s=cpu_s, **counters)
        if slow is not None:
            flight.note("slow_iteration", **slow)
            log.warning("slow_iteration " + json.dumps(slow))
        stream = getattr(self, "_metrics_stream", None)
        if stream is not None:
            stream.emit("iteration", iteration=self.iter_,
                        seconds=round(seconds, 6), phase_s=phase_s,
                        cpu_s=cpu_s, **counters,
                        compiles=guards.phase_compile_counts(),
                        cache=guards.global_cache_counts())

    def train_metrics_tree(self) -> Dict[str, Any]:
        """The live training-metrics tree the in-train Prometheus
        endpoint (``tpu_metrics_port`` under ``lgb.train``) serves:
        iteration progress, phase-keyed compile counters, persistent-
        cache counters, and the latest rank-stats aggregate (median /
        p99 / max over ranks, straggler flags) when sampling is armed.
        Host-only reads — scraping must not touch the device."""
        from ..analysis import guards
        tree = {
            "training": True,
            "iteration": self.iter_,
            "compiles": guards.phase_compile_counts(),
            "cache": guards.global_cache_counts(),
        }
        rs = getattr(self, "_rank_stats", None)
        if rs is not None:
            tree["rank_stats"] = rs.latest_tree()
        return tree

    def _linear_tree_iter(self, tree, row_leaf, grad_k, hess_k, mask,
                          cur_tree_id: int, first_iter: bool) -> None:
        """Host-orchestrated linear-leaf fitting + score updates for one tree
        (reference: LinearTreeLearner::CalculateLinear; CPU-only there too)."""
        from .linear import (add_bias_linear, fit_linear_leaves,
                             linear_leaf_outputs)
        host = HostTree(jax.device_get(tree), shrinkage=self.shrinkage_rate)
        if host.num_nodes == 0:
            host.num_leaves = 1
        raw = self.train_set.raw_data
        leaf_np = np.asarray(row_leaf)
        g_np = np.asarray(grad_k * mask)
        h_np = np.asarray(hess_k * mask)
        is_cat = np.asarray(self.is_cat_arr)
        fit_linear_leaves(host, raw, leaf_np, g_np, h_np, is_cat,
                          float(self.config.get("linear_lambda", 0.0)),
                          shrinkage=self.shrinkage_rate)
        delta = linear_leaf_outputs(host, raw, leaf_np)
        self.train_score = self.train_score.at[cur_tree_id].add(
            jnp.asarray(delta, jnp.float32))
        for vs in self.valid_sets:
            vleaf = route_one_tree(
                vs.binned, tree.split_feature, tree.split_bin,
                tree.cat_bitset, tree.default_left, tree.left_child,
                tree.right_child, tree.num_nodes, *self._route_args())
            vdelta = linear_leaf_outputs(
                host, vs.dataset.raw_data, np.asarray(vleaf)[: vs.n_real])
            vs.score = vs.score.at[cur_tree_id, : vs.n_real].add(
                jnp.asarray(vdelta, jnp.float32))
        if first_iter and abs(self._init_scores[cur_tree_id]) > 1e-10:
            init = self._init_scores[cur_tree_id]
            host.leaf_value = host.leaf_value + init
            add_bias_linear(host, init)
        self.models.append(host)
        return host.num_nodes > 0

    @property
    def num_total_trees(self) -> int:
        # under the trees mutex so a read-locked num_trees()/
        # current_iteration() never observes a mid-flush torn count
        # (a concurrent read-locked predict may be flushing)
        with self._trees_mu:
            return len(self.models) + len(self._dev_trees)

    def _flush_trees(self) -> bool:
        """Materialize pending device trees to host in one batched transfer;
        returns True if training should stop (an iteration produced no
        splittable leaf — reference: gbdt.cpp:440-450)."""
        with self._trees_mu:
            return self._flush_trees_locked()

    def _flush_trees_locked(self) -> bool:
        if not self._dev_trees:
            return False
        trees = [t for t, _ in self._dev_trees]
        shrinks = [s for _, s in self._dev_trees]
        # one batched device_get of all pending trees; deliberately NOT a
        # jnp.stack program — its shape would depend on the pending count and
        # recompile for every distinct flush size
        # span `flush_trees`: the one site where the update loop blocks on
        # the device (the fetch waits for the step that grew the trees)
        with span("flush_trees"):
            bump("host_syncs")
            multiproc = getattr(self, "_multiproc", False)
            if not multiproc:
                # the copies are asked for ahead of the wait, as
                # `device_get` alone would: they start when the step
                # ends, not a host round trip an array after it
                jax.copy_to_host_async(trees)
            # span `step_wait`: the wait for the step alone; what is
            # left of `flush_trees` is the copy of the trees' arrays
            with span("step_wait"):
                jax.block_until_ready(trees)
            if multiproc:
                # replicated device trees are not fully addressable across
                # processes; pull the local replica of each array
                host_trees = jax.tree.map(_to_host, trees)
            else:
                host_trees = jax.device_get(trees)
            bump("d2h_bytes", sum(
                leaf.nbytes for leaf in jax.tree.leaves(host_trees)))
        # the fetched trees' device arrays go with their last references:
        # this frame's here, the pending list's in `_decode_trees`
        del trees
        with span("decode_trees"):
            return self._decode_trees(host_trees, shrinks)

    def _decode_trees(self, host_trees, shrinks) -> bool:
        """The fetched trees into ``self.models``, and the pending device
        trees released; True if training should stop (the last flushed
        iteration had no splits at all)."""
        k = self.num_tree_per_iteration
        # copy-on-write: mutate a private list and rebind once, so code
        # reading self.models WITHOUT the trees mutex (model text dumps,
        # leaf-value bounds) always sees a self-consistent list — either
        # fully pre-flush or fully post-flush, never mid-append
        models = list(self.models)
        for i, one in enumerate(host_trees):
            ht = HostTree(one, shrinkage=shrinks[i])
            if ht.num_nodes == 0:
                ht.num_leaves = 1
            models.append(ht)
        # stop if the last flushed iteration had no splits at all
        # (reference: gbdt.cpp:440-450 — the failed iteration's trees are
        # popped unless they are the very first, which stay as constant trees)
        stop = False
        tail = models[-k:]
        if len(tail) == k and all(m.num_nodes == 0 for m in tail):
            if len(models) > k:
                models = models[:-k]
                # removal: drop any cached stack holding the popped tail
                self._invalidate_device_trees()
            self.iter_ -= 1
            log.warning("Stopped training because there are no more leaves "
                        "that meet the split requirements")
            stop = True
        self.models = models
        self._dev_trees = []
        return stop

    def _renew_tree_output(self, tree: TreeArrays, row_leaf, mask,
                           cur_tree_id: int) -> TreeArrays:
        """(reference: TreeLearner::RenewTreeOutput + objective RenewTreeOutput,
        regression_objective.hpp:197)"""
        obj = self.objective
        if obj is None or not obj.renew_leaves:
            return tree
        residual = obj.label - self.train_score[cur_tree_id]
        w = mask if self.row_weight is None else mask * self.row_weight
        rung = self.grower_params.num_leaves
        renewed = renew_leaf_quantile(
            residual, w, row_leaf, rung, float(obj.renew_alpha))
        # only leaves that exist keep renewed values (others stay at 0)
        live = jnp.arange(rung) < tree.num_leaves
        return tree._replace(
            leaf_value=jnp.where(live, renewed, tree.leaf_value))

    def _update_score(self, host: HostTree, tree: TreeArrays, row_leaf,
                      cur_tree_id: int) -> None:
        """(reference: GBDT::UpdateScore, gbdt.cpp:491)"""
        self.train_score = self.train_score.at[cur_tree_id].set(
            _add_leaf_outputs(self.train_score[cur_tree_id],
                              tree.leaf_value, row_leaf))
        self._update_valid_scores(tree, cur_tree_id)

    def _update_valid_scores(self, tree: TreeArrays, cur_tree_id: int) -> None:
        if not self.valid_sets:
            return
        with span("valid_scores"):
            for vs in self.valid_sets:
                leaf = route_one_tree(
                    vs.binned, tree.split_feature, tree.split_bin,
                    tree.cat_bitset, tree.default_left, tree.left_child,
                    tree.right_child, tree.num_nodes, *self._route_args())
                vs.score = vs.score.at[cur_tree_id].set(_add_leaf_outputs(
                    vs.score[cur_tree_id], tree.leaf_value, leaf))

    def apply_tree_to_scores(self, host: HostTree, cur_tree_id: int,
                             factor: float, train: bool = True,
                             valid: bool = True) -> None:
        """Add ``factor * tree_output`` to cached scores — the workhorse behind
        rollback and DART drop/normalize (reference: Tree::Shrinkage +
        ScoreUpdater::AddScore combos in gbdt.cpp:454 / dart.hpp:131-198)."""
        sf = jnp.asarray(host.split_feature)
        sb = jnp.asarray(host.split_bin)
        cb = jnp.asarray(host.cat_bitset)
        dl = jnp.asarray(host.default_left)
        lc = jnp.asarray(host.left_child)
        rc = jnp.asarray(host.right_child)
        nn = jnp.asarray(host.num_nodes)
        lv = jnp.asarray(host.leaf_value * factor)
        if getattr(host, "is_linear", False):
            # linear leaves contributed leaf_const + x.coeff to the scores;
            # replay the same formula (host-side) for exact add/subtract
            from .linear import linear_leaf_outputs
            if train:
                leaf = route_one_tree(
                    self._routing_binned(), sf, sb, cb, dl, lc, rc, nn,
                    *self._route_args())
                delta = linear_leaf_outputs(
                    host, self.train_set.raw_data, np.asarray(leaf)) * factor
                self.train_score = self.train_score.at[cur_tree_id].add(
                    jnp.asarray(delta, jnp.float32))
            if valid:
                for vs in self.valid_sets:
                    vleaf = route_one_tree(
                        vs.binned, sf, sb, cb, dl, lc, rc, nn,
                        *self._route_args())
                    vdelta = linear_leaf_outputs(
                        host, vs.dataset.raw_data,
                        np.asarray(vleaf)[: vs.n_real]) * factor
                    vs.score = vs.score.at[cur_tree_id, : vs.n_real].add(
                        jnp.asarray(vdelta, jnp.float32))
            return
        if train:
            leaf = route_one_tree(self._routing_binned(), sf, sb, cb, dl,
                                  lc, rc, nn, *self._route_args())
            self.train_score = self.train_score.at[cur_tree_id].set(
                _add_leaf_outputs(self.train_score[cur_tree_id], lv, leaf))
        if valid:
            for vs in self.valid_sets:
                vleaf = route_one_tree(vs.binned, sf, sb, cb, dl, lc, rc,
                                       nn, *self._route_args())
                vs.score = vs.score.at[cur_tree_id].set(
                    _add_leaf_outputs(vs.score[cur_tree_id], lv, vleaf))

    def rollback_one_iter(self) -> None:
        """(reference: GBDT::RollbackOneIter, gbdt.cpp:454)"""
        self._flush_trees()
        if self.iter_ <= 0:
            return
        k = self.num_tree_per_iteration
        for cur_tree_id in range(k):
            host = self.models[len(self.models) - k + cur_tree_id]
            self.apply_tree_to_scores(host, cur_tree_id, -1.0)
        del self.models[len(self.models) - k:]
        self._invalidate_device_trees()
        self.iter_ -= 1

    # -- checkpoint / resume (io/checkpoint.py; reference: the model-text
    # snapshots of gbdt.cpp:250-254 + init_model warm starts — here the
    # snapshot is the COMPLETE optimizer state so resume is bit-identical)
    def snapshot_compatible(self, state) -> Optional[str]:
        """Reason this training run cannot resume from ``state`` (None =
        compatible). Structural checks only — a resumed run is expected to
        use the same params as the interrupted one."""
        if not isinstance(state, dict) or state.get("format") != 1:
            return "unknown snapshot format"
        meta = state.get("meta", {})
        want = {"boosting": self.boosting_type, "num_data": self._n_real,
                "trees_per_iteration": self.num_tree_per_iteration,
                "num_leaves": self.max_leaves}
        for key, val in want.items():
            if meta.get(key) != val:
                return f"{key}: snapshot has {meta.get(key)!r}, " \
                       f"this run has {val!r}"
        names = [n for n, _ in state.get("valid_scores", ())]
        if names != [vs.name for vs in self.valid_sets]:
            return (f"validation sets differ (snapshot {names}, run "
                    f"{[vs.name for vs in self.valid_sets]})")
        expect_compact = bool(self._use_compact
                              and int(state.get("iteration", 0)) >= 1)
        if (state.get("compact") is not None) != expect_compact:
            return ("row-storage layout differs (compact vs masked grower "
                    "— tpu_grower or data size changed)")
        return None

    def capture_training_state(self) -> Dict[str, Any]:
        """Host snapshot of the complete training state.

        The ONLY planned device->host transfers outside stop checks: one
        batched fetch per ``tpu_checkpoint_freq`` boundary, off the jit
        hot path (the steady-state guard asserts exactly this in
        tests/test_checkpoint.py). Covers everything a bit-identical
        resume needs: trees, iteration counter, cached train/valid
        scores, sampling/feature RNG state, bagging cache, CEGB state,
        the compact grower's permuted row records, and (via subclass
        hooks) DART drop state."""
        self._flush_trees()
        with self._trees_mu:
            models = list(self.models)
        strat = self.sample_strategy
        bag_cached = getattr(strat, "_cached", None)
        obj = self.objective
        pos_biases = getattr(obj, "pos_biases", None)
        state: Dict[str, Any] = {
            "format": 1,
            "meta": {
                "boosting": self.boosting_type,
                "num_data": self._n_real,
                "trees_per_iteration": self.num_tree_per_iteration,
                "num_leaves": self.max_leaves,
            },
            "iteration": int(self.iter_),
            "models": models,
            "shrinkage_rate": float(self.shrinkage_rate),
            "init_scores": list(self._init_scores),
            "has_init_score": bool(self._has_init_score),
            "train_score": _to_host(self.train_score),
            "valid_scores": [(vs.name, _to_host(vs.score))
                             for vs in self.valid_sets],
            "feat_rng": self._feat_rng.get_state(),
            "bag_cached": None if bag_cached is None
            else _to_host(bag_cached),
            "cegb_used": None if self._cegb_used is None
            else _to_host(self._cegb_used),
            "cegb_charged": None if self._cegb_charged is None
            else _to_host(self._cegb_charged),
            "pos_biases": None if pos_biases is None
            else _to_host(pos_biases),
            "linear_any_split": bool(getattr(self, "_linear_any_split",
                                             False)),
            "compact": None,
        }
        if self._compact is not None:
            # the permuted row records ARE load-bearing for bit-identity:
            # histogram/score summation order follows the physical row
            # order, so resume must restore the exact bytes, not rebuild
            # from the original order
            c = self._compact
            state["compact"] = {
                "work": _to_host(c["work"]),
                "scratch": _to_host(c["scratch"]),
                "epoch": int(c["epoch"]),
            }
        return state

    def restore_training_state(self, state: Dict[str, Any]) -> None:
        """Rebind this (freshly constructed) trainer to a snapshot. The
        caller validates ``snapshot_compatible`` first."""
        with self._trees_mu:
            self.models = list(state["models"])
            self._dev_trees = []
            self._invalidate_device_trees()
        self.iter_ = int(state["iteration"])
        self.shrinkage_rate = float(state["shrinkage_rate"])
        self._init_scores = list(state["init_scores"])
        self._has_init_score = bool(state["has_init_score"])
        self.train_score = _device_put_like(state["train_score"],
                                            self.train_score)
        for vs, (name, arr) in zip(self.valid_sets, state["valid_scores"]):
            vs.score = _device_put_like(arr, vs.score)
        self._feat_rng.set_state(state["feat_rng"])
        if state.get("bag_cached") is not None \
                and hasattr(self.sample_strategy, "_cached"):
            self.sample_strategy._cached = _device_put_like(
                state["bag_cached"], self.sample_strategy._cached)
        if state.get("cegb_used") is not None:
            self._cegb_used = _device_put_like(state["cegb_used"],
                                               self._cegb_used)
        if state.get("cegb_charged") is not None:
            self._cegb_charged = _device_put_like(state["cegb_charged"],
                                                  self._cegb_charged)
        if state.get("pos_biases") is not None \
                and self.objective is not None:
            self.objective.pos_biases = _device_put_like(
                state["pos_biases"], getattr(self.objective, "pos_biases",
                                             None))
        self._linear_any_split = bool(state.get("linear_any_split", False))
        comp = state.get("compact")
        if comp is not None:
            if self._compact is None:
                self._setup_compact_state()
            c = self._compact
            c["work"] = _device_put_like(comp["work"], c["work"])
            c["scratch"] = _device_put_like(comp["scratch"], c["scratch"])
            c["epoch"] = int(comp["epoch"])
            c["perm_epoch"] = -1
            c["perm"] = None

    def _routing_binned(self) -> jax.Array:
        """Binned rows in the same order as the cached train scores (the
        compact grower permutes rows; DART drops / rollback route through
        the current work order)."""
        if self._compact is not None:
            f = self._compact["layout"].num_features
            return self._compact_rows(self._compact["work"])[:, :f]
        return self.binned

    # -- evaluation ----------------------------------------------------------
    def eval_train(self) -> List[Tuple[str, str, float, bool]]:
        if self._compact is not None and self.train_metrics:
            # train scores live in the compact grower's permuted row order;
            # un-permute them back to the ORIGINAL order so every metric —
            # including query-structured NDCG/MAP — sees its own layout
            # (pad rows carry ids >= n_real and drop out of the slice)
            perm = self._compact_perm()
            raw = _to_host(self.train_score)
            unperm = np.empty_like(raw)
            unperm[:, perm] = raw
            return self._eval("training", unperm[:, :self._n_real],
                              self.train_metrics)
        return self._eval("training", _to_host(self.train_score),
                          self.train_metrics)

    def eval_valid(self) -> List[Tuple[str, str, float, bool]]:
        out = []
        for vs in self.valid_sets:
            out.extend(self._eval(vs.name, _to_host(vs.score), vs.metrics,
                                  n_real=vs.n_real))
        return out

    def _eval(self, name, score, metrics, n_real: Optional[int] = None):
        convert = self.objective.convert_output if self.objective else None
        if n_real is None:
            n_real = self._n_real if hasattr(self, "_n_real") else score.shape[1]
        score = score[:, :n_real]
        raw = score[0] if self.num_tree_per_iteration == 1 else score
        out = []
        for m in metrics:
            if hasattr(m, "eval_all"):
                for k_at, v in zip(m.eval_at, m.eval_all(raw)):
                    out.append((name, f"{m.name}@{k_at}", v, m.higher_better))
            else:
                out.append((name, m.name, m.eval(raw, convert), m.higher_better))
        return out

    # -- prediction ----------------------------------------------------------
    def _predict_cfg(self):
        """(tbatch, row-bucket ladder, engine) resolved from config per
        call — cheap, and reset_parameter may change them mid-session."""
        cfg = self.config
        tb = max(1, min(int(cfg.get("tpu_predict_tbatch", 16) or 16), 128))
        ladder = parse_bucket_ladder(cfg.get("tpu_predict_buckets", "auto"))
        engine = str(cfg.get("tpu_predict_engine", "batched")).lower()
        return tb, ladder, engine

    def _pred_route_args(self):
        """(nan_bin, is_cat) in ORIGINAL feature space — prediction inputs
        are binned per original feature (no bundling)."""
        if self._efb is not None:
            return self._orig_nan_arr, self._orig_cat_arr
        return self.nan_bin_arr, self.is_cat_arr

    def _model_window(self, num_iteration: Optional[int],
                      start_iteration: int) -> List[HostTree]:
        """Model slice for a prediction window (reference: start_iteration
        in GBDT::Predict* / Predictor; num_iteration_for_pred_)."""
        models = self.models
        k = self.num_tree_per_iteration
        if start_iteration > 0:
            models = models[start_iteration * k:]
        if num_iteration is not None and num_iteration > 0:
            models = models[: num_iteration * k]
        return models

    @staticmethod
    def _models_max_depth(models: Sequence[HostTree]) -> int:
        """Deepest root-to-leaf path in the window — the walk-step count
        the engine needs (recorded per HostTree by the grower)."""
        return max((int(np.max(m.leaf_depth[:m.num_leaves], initial=0))
                    for m in models), default=0)

    def _device_trees_plain(self, num_iteration: Optional[int] = None,
                            start_iteration: int = 0):
        """(unpadded StackedTrees, t_real): the pre-engine layout, kept for
        tpu_predict_engine=scan (parity/bench reference path)."""
        with self._trees_mu:
            self._flush_trees()
            models = self._model_window(num_iteration, start_iteration)
            max_lv = max((len(m.leaf_value) for m in models),
                         default=self.max_leaves)
            return stack_trees(models, max_lv - 1, max_lv), len(models)

    #: device-tree cache slots kept before evicting the oldest (each slot
    #: holds one padded model copy on device; serving uses 1-2 slots)
    _DTC_SLOTS = 8

    def _invalidate_device_trees(self) -> None:
        """Drop BOTH device model caches — the padded tree stacks AND
        the TreeSHAP path arrays. Every mutation that invalidates one
        invalidates the other: a rollback/RF/DART leaf rescale changes
        leaf values (and expected values) without necessarily changing
        the tree count, so the paths' cached ``ev`` would silently
        serve stale contributions if it outlived the stack. The cached
        host score baseline (drift_reference) rides along: those same
        mutations change train_score at an unchanged tree count."""
        self._device_trees_cache = None
        self._shap_paths_cache = None
        self._drift_score_host = None
        self._serve_engine_memo = None
        self._shap_tables_cache = None

    def _device_trees_batched(self, num_iteration: Optional[int] = None,
                              start_iteration: int = 0, tbatch: int = 16):
        """(StackedTrees padded to the tree-count bucket, t_real, depth).

        Cached per (tbatch, start_iteration, num_iteration) and
        APPEND-PADDED: trees grown since the last fill are stacked alone
        (a transfer the size of the delta, not the model) and written
        into the padded device arrays, so mid-train predict stops
        re-stacking the whole model every iteration. Windows are
        first-class keys because they ARE the common serving shape —
        Booster.predict defaults num_iteration to best_iteration after
        early-stopped training — and the models list is append-only, so
        a window's contents are stable under appends. Distinct chunk
        sizes (plain vs early-stop predicts) get their own slots; the
        oldest slot is evicted past _DTC_SLOTS. Cache fill and
        model-list read run under the trees mutex so concurrent
        read-locked predicts (basic.py) see a consistent (models, cache)
        pair — the reference serializes the same window behind its
        shared C API lock (src/c_api.cpp:163).
        """
        with self._trees_mu:
            self._flush_trees()
            models = self._model_window(num_iteration, start_iteration)
            t = len(models)
            # width from the models themselves: num_leaves may have been
            # changed mid-training via reset_parameter
            max_lv = max((len(m.leaf_value) for m in models),
                         default=self.max_leaves)
            cat_w = max((m.cat_bitset.shape[1] for m in models), default=1)
            t_bkt = tree_bucket(t, tbatch)
            if self._device_trees_cache is None:
                self._device_trees_cache = {}
            cache = self._device_trees_cache
            key = (tbatch, start_iteration,
                   num_iteration if num_iteration is not None
                   and num_iteration > 0 else None)
            c = cache.get(key)
            if (c is not None and c["max_lv"] == max_lv
                    and c["cat_w"] == cat_w and t >= c["t_real"]):
                if t > c["t_real"]:
                    t0 = c["t_real"]
                    fresh = stack_trees(models[t0:], max_lv - 1, max_lv,
                                        cat_w=cat_w)
                    st = c["st"]
                    if t_bkt != c["t_bucket"]:
                        # bucket grew: extend the padded arrays on device
                        # (the old trees never re-cross PCIe)
                        grow = t_bkt - c["t_bucket"]
                        st = jax.tree.map(
                            lambda a: jnp.concatenate(
                                [a, jnp.zeros((grow,) + a.shape[1:],
                                              a.dtype)]), st)
                    st = jax.tree.map(lambda a, new: a.at[t0:t].set(new),
                                      st, fresh)
                    c.update(st=st, t_real=t, t_bucket=t_bkt,
                             depth=max(c["depth"],
                                       self._models_max_depth(models[t0:])))
                    # derived serving slabs (level heap, quantized
                    # leaves) were built from the pre-append stack —
                    # drop them; the next serving predict rebuilds
                    for derived in ("level", "level_depth", "quant"):
                        c.pop(derived, None)
                return c["st"], c["t_real"], c["depth"]
            depth = self._models_max_depth(models)
            st = stack_trees(models, max_lv - 1, max_lv, cat_w=cat_w,
                             pad_to=t_bkt)
            cache[key] = {
                "st": st, "t_real": t, "t_bucket": t_bkt, "depth": depth,
                "max_lv": max_lv, "cat_w": cat_w}
            while len(cache) > self._DTC_SLOTS:
                cache.pop(next(k for k in cache if k != key))
            return st, t, depth

    def _device_trees_entry(self, num_iteration: Optional[int],
                            start_iteration: int, tbatch: int):
        """(st, t_real, depth, cache-slot dict) — the slot carries the
        derived serving slabs (level heap / quantized leaves) next to
        the padded stack they were built from."""
        st, t_real, depth = self._device_trees_batched(
            num_iteration, start_iteration, tbatch)
        key = (tbatch, start_iteration,
               num_iteration if num_iteration is not None
               and num_iteration > 0 else None)
        with self._trees_mu:
            c = (self._device_trees_cache or {}).get(key)
        return st, t_real, depth, c

    # -- serving engines (ROADMAP 4: level-order relayout + leaf quant) ------
    def _level_cap(self) -> int:
        try:
            cap = int(self.config.get("tpu_level_depth_cap",
                                      DEFAULT_LEVEL_DEPTH_CAP)
                      or DEFAULT_LEVEL_DEPTH_CAP)
        except (TypeError, ValueError):
            cap = DEFAULT_LEVEL_DEPTH_CAP
        return max(1, cap)

    def _level_state(self, c: Dict[str, Any], depth: int):
        """The LevelTrees heap relayout for a device-tree cache slot,
        built once at stack time per (stack, depth) and cached in the
        slot (the _device_trees_cache half of the level engine)."""
        depth = max(1, depth)
        with self._trees_mu:
            lv = c.get("level")
            if lv is not None and c.get("level_depth") == depth:
                return lv
        nan_a, cat_a = self._pred_route_args()
        lv = build_level_layout(c["st"], nan_a, cat_a, depth)
        with self._trees_mu:
            c["level"], c["level_depth"] = lv, depth
        return lv

    def _quant_mode(self) -> Optional[str]:
        """Validated ``tpu_leaf_quant`` (None = off)."""
        m = str(self.config.get("tpu_leaf_quant", "off") or "off").lower()
        if m in ("", "off", "0", "false", "none"):
            return None
        if m not in ("int8", "f16"):
            if not getattr(self, "_warned_leaf_quant", False):
                log.warning(f"tpu_leaf_quant={m!r} is not one of "
                            "off|int8|f16; serving f32 leaves")
                self._warned_leaf_quant = True
            return None
        return m

    def _quant_state(self, c: Dict[str, Any], mode: str):
        """(slab, scale, recorded bound) for a cache slot: the
        quantized serving leaf values with per-tree scales and the
        RECORDED max-score-error bound, computed once at stack time and
        shipped in the slot next to the stack."""
        with self._trees_mu:
            q = c.get("quant")
            if q is not None and q[0] == mode:
                return q[1], q[2], q[3]
        k = max(self.num_tree_per_iteration, 1)
        t_total = c["st"].leaf_value.shape[0]
        class_ids = jnp.arange(t_total, dtype=jnp.int32) % k
        slab, scale, bound = quantize_leaves(
            c["st"].leaf_value, class_ids, mode, num_class=k)
        q = (mode, slab, scale, float(bound))
        with self._trees_mu:
            c["quant"] = q
        return q[1], q[2], q[3]

    def leaf_quant_bound(self, num_iteration: Optional[int] = None,
                         start_iteration: int = 0) -> Optional[float]:
        """The recorded max-score-error bound the quantized model stack
        ships: an exact upper bound on |quantized raw score - f32 raw
        score| for ANY row (per-tree worst-case dequantization error,
        summed per class, maxed over classes). None when
        ``tpu_leaf_quant`` is off."""
        mode = self._quant_mode()
        if mode is None:
            return None
        tb = self._predict_cfg()[0]
        _, t_real, _, c = self._device_trees_entry(
            num_iteration, start_iteration, tb)
        if t_real == 0 or c is None:
            return 0.0
        return self._quant_state(c, mode)[2]

    def _resolve_serving_engine(self, engine: str, depth: int) -> str:
        """``walk`` or ``level`` via the registry's serving resolve
        order (user > env > depth heuristic), memoized per (engine knob,
        depth, cap) so the choice is logged once."""
        from ..engines import registry as engreg
        cap = self._level_cap()
        memo = getattr(self, "_serve_engine_memo", None)
        if memo is None:
            memo = self._serve_engine_memo = {}
        key = (engine, depth, cap)
        hit = memo.get(key)
        if hit is not None:
            return hit
        res = engreg.resolve_serving_engine(
            self.config, depth=depth, level_cap=cap,
            quant=self._quant_mode() or "off")
        memo[key] = res.engine
        if res.source != "user":
            log.info(f"serving engine: {res.entry_id} "
                     f"({res.source}; depth={depth}, cap={cap})")
        return res.engine

    def _pad_request_to_bucket(self, mat: np.ndarray, rung: int,
                               packed: bool) -> jax.Array:
        """Host-pad a request matrix to its bucket rung and device_put.

        Pure numpy + one transfer: no compilation, no device->host — the
        zero-recompile serving contract depends on the padding happening
        BEFORE the array reaches a jitted program (tpulint R002)."""
        if mat.shape[0] != rung:
            mat = np.pad(mat, ((0, rung - mat.shape[0]), (0, 0)))
        if packed:
            from ..io.dataset import pack4_matrix
            mat = pack4_matrix(mat)
        return jnp.asarray(mat)

    def predict_raw_device(self, binned,
                           num_iteration: Optional[int] = None,
                           start_iteration: int = 0,
                           early_stop=None,
                           device_packed: bool = False) -> jax.Array:
        """Raw UNAVERAGED score sums, left on device: [K, n_padded] with
        the first ``binned.shape[0]`` columns valid.

        The serving hot path: numpy requests pad on host up to a bucket
        rung, trees come from the bucketed append-pad cache, and the
        jitted engine program is keyed on (row rung, tree bucket, depth
        bucket, num_class) — after one warmup per rung, mixed batch
        sizes run with zero compiles and zero device->host transfers.
        Requests larger than the ladder run as one GSPMD row-sharded
        program over the training mesh when one exists (each shard padded
        to its own rung), else they are the caller's to slice
        (predict_raw_binned does). ``early_stop`` is an optional
        (margin, freq) pair (reference: prediction_early_stop.cpp)."""
        k = self.num_tree_per_iteration
        n = binned.shape[0]
        tb_cfg, ladder, engine = self._predict_cfg()
        margin, freq = early_stop if early_stop else (0.0, 0)
        use_stop = freq > 0 and margin > 0.0
        nan_a, cat_a = self._pred_route_args()
        if engine == "scan":
            # pre-engine reference path: serial tree scan, jitted on the
            # concrete batch shape (recompiles per size by design)
            st, _ = self._device_trees_plain(num_iteration, start_iteration)
            return predict_raw_scan(
                jnp.asarray(binned), st, nan_a, cat_a, np.int32(k), k,
                early_stop_margin=float(margin) if use_stop else 0.0,
                early_stop_freq=int(freq) if use_stop else 0)
        # with early stopping the tree chunk must land on the reference's
        # exact iteration-multiple-of-freq checkpoints
        tbatch = early_stop_tbatch(k, freq, tb_cfg) if use_stop else tb_cfg
        st, t_real, depth, c = self._device_trees_entry(
            num_iteration, start_iteration, tbatch)
        if t_real == 0:
            return jnp.zeros((k, n), jnp.float32)
        kwargs = dict(
            num_class=k, tbatch=tbatch,
            early_stop_margin=float(margin) if use_stop else 0.0,
            early_stop_freq=int(freq) if use_stop else 0,
            any_cat=self._pred_any_cat)
        kk = np.int32(k)
        eng = self._resolve_serving_engine(engine, depth)
        qmode = self._quant_mode()
        slab, scale = ((self._quant_state(c, qmode)[:2])
                       if qmode else (st.leaf_value, None))
        if eng == "level":
            lvt = self._level_state(c, depth)

            def run(dev, packed_flag):
                return predict_raw_level(
                    dev, lvt, slab, kk, depth=max(1, depth),
                    packed=packed_flag, leaf_scale=scale, **kwargs)
        else:
            walk_st = st._replace(leaf_value=slab) if qmode else st

            def run(dev, packed_flag):
                return predict_raw_batched(
                    dev, walk_st, nan_a, cat_a, kk,
                    depth=depth_bucket(depth), packed=packed_flag,
                    leaf_scale=scale, **kwargs)
        if not isinstance(binned, np.ndarray):
            # device-array input (the serving device-featurize path hands
            # an already-rung-padded — possibly nibble-packed — matrix;
            # internal/test callers may pass unpadded, which pads here)
            rung = bucket_rows(n, ladder)
            if rung is not None and rung != n:
                binned = jnp.pad(binned, ((0, rung - n), (0, 0)))
            return run(binned, device_packed)
        packed = self._pred_pack4
        rung = bucket_rows(n, ladder)
        if rung is not None:
            dev = self._pad_request_to_bucket(binned, rung, packed)
            return run(dev, packed)
        if self._can_shard_predict(n, ladder):
            from ..parallel.mesh import (mesh_axis_sizes, predict_shard_pad,
                                         row_sharding_2d)
            num_shards = mesh_axis_sizes(self.mesh)[0]
            n_pad = predict_shard_pad(n, num_shards, ladder)
            mat = np.pad(binned, ((0, n_pad - n), (0, 0)))
            if packed:
                from ..io.dataset import pack4_matrix
                mat = pack4_matrix(mat)
            dev = jax.device_put(mat, row_sharding_2d(self.mesh))
            return run(dev, packed)
        raise ValueError(
            f"request of {n} rows overflows the serving ladder "
            f"(max {ladder[-1]}) and cannot be row-sharded here; slice it "
            "(predict_raw_binned does) or raise tpu_predict_buckets")

    def _can_shard_predict(self, n: int, ladder) -> bool:
        """True when an oversize request can run as ONE GSPMD row-sharded
        program over the training mesh (per-shard share fits the ladder);
        otherwise callers slice through the largest rung."""
        if self.mesh is None or getattr(self, "_multiproc", False):
            return False
        from ..parallel.mesh import mesh_axis_sizes, predict_shard_pad
        num_shards = mesh_axis_sizes(self.mesh)[0]
        return predict_shard_pad(n, num_shards, ladder) is not None

    def _average_divisor(self, num_iteration: Optional[int],
                         start_iteration: int) -> int:
        """RF ``average_output`` divisor: the iteration count actually
        accumulated in the prediction window after start/num slicing
        (reference: num_iteration_for_pred_). The ONE implementation
        behind every averaging prediction path — predict_raw_binned,
        Booster.predict_device and Booster.predict_serving."""
        with self._trees_mu:
            t_real = len(self._model_window(num_iteration,
                                            start_iteration))
        return max(t_real // max(self.num_tree_per_iteration, 1), 1)

    def predict_raw_binned(self, binned,
                           num_iteration: Optional[int] = None,
                           start_iteration: int = 0,
                           early_stop=None) -> np.ndarray:
        """Raw scores [K, N] for already-binned rows. ``early_stop`` is an
        optional (margin, freq) pair (reference:
        src/boosting/prediction_early_stop.cpp)."""
        self._flush_trees()
        if not self.models:
            n = binned.shape[0]
            return np.zeros((self.num_tree_per_iteration, n), np.float32)
        n = binned.shape[0]
        _, ladder, engine = self._predict_cfg()
        oversize = (engine != "scan" and isinstance(binned, np.ndarray)
                    and bucket_rows(n, ladder) is None
                    and not self._can_shard_predict(n, ladder))
        if oversize:
            # above the ladder with no mesh: slices of the largest rung,
            # each hitting the warm max-rung program (early stopping is
            # per row, so slicing preserves its semantics exactly)
            top = ladder[-1]
            parts = []
            for a in range(0, n, top):
                raw = self.predict_raw_device(
                    binned[a:a + top], num_iteration, start_iteration,
                    early_stop)
                parts.append(np.asarray(raw)[:, :min(top, n - a)])
            raw = np.concatenate(parts, axis=1)
        else:
            raw = np.asarray(self.predict_raw_device(
                binned, num_iteration, start_iteration, early_stop))[:, :n]
        if self.average_output:
            raw = raw / self._average_divisor(num_iteration,
                                              start_iteration)
        return raw

    def bin_matrix(self, arr: np.ndarray) -> np.ndarray:
        """Bin raw feature rows with the training BinMappers (host side)."""
        from ..io.binning import bin_columns
        ds = self.train_set
        arr = np.asarray(arr)
        if arr.dtype != np.float32:     # float32 upcasts exactly per-compare
            arr = arr.astype(np.float64, copy=False)
        if arr.ndim == 1:
            arr = arr.reshape(1, -1)
        if arr.shape[1] != ds.num_total_features:
            raise ValueError(
                f"input has {arr.shape[1]} features, model expects "
                f"{ds.num_total_features}")
        return bin_columns(ds.mappers, arr, ds.binned.dtype)

    # -- serving featurization (ISSUE 13: the one-copy hot path) -------------
    def _serve_featurize_mode(self) -> str:
        """Resolved ``tpu_serve_featurize`` for this model: ``device``
        (default — a serving request is one host->device copy of raw
        float32, binned by the jitted ops/device_bin.py program) or
        ``host`` (the bin_columns parity/escape hatch). Demotes to host
        with a one-time warning when the model cannot take the device
        featurizer (scan engine, int32-overflowing categorical codes)."""
        mode = str(self.config.get("tpu_serve_featurize", "device")).lower()
        if mode not in ("device", "host"):
            log.warning(f"unrecognized tpu_serve_featurize={mode!r}; "
                        "using 'device'")
            mode = "device"
        if mode == "host":
            return "host"
        if self._predict_cfg()[2] == "scan":
            return "host"        # scan path has no rung padding to key on
        return "device" if self._featurize_state() is not None else "host"

    def _featurize_state(self):
        """Device-resident binning state (built once per model), or None
        when the model is not device-featurizable (warned once)."""
        cached = getattr(self, "_featurize_dev", None)
        if cached is not None:
            return cached if cached != "ineligible" else None
        from ..io.binning import export_featurize_state
        from ..ops.device_bin import device_bin_state
        host_state = export_featurize_state(self.train_set.mappers)
        if host_state.reason is not None:
            log.warning(f"tpu_serve_featurize=device unavailable "
                        f"({host_state.reason}); serving bins on host")
            self._featurize_dev = "ineligible"
            return None
        self._featurize_dev = device_bin_state(host_state)
        return self._featurize_dev

    def drift_reference(self):
        """Serving drift-monitor reference (ISSUE 14): ``(bin-occupancy
        probs [F, B], per-feature bin counts [F], training raw margins
        [K, N] device array or None)``.

        The occupancy is the training data's normalized per-feature bin
        distribution (cached on the dataset — the serving registry
        materializes it during the deploy warm phase so it ships WITH
        the model); the margins seed the fixed-edge score-distribution
        baseline and are returned as a CACHED host copy, so the [K, N]
        d2h also happens once, in the warm phase, not at the post-swap
        monitor attach. Live serving windows are compared against both
        (PSI / KL) by obs/drift.DriftMonitor."""
        probs, nbins = self.train_set.reference_bin_distribution()
        self._flush_trees()
        key = len(self.models)          # continued training MUST refresh
        cached = getattr(self, "_drift_score_host", None)
        if cached is None or cached[0] != key:
            ts = getattr(self, "train_score", None)
            cached = (key, False if ts is None else np.asarray(ts))
            self._drift_score_host = cached
        return probs, nbins, (None if cached[1] is False else cached[1])

    def featurize_rung(self, arr32: np.ndarray) -> jax.Array:
        """Pad a raw float32 request to its bucket rung, upload it (THE
        one host->device copy of a serving request) and bin it with the
        jitted featurizer — device-resident bins in the exact layout the
        host path would produce (pack4 included), ready for
        predict_raw_device(device_packed=self._pred_pack4)."""
        from ..ops.device_bin import bin_rows_device
        ds = self.train_set
        if arr32.shape[1] != ds.num_total_features:
            raise ValueError(
                f"input has {arr32.shape[1]} features, model expects "
                f"{ds.num_total_features}")
        n = arr32.shape[0]
        rung = self._serving_rung(n)
        if n != rung:
            arr32 = np.pad(arr32, ((0, rung - n), (0, 0)))
        state = self._featurize_state()
        if state is None:
            raise ValueError("model is not device-featurizable; use the "
                             "host binner (tpu_serve_featurize=host)")
        return bin_rows_device(jnp.asarray(arr32), state, np.int32(n),
                               out_dtype=ds.binned.dtype.name,
                               packed=self._pred_pack4)

    # -- device TreeSHAP / leaf-index serving (ISSUE 13 endpoints) -----------
    #: shap-path cache slots (per prediction window; serving uses 1-2)
    _SHAP_SLOTS = 4

    def _device_shap_state(self, num_iteration: Optional[int],
                           start_iteration: int, tbatch: int):
        """(StackedTrees, ShapPaths, t_real, depth) for a window.

        The tree stack comes from the shared append-pad device cache
        (_device_trees_batched); the per-leaf path arrays are extracted
        once per (window, model length) and cached — the row-independent
        half of TreeSHAP, the analogue of the reference computing each
        tree's decision paths once per PredictContrib call."""
        from ..ops.treeshap_device import build_shap_paths
        st, t_real, depth = self._device_trees_batched(
            num_iteration, start_iteration, tbatch)
        with self._trees_mu:
            # slice to the stacked length: a tree appended between the two
            # mutex sections must not desync paths from the stack
            models = self._model_window(num_iteration,
                                        start_iteration)[:t_real]
            key = (tbatch, start_iteration,
                   num_iteration if num_iteration is not None
                   and num_iteration > 0 else None)
            cache = getattr(self, "_shap_paths_cache", None)
            if cache is None:
                cache = self._shap_paths_cache = {}
            c = cache.get(key)
            d_bkt = depth_bucket(depth)
            if c is not None and c["t_real"] == t_real \
                    and c["d_bkt"] == d_bkt:
                return st, c["paths"], t_real, depth
            paths = build_shap_paths(models, st.leaf_value.shape[1], d_bkt,
                                     pad_to=st.num_trees)
            cache[key] = {"paths": paths, "t_real": t_real, "d_bkt": d_bkt}
            while len(cache) > self._SHAP_SLOTS:
                cache.pop(next(k for k in cache if k != key))
            return st, paths, t_real, depth

    def _shap_table_mode(self) -> str:
        raw = str(self.config.get("tpu_shap_tables", "auto")).strip().lower()
        if raw in ("auto", "on", "off"):
            return raw
        if not getattr(self, "_warned_shap_tables", False):
            self._warned_shap_tables = True
            log.warning(f"tpu_shap_tables={raw!r} unknown (auto|on|off); "
                        "using auto")
        return "auto"

    def _device_shap_tables_bucketed(self, st, paths, t_real: int,
                                     depth: int,
                                     num_iteration: Optional[int],
                                     start_iteration: int, tbatch: int):
        """ShapTables at the window's (tree bucket, depth bucket), or
        None when gated off / over the ``tpu_shap_table_mb`` budget (the
        loop kernel then serves).

        Built once per (window, model length) at deploy time — never on
        the serving path — and cached next to the path arrays (bounded
        by the same ``_SHAP_SLOTS``; the negative decision is cached too
        so the budget check costs one host sync total). Same
        invalidation as every device-tree cache
        (``_invalidate_device_trees``). The build (one host sync for
        mask_bits + the jitted table construction) runs OUTSIDE
        ``_trees_mu`` — concurrent first builders race benignly (same
        inputs, last writer wins), and an invalidation mid-build drops
        the store instead of resurrecting a stale cache."""
        from ..ops.treeshap_device import build_shap_tables, shap_table_bytes
        mode = self._shap_table_mode()
        if mode == "off" or t_real == 0:
            return None
        d_bkt = depth_bucket(depth)
        key = (tbatch, start_iteration,
               num_iteration if num_iteration is not None
               and num_iteration > 0 else None)
        with self._trees_mu:
            cache = getattr(self, "_shap_tables_cache", None)
            if cache is None:
                cache = self._shap_tables_cache = {}
                _register_shap_table_probe(self)
            c = cache.get(key)
            if c is not None and c["t_real"] == t_real \
                    and c["d_bkt"] == d_bkt and c["mode"] == mode:
                return c["tables"]
        mask_bits = int(jax.device_get(jnp.max(paths.ulen)))
        budget_mb = max(int(self.config.get("tpu_shap_table_mb", 64)), 0)
        need = shap_table_bytes(st.num_trees, st.leaf_value.shape[1],
                                mask_bits, d_bkt)
        if need > budget_mb << 20:
            if mode == "on":
                raise ValueError(
                    f"tpu_shap_tables=on but the UNWIND tables need "
                    f"{need / 2**20:.1f} MiB "
                    f"(> tpu_shap_table_mb={budget_mb}); raise the "
                    "budget or use tpu_shap_tables=auto")
            log.info(f"shap tables skipped: {need / 2**20:.1f} MiB over "
                     f"the {budget_mb} MiB budget (loop kernel serves "
                     "pred_contrib)")
            tables = None
        else:
            tables = build_shap_tables(paths, st.leaf_value,
                                       mask_bits=mask_bits, depth=d_bkt)
        with self._trees_mu:
            cache = getattr(self, "_shap_tables_cache", None)
            if cache is not None:
                cache[key] = {"tables": tables, "t_real": t_real,
                              "d_bkt": d_bkt, "mode": mode}
                while len(cache) > self._SHAP_SLOTS:
                    cache.pop(next(k for k in cache if k != key))
        return tables

    def _serving_rung(self, n: int) -> int:
        """Bucket rung for one serving batch, or a structural error when
        the request overflows the ladder — THE one bounds check shared
        by the featurize and host-binned serving paths."""
        _, ladder, _ = self._predict_cfg()
        rung = bucket_rows(n, ladder)
        if rung is None:
            raise ValueError(
                f"request of {n} rows overflows the serving ladder "
                f"(max {ladder[-1]}); slice it or raise "
                "tpu_predict_buckets")
        return rung

    def _serving_device_request(self, binned, device_packed: bool):
        """(device matrix at a rung, packed?) for a serving batch that may
        arrive host-binned (numpy) or device-featurized (jax.Array)."""
        if not isinstance(binned, np.ndarray):
            return binned, device_packed
        rung = self._serving_rung(binned.shape[0])
        return (self._pad_request_to_bucket(binned, rung, self._pred_pack4),
                self._pred_pack4)

    def predict_contrib_padded(self, binned,
                               num_iteration: Optional[int] = None,
                               start_iteration: int = 0,
                               device_packed: bool = False) -> np.ndarray:
        """Exact TreeSHAP contributions [rung, K*(F+1)] via the device
        engine (ops/treeshap_device.py), rung-padded like
        predict_serving — the ``pred_contrib`` serving endpoint's one
        device dispatch. Matches ops/treeshap.py's numpy reference
        within f32 tolerance and sums to the raw score per row."""
        from ..ops.treeshap_device import shap_batched, shap_batched_tables
        k = self.num_tree_per_iteration
        tb_cfg, _, _ = self._predict_cfg()
        f = self.train_set.num_total_features
        st, paths, t_real, depth = self._device_shap_state(
            num_iteration, start_iteration, tb_cfg)
        if t_real == 0:
            return np.zeros((binned.shape[0], k * (f + 1)), np.float32)
        tables = self._device_shap_tables_bucketed(
            st, paths, t_real, depth, num_iteration, start_iteration,
            tb_cfg)
        dev, packed = self._serving_device_request(binned, device_packed)
        nan_a, cat_a = self._pred_route_args()
        if tables is not None:
            out = shap_batched_tables(
                dev, st, tables, nan_a, cat_a, np.int32(k), num_class=k,
                depth=depth_bucket(depth), tbatch=tb_cfg,
                any_cat=self._pred_any_cat, packed=packed, num_features=f)
        else:
            out = shap_batched(dev, st, paths, nan_a, cat_a, np.int32(k),
                               num_class=k, depth=depth_bucket(depth),
                               tbatch=tb_cfg, any_cat=self._pred_any_cat,
                               packed=packed, num_features=f)
        arr = np.asarray(out)                     # [K, rung, F+1]
        return arr.transpose(1, 0, 2).reshape(arr.shape[1], -1)

    def predict_leaf_padded(self, binned,
                            num_iteration: Optional[int] = None,
                            start_iteration: int = 0,
                            device_packed: bool = False) -> np.ndarray:
        """Per-tree leaf indices [rung, t_real] via the depth walk —
        the ``pred_leaf`` serving endpoint (reference: PredictLeafIndex).
        The walk already computes the final node ids for every predict;
        this returns them rung-padded so per-request slicing stays on
        the host (the coalescer's zero-recompile contract)."""
        tb, _, engine = self._predict_cfg()
        st, t_real, depth, c = self._device_trees_entry(
            num_iteration, start_iteration, tb)
        if t_real == 0:
            return np.zeros((binned.shape[0], 0), np.int32)
        dev, packed = self._serving_device_request(binned, device_packed)
        nan_a, cat_a = self._pred_route_args()
        eng = self._resolve_serving_engine(engine, depth)
        if eng == "level":
            lv = predict_leaf_level(
                dev, self._level_state(c, depth), depth=max(1, depth),
                tbatch=tb, any_cat=self._pred_any_cat, packed=packed)
        else:
            lv = predict_leaf_batched(
                dev, st, nan_a, cat_a, depth=depth_bucket(depth),
                tbatch=tb, any_cat=self._pred_any_cat, packed=packed)
        return np.asarray(lv)[:t_real].T          # [rung, t_real]

    def predict_raw_matrix(self, arr: np.ndarray,
                           num_iteration: Optional[int] = None,
                           start_iteration: int = 0,
                           early_stop=None) -> np.ndarray:
        if getattr(self, "_linear", False):
            from .linear import linear_leaf_outputs
            if early_stop is not None:
                log.warning(
                    "pred_early_stop is ignored with linear_tree models")
            self._flush_trees()
            if arr.ndim == 1:
                arr = arr.reshape(1, -1)
            leaves = self.predict_leaf_matrix(arr, num_iteration,
                                              start_iteration)
            models = self.models[start_iteration
                                 * self.num_tree_per_iteration:]
            if num_iteration is not None and num_iteration > 0:
                models = models[: num_iteration
                                * self.num_tree_per_iteration]
            k = self.num_tree_per_iteration
            out = np.zeros((k, arr.shape[0]), np.float64)
            for i, m in enumerate(models):
                out[i % k] += linear_leaf_outputs(m, arr, leaves[:, i])
            return out.astype(np.float32)
        return self.predict_raw_binned(self.bin_matrix(arr), num_iteration,
                                       start_iteration, early_stop)

    def predict_leaf_matrix(self, arr: np.ndarray,
                            num_iteration: Optional[int] = None,
                            start_iteration: int = 0) -> np.ndarray:
        """Per-row, per-tree leaf indices [N, T] via the walk engine
        (reference: PredictLeafIndex), bucketed like predict_raw_device."""
        binned = self.bin_matrix(arr)
        n = binned.shape[0]
        nan_a, cat_a = self._pred_route_args()
        tb, ladder, engine = self._predict_cfg()
        if engine == "scan":
            from ..ops.predict import predict_leaf_index
            trees, _ = self._device_trees_plain(num_iteration,
                                                start_iteration)
            return np.asarray(predict_leaf_index(
                jnp.asarray(binned), trees, nan_a, cat_a)).T
        st, t_real, depth, c = self._device_trees_entry(
            num_iteration, start_iteration, tb)
        if t_real == 0 or n == 0:
            return np.zeros((n, t_real), np.int32)
        packed = self._pred_pack4
        eng = self._resolve_serving_engine(engine, depth)
        top = ladder[-1]
        parts = []
        for a in range(0, n, top):
            sl = binned[a:a + top]
            rung = bucket_rows(sl.shape[0], ladder)
            dev = self._pad_request_to_bucket(sl, rung, packed)
            if eng == "level":
                lv = predict_leaf_level(
                    dev, self._level_state(c, depth),
                    depth=max(1, depth), tbatch=tb,
                    any_cat=self._pred_any_cat, packed=packed)
            else:
                lv = predict_leaf_batched(
                    dev, st, nan_a, cat_a, depth=depth_bucket(depth),
                    tbatch=tb, any_cat=self._pred_any_cat, packed=packed)
            parts.append(np.asarray(lv)[:t_real, :sl.shape[0]])
        return np.concatenate(parts, axis=1).T

    @property
    def current_iteration(self) -> int:
        return self.num_total_trees // max(self.num_tree_per_iteration, 1)

    # -- feature importance (reference: GBDT::FeatureImportance, gbdt.cpp) ---
    def feature_importance(self, importance_type: str = "split",
                           iteration: Optional[int] = None) -> np.ndarray:
        num_features = getattr(self, "_num_orig_features", None) \
            or int(self.binned.shape[1]) if hasattr(self, "binned") \
            else max((int(m.split_feature.max(initial=-1)) + 1)
                     for m in self.models) if self.models else 0
        out = np.zeros(num_features, np.float64)
        self._flush_trees()
        models = self.models
        if iteration is not None and iteration > 0:
            models = models[: iteration * self.num_tree_per_iteration]
        for m in models:
            for i in range(m.num_nodes):
                f = int(m.split_feature[i])
                if f < 0:
                    continue
                if importance_type == "split":
                    out[f] += 1.0
                else:
                    out[f] += max(float(m.split_gain[i]), 0.0)
        return out
