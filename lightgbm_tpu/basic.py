"""Public ``Dataset`` / ``Booster`` API.

Mirror of the reference's Python binding surface
(reference: python-package/lightgbm/basic.py — class Dataset :1900+
[`construct` :2517, `_lazy_init` :2102, `create_valid` :2454], class Booster
:3586 [`update` :4092, `predict` :4701, `rollback_one_iter`, `eval` family,
`save_model`, `feature_importance`]).

Unlike the reference there is no C API / ctypes boundary: the Booster drives the
JAX GBDT directly (boosting/gbdt.py). The binned dataset and all scores live in
TPU HBM; this layer only does host-side bookkeeping.
"""
from __future__ import annotations

import copy
import json
import os
import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Union
from typing import Sequence as _Seq

import numpy as np

from .config import Config, alias_table
from .io.dataset import BinnedDataset, Metadata
from .metrics import create_metrics
from .objectives import create_objective
from .utils import log
from .utils.rwlock import RWLock, read_locked, write_locked

_ArrayLike = Any


class Sequence:
    """Generic random-access data interface for streaming Dataset
    construction (reference: lightgbm.Sequence, python-package/lightgbm/
    basic.py:915). Subclasses implement ``__getitem__`` (int -> one row
    [F]; slice -> batch [K, F]) and ``__len__``; ``batch_size`` controls
    the streaming read granularity. The raw [N, F] matrix is never
    materialized — sampling uses random row access, construction reads
    ``batch_size`` rows at a time."""

    batch_size = 4096

    def __getitem__(self, idx):  # pragma: no cover - abstract
        raise NotImplementedError("Sequence subclasses implement __getitem__")

    def __len__(self):  # pragma: no cover - abstract
        raise NotImplementedError("Sequence subclasses implement __len__")


def _as_sequences(data):
    """data as a list of Sequence objects, or None when not Sequence-like."""
    if isinstance(data, Sequence):
        return [data]
    if isinstance(data, (list, tuple)) and data \
            and all(isinstance(s, Sequence) for s in data):
        return list(data)
    return None


class Dataset:
    """Training/validation data container (reference: Dataset, basic.py:1900).

    Lazily constructed: binning happens at ``construct()`` (first use by
    ``train``), so parameters passed at Booster creation can still influence it
    — same two-phase design as the reference.
    """

    def __init__(
        self,
        data: _ArrayLike,
        label: Optional[_ArrayLike] = None,
        reference: Optional["Dataset"] = None,
        weight: Optional[_ArrayLike] = None,
        group: Optional[_ArrayLike] = None,
        init_score: Optional[_ArrayLike] = None,
        feature_name: Union[str, _Seq[str]] = "auto",
        categorical_feature: Union[str, _Seq] = "auto",
        params: Optional[Dict[str, Any]] = None,
        free_raw_data: bool = True,
        position: Optional[_ArrayLike] = None,
    ):
        # shared-state discipline (reference: the C API's yamc shared mutex,
        # src/c_api.cpp:163): public methods below are @read_locked /
        # @write_locked against this lock; tpulint R007 enforces coverage
        self._api_lock = RWLock()
        self.data = data
        self.label = label
        self.reference = reference
        self.weight = weight
        self.group = group
        self.init_score = init_score
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature
        self.params = copy.deepcopy(params) if params else {}
        self.free_raw_data = free_raw_data
        self.position = position
        self._inner: Optional[BinnedDataset] = None
        self.used_indices: Optional[np.ndarray] = None

    # binning-relevant parameters a Booster forwards into a not-yet-constructed
    # Dataset (reference: Dataset._update_params, python-package basic.py —
    # train()/Booster() push their params into the lazily-built Dataset)
    _DATASET_PARAM_KEYS = (
        "max_bin", "min_data_in_bin", "bin_construct_sample_cnt",
        "use_missing", "zero_as_missing", "data_random_seed",
        "feature_pre_filter", "max_bin_by_feature", "linear_tree",
        "forcedbins_filename", "enable_bundle", "max_conflict_rate")

    def _update_params(self, params: Optional[Dict[str, Any]]) -> "Dataset":
        """Merge binning params from a Booster into a not-yet-constructed
        Dataset — training params win, matching the reference's
        ``self.params.update(params)`` (reference: Dataset._update_params,
        python-package/lightgbm/basic.py). Once constructed, differing values
        only warn."""
        if not params:
            return self
        at = alias_table()
        # canonical names take priority over their aliases when both appear
        # (reference: _ConfigAliases / Config::Set alias resolution)
        incoming = {}
        for pass_aliases in (True, False):
            for key, value in params.items():
                canon = at.get(key, key)
                is_alias = key != canon
                if canon in self._DATASET_PARAM_KEYS and is_alias == pass_aliases:
                    incoming[canon] = value
        if not incoming:
            return self
        if self._inner is None:
            self.params.update(incoming)
        else:
            cfg = Config(self.params)
            for key, value in incoming.items():
                current = cfg.get(key)
                if Config({key: value}).get(key) != current:
                    log.warning(
                        f"Dataset was already constructed with {key}="
                        f"{current!r}; training parameter {key}={value!r} is "
                        "ignored (reconstruct the Dataset to change binning)")
        return self

    # -- construction --------------------------------------------------------
    @write_locked
    def construct(self) -> "Dataset":
        """(reference: Dataset.construct, basic.py:2517)"""
        if self._inner is not None:
            return self
        from .obs.spans import span
        with span("construct"):
            return self._construct()

    def _construct(self) -> "Dataset":
        cfg = Config(self.params)
        if isinstance(self.data, str) and (self.data.endswith(".npz")
                                           or self.data.endswith(".bin")):
            # binary dataset reload (reference: DatasetLoader::LoadFromBinFile)
            self._inner = BinnedDataset.load_binary(self.data)
            md = self._inner.metadata
            if self.label is not None:
                md.set_label(_maybe_series(self.label))
            if self.weight is not None:
                md.set_weight(_maybe_series(self.weight))
            if self.group is not None:
                md.set_group(self.group)
            if self.init_score is not None:
                md.set_init_score(self.init_score)
            if self.position is not None:
                md.set_position(self.position)
            if self.free_raw_data:
                self.data = None
            return self
        ref_inner = None
        if self.reference is not None:
            self.reference.construct()
            ref_inner = self.reference._inner
        feature_names = (
            None if self.feature_name == "auto" else list(self.feature_name))
        cat = (None if self.categorical_feature == "auto"
               else self.categorical_feature)
        seqs = _as_sequences(self.data)
        if seqs is not None:
            self._inner = BinnedDataset.construct_from_sequences(
                seqs,
                max_bin=cfg.max_bin,
                min_data_in_bin=cfg.min_data_in_bin,
                bin_construct_sample_cnt=cfg.bin_construct_sample_cnt,
                use_missing=cfg.use_missing,
                zero_as_missing=cfg.zero_as_missing,
                categorical_feature=cat,
                feature_names=feature_names,
                data_random_seed=cfg.get("data_random_seed", 1),
                reference=ref_inner,
                forcedbins_filename=str(
                    cfg.get("forcedbins_filename", "") or ""),
                max_bin_by_feature=cfg.get("max_bin_by_feature"),
                enable_bundle=bool(cfg.get("enable_bundle", True)),
                max_conflict_rate=float(cfg.get("max_conflict_rate", 1e-4)),
            )
            self._finish_metadata()
            if self.free_raw_data:
                self.data = None
            return self
        self._inner = BinnedDataset.construct(
            self.data,
            max_bin=cfg.max_bin,
            min_data_in_bin=cfg.min_data_in_bin,
            bin_construct_sample_cnt=cfg.bin_construct_sample_cnt,
            use_missing=cfg.use_missing,
            zero_as_missing=cfg.zero_as_missing,
            categorical_feature=cat,
            feature_names=feature_names,
            data_random_seed=cfg.get("data_random_seed", 1),
            reference=ref_inner,
            # linear leaves fit against raw values (reference keeps raw data
            # when linear_tree is set, dataset.h raw_data_)
            keep_raw=not self.free_raw_data
            or bool(cfg.get("linear_tree", False)),
            forcedbins_filename=str(cfg.get("forcedbins_filename", "") or ""),
            max_bin_by_feature=cfg.get("max_bin_by_feature"),
            enable_bundle=bool(cfg.get("enable_bundle", True)),
            max_conflict_rate=float(
                cfg.get("max_conflict_rate", 1e-4)),
        )
        self._finish_metadata()
        if self.free_raw_data:
            self.data = None
        return self

    def _finish_metadata(self) -> None:
        md = self._inner.metadata
        if self.label is not None:
            md.set_label(_maybe_series(self.label))
        md.set_weight(_maybe_series(self.weight))
        if self.group is not None:
            md.set_group(self.group)
        md.set_init_score(self.init_score)
        md.set_position(self.position)

    @write_locked
    def subset(self, used_indices, params=None) -> "Dataset":
        """Row-subset Dataset sharing this dataset's bin mappers
        (reference: Dataset.subset, python-package basic.py ->
        LGBM_DatasetGetSubset, c_api.cpp; used by cv folds and sklearn).

        The parent must be constructed; the subset re-uses its binned rows
        directly (no re-binning), so bin boundaries match exactly."""
        self.construct()
        # sorted unique indices: group reconstruction and row extraction
        # must agree on order (the reference sorts used_indices the same way)
        idx = np.unique(np.asarray(used_indices, np.int64).reshape(-1))
        inner = self._inner
        sub = Dataset.__new__(Dataset)   # bypasses __init__: lock it here
        sub._api_lock = RWLock()
        sub.data = None
        sub.label = None
        sub.reference = self
        sub.weight = None
        sub.group = None
        sub.init_score = None
        sub.feature_name = self.feature_name
        sub.categorical_feature = self.categorical_feature
        sub.params = copy.deepcopy(params or self.params)
        sub.free_raw_data = self.free_raw_data
        sub.position = None
        sub.used_indices = idx
        si = BinnedDataset()
        si.binned = inner.binned[idx]
        si.bundle_info = inner.bundle_info
        si.mappers = inner.mappers
        si.feature_names = inner.feature_names
        si.max_num_bins = inner.max_num_bins
        si.num_data = len(idx)
        si.num_total_features = inner.num_total_features
        si.used_features = inner.used_features
        si.categorical_features = inner.categorical_features
        if inner.raw_data is not None:
            si.raw_data = inner.raw_data[idx]
        md = Metadata(len(idx))
        src = inner.metadata
        if src.label is not None:
            md.set_label(src.label[idx])
        if src.weight is not None:
            md.set_weight(src.weight[idx])
        if src.init_score is not None:
            isc = np.asarray(src.init_score)
            md.set_init_score(isc[idx] if isc.ndim == 2
                              else (isc[idx] if isc.size == src.num_data
                                    else isc.reshape(-1, src.num_data)
                                    [:, idx].reshape(-1)))
        if src.position is not None:
            md.set_position(src.position[idx])
        if src.query_boundaries is not None:
            # rebuild per-query sizes from the selected rows; a subset that
            # splits a query apart cannot keep valid ranking structure
            # (reference: Metadata partitioning, CheckOrPartition)
            qb = src.query_boundaries
            qid = np.searchsorted(qb, idx, side="right") - 1
            sizes = np.bincount(qid, minlength=len(qb) - 1)
            full = np.diff(qb)
            partial = (sizes > 0) & (sizes != full)
            if partial.any():
                raise ValueError(
                    "Dataset.subset would split query groups "
                    f"{np.nonzero(partial)[0][:5].tolist()}...; ranking "
                    "subsets must select whole queries")
            md.set_group(sizes[sizes > 0])
        si.metadata = md
        sub._inner = si
        return sub

    @read_locked
    def create_valid(self, data, label=None, weight=None, group=None,
                     init_score=None, params=None, position=None) -> "Dataset":
        """(reference: Dataset.create_valid, basic.py:2454)"""
        return Dataset(
            data, label=label, reference=self, weight=weight, group=group,
            init_score=init_score, params=params or self.params,
            free_raw_data=self.free_raw_data, position=position)

    # -- setters (reference: set_field family) -------------------------------
    @write_locked
    def set_label(self, label) -> "Dataset":
        self.label = label
        if self._inner is not None:
            self._inner.metadata.set_label(_maybe_series(label))
        return self

    @write_locked
    def set_weight(self, weight) -> "Dataset":
        self.weight = weight
        if self._inner is not None:
            self._inner.metadata.set_weight(_maybe_series(weight))
        return self

    @write_locked
    def set_group(self, group) -> "Dataset":
        self.group = group
        if self._inner is not None:
            self._inner.metadata.set_group(group)
        return self

    @write_locked
    def set_init_score(self, init_score) -> "Dataset":
        self.init_score = init_score
        if self._inner is not None:
            self._inner.metadata.set_init_score(init_score)
        return self

    @write_locked
    def set_position(self, position) -> "Dataset":
        self.position = position
        if self._inner is not None:
            self._inner.metadata.set_position(position)
        return self

    @write_locked
    def save_binary(self, filename: str) -> "Dataset":
        """Persist the constructed dataset (reference: Dataset.save_binary ->
        LGBM_DatasetSaveBinary; reload by passing the file path as data)."""
        self.construct()
        self._inner.save_binary(filename)
        return self

    @read_locked
    def get_label(self):
        if self._inner is not None and self._inner.metadata.label is not None:
            return self._inner.metadata.label
        return self.label

    @read_locked
    def get_weight(self):
        if self._inner is not None:
            return self._inner.metadata.weight
        return self.weight

    @read_locked
    def get_group(self):
        if self._inner is not None:
            return self._inner.metadata.group
        return self.group

    @read_locked
    def get_init_score(self):
        if self._inner is not None:
            return self._inner.metadata.init_score
        return self.init_score

    @read_locked
    def get_field(self, name):
        getter = {"label": self.get_label, "weight": self.get_weight,
                  "group": self.get_group, "init_score": self.get_init_score}
        if name not in getter:
            raise KeyError(name)
        return getter[name]()

    @write_locked
    def set_field(self, name, value):
        setter = {"label": self.set_label, "weight": self.set_weight,
                  "group": self.set_group, "init_score": self.set_init_score,
                  "position": self.set_position}
        if name not in setter:
            raise KeyError(name)
        return setter[name](value)

    @read_locked
    def num_data(self) -> int:
        if self._inner is not None:
            return self._inner.num_data
        seqs = _as_sequences(self.data)
        if seqs is not None:
            return int(sum(len(s) for s in seqs))
        arr = np.asarray(self.data if not hasattr(self.data, "values")
                         else self.data.values)
        return arr.shape[0]

    @read_locked
    def num_feature(self) -> int:
        if self._inner is not None:
            return self._inner.num_total_features
        seqs = _as_sequences(self.data)
        if seqs is not None:
            probe = next((s for s in seqs if len(s)), None)
            return (int(np.asarray(probe[0]).reshape(-1).shape[0])
                    if probe is not None else 0)
        arr = np.asarray(self.data if not hasattr(self.data, "values")
                         else self.data.values)
        return arr.shape[1] if arr.ndim == 2 else 1

    @write_locked
    def get_feature_name(self) -> List[str]:
        self.construct()
        return list(self._inner.feature_names)


def _maybe_series(x):
    if x is None:
        return None
    if hasattr(x, "tocsr") and hasattr(x, "toarray"):  # scipy.sparse
        return x.toarray()
    if hasattr(x, "values"):
        return np.asarray(x.values)
    return np.asarray(x)


class Booster:
    """The trained/training model handle (reference: Booster, basic.py:3586)."""

    def __init__(
        self,
        params: Optional[Dict[str, Any]] = None,
        train_set: Optional[Dataset] = None,
        model_file: Optional[str] = None,
        model_str: Optional[str] = None,
    ):
        # every public method below holds this as reader or writer — the
        # reference's per-handle shared mutex (src/c_api.cpp:163); fixes
        # the predict/update race on the device-tree cache
        self._api_lock = RWLock()
        params = copy.deepcopy(params) if params else {}
        self.params = params
        self.best_iteration = -1
        self.best_score: Dict = {}
        self._train_data_name = "training"
        self._custom_objective: Optional[Callable] = None
        self._pending_finish = False

        if train_set is not None:
            if not isinstance(train_set, Dataset):
                raise TypeError("Training data should be a Dataset instance")
            train_set._update_params(params)
            # multi-host bootstrap must precede dataset construction: bin
            # mappers are synced across processes at construct time
            # (reference: Network::Init runs before LoadData,
            # application.cpp:88)
            from .parallel.multihost import maybe_init_distributed
            maybe_init_distributed(params)
            train_set.construct()
            from .obs.spans import span
            with span("booster_init"):
                self.config = Config(params)
                objective = self.config.objective
                if callable(objective):
                    self._custom_objective = objective
                    objective = None
                    obj = None
                else:
                    obj = create_objective(objective, self.config)
                from .boosting import create_boosting
                self._gbdt = create_boosting(self.config, train_set._inner,
                                             obj)
                self.train_set = train_set
                self._gbdt.set_train_metrics(
                    create_metrics(self.config.metric, self.config))
            self._valid_names: List[str] = []
        elif model_file is not None or model_str is not None:
            from .model_io import load_booster
            if model_file is not None:
                with open(model_file) as f:
                    model_str = f.read()
            load_booster(self, model_str, params)
        else:
            raise ValueError(
                "need at least one of train_set, model_file and model_str")

    # -- continue-training (reference: init_model -> gbdt.cpp:250-258) ------
    def _attach_pre_model(self, pre_model, pre_train_raw: np.ndarray) -> None:
        """Seed cached train scores with a loaded model's raw predictions and
        keep its value-space trees for prediction/saving."""
        self._pre_model = pre_model
        g = self._gbdt
        k, n = pre_train_raw.shape
        import jax.numpy as jnp
        if k != g.num_tree_per_iteration:
            raise ValueError(
                f"init_model has {k} trees/iteration, training config has "
                f"{g.num_tree_per_iteration}")
        g.train_score = g.train_score.at[:, :n].add(jnp.asarray(pre_train_raw))
        # suppress boost_from_average: scores already carry the loaded model
        g._has_init_score = True

    def _seed_valid_scores(self, which: int, pre_raw: np.ndarray) -> None:
        import jax.numpy as jnp
        vs = self._gbdt.valid_sets[which]
        vs.score = vs.score.at[:, : pre_raw.shape[1]].add(jnp.asarray(pre_raw))

    @read_locked
    def refit(self, data, label, decay_rate: Optional[float] = None,
              weight=None, **kwargs) -> "Booster":
        """Re-fit all leaf values on new data, keeping tree structures
        (reference: Booster.refit, basic.py -> GBDT::RefitTree gbdt.cpp:258:
        gradients computed once per iteration at the running score, and each
        leaf's value becomes decay*old + (1-decay)*shrinkage*(-ThL1(G)/(H+l2)))."""
        from .model_io import LoadedGBDT, loaded_to_string
        if kwargs:
            raise TypeError(
                f"refit got unsupported arguments: {sorted(kwargs)}")
        if decay_rate is None:
            decay_rate = float((self.config.get("refit_decay_rate", 0.9)
                                if self.config else 0.9))
        cfg = self.config or Config(self.params or {})
        lam1 = float(cfg.get("lambda_l1", 0.0))
        lam2 = float(cfg.get("lambda_l2", 0.0))
        loaded = LoadedGBDT(self.model_to_string())
        obj = loaded.objective
        if obj is None:
            raise ValueError("refit requires a model with a known objective")
        import jax.numpy as jnp
        X = np.asarray(_maybe_series(data), np.float64)
        y = np.asarray(_maybe_series(label), np.float64)
        md = Metadata(len(y))
        md.set_label(y)
        md.set_weight(_maybe_series(weight))
        obj.init(md, len(y))
        k = loaded.num_tree_per_iteration
        score = np.zeros((k, len(y)), np.float64)
        for it in range(len(loaded.models) // k):
            # gradients once per iteration (reference: gbdt.cpp:279-281)
            sc = score[0] if k == 1 else score
            g, h = obj.get_gradients(jnp.asarray(sc, jnp.float32))
            g = np.asarray(g, np.float64).reshape(k, -1)
            h = np.asarray(h, np.float64).reshape(k, -1)
            for cls in range(k):
                t = loaded.models[it * k + cls]
                leaf = t.route(X)
                nl = t.num_leaves
                gs = np.bincount(leaf, weights=g[cls], minlength=nl)
                hs = np.bincount(leaf, weights=h[cls], minlength=nl)
                thr = np.sign(gs) * np.maximum(np.abs(gs) - lam1, 0.0)
                new_val = -thr / (hs + lam2 + 1e-15) * t.shrinkage
                t.leaf_value = (decay_rate * t.leaf_value
                                + (1.0 - decay_rate) * new_val)
                score[cls] += t.leaf_value[leaf]
        return Booster(model_str=loaded_to_string(loaded))

    # -- training ------------------------------------------------------------
    @write_locked
    def add_valid(self, data: Dataset, name: str) -> "Booster":
        """(reference: Booster.add_valid, basic.py:3963)"""
        if not isinstance(data, Dataset):
            raise TypeError("Validation data should be a Dataset instance")
        # validation data MUST share the training BinMappers or tree split
        # bins would be meaningless on it (reference: Dataset._set_reference,
        # basic.py — train() rebinds valid sets to the train set silently)
        if data.reference is not self.train_set:
            if data._inner is not None \
                    and data._inner.mappers is self.train_set._inner.mappers:
                pass  # already constructed against the right mappers
            elif data.data is None and data._inner is not None:
                raise ValueError(
                    "validation Dataset was constructed without "
                    "reference=train_set and its raw data was freed; "
                    "create it with train_set.create_valid(...) or "
                    "free_raw_data=False")
            else:
                data.reference = self.train_set
                data._inner = None  # force re-binning with train mappers
        data.construct()
        metrics = create_metrics(self.config.metric, self.config)
        self._gbdt.add_valid(data._inner, name, metrics)
        self._valid_names.append(name)
        return self

    @write_locked
    def update(self, train_set: Optional[Dataset] = None,
               fobj: Optional[Callable] = None) -> bool:
        """One boosting iteration; True if no further splits were possible
        (reference: Booster.update, basic.py:4092)."""
        if train_set is not None:
            raise NotImplementedError(
                "changing train_set on update is not supported")
        from .analysis.guards import compile_phase
        from .obs.spans import bump, span
        fobj = fobj or self._custom_objective
        t0 = time.perf_counter()
        # the update's span: the spans inside it carry the booster's
        # iter_, and it holds the counters the closing tick reports
        with span("iteration", iteration=self._gbdt.iter_):
            # every compile inside an update is attributed to the
            # train_step phase (guards.compile_counter by_phase, the
            # metrics plane, and the flight recorder all key on it)
            with compile_phase("train_step"):
                if fobj is not None:
                    grad, hess = _call_custom_objective(fobj, self)
                    finished = self._gbdt.train_one_iter(grad, hess)
                else:
                    finished = self._gbdt.train_one_iter()
            # sampled per-rank attribution (obs/ranks.py): at the
            # tpu_rank_stats_every cadence ONLY, block on the step's
            # device work so step_s is a real measurement (not
            # dispatch), then let the rank-stats plane probe the
            # collective and publish; off-sample iterations take neither
            # the block nor the probe, so the steady-state 0-d2h guard
            # holds between samples. The tick's seconds are captured
            # BEFORE sample_step: the sampling overhead (barrier wait
            # for a slow peer, the rank-0 KV gather) must not inflate
            # the metrics stream's iteration wall
            # span `update_tick`: the update's own telemetry, the last
            # thing in it (its seconds are in no `phase_s`: the tick
            # writes that table before this span closes)
            with span("update_tick"):
                rank_stats = getattr(self._gbdt, "_rank_stats", None)
                if rank_stats is not None and rank_stats.due(
                        self._gbdt.iter_):
                    import jax
                    bump("host_syncs")
                    jax.block_until_ready(self._gbdt.train_score)
                    elapsed = time.perf_counter() - t0
                    rank_stats.sample_step(self._gbdt.iter_, elapsed)
                else:
                    elapsed = time.perf_counter() - t0
                self._gbdt._obs_iteration_tick(elapsed)
        # a stop detected by a mid-training flush (e.g. in reset_parameter)
        pending, self._pending_finish = self._pending_finish, False
        return finished or pending

    @write_locked
    def rollback_one_iter(self) -> "Booster":
        self._gbdt.rollback_one_iter()
        return self

    @write_locked
    def reset_parameter(self, params: Dict[str, Any]) -> "Booster":
        """(reference: Booster.reset_parameter → GBDT::ResetConfig gbdt.cpp:795)"""
        self.params.update(params)
        self.config.set(params)
        gbdt = self._gbdt
        gbdt.learning_rate = float(self.config.learning_rate)
        gbdt.shrinkage_rate = gbdt.learning_rate
        old_gp = gbdt.grower_params
        from .boosting.gbdt import bucketed_tree_shape
        from .engines import registry as engine_registry
        # re-resolve EVERY engine knob through the registry from the
        # JUST-updated config, not the _setup_train-era attributes —
        # reset_parameter({"tpu_step_buckets": "off"}) must actually take
        # the exact-keyed escape hatch and a hist-overlap/mbatch/layout
        # toggle must not be a silent no-op
        resolved = engine_registry.resolve(
            self.config, shape=getattr(gbdt, "_engine_shape", None))
        gbdt._engine_resolution = resolved
        gbdt._step_buckets = resolved.step_buckets
        key_leaves, key_depth = bucketed_tree_shape(
            gbdt._step_buckets,
            int(self.config.num_leaves), int(self.config.max_depth))
        gbdt._max_depth_cfg = int(self.config.max_depth)
        resolved_fb, resolved_mbatch = (resolved.fused_block,
                                        resolved.hist_mbatch)
        clamp_ctx = getattr(gbdt, "_fused_clamp_ctx", None)
        if resolved_fb and clamp_ctx:
            # the compact row layout is already built: re-run the SAME
            # record-width clamp _setup_compact_state applied
            resolved_fb, resolved_mbatch = engine_registry.fit_fused_flush(
                resolved, clamp_ctx["num_cols"], clamp_ctx["num_bins"],
                clamp_ctx["num_features"],
                env_override=os.environ.get("LGBM_TPU_FUSED_BS", ""))
        if resolved.fused_block and not resolved_fb:
            # the clamp took the fused kernel off: the standalone depth
            resolved_mbatch = engine_registry.resolve_mbatch(self.config)
        gbdt.grower_params = gbdt.grower_params._replace(
            num_leaves=key_leaves,
            max_depth=key_depth,
            step_buckets=gbdt._step_buckets,
            hist_overlap=resolved.hist_overlap,
            hist_impl=resolved.hist_impl,
            hist_mbatch=resolved_mbatch,
            hist_layout=resolved.hist_layout,
            fused_block=resolved_fb,
            lambda_l1=float(self.config.lambda_l1),
            lambda_l2=float(self.config.lambda_l2),
            min_data_in_leaf=float(self.config.min_data_in_leaf),
            min_sum_hessian_in_leaf=float(self.config.min_sum_hessian_in_leaf),
            min_gain_to_split=float(self.config.min_gain_to_split),
            max_delta_step=float(self.config.max_delta_step),
        )
        gbdt.max_leaves = int(self.config.num_leaves)
        gbdt.feature_fraction = float(self.config.feature_fraction)
        if gbdt.grower_params != old_gp:
            # the step fns close over grower_params; rebuild only on actual
            # change — learning_rate (the common per-iteration schedule) is a
            # runtime argument, and an unconditional invalidation would force
            # an XLA recompile every iteration
            gbdt._step_fn = None
            if getattr(gbdt, "_compact", None) is not None:
                # flush trees grown under the old num_leaves first so the
                # pending-tree stack never mixes shapes; a no-split stop
                # detected here must reach the engine loop, not be dropped
                self._pending_finish = gbdt._flush_trees() or \
                    self._pending_finish
                gbdt._compact["step"] = None
        return self

    # -- checkpoint / resume (io/checkpoint.py) ------------------------------
    def _capture_checkpoint(self, callback_states: Optional[Dict] = None
                            ) -> Dict[str, Any]:
        """Complete training-state snapshot dict (gbdt state + the
        booster-level early-stopping bests + engine callback states)."""
        state = self._gbdt.capture_training_state()
        state["best_iteration"] = int(self.best_iteration)
        state["best_score"] = copy.deepcopy(self.best_score)
        if callback_states:
            state["callbacks"] = callback_states
        return state

    @read_locked
    def save_checkpoint(self, directory: str, keep: int = 3,
                        callback_states: Optional[Dict] = None):
        """Write an atomic training snapshot to ``directory``.

        Pending device trees flush first (one batched transfer), then the
        complete state lands via write-temp-fsync-rename with a checksum
        and keep-last-``keep`` rotation (io/checkpoint.py). Multi-host:
        every process participates in the (collective) state fetch but
        only process 0 writes — all ranks resume from the one file.
        Returns the snapshot path (None on non-writing ranks)."""
        from .io.checkpoint import write_snapshot
        self._gbdt._flush_trees()
        state = self._capture_checkpoint(callback_states)
        import jax
        if jax.process_index() != 0:
            return None
        return write_snapshot(directory, int(state["iteration"]), state,
                              keep=keep)

    @write_locked
    def _restore_checkpoint(self, state: Dict[str, Any],
                            callbacks=None) -> None:
        """Rebind this booster to a snapshot (raises ValueError when the
        snapshot is structurally incompatible with this run)."""
        reason = self._gbdt.snapshot_compatible(state)
        if reason is not None:
            raise ValueError(reason)
        self._gbdt.restore_training_state(state)
        self.best_iteration = int(state.get("best_iteration", -1))
        self.best_score = state.get("best_score", {}) or {}
        saved = state.get("callbacks") or {}
        for cb in callbacks or ():
            key = getattr(cb, "_ckpt_key", None)
            cb_state = getattr(cb, "state", None)
            if key and key in saved and isinstance(cb_state, dict):
                cb_state.clear()
                cb_state.update(copy.deepcopy(saved[key]))

    # -- evaluation ----------------------------------------------------------
    @write_locked
    def eval_train(self, feval=None):
        out = self._gbdt.eval_train()
        out = [(self._train_data_name, m, v, hb) for (_, m, v, hb) in out]
        if feval is not None:
            out.extend(self._eval_custom(feval, self._train_data_name, "train"))
        return out

    @write_locked
    def eval_valid(self, feval=None):
        out = self._gbdt.eval_valid()
        if feval is not None:
            for i, name in enumerate(self._valid_names):
                out.extend(self._eval_custom(feval, name, i))
        return out

    def _eval_custom(self, feval, name, which):
        fevals = feval if isinstance(feval, (list, tuple)) else [feval]
        if which == "train":
            from .parallel.multihost import to_host
            raw = to_host(self._gbdt.train_score)
            if getattr(self._gbdt, "_compact", None) is not None:
                # compact grower keeps train scores in a permuted row order;
                # user fevals see the dataset's original order
                perm = self._gbdt._compact_perm()
                unperm = np.empty_like(raw)
                unperm[:, perm] = raw
                raw = unperm[:, :self._gbdt._n_real]
            data = self.train_set
        else:
            vs = self._gbdt.valid_sets[which]
            raw = np.asarray(vs.score)
            data = _DatasetView(vs.dataset)
        # multiclass preds are handed to custom metrics as [n, K], matching
        # the reference's documented feval contract (sklearn.py/engine.py)
        preds = raw[0] if raw.shape[0] == 1 else raw.T
        out = []
        for f in fevals:
            res = f(preds, data)
            if isinstance(res, list):
                for metric, value, hb in res:
                    out.append((name, metric, value, hb))
            else:
                metric, value, hb = res
                out.append((name, metric, value, hb))
        return out

    # -- prediction ----------------------------------------------------------
    @read_locked
    def predict(
        self,
        data: _ArrayLike,
        start_iteration: int = 0,
        num_iteration: Optional[int] = None,
        raw_score: bool = False,
        pred_leaf: bool = False,
        pred_contrib: bool = False,
        validate_features: bool = False,
        **kwargs,
    ) -> np.ndarray:
        """(reference: Booster.predict, basic.py:4701 → Predictor)"""
        inner = self._gbdt
        start_iteration, num_iteration = self._predict_window(
            start_iteration, num_iteration)
        arr = np.asarray(_maybe_series(data), dtype=np.float64)
        (pre, pre_start, pre_cut, own_start, own_cut, pre_empty,
         own_empty) = self._global_tree_window(start_iteration,
                                               num_iteration)
        if pred_leaf:
            own = (inner.predict_leaf_matrix(arr, own_cut, own_start)
                   if not own_empty else None)
            if not pre_empty:
                pre_leaf = pre.predict_leaf_matrix(arr, pre_cut, pre_start)
                own = (pre_leaf if own is None
                       else np.concatenate([pre_leaf, own], axis=1))
            return own
        if pred_contrib:
            return self._predict_contrib(arr, num_iteration, start_iteration)
        early = self._predict_early_stop(kwargs)
        raw = (inner.predict_raw_matrix(arr, own_cut, own_start, early)
               if not own_empty else None)   # [K, N]
        if not pre_empty:
            pre_raw = pre.predict_raw_matrix(arr, pre_cut, pre_start)
            raw = pre_raw if raw is None else raw + pre_raw
        if raw is None:
            raw = np.zeros((max(inner.num_tree_per_iteration, 1),
                            arr.shape[0]), np.float32)
        k = raw.shape[0]
        if raw_score or inner.objective is None:
            return raw[0] if k == 1 else raw.T
        conv = np.asarray(inner.objective.convert_output(
            raw.T if k > 1 else raw[0]))
        return conv

    @read_locked
    def predict_device(self, data: _ArrayLike,
                       start_iteration: int = 0,
                       num_iteration: Optional[int] = None):
        """Serve raw scores WITHOUT materializing them on the host.

        Bins the request, routes it through the bucketed inference engine
        (ops/predict.py) and returns a device-resident ``jax.Array`` —
        ``[N]`` raw scores for binary/regression, ``[N, K]`` for
        multiclass — for downstream device pipelines to consume in HBM.
        Steady-state calls (warm bucket rung) compile nothing; the only
        transfers are the request upload and the final [K, rung] -> [K, N]
        device-side slice. Loaded-from-file models predict on the host
        path and are not supported here."""
        inner = self._device_serving_inner()
        start_iteration, num_iteration = self._predict_window(
            start_iteration, num_iteration)
        arr = np.asarray(_maybe_series(data), dtype=np.float64)
        if arr.ndim == 1:
            arr = arr.reshape(1, -1)
        n = arr.shape[0]
        import jax.numpy as jnp
        binned = inner.bin_matrix(arr)
        _, ladder, engine = inner._predict_cfg()
        from .ops.predict import bucket_rows
        if (engine != "scan" and bucket_rows(n, ladder) is None
                and not inner._can_shard_predict(n, ladder)):
            # above the ladder with no mesh: device-side concat of
            # max-rung slices, each through the warm max-rung program
            top = ladder[-1]
            parts = [inner.predict_raw_device(
                binned[a:a + top], num_iteration,
                start_iteration)[:, :min(top, n - a)]
                for a in range(0, n, top)]
            raw = jnp.concatenate(parts, axis=1)
        else:
            raw = inner.predict_raw_device(binned, num_iteration,
                                           start_iteration)[:, :n]
        if inner.average_output:
            raw = raw / inner._average_divisor(num_iteration,
                                               start_iteration)
        return raw[0] if raw.shape[0] == 1 else raw.T

    def _global_tree_window(self, start_iteration: int,
                            num_iteration: Optional[int]):
        """Split a (start, num) iteration window across the loaded base
        model and this booster's own trees — global tree-window semantics
        (reference: models_ holds loaded-then-new trees in order and
        start/num address that sequence). THE one implementation behind
        predict() and _predict_contrib(); returns ``(pre, pre_start,
        pre_cut, own_start, own_cut, pre_empty, own_empty)`` with
        ``None`` cuts meaning "to the end"."""
        pre = getattr(self, "_pre_model", None)
        pre_iters = pre.current_iteration if pre is not None else 0
        end = (start_iteration + num_iteration
               if num_iteration is not None and num_iteration > 0 else None)
        pre_start = min(start_iteration, pre_iters)
        pre_cut = (max(min(end, pre_iters) - pre_start, 0)
                   if end is not None else None)
        own_start = max(start_iteration - pre_iters, 0)
        own_cut = (max(end - pre_iters - own_start, 0)
                   if end is not None else None)
        pre_empty = pre is None or pre_start >= pre_iters or pre_cut == 0
        return (pre, pre_start, pre_cut, own_start, own_cut, pre_empty,
                own_cut == 0)

    def _predict_window(self, start_iteration: int,
                        num_iteration: Optional[int]):
        """Params-level prediction-window resolution shared by every
        prediction entry (reference: start_iteration_predict /
        num_iteration_predict, config.h predict section; default window
        cuts at best_iteration after early-stopped training)."""
        src = self.params or {}
        if start_iteration == 0 and int(src.get("start_iteration_predict",
                                                0) or 0) > 0:
            start_iteration = int(src["start_iteration_predict"])
        if num_iteration is None and int(src.get("num_iteration_predict",
                                                 -1) or -1) > 0:
            num_iteration = int(src["num_iteration_predict"])
        if num_iteration is None:
            num_iteration = (self.best_iteration
                             if self.best_iteration > 0 else None)
        return start_iteration, num_iteration

    def _predict_early_stop(self, kwargs=None):
        """Resolved ``(margin, freq)`` pair or None: the pred_early_stop
        controls shared by predict() and predict_serving. The reference
        only early-stops classification predictions (predictor.hpp
        NeedAccuratePrediction gate)."""
        kwargs = kwargs or {}
        src = self.params or {}
        want = kwargs.get("pred_early_stop",
                          bool(src.get("pred_early_stop")))
        if not want:
            return None
        inner = self._gbdt
        obj_name = getattr(inner.objective, "name", "")
        if obj_name != "binary" and inner.num_tree_per_iteration <= 1:
            return None
        return (float(kwargs.get("pred_early_stop_margin",
                                 src.get("pred_early_stop_margin", 10.0))),
                int(kwargs.get("pred_early_stop_freq",
                               src.get("pred_early_stop_freq", 10))))

    def _device_serving_inner(self):
        """The trained GBDT behind the device serving fast path, or a
        ``NotImplementedError`` naming why this booster cannot take it
        (loaded-from-file and continue-trained models predict on the host
        path — see predict_device)."""
        inner = self._gbdt
        if not hasattr(inner, "predict_raw_device"):
            raise NotImplementedError(
                "device serving needs a trained booster (models loaded "
                "from file predict on the host path; use predict())")
        if getattr(self, "_pre_model", None) is not None:
            raise NotImplementedError(
                "device serving does not support continue-trained "
                "boosters (the loaded base model predicts on the host "
                "path); use predict()")
        return inner

    def _serving_request(self, data, start_iteration: int,
                         num_iteration: Optional[int]):
        """``(inner, start_iteration, num_iteration, arr32, n)`` — the
        request-normalization preamble shared by every serving endpoint
        (predict/leaf/contrib): window resolution and the float32 cast
        (the serving wire format) live HERE, once."""
        inner = self._device_serving_inner()
        start_iteration, num_iteration = self._predict_window(
            start_iteration, num_iteration)
        arr = np.asarray(_maybe_series(data), dtype=np.float32)
        if arr.ndim == 1:
            arr = arr.reshape(1, -1)
        return inner, start_iteration, num_iteration, arr, arr.shape[0]

    @staticmethod
    def _serving_binned(inner, arr32: np.ndarray):
        """Bins for one serving batch: the jitted device featurizer
        (default — returns the rung-padded device matrix, pack4 layout
        included) or the host ``bin_columns`` escape hatch
        (``tpu_serve_featurize=host``; predict_raw_device pads it)."""
        if inner._serve_featurize_mode() == "device":
            return inner.featurize_rung(arr32)
        return inner.bin_matrix(arr32)

    @read_locked
    def predict_serving(self, data: _ArrayLike, raw_score: bool = False,
                        start_iteration: int = 0,
                        num_iteration: Optional[int] = None,
                        observe=None):
        """One coalesced serving batch: ``(padded host scores, n_valid)``.

        The serving twin of :meth:`predict`: bins the request, routes it
        through the bucketed device engine, applies the objective's
        output conversion at the PADDED rung shape, and returns the
        padded host array — callers (serving/coalescer.py) slice their
        per-request rows on the host, so no device op ever carries a
        request-dependent shape. That is the coalescer's zero-recompile
        contract: :meth:`predict_device`'s device-side ``[:, :n]`` slice
        would lower one trivial program per distinct request size.

        Rows ``[:n_valid]`` of the result equal
        ``predict(float32(data))`` bit-for-bit (row routing, score sums,
        and the elementwise output conversion are all per-row
        independent, so padding rows change nothing; float32 is the
        serving wire format below). Shape ``[rung]`` for
        binary/regression, ``[rung, K]`` for multiclass. The request
        must fit the bucket ladder.

        Honors the same params-level controls predict() does — the
        start_iteration_predict / num_iteration_predict window and the
        pred_early_stop margin/freq approximation (both per-row
        independent, so parity survives batching).

        The serving wire format is raw float32 (requests cast here, in
        BOTH featurize modes, so flipping ``tpu_serve_featurize`` can
        never change a response): with the default ``device`` mode the
        request is ONE host->device copy of the padded raw f32 matrix —
        binning runs as a jitted program (ops/device_bin.py), bit-
        identical to the ``host`` escape hatch's ``bin_columns`` pass."""
        inner, start_iteration, num_iteration, arr, n = \
            self._serving_request(data, start_iteration, num_iteration)
        early = self._predict_early_stop()
        binned = self._serving_binned(inner, arr)
        raw_dev = inner.predict_raw_device(
            binned, num_iteration, start_iteration, early_stop=early,
            device_packed=inner._pred_pack4)              # [K, rung] device
        raw = np.asarray(raw_dev)                         # [K, rung] host
        if observe is not None:
            # drift window (obs/drift.py): pure on-device adds of the
            # tick's bins + raw margins, enqueued AFTER the response
            # materialized so the accumulates overlap the host-side
            # slice/complete work instead of sitting on the latency path
            observe.observe_binned(binned, n)
            observe.observe_scores(raw_dev, n)
        if inner.average_output:
            raw = raw / inner._average_divisor(num_iteration,
                                               start_iteration)
        k = raw.shape[0]
        out = raw[0] if k == 1 else raw.T
        if raw_score or inner.objective is None:
            return out, n
        # elementwise (sigmoid) / per-row (softmax) conversion on the
        # padded shape: one eager program per rung, warmed alongside the
        # predict program by warm_predict_ladder
        return np.asarray(inner.objective.convert_output(out)), n

    @read_locked
    def predict_leaf_serving(self, data: _ArrayLike,
                             start_iteration: int = 0,
                             num_iteration: Optional[int] = None,
                             observe=None):
        """One coalesced ``pred_leaf`` batch: ``(padded leaves, n_valid)``.

        The serving twin of ``predict(pred_leaf=True)`` (reference:
        PredictLeafIndex): the depth walk's final node ids, returned
        rung-padded ``[rung, T]`` so callers slice per-request rows on
        the host. Rows ``[:n_valid]`` equal the reference routing
        bit-for-bit — leaf-index embeddings for downstream rankers."""
        inner, start_iteration, num_iteration, arr, n = \
            self._serving_request(data, start_iteration, num_iteration)
        binned = self._serving_binned(inner, arr)
        out = inner.predict_leaf_padded(
            binned, num_iteration, start_iteration,
            device_packed=inner._pred_pack4)
        if observe is not None:
            observe.observe_binned(binned, n)
        return out, n

    @read_locked
    def predict_contrib_serving(self, data: _ArrayLike,
                                start_iteration: int = 0,
                                num_iteration: Optional[int] = None,
                                observe=None):
        """One coalesced ``pred_contrib`` batch:
        ``(padded [rung, K*(F+1)] contributions, n_valid)``.

        Exact TreeSHAP (Lundberg et al.; reference ``Tree::TreeSHAP``,
        src/io/tree.cpp) served from the device engine
        (ops/treeshap_device.py) through the same rung ladder as
        predict — matches the numpy reference within f32 tolerance and
        sums to the raw score per row."""
        inner, start_iteration, num_iteration, arr, n = \
            self._serving_request(data, start_iteration, num_iteration)
        binned = self._serving_binned(inner, arr)
        out = inner.predict_contrib_padded(
            binned, num_iteration, start_iteration,
            device_packed=inner._pred_pack4)
        if observe is not None:
            observe.observe_binned(binned, n)
        return out, n

    def _serve_endpoints(self) -> tuple:
        """Resolved ``tpu_serve_endpoints``: which request kinds this
        booster's servers warm and accept. ``predict`` is always on."""
        cfg = self._gbdt.config
        raw = str(cfg.get("tpu_serve_endpoints", "predict") or "predict")
        eps = {e.strip().lower() for e in raw.split(",") if e.strip()}
        unknown = eps - {"predict", "leaf", "contrib"}
        if unknown:
            log.warning(f"unknown tpu_serve_endpoints {sorted(unknown)}; "
                        "valid: predict, leaf, contrib")
            eps -= unknown
        eps.add("predict")
        return tuple(sorted(eps))

    @read_locked
    def warm_predict_ladder(self, max_rows: Optional[int] = None,
                            start_iteration: int = 0,
                            num_iteration: Optional[int] = None
                            ) -> Dict[str, Any]:
        """Pre-compile the serving bucket ladder; returns warmup stats.

        Pushes one dummy request per row rung (ops/predict.warmup_rungs)
        through the full serving path — binning, the bucketed predict
        program, and the output conversion — so a server that warms
        before taking traffic compiles NOTHING in steady state, and a
        hot-swap candidate warms before the swap commits. With
        ``tpu_compile_cache_dir`` set, a restarted process re-arms the
        whole ladder from the persistent cache with zero backend
        compiles (the returned ``cache`` counters prove it: hits ==
        requests, misses == 0 on a warm cache).

        Every endpoint in ``tpu_serve_endpoints`` warms per rung —
        predict always, plus the ``pred_leaf`` walk and the device
        TreeSHAP ``pred_contrib`` programs when enabled — so all three
        request kinds serve mixed batch sizes with zero steady-state
        compiles through the same ladder.

        Stats: ``rungs`` warmed, ``endpoints``, ``seconds``,
        ``lowerings`` / ``backend_compiles`` spent, and the
        persistent-cache ``cache`` ``{requests, hits, misses}``.
        ``max_rows`` caps the rung enumeration
        (``tpu_serve_warm_max_rows``); the scan escape-hatch engine
        recompiles per shape by design and reports ``skipped``."""
        import time as _time

        from .analysis import guards
        from .analysis.faultinject import active_plan
        from .ops.predict import parse_bucket_ladder, warmup_rungs
        inner = self._device_serving_inner()
        cfg = inner.config
        if str(cfg.get("tpu_predict_engine", "batched")).lower() == "scan":
            return {"rungs": [], "seconds": 0.0,
                    "skipped": "tpu_predict_engine=scan recompiles per "
                               "shape by design"}
        if max_rows is None:
            max_rows = int(cfg.get("tpu_serve_warm_max_rows", 0) or 0)
        ladder = parse_bucket_ladder(cfg.get("tpu_predict_buckets", "auto"))
        rungs = warmup_rungs(ladder, max_rows)
        from .obs import flight
        from .obs.spans import span
        n_feat = inner.train_set.num_total_features
        endpoints = self._serve_endpoints()
        plan = active_plan(cfg)
        t0 = _time.time()
        with guards.compile_counter() as cc, \
                guards.cache_counter() as cache, \
                guards.compile_phase("predict_warmup"):
            for rung in rungs:
                # ordinal-matched site (no iteration= kwarg): warmup=N
                # means the Nth rung warmed this process
                plan.fire("warmup", rung=rung)
                dummy = np.zeros((rung, n_feat), np.float32)
                with span("predict_warmup"):
                    self.predict_serving(dummy,
                                         start_iteration=start_iteration,
                                         num_iteration=num_iteration)
                    if "leaf" in endpoints:
                        self.predict_leaf_serving(
                            dummy, start_iteration=start_iteration,
                            num_iteration=num_iteration)
                    if "contrib" in endpoints:
                        self.predict_contrib_serving(
                            dummy, start_iteration=start_iteration,
                            num_iteration=num_iteration)
                flight.note("warmup_rung", rung=rung)
        return {"rungs": list(rungs), "endpoints": list(endpoints),
                "seconds": round(_time.time() - t0, 3),
                "lowerings": cc.lowerings,
                "backend_compiles": cc.backend_compiles,
                "cache": {"requests": cache.requests, "hits": cache.hits,
                          "misses": cache.misses}}

    @read_locked
    def serve(self, **kwargs):
        """Stand up a :class:`~lightgbm_tpu.serving.PredictionServer` on
        this booster: micro-batch coalescing over the bucket ladder,
        bounded admission, per-request deadlines, and hot-swap-ready
        model registry. Keyword arguments override the ``tpu_serve_*``
        config knobs (``tick_ms``, ``queue_max``, ``deadline_ms``,
        ``warm_max_rows``, ``warm``, ``version``); ``metrics_port``
        (or ``tpu_metrics_port``) exposes ``GET /metrics`` Prometheus
        text + ``/healthz`` over stdlib HTTP (obs/metrics.py)."""
        from .serving import PredictionServer
        return PredictionServer(self, **kwargs)

    def _predict_contrib(self, arr, num_iteration, start_iteration: int = 0):
        """Exact TreeSHAP contributions [N, K*(F+1)] (reference:
        PredictContrib -> Tree::TreeSHAP, src/io/tree.cpp).

        Trained boosters route in bin space (bit-identical to training);
        loaded models and continue-training bases route on the model text's
        raw-value thresholds, like the reference's dataset-free path.
        Linear trees attribute their constant leaf outputs, matching the
        reference (TreeSHAP reads leaf_value_, never leaf coefficients).

        The (start_iteration, num_iteration) window addresses the global
        loaded+new tree sequence exactly like predict() — SHAP is
        additive over trees, so windowing the model stack is the whole
        story (the ``start_iteration != 0 is not supported`` restriction
        is gone)."""
        from .ops.treeshap import booster_contrib, loaded_booster_contrib
        g = self._gbdt
        k = max(g.num_tree_per_iteration, 1)
        arr = np.atleast_2d(np.asarray(arr, np.float64))
        if not hasattr(g, "bin_matrix"):
            # model-only path (Booster(model_file=...))
            models = g.models[start_iteration * k:]
            if num_iteration is not None and num_iteration > 0:
                models = models[: num_iteration * k]
            return loaded_booster_contrib(models, arr, k,
                                          g.max_feature_idx + 1)
        (pre, pre_start, pre_cut, own_start, own_cut, pre_empty,
         own_empty) = self._global_tree_window(start_iteration,
                                               num_iteration)
        g._flush_trees()
        models = [] if own_empty else g.models[own_start * k:]
        if own_cut is not None:
            models = models[: own_cut * k]
        binned = np.asarray(g.bin_matrix(arr))
        # tree split_feature holds ORIGINAL feature ids; under EFB the
        # gbdt's nan/cat arrays are column-space, so route with the
        # original-space twins like every other prediction path
        if getattr(g, "_efb", None) is not None:
            nan_bin = np.asarray(g._orig_nan_arr)
            is_cat = np.asarray(g._orig_cat_arr)
        else:
            nan_bin = np.asarray(g.nan_bin_arr)
            is_cat = np.asarray(g.is_cat_arr)

        from .obs.spans import span
        from .ops.split import go_left_scalar_np
        with span("contrib"):
            out = booster_contrib(models, binned, nan_bin, is_cat,
                                  go_left_scalar_np,
                                  g.num_tree_per_iteration,
                                  int(binned.shape[1]))
        if not pre_empty:
            # continue-trained: SHAP is additive over trees, so the loaded
            # base model's contributions (raw-space routing) sum in
            pre_models = pre.models[pre_start * k:]
            if pre_cut is not None:
                pre_models = pre_models[: pre_cut * k]
            out = out + loaded_booster_contrib(
                pre_models, arr, k, int(binned.shape[1]))
        return out

    # -- model IO ------------------------------------------------------------
    @read_locked
    def model_to_string(self, num_iteration: Optional[int] = None,
                        start_iteration: int = 0,
                        importance_type: str = "split") -> str:
        from .model_io import booster_to_string, merge_model_texts
        if num_iteration is None and self.best_iteration > 0:
            # reference behavior: default save cuts at best_iteration
            # (basic.py save_model num_iteration doc)
            num_iteration = self.best_iteration
        pre = getattr(self, "_pre_model", None)
        if pre is None:
            return booster_to_string(self, num_iteration)
        pre_cut, own_cut = self._split_iteration_window(num_iteration, pre)
        text = booster_to_string(self, own_cut)
        return merge_model_texts(pre, text, pre_num_iteration=pre_cut)

    @staticmethod
    def _split_iteration_window(num_iteration, pre):
        """Split a leading num_iteration window across a loaded base model
        and the booster's own trees: (pre_cut, own_cut), None = all."""
        if num_iteration is None or num_iteration <= 0:
            return None, None
        if pre is None:
            return None, num_iteration
        return (min(num_iteration, pre.current_iteration),
                max(num_iteration - pre.current_iteration, 0))

    @read_locked
    def save_model(self, filename: str, num_iteration: Optional[int] = None,
                   start_iteration: int = 0,
                   importance_type: str = "split") -> "Booster":
        with open(filename, "w") as f:
            f.write(self.model_to_string(num_iteration))
        return self

    @read_locked
    def dump_model(self, num_iteration: Optional[int] = None,
                   start_iteration: int = 0,
                   importance_type: str = "split") -> Dict:
        from .model_io import booster_to_dict
        if getattr(self, "_pre_model", None) is not None:
            # continue-trained boosters dump via the merged text (keeps the
            # loaded trees; a text round-trip is exact for them)
            from .model_io import LoadedGBDT, loaded_dump
            return loaded_dump(LoadedGBDT(self.model_to_string(num_iteration)))
        return booster_to_dict(self, num_iteration)

    # -- introspection -------------------------------------------------------
    @read_locked
    def num_trees(self) -> int:
        g = self._gbdt
        own = g.num_total_trees if hasattr(g, "num_total_trees") \
            else len(g.models)
        pre = getattr(self, "_pre_model", None)
        return own + (len(pre.models) if pre is not None else 0)

    @read_locked
    def current_iteration(self) -> int:
        pre = getattr(self, "_pre_model", None)
        return self._gbdt.current_iteration + \
            (pre.current_iteration if pre is not None else 0)

    @read_locked
    def num_model_per_iteration(self) -> int:
        return self._gbdt.num_tree_per_iteration

    @read_locked
    def num_feature(self) -> int:
        ts = getattr(self._gbdt, "train_set", None)
        if ts is not None:
            return ts.num_total_features
        return self._gbdt.max_feature_idx + 1  # loaded model

    @read_locked
    def feature_name(self) -> List[str]:
        ts = getattr(self._gbdt, "train_set", None)
        if ts is not None:
            return list(ts.feature_names)
        return list(self._gbdt.feature_names)  # loaded model

    @read_locked
    def feature_importance(self, importance_type: str = "split",
                           iteration: Optional[int] = None) -> np.ndarray:
        imp = self._gbdt.feature_importance(importance_type, iteration)
        pre = getattr(self, "_pre_model", None)
        if pre is not None:
            pre_imp = pre.feature_importance(importance_type)
            n = max(len(imp), len(pre_imp))
            out = np.zeros(n, imp.dtype)
            out[: len(imp)] += imp
            out[: len(pre_imp)] += pre_imp
            return out
        return imp

    def _all_leaf_values(self):
        pre = getattr(self, "_pre_model", None)
        models = list(self._gbdt.models) + \
            (list(pre.models) if pre is not None else [])
        return models

    @read_locked
    def lower_bound(self):
        return min((m.leaf_value.min() for m in self._all_leaf_values()),
                   default=0.0)

    @read_locked
    def upper_bound(self):
        return max((m.leaf_value.max() for m in self._all_leaf_values()),
                   default=0.0)


class _DatasetView:
    """Minimal Dataset-like view over an internal BinnedDataset (for feval)."""

    def __init__(self, inner: BinnedDataset):
        self._inner = inner

    def get_label(self):
        return self._inner.metadata.label

    def get_weight(self):
        return self._inner.metadata.weight

    def get_group(self):
        return self._inner.metadata.group


def _call_custom_objective(fobj: Callable, booster: Booster):
    """Custom objective protocol: fobj(preds, train_dataset) -> (grad, hess)
    (reference: Booster.update fobj path, basic.py:4117-4132)."""
    gbdt = booster._gbdt
    raw = np.asarray(gbdt.train_score)
    # multiclass: hand the custom objective [n, K] preds and accept [n, K]
    # (or flat row-major) grads back — the reference's documented contract
    preds = raw[0] if raw.shape[0] == 1 else raw.T
    grad, hess = fobj(preds, booster.train_set)
    grad = np.asarray(grad, np.float32)
    hess = np.asarray(hess, np.float32)
    k, n = gbdt.num_tree_per_iteration, gbdt.num_data
    if grad.size != k * n:
        raise ValueError(f"gradient size {grad.size} != num_class*num_data {k * n}")
    if k > 1:
        grad = grad.reshape(n, k).T
        hess = hess.reshape(n, k).T
    return grad, hess
