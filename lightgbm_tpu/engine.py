"""Training entry points: ``train()`` and ``cv()``.

Mirror of the reference's engine
(reference: python-package/lightgbm/engine.py — train :109 [callback loop +
booster.update :309-345], cv :611, CVBooster :354, early-stop handling :342).
"""
from __future__ import annotations

import collections
import contextlib
import copy
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Union

import numpy as np

from . import callback as callback_mod
from .basic import Booster, Dataset
from .config import Config, alias_table
from .utils import log


def _setup_callbacks(params: Dict[str, Any],
                     callbacks: Optional[Sequence[Callable]]):
    """Resolve the callback set for a training run: inject auto early stopping
    (disabled in dart mode, where tree renormalization invalidates
    best_iteration truncation) and split/sort by before/after-iteration
    (reference: engine.py:262-307 callback setup in train() and cv())."""
    cbs = set(callbacks) if callbacks else set()
    cfg = Config(params)
    early_round = int(cfg.early_stopping_round or 0)
    if early_round > 0 and cfg.boosting != "dart":
        cbs.add(callback_mod.early_stopping(
            early_round, bool(params.get("first_metric_only", False)),
            min_delta=float(params.get("early_stopping_min_delta", 0.0))))
    order_key = lambda cb: getattr(cb, "order", 0)
    cbs_before = sorted(
        (cb for cb in cbs if getattr(cb, "before_iteration", False)),
        key=order_key)
    cbs_after = sorted(
        (cb for cb in cbs if not getattr(cb, "before_iteration", False)),
        key=order_key)
    return cbs_before, cbs_after


def train(
    params: Dict[str, Any],
    train_set: Dataset,
    num_boost_round: int = 100,
    valid_sets: Optional[Sequence[Dataset]] = None,
    valid_names: Optional[Sequence[str]] = None,
    feval: Optional[Union[Callable, Sequence[Callable]]] = None,
    init_model: Optional[Union[str, Booster]] = None,
    keep_training_booster: bool = False,
    callbacks: Optional[Sequence[Callable]] = None,
) -> Booster:
    """Train a booster (reference: engine.py:109)."""
    params = copy.deepcopy(params) if params else {}
    # num_boost_round may come via params aliases (reference: engine.py:139-160)
    at = alias_table()
    for key in list(params.keys()):
        if at.get(key) == "num_iterations" and params[key] is not None:
            num_boost_round = int(params.pop(key))
    params["num_iterations"] = num_boost_round

    # one telemetry session around the WHOLE run — dataset construction
    # included (binning is a span-taxonomy phase) — held as a context
    # manager so the profiler trace closes on every error path
    # (obs/spans.trace_session; tpu_trace_mode=annotations enables span
    # names without a full profiler trace)
    from . import obs
    cfg0 = Config(params)
    trace_dir = str(cfg0.get("tpu_trace_dir", "") or "")
    trace_mode = obs.spans.resolve_trace_mode(cfg0.get("tpu_trace_mode"))
    session = (obs.spans.trace_session(trace_dir, trace_mode)
               if (trace_dir or cfg0.is_explicit("tpu_trace_mode"))
               else contextlib.nullcontext())
    # per-RUN summary baseline: the span phase-time table AND the
    # seen-span set are process-cumulative, and a second train() in the
    # same process (cv folds, sklearn refits, train-after-serve) must
    # not re-report the first run's seconds or phases; taken BEFORE
    # construction so construct-phase spans (binning) count
    obs_baseline = {"phase": obs.spans.phase_times(),
                    "seen": obs.spans.seen_counts()}
    with session:
        try:
            booster = _train_impl(params, train_set, num_boost_round,
                                  valid_sets, valid_names, feval,
                                  init_model, callbacks, obs_baseline)
        except BaseException as err:
            # the flight recorder's "any crash escaping lgb.train" dump
            # site — HERE, not around the boosting loop, so a death
            # during dataset construction / multihost bootstrap /
            # init_model load / checkpoint auto-resume still ships its
            # post-mortem (the r05 gap). ALWAYS dump: a
            # TrainingInterrupted from the boosting loop already dumped
            # inside _train_impl, and re-dumping here only extends that
            # record with the final-snapshot events — while one raised
            # BEFORE the loop (bootstrap deadline, sync barrier) would
            # otherwise leave nothing on disk.
            from .obs import flight
            from .parallel.multihost import TrainingInterrupted
            interrupted = isinstance(err, TrainingInterrupted)
            if not interrupted:
                flight.note("crash", error=repr(err)[:300])
            path = flight.dump(
                "TrainingInterrupted" if interrupted
                else f"crash: {type(err).__name__}",
                extra={"error": repr(err)[:300]})
            if path and not interrupted:
                log.warning(f"flight recorder dumped to {path}")
            raise
    return booster


def _train_impl(
    params: Dict[str, Any],
    train_set: Dataset,
    num_boost_round: int,
    valid_sets: Optional[Sequence[Dataset]],
    valid_names: Optional[Sequence[str]],
    feval: Optional[Union[Callable, Sequence[Callable]]],
    init_model: Optional[Union[str, Booster]],
    callbacks: Optional[Sequence[Callable]],
    obs_baseline: Dict[str, Any],
) -> Booster:
    # continue-training: the loaded model's trees stay value-space
    # (reference: engine.py init_model -> _InnerPredictor; gbdt.cpp:250-258);
    # its raw predictions seed all cached scores and its tree blocks are
    # re-emitted ahead of the new ones at save time
    pre_model = None
    if init_model is None and params.get("input_model"):
        init_model = str(params["input_model"])
    if init_model is not None:
        from .model_io import LoadedGBDT
        if isinstance(init_model, str):
            with open(init_model) as fh:
                pre_model = LoadedGBDT(fh.read())
        else:
            pre_model = LoadedGBDT(init_model.model_to_string())

    train_set._update_params(params)
    # multi-host bootstrap must precede dataset construction (bin-mapper
    # sync) AND any backend-initializing call (reference: Network::Init
    # before LoadData, application.cpp:88)
    from .parallel.multihost import maybe_init_distributed
    maybe_init_distributed(params)
    if pre_model is not None and train_set.data is None:
        raise ValueError(
            "continue-training needs the Dataset's raw data to score the "
            "loaded model; construct the Dataset with free_raw_data=False")
    pre_train_raw = (pre_model.predict_raw_matrix(np.asarray(train_set.data))
                     if pre_model is not None else None)
    train_set.construct()
    booster = Booster(params=params, train_set=train_set)
    booster._train_data_name = "training"
    if pre_model is not None:
        booster._attach_pre_model(pre_model, pre_train_raw)

    is_valid_contain_train = False
    name_valid_sets = []
    if valid_sets is not None:
        if isinstance(valid_sets, Dataset):
            valid_sets = [valid_sets]
        for i, valid_data in enumerate(valid_sets):
            if valid_names is not None and len(valid_names) > i:
                name = valid_names[i]
            else:
                name = f"valid_{i}"
            if valid_data is train_set:
                is_valid_contain_train = True
                booster._train_data_name = name
                continue
            pre_valid_raw = None
            if pre_model is not None:
                if valid_data.data is None:
                    raise ValueError(
                        "continue-training needs raw valid data "
                        "(free_raw_data=False)")
                pre_valid_raw = pre_model.predict_raw_matrix(
                    np.asarray(valid_data.data))
            booster.add_valid(valid_data, name)
            if pre_valid_raw is not None:
                booster._seed_valid_scores(-1, pre_valid_raw)

    cbs_before, cbs_after = _setup_callbacks(params, callbacks)
    snapshot_freq = int(params.get("snapshot_freq", -1) or -1)
    snapshot_out = str(params.get("output_model", "LightGBM_model.txt"))

    # fault tolerance: full-state checkpoints + collective watchdog
    # (io/checkpoint.py, parallel/multihost.py; see config.py knobs)
    cfg = booster.config
    ckpt_dir = str(cfg.get("tpu_checkpoint_dir", "") or "")
    ckpt_freq = int(cfg.get("tpu_checkpoint_freq", 0) or 0)
    ckpt_keep = int(cfg.get("tpu_checkpoint_keep", 3) or 3)
    deadline = float(cfg.get("tpu_collective_deadline_s", 0.0) or 0.0)
    from .analysis.faultinject import active_plan
    from .parallel.multihost import TrainingInterrupted, run_with_deadline
    plan = active_plan(cfg)
    all_cbs = cbs_before + cbs_after

    def _callback_states():
        out = {}
        for cb in all_cbs:
            key = getattr(cb, "_ckpt_key", None)
            st = getattr(cb, "state", None)
            if key and isinstance(st, dict):
                out[key] = copy.deepcopy(st)
        return out

    def _write_checkpoint():
        booster.save_checkpoint(ckpt_dir, keep=ckpt_keep,
                                callback_states=_callback_states())

    start_iteration = 0
    if ckpt_dir:
        from .io import checkpoint as ckpt_mod
        found = ckpt_mod.load_latest(ckpt_dir)
        # multi-host: every rank must agree on the resume point BEFORE any
        # state is restored — a rank that cannot see the snapshot (dir not
        # on a shared filesystem, torn read) would otherwise start at 0
        # while the others start at N, desyncing every collective in the
        # step. On disagreement all ranks start fresh, which is safe.
        import jax
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils as _mu
            it = -1 if found is None else int(found["iteration"])
            all_its = np.asarray(_mu.process_allgather(np.int64(it)))
            if not (all_its == all_its[0]).all():
                log.warning(
                    f"checkpoint resume iteration disagrees across ranks "
                    f"({list(map(int, all_its))}); is tpu_checkpoint_dir "
                    f"on a shared filesystem? starting fresh on all ranks")
                found = None
        if found is not None:
            try:
                booster._restore_checkpoint(found, callbacks=all_cbs)
                start_iteration = int(found["iteration"])
                log.info(f"Resuming from checkpoint at iteration "
                         f"{start_iteration} ({ckpt_dir})")
            except ValueError as err:
                log.warning(f"ignoring incompatible checkpoint in "
                            f"{ckpt_dir}: {err}")

    # telemetry (lightgbm_tpu/obs): the trace session is already held by
    # train() around this whole function; here the flight recorder and
    # the metrics stream get their run-level hooks
    from . import obs
    from .obs import flight
    mstream = booster._gbdt._metrics_stream
    if mstream is not None:
        mstream.emit("mark", name="train_begin",
                     iteration=start_iteration,
                     num_boost_round=num_boost_round)

    # scrapeable while it TRAINS: tpu_metrics_port binds the same
    # Prometheus-text endpoint the serving tier uses, serving the live
    # training tree (iteration progress, phase-keyed compiles, cache
    # counters, rank-stats aggregate incl. straggler flags) for the
    # duration of the run. Rank 0 only — one scrape target per pod, the
    # same single-writer contract as the metrics stream.
    mserver = None
    mport = int(cfg.get("tpu_metrics_port", 0) or 0)
    if mport > 0:
        import jax
        if jax.process_index() == 0:
            from .obs.metrics import MetricsServer
            try:
                mserver = MetricsServer(booster._gbdt.train_metrics_tree,
                                        port=mport)
                log.info(f"training metrics endpoint on "
                         f":{mserver.port} (/metrics, /healthz)")
            except OSError as err:
                log.warning(
                    f"cannot bind tpu_metrics_port={mport}: {err}; "
                    "training continues unscrapeable")

    def _flight_dump(reason: str, err: BaseException) -> None:
        # the TrainingInterrupted dump site; other crashes dump from the
        # train() wrapper, which covers construction/resume too
        flight.note("training_interrupted", error=repr(err)[:300])
        path = flight.dump(reason, extra={"error": repr(err)[:300]})
        if path:
            log.warning(f"flight recorder dumped to {path}")

    try:
        evaluation_result_list: List = []
        for i in range(start_iteration, num_boost_round):
            for cb in cbs_before:
                cb(callback_mod.CallbackEnv(
                    model=booster, params=params, iteration=i,
                    begin_iteration=0, end_iteration=num_boost_round,
                    evaluation_result_list=None))
            plan.fire("iteration", iteration=i)
            if deadline > 0:
                # collective watchdog: a hung distributed step surfaces as
                # a structured TrainingInterrupted (handled below with a
                # final snapshot) instead of stalling the pod silently
                def _step(i=i):
                    plan.fire("step", iteration=i)
                    return booster.update()
                finished = run_with_deadline(
                    _step, deadline, f"boosting iteration {i}")
            else:
                plan.fire("step", iteration=i)
                finished = booster.update()

            evaluation_result_list = []
            if (valid_sets is not None and (booster._valid_names
                                            or is_valid_contain_train)) or feval:
                if is_valid_contain_train:
                    evaluation_result_list.extend(booster.eval_train(feval))
                evaluation_result_list.extend(booster.eval_valid(feval))
            try:
                for cb in cbs_after:
                    cb(callback_mod.CallbackEnv(
                        model=booster, params=params, iteration=i,
                        begin_iteration=0, end_iteration=num_boost_round,
                        evaluation_result_list=evaluation_result_list))
            except callback_mod.EarlyStopException as e:
                booster.best_iteration = e.best_iteration + 1
                evaluation_result_list = e.best_score or []
                break
            # periodic model snapshots (reference: GBDT::Train, gbdt.cpp:250-254
            # -> model.txt.snapshot_iter_N every snapshot_freq iterations).
            # The save flushes pending device trees; capture its stop signal
            # instead of discarding it (a no-split iteration pops its trees)
            if snapshot_freq > 0 and (i + 1) % snapshot_freq == 0:
                finished = booster._gbdt._flush_trees() or finished
                booster.save_model(f"{snapshot_out}.snapshot_iter_{i + 1}")
            # full-state checkpoint tick: the ONE planned device->host
            # fetch outside stop checks (atomic write, keep-last-k). The
            # flight ring rides along — a later SIGKILL leaves the events
            # as of the last durable snapshot on disk
            if ckpt_dir and ckpt_freq > 0 and (i + 1) % ckpt_freq == 0:
                finished = booster._gbdt._flush_trees() or finished
                _write_checkpoint()
                flight.dump(f"checkpoint tick @ iteration {i + 1}")
            if finished:
                log.info("Finished training (no further splits possible)")
                break

    except TrainingInterrupted as err:
        # a deadline fired (hung collective / preempted peer): write a
        # best-effort final snapshot, then surface the structured error.
        # The snapshot itself runs under a deadline — when the hung step
        # still holds the booster lock or the device state is
        # unfetchable, resume falls back to the last periodic snapshot.
        # The flight dump ships the post-mortem either way.
        _flight_dump("TrainingInterrupted", err)
        if ckpt_dir:
            try:
                run_with_deadline(_write_checkpoint,
                                  max(deadline, 30.0),
                                  "final interrupt snapshot")
                log.warning(f"training interrupted ({err}); final "
                            f"snapshot written to {ckpt_dir}")
            except BaseException as snap_err:  # noqa: BLE001 - best effort
                log.warning(f"training interrupted ({err}); final "
                            f"snapshot failed: {snap_err}")
        raise
    finally:
        if mserver is not None:
            mserver.stop()
        if mstream is not None:
            from .analysis import guards
            # spans_seen: sites newly ENTERED during this run — host
            # spans plus programs traced this run. A program reused from
            # the process jit cache (module-level grow_tree across
            # boosters) was named at its original trace and does not
            # re-enter; the cumulative registry is spans.seen_spans()
            mstream.emit(
                "summary",
                iteration=booster._gbdt.iter_,
                phase_times=obs.spans.phase_times_since(
                    obs_baseline["phase"]),
                spans_seen=sorted(obs.spans.seen_since(
                    obs_baseline["seen"])),
                compiles=guards.phase_compile_counts(),
                cache=guards.global_cache_counts())
    # record final scores (reference: engine.py:346-352)
    if evaluation_result_list:
        best: Dict[str, Dict[str, float]] = collections.OrderedDict()
        for name, metric, value, _ in evaluation_result_list:
            best.setdefault(name, collections.OrderedDict())[metric] = value
        booster.best_score = best
    return booster


class CVBooster:
    """Container of per-fold boosters (reference: engine.py:354)."""

    def __init__(self, boosters: Optional[List[Booster]] = None):
        self.boosters = boosters or []
        self.best_iteration = -1

    def append(self, booster: Booster) -> "CVBooster":
        self.boosters.append(booster)
        return self

    def __getattr__(self, name):
        def handler_function(*args, **kwargs):
            return [getattr(b, name)(*args, **kwargs) for b in self.boosters]
        return handler_function


def _make_n_folds(full_data: Dataset, nfold: int, params: Dict,
                  seed: int, stratified: bool, shuffle: bool,
                  group: Optional[np.ndarray]):
    full_data.construct()
    num_data = full_data.num_data()
    rng = np.random.RandomState(seed)
    if group is not None:
        # group-aware folds: whole queries per fold (reference: engine.py:436)
        ngroups = len(group)
        gidx = np.arange(ngroups)
        if shuffle:
            rng.shuffle(gidx)
        gfolds = np.array_split(gidx, nfold)
        boundaries = np.concatenate([[0], np.cumsum(group)])
        folds = []
        for gf in gfolds:
            rows = np.concatenate(
                [np.arange(boundaries[g], boundaries[g + 1]) for g in gf]) \
                if len(gf) else np.array([], dtype=np.int64)
            folds.append(np.sort(rows))
    elif stratified:
        label = np.asarray(full_data.get_label())
        folds = [[] for _ in range(nfold)]
        for cls in np.unique(label):
            idx = np.where(label == cls)[0]
            if shuffle:
                rng.shuffle(idx)
            for i, part in enumerate(np.array_split(idx, nfold)):
                folds[i].append(part)
        folds = [np.sort(np.concatenate(f)) for f in folds]
    else:
        idx = np.arange(num_data)
        if shuffle:
            rng.shuffle(idx)
        folds = [np.sort(f) for f in np.array_split(idx, nfold)]
    return folds


def cv(
    params: Dict[str, Any],
    train_set: Dataset,
    num_boost_round: int = 100,
    folds=None,
    nfold: int = 5,
    stratified: bool = True,
    shuffle: bool = True,
    metrics: Optional[Union[str, Sequence[str]]] = None,
    feval: Optional[Callable] = None,
    init_model=None,
    seed: int = 0,
    callbacks: Optional[Sequence[Callable]] = None,
    eval_train_metric: bool = False,
    return_cvbooster: bool = False,
) -> Dict[str, List[float]]:
    """K-fold cross-validation (reference: engine.py:611)."""
    params = copy.deepcopy(params) if params else {}
    if metrics is not None:
        params["metric"] = metrics
    at = alias_table()
    for key in list(params.keys()):
        if at.get(key) == "num_iterations" and params[key] is not None:
            num_boost_round = int(params.pop(key))

    train_set.construct()
    objective = params.get("objective", "regression")
    if stratified and (not isinstance(objective, str)
                       or "binary" not in str(objective)
                       and "multiclass" not in str(objective)):
        stratified = False

    data = train_set._inner
    raw = None
    if train_set.data is not None:
        raw = np.asarray(train_set.data, dtype=np.float64)
    else:
        raise ValueError("cv() needs the raw data; construct the Dataset with "
                         "free_raw_data=False or pass data directly")
    label = np.asarray(train_set.get_label())
    weight = train_set.get_weight()
    group = train_set.get_group()

    if folds is None:
        folds_idx = _make_n_folds(train_set, nfold, params, seed, stratified,
                                  shuffle, group)
        folds = []
        all_idx = np.arange(train_set.num_data())
        for te in folds_idx:
            tr = np.setdiff1d(all_idx, te, assume_unique=False)
            folds.append((tr, te))
    elif hasattr(folds, "split"):
        folds = list(folds.split(raw, label, groups=None))

    cvbooster = CVBooster()
    fold_params = {k: v for k, v in params.items()}
    for tr, te in folds:
        def subset(idx):
            w = None if weight is None else np.asarray(weight)[idx]
            g = None
            if group is not None:
                # recompute group sizes from membership (queries kept whole)
                boundaries = np.concatenate([[0], np.cumsum(group)])
                qid = np.searchsorted(boundaries, idx, side="right") - 1
                _, counts = np.unique(qid, return_counts=True)
                g = counts
            return Dataset(raw[idx], label=label[idx], weight=w, group=g,
                           params=params, free_raw_data=False)
        dtr = subset(tr)
        dte = dtr.create_valid(raw[te], label=label[te],
                               weight=None if weight is None
                               else np.asarray(weight)[te])
        if group is not None:
            boundaries = np.concatenate([[0], np.cumsum(group)])
            qid = np.searchsorted(boundaries, te, side="right") - 1
            _, counts = np.unique(qid, return_counts=True)
            dte.set_group(counts)
        dtr._update_params(fold_params)
        dtr.construct()
        bst = Booster(params=fold_params, train_set=dtr)
        bst._train_data_name = "train"
        bst.add_valid(dte, "valid")
        cvbooster.append(bst)

    # all folds advance together one iteration at a time so per-iteration
    # fold means/stdvs are recorded and early stopping acts on the CV
    # aggregate (reference: engine.py:611 cv loop + _agg_cv_result)
    cbs_before, cbs_after = _setup_callbacks(params, callbacks)

    results: Dict[str, List[float]] = collections.OrderedDict()
    for i in range(num_boost_round):
        for cb in cbs_before:
            cb(callback_mod.CallbackEnv(
                model=cvbooster, params=params, iteration=i,
                begin_iteration=0, end_iteration=num_boost_round,
                evaluation_result_list=None))
        for bst in cvbooster.boosters:
            bst.update()
        merged: Dict = collections.OrderedDict()
        for bst in cvbooster.boosters:
            entries = []
            if eval_train_metric:
                entries.extend(bst.eval_train(feval))
            entries.extend(bst.eval_valid(feval))
            for name, metric, value, hib in entries:
                merged.setdefault((name, metric, hib), []).append(value)
        agg_list = []
        for (name, metric, hib), vals in merged.items():
            key = f"{name} {metric}"
            results.setdefault(f"{key}-mean", []).append(float(np.mean(vals)))
            results.setdefault(f"{key}-stdv", []).append(float(np.std(vals)))
            # same shape the reference hands to callbacks: ("cv_agg", ...)
            agg_list.append(("cv_agg", key, float(np.mean(vals)), hib))
        try:
            for cb in cbs_after:
                cb(callback_mod.CallbackEnv(
                    model=cvbooster, params=params, iteration=i,
                    begin_iteration=0, end_iteration=num_boost_round,
                    evaluation_result_list=agg_list))
        except callback_mod.EarlyStopException as e:
            cvbooster.best_iteration = e.best_iteration + 1
            for bst in cvbooster.boosters:
                bst.best_iteration = cvbooster.best_iteration
            for key in list(results):
                results[key] = results[key][:cvbooster.best_iteration]
            break

    out: Dict[str, Any] = dict(results)
    if return_cvbooster:
        out["cvbooster"] = cvbooster
    return out
