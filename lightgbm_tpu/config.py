"""Parameter schema for lightgbm_tpu.

TPU-native re-design of the reference's config system: a single ``Config``
dataclass-like object with defaults, ~180 aliases, and consistency checks
(reference: include/LightGBM/config.h:39, src/io/config.cpp:286 ``Config::Set``,
generated alias table in src/io/config_auto.cpp). Unlike the reference we keep the
schema in one Python table (PARAMS below) from which aliases, defaults and docs are
derived — same "schema as single source of truth" idea, no codegen step needed.
"""
from __future__ import annotations

import copy
from typing import Any, Dict, Iterable, List, Optional, Tuple

from .utils import log

# ---------------------------------------------------------------------------
# Schema: name -> (default, type, aliases)
# Mirrors the parameter surface documented in the reference's
# include/LightGBM/config.h doc-comments / docs/Parameters.rst.
# ---------------------------------------------------------------------------
PARAMS: Dict[str, Tuple[Any, type, Tuple[str, ...]]] = {
    # core
    "objective": ("regression", str, ("objective_type", "app", "application", "loss")),
    "boosting": ("gbdt", str, ("boosting_type", "boost")),
    "data_sample_strategy": ("bagging", str, ()),
    "num_iterations": (100, int, (
        "num_iteration", "n_iter", "num_tree", "num_trees", "num_round", "num_rounds",
        "nrounds", "num_boost_round", "n_estimators", "max_iter")),
    "learning_rate": (0.1, float, ("shrinkage_rate", "eta")),
    "num_leaves": (31, int, ("num_leaf", "max_leaves", "max_leaf", "max_leaf_nodes")),
    "tree_learner": ("serial", str, ("tree", "tree_type", "tree_learner_type")),
    "num_threads": (0, int, ("num_thread", "nthread", "nthreads", "n_jobs")),
    "device_type": ("tpu", str, ("device",)),
    "seed": (None, int, ("random_seed", "random_state")),
    "deterministic": (False, bool, ()),
    # learning control
    "stop_check_freq": (1, int, ()),  # TPU extension: batched stop checks
    "force_col_wise": (False, bool, ()),
    "force_row_wise": (False, bool, ()),
    "max_depth": (-1, int, ()),
    "min_data_in_leaf": (20, int, (
        "min_data_per_leaf", "min_data", "min_child_samples", "min_samples_leaf")),
    "min_sum_hessian_in_leaf": (1e-3, float, (
        "min_sum_hessian_per_leaf", "min_sum_hessian", "min_hessian", "min_child_weight")),
    "bagging_fraction": (1.0, float, ("sub_row", "subsample", "bagging")),
    "pos_bagging_fraction": (1.0, float, ("pos_sub_row", "pos_subsample", "pos_bagging")),
    "neg_bagging_fraction": (1.0, float, ("neg_sub_row", "neg_subsample", "neg_bagging")),
    "bagging_freq": (0, int, ("subsample_freq",)),
    "bagging_seed": (3, int, ("bagging_fraction_seed",)),
    "bagging_by_query": (False, bool, ()),
    "feature_fraction": (1.0, float, ("sub_feature", "colsample_bytree")),
    "feature_fraction_bynode": (1.0, float, ("sub_feature_bynode", "colsample_bynode")),
    "feature_fraction_seed": (2, int, ()),
    "extra_trees": (False, bool, ("extra_tree",)),
    "extra_seed": (6, int, ()),
    "early_stopping_round": (0, int, (
        "early_stopping_rounds", "early_stopping", "n_iter_no_change")),
    "early_stopping_min_delta": (0.0, float, ()),
    "first_metric_only": (False, bool, ()),
    "max_delta_step": (0.0, float, ("max_tree_output", "max_leaf_output")),
    "lambda_l1": (0.0, float, ("reg_alpha", "l1_regularization")),
    "lambda_l2": (0.0, float, ("reg_lambda", "lambda", "l2_regularization")),
    "linear_lambda": (0.0, float, ()),
    "min_gain_to_split": (0.0, float, ("min_split_gain",)),
    # dart
    "drop_rate": (0.1, float, ("rate_drop",)),
    "max_drop": (50, int, ()),
    "skip_drop": (0.5, float, ()),
    "xgboost_dart_mode": (False, bool, ()),
    "uniform_drop": (False, bool, ()),
    "drop_seed": (4, int, ()),
    # voting-parallel (PV-Tree) vote size (reference: config.h top_k)
    "top_k": (20, int, ("topk",)),
    # goss
    "top_rate": (0.2, float, ()),
    "other_rate": (0.1, float, ()),
    # cat
    "min_data_per_group": (100, int, ()),
    "max_cat_threshold": (32, int, ()),
    "cat_l2": (10.0, float, ()),
    "cat_smooth": (10.0, float, ()),
    "max_cat_to_onehot": (4, int, ()),
    # constraints
    "monotone_constraints": (None, object, ("mc", "monotone_constraint")),
    "monotone_constraints_method": ("basic", str, ("monotone_constraining_method", "mc_method")),
    "monotone_penalty": (0.0, float, ("monotone_splits_penalty", "ms_penalty", "mc_penalty")),
    "feature_contri": (None, object, ("feature_contrib", "fc", "fp", "feature_penalty")),
    "interaction_constraints": (None, object, ()),
    "forcedsplits_filename": ("", str, ("fs", "forced_splits_filename", "forced_splits_file", "forced_splits")),
    "refit_decay_rate": (0.9, float, ()),
    # cegb
    "cegb_tradeoff": (1.0, float, ()),
    "cegb_penalty_split": (0.0, float, ()),
    "cegb_penalty_feature_lazy": (None, object, ()),
    "cegb_penalty_feature_coupled": (None, object, ()),
    # misc learning
    "path_smooth": (0.0, float, ()),
    "verbosity": (1, int, ("verbose",)),
    "use_quantized_grad": (False, bool, ()),
    "num_grad_quant_bins": (4, int, ()),
    "quant_train_renew_leaf": (False, bool, ()),
    "stochastic_rounding": (True, bool, ()),
    # dataset
    "linear_tree": (False, bool, ("linear_trees",)),
    "max_bin": (255, int, ("max_bins",)),
    "max_bin_by_feature": (None, object, ()),
    "min_data_in_bin": (3, int, ()),
    "bin_construct_sample_cnt": (200000, int, ("subsample_for_bin",)),
    "data_random_seed": (1, int, ("data_seed",)),
    "is_enable_sparse": (True, bool, ("is_sparse", "enable_sparse", "sparse")),
    "enable_bundle": (True, bool, ("is_enable_bundle", "bundle")),
    "use_missing": (True, bool, ()),
    "zero_as_missing": (False, bool, ()),
    "feature_pre_filter": (True, bool, ()),
    "pre_partition": (False, bool, ("is_pre_partition",)),
    "two_round": (False, bool, ("two_round_loading", "use_two_round_loading")),
    "header": (False, bool, ("has_header",)),
    "label_column": ("", str, ("label",)),
    "weight_column": ("", str, ("weight",)),
    "group_column": ("", str, ("group", "group_id", "query_column", "query", "query_id")),
    "ignore_column": ("", str, ("ignore_feature", "blacklist")),
    "categorical_feature": ("", object, ("cat_feature", "categorical_column", "cat_column", "categorical_features")),
    "forcedbins_filename": ("", str, ()),
    "save_binary": (False, bool, ("is_save_binary", "is_save_binary_file")),
    "precise_float_parser": (False, bool, ()),
    "parser_config_file": ("", str, ()),
    # predict
    "start_iteration_predict": (0, int, ()),
    "num_iteration_predict": (-1, int, ()),
    "predict_raw_score": (False, bool, ("is_predict_raw_score", "predict_rawscore", "raw_score")),
    "predict_leaf_index": (False, bool, ("is_predict_leaf_index", "leaf_index")),
    "predict_contrib": (False, bool, ("is_predict_contrib", "contrib")),
    "predict_disable_shape_check": (False, bool, ()),
    "pred_early_stop": (False, bool, ()),
    "pred_early_stop_freq": (10, int, ()),
    "pred_early_stop_margin": (10.0, float, ()),
    # objective
    "num_class": (1, int, ("num_classes",)),
    "is_unbalance": (False, bool, ("unbalance", "unbalanced_sets")),
    "scale_pos_weight": (1.0, float, ()),
    "sigmoid": (1.0, float, ()),
    "boost_from_average": (True, bool, ()),
    "reg_sqrt": (False, bool, ()),
    "alpha": (0.9, float, ()),
    "fair_c": (1.0, float, ()),
    "poisson_max_delta_step": (0.7, float, ()),
    "tweedie_variance_power": (1.5, float, ()),
    "lambdarank_truncation_level": (30, int, ()),
    "lambdarank_norm": (True, bool, ()),
    "label_gain": (None, object, ()),
    "lambdarank_position_bias_regularization": (0.0, float, ()),
    "objective_seed": (5, int, ()),
    # metric
    "metric": (None, object, ("metrics", "metric_types")),
    "metric_freq": (1, int, ("output_freq",)),
    "is_provide_training_metric": (False, bool, ("training_metric", "is_training_metric", "train_metric")),
    "eval_at": ((1, 2, 3, 4, 5), object, ("ndcg_eval_at", "ndcg_at", "map_eval_at", "map_at")),
    "multi_error_top_k": (1, int, ()),
    "auc_mu_weights": (None, object, ()),
    # network (reference: socket/MPI config; here: jax.distributed / mesh shape)
    "num_machines": (1, int, ("num_machine",)),
    "local_listen_port": (12400, int, ("local_port", "port")),
    "time_out": (120, int, ()),
    "machine_list_filename": ("", str, ("machine_list_file", "machine_list", "mlist")),
    "machines": ("", str, ("workers", "nodes")),
    # tpu-specific (new in this framework; no reference analogue)
    "tpu_hist_impl": ("auto", str, ()),     # auto | xla | pallas
    # serial-learner row storage: 'compact' physically partitions rows into
    # per-leaf segments (O(N*depth)/tree), 'masked' streams all rows per
    # split (O(N*num_leaves)/tree); 'auto' picks compact for large data
    "tpu_grower": ("auto", str, ()),        # auto | compact | masked
    # observability (lightgbm_tpu/obs): phase-named device traces, the
    # flight-recorder ring, and the metrics plane. tpu_trace_dir writes a
    # jax.profiler trace of the run (Perfetto/TensorBoard) with every
    # program carrying its span taxonomy name (obs/spans.py);
    # tpu_trace_mode=annotations enables the span names + host phase
    # table WITHOUT the full profiler trace
    "tpu_trace_dir": ("", str, ()),
    "tpu_trace_mode": ("full", str, ("trace_mode",)),  # full | annotations
    # per-iteration JSONL metrics stream (obs/metrics.py): one record per
    # update with wall seconds + cumulative phase-keyed compile counts +
    # compile-cache counters; bench.py derives its BENCH-row counters
    # from it and scripts/obs prints the per-phase rollup
    "tpu_metrics_path": ("", str, ("metrics_path",)),
    # flight recorder (obs/flight.py): bounded in-memory ring of
    # structured events dumped as JSONL on TrainingInterrupted / crash,
    # on a blown hot-swap, and at checkpoint ticks; 0 disables
    "tpu_flight_buffer": (512, int, ("flight_buffer",)),
    # metrics endpoint (GET /metrics Prometheus text + /healthz): bound
    # at PredictionServer start AND for the duration of lgb.train when
    # > 0 (scripts/serve --metrics-port overrides) — a pod run is
    # scrapeable while it trains (iteration progress, phase-keyed
    # compile counters, rank-stats aggregate incl. straggler flags)
    "tpu_metrics_port": (0, int, ("metrics_port",)),
    # per-rank runtime attribution (obs/ranks.py): every N iterations
    # the booster blocks on the step (true step wall), times one
    # collective-arrival probe, and publishes both through the
    # coordination-service KV; rank 0 aggregates median/p99/max and
    # flags stragglers into the flight recorder + metrics stream.
    # 0 disables (default) — off-sample iterations are untouched, so
    # the steady-state 0-d2h contract holds between samples
    "tpu_rank_stats_every": (0, int, ("rank_stats_every",)),
    # straggler threshold: a rank is flagged when its sampled iteration
    # wall exceeds this factor x the rolling cross-rank median
    "tpu_straggler_factor": (3.0, float, ("straggler_factor",)),
    "tpu_part_block": (2048, int, ()),      # compact partition stream block
    "tpu_hist_block": (16384, int, ()),     # compact histogram stream block
    # batched-M histogram depth: K row blocks per one-hot contraction fill
    # M = 8K of the 128 MXU rows. 8 is the STANDALONE engines' default;
    # unset, a fused entry runs 2 (engines/registry.py FUSED_MBATCH: the
    # fused kernel's pending ring is ten times slower at 8 on the chip).
    # Set, it reaches both. The ring multiplies histogram-side VMEM
    # residency by K, so tpu_fused_block is re-clamped against it
    "tpu_hist_mbatch": (8, int, ("hist_mbatch",)),
    # Mosaic one-hot register layout for the histogram engines: "lane"
    # keeps bins along lanes (channel-major output), "sublane" lays bins
    # along sublanes for B <= 64 (ops/pallas_histogram.py
    # _hist_kernel_sublane: the one-hot compare fills the register tile;
    # ops/fused_split.py hist_contract: the same operands with the
    # one-hot streamed). auto = lane; sublane has not run on the chip
    "tpu_hist_layout": ("auto", str, ("hist_layout",)),
    # per-leaf narrowed quantized accumulation (reference:
    # GetHistBitsInLeaf): 0 = auto (currently the int8 -> int32 engine
    # everywhere — the measured layout sweep shows the packed-pair
    # engine's radix-capped chunks lose at B <= 64, so narrow is the
    # measured OPT-IN), 16 = narrow where eligible (small leaves take
    # the packed-pair engine: grad/hess and inbag/raw pairs share one
    # f32 channel each — half the contraction work, bit-identical
    # sums), 32 = always the int8 -> int32 engine
    "tpu_quant_hist_bits": (0, int, ("quant_hist_bits",)),
    # retired: accepted and ignored, so that benchmarks/configs/*.json
    # (`tpu_autotune: off`) load without an "Unknown parameter" warning;
    # it goes with those lines in the next `benchmark` issue (ROADMAP D3)
    "tpu_autotune": ("off", str, ("autotune",)),
    # data-parallel histogram reduction: reduce-scatter over the feature
    # axis + best-split all-gather vs full-histogram all-reduce
    # (ops/grower_compact.py hist_scatter)
    "tpu_hist_scatter": ("auto", str, ()),  # auto | on | off
    # training-mesh shape: "" = all devices on a 1-D row axis (the
    # default), "N" = first N devices 1-D, "RxC" = 2-D rows x features
    # (the wide one-hot shape: the masked grower's binned matrix shards
    # over BOTH axes; compact/feature learners are row-mesh only). The
    # spmd flight check (analysis/spmd_check.py) lowers every learner
    # mode under faked values of this knob before a pod is rented.
    "tpu_mesh_shape": ("", str, ("mesh_shape",)),  # "" | "N" | "RxC"
    # bucketed grower-step ladder (compile-once training): the step
    # program's jit key carries the power-of-two leaf RUNG and the
    # {unlimited, bounded} depth bucket instead of the exact
    # (num_leaves, max_depth) pair — actual budgets ride as traced
    # scalars, so a full run compiles O(1) step programs and every
    # config in a rung shares one persistent-cache entry
    # (ops/grower.py leaf_rung/depth_rung). off = exact-keyed parity path
    "tpu_step_buckets": ("auto", str, ("step_buckets",)),  # auto | on | off
    # persistent XLA compilation cache: resumed/checkpointed runs and
    # repeated bench rounds skip backend compilation entirely
    # (jax_compilation_cache_dir; hits/misses counted by
    # analysis/guards.cache_counter and recorded in BENCH rows)
    "tpu_compile_cache_dir": ("", str, ("compile_cache_dir",)),
    # async histogram-collective overlap (data-parallel / voting): build
    # each leaf histogram in 2 feature groups and reduce each group
    # separately — group g's psum_scatter/all-reduce issues while group
    # g+1 still accumulates (double-buffered hist slots); collective
    # bytes unchanged, trees bit-identical (ops/grower_compact.py)
    "tpu_hist_overlap": ("auto", str, ("hist_overlap",)),  # auto | on | off
    # fused per-split Mosaic kernel (partition + smaller-child histogram in
    # one streamed walk, ops/fused_split.py): auto = on with a TPU backend
    "tpu_fused": ("auto", str, ()),         # auto | on | off
    "tpu_fused_block": (512, int, ()),      # fused kernel block size (x32)
    "tpu_fused_interpret": (False, bool, ()),  # CI: Pallas interpret on CPU
    "num_shards": (0, int, ()),             # 0 = use all local devices when tree_learner != serial
    # inference engine (ops/predict.py): trees walked tbatch at a time so
    # each depth step is one [Tb, N] gather dispatch
    "tpu_predict_tbatch": (16, int, ("predict_tbatch",)),
    # row-bucket ladder for zero-recompile serving: requests pad up to a
    # geometric rung ("auto" = x2 from 1k to 1M) and the jitted predict
    # program is keyed on (row rung, tree bucket, depth bucket, num_class)
    "tpu_predict_buckets": ("auto", str, ("predict_buckets",)),
    # serving-engine selector (engines/registry.py serving entries):
    # "batched"/"walk" = the depth-batched pointer walk, "level" = the
    # level-order heap relayout (contiguous per-depth slabs; falls back
    # to the walk past tpu_level_depth_cap), "auto" = registry resolve
    # order (user > env LGBM_TPU_PREDICT_ENGINE > depth heuristic),
    # "scan" = the pre-engine serial tree scan
    # (recompiles per batch shape; parity/bench reference)
    "tpu_predict_engine": ("batched", str, ()),
    # level-engine heap depth cap: per-level slab memory is O(2^D) per
    # tree, so buckets deeper than this keep the pointer walk
    "tpu_level_depth_cap": (10, int, ()),
    # opt-in serving leaf-value quantization ("off" | "int8" | "f16"):
    # narrower leaf slabs for the score gather, with a RECORDED
    # max-score-error bound shipped in the model stack
    # (GBDT.leaf_quant_bound); pred_leaf/pred_contrib stay exact f32
    "tpu_leaf_quant": ("off", str, ()),
    # 4-bit nibble packing of served request matrices when every feature
    # has <= 16 bins (io/dataset.py pack4_matrix; halves request HBM)
    "tpu_bin_pack4": (False, bool, ("bin_pack4",)),
    # serving layer (lightgbm_tpu/serving/): the async micro-batch
    # coalescer aggregates concurrent predict requests into one
    # rung-sized device batch per tick, with per-request deadlines,
    # a bounded admission queue (structured ServerOverloaded instead of
    # unbounded latency), and pre-warmed hot-swappable models
    "tpu_serve_tick_ms": (5.0, float, ("serve_tick_ms",)),
    # admission bound, in ROWS queued (not requests): a submit that would
    # push the queue past it raises ServerOverloaded (load shedding)
    "tpu_serve_queue_max": (8192, int, ("serve_queue_max",)),
    # default per-request deadline: a request not served by then gets a
    # structured ServingTimeout instead of waiting forever
    "tpu_serve_deadline_ms": (1000.0, float, ("serve_deadline_ms",)),
    # cap (in rows) on the ladder rungs pre-compiled at deploy/warmup
    # time; 0 warms the FULL tpu_predict_buckets ladder (on the auto
    # ladder that is rungs up to 1M rows — minutes of compiles and a
    # 1M-row dummy request per rung, so the default caps at 16k and the
    # full warm is an explicit opt-in). The coalescer never builds a
    # batch larger than its largest warmed rung, so the post-warmup
    # serving steady state compiles nothing
    "tpu_serve_warm_max_rows": (16384, int, ("serve_warm_max_rows",)),
    # serving featurization: "device" (default) bins a request with the
    # jitted raw->binned program (ops/device_bin.py) so a serving batch
    # is ONE host->device copy of raw float32; "host" keeps the
    # bin_columns numpy path (bit-identical parity/escape hatch)
    "tpu_serve_featurize": ("device", str, ("serve_featurize",)),
    # endpoints a server warms and accepts through the coalescer ladder:
    # comma list of predict / leaf / contrib. Warming compiles one
    # program per (endpoint, rung), so the non-default endpoints are
    # opt-in; submitting to an unlisted endpoint raises structurally
    # (serving it cold would compile in the request path)
    "tpu_serve_endpoints": ("predict", str, ("serve_endpoints",)),
    # background-tier coalescer lanes: a comma list of request kinds
    # (e.g. "contrib") whose batches only cut when NO foreground
    # (predict/leaf) rows are queued — explanation throughput must not
    # touch predict p99. "" (default) keeps every kind foreground FIFO.
    "tpu_serve_background_kinds": ("", str, ("serve_background_kinds",)),
    # precomputed TreeSHAP UNWIND tables (ops/treeshap_device.py):
    # "auto" (default) builds the per-leaf mask tables at deploy time
    # when they fit tpu_shap_table_mb and collapses the per-row kernel
    # to agreement-bits + table lookups; "off" keeps the EXTEND/UNWIND
    # loops; "on" forces tables (errors when over budget)
    "tpu_shap_tables": ("auto", str, ()),
    # HBM budget (MiB) for the deploy-time UNWIND table cache — the
    # R012 bound the witness cache probe reports against
    "tpu_shap_table_mb": (64, int, ()),
    # serving drift monitors (obs/drift.py): every served batch's binned
    # matrix folds into a device-resident [F, B] bin-occupancy
    # accumulator (plus a fixed-edge histogram of raw margins) with pure
    # on-device adds; every N serving ticks the window flushes to host
    # (the ONE declared d2h), PSI/KL per feature and score drift are
    # computed against the training-data reference distribution, and
    # hysteresis-gated drift_detected events land in the flight recorder
    # + Prometheus gauges. 0 disables (default) — the machine-readable
    # "model went stale / traffic shifted" refit trigger of ROADMAP 4
    "tpu_drift_flush_every": (0, int, ("drift_flush_every",)),
    # PSI above this marks a feature (or the score distribution) drifted
    # (drift_detected event); it un-marks (drift_cleared) only below
    # half the threshold — the hysteresis band that stops flapping.
    # 0.2 is the conventional "significant shift" PSI cut
    "tpu_drift_psi_threshold": (0.2, float, ("drift_psi_threshold",)),
    # fixed-edge bin count of the raw-margin (score) histogram; edges
    # come from the training-score reference range at attach time
    "tpu_drift_score_bins": (32, int, ("drift_score_bins",)),
    # PSI compares ~equal-reference-mass GROUPS of adjacent bins, not
    # the raw mapper bins (a finite window leaves most of a 255-bin
    # quantile mapper empty and unshifted traffic would read as
    # drifted); 10-20 is the conventional PSI bucket count
    "tpu_drift_bins": (16, int, ("drift_bins",)),
    # minimum rows a flush window needs before drift EVENTS fire (PSI
    # sampling noise has expectation ~(G-1)/rows, so a low-traffic
    # window would cry wolf on unshifted traffic); gauges/records still
    # update every flush. 0 = auto: 20 x tpu_drift_bins
    "tpu_drift_min_rows": (0, int, ("drift_min_rows",)),
    # serving SLO tracker (obs/drift.py): a served request is "good"
    # when it completes within tpu_serve_slo_ms; rolling good/bad counts
    # feed multi-window (5 m / 1 h) error-budget burn rates exposed as
    # gauges, with slo_burn flight events on sustained burn > 1.
    # 0 disables (default)
    "tpu_serve_slo_ms": (0.0, float, ("serve_slo_ms",)),
    # target good fraction of the SLO (burn rate 1.0 == exactly spending
    # the 1 - target error budget)
    "tpu_serve_slo_target": (0.99, float, ("serve_slo_target",)),
    # fault tolerance (io/checkpoint.py, parallel/multihost.py watchdog,
    # analysis/faultinject.py): atomic full-state snapshots every
    # tpu_checkpoint_freq iterations into tpu_checkpoint_dir (keep-last-k
    # rotation); lgb.train auto-resumes from the latest valid snapshot.
    # Unlike snapshot_freq (model text only), these snapshots carry the
    # complete optimizer state and resume BIT-IDENTICALLY.
    "tpu_checkpoint_dir": ("", str, ("checkpoint_dir",)),
    "tpu_checkpoint_freq": (0, int, ("checkpoint_freq",)),
    "tpu_checkpoint_keep": (3, int, ("checkpoint_keep",)),
    # collective watchdog: a multihost bootstrap / training step that
    # exceeds the deadline raises a structured TrainingInterrupted (after
    # a final snapshot) instead of hanging the pod; 0 disables
    "tpu_collective_deadline_s": (0.0, float, ("collective_deadline",)),
    "tpu_collective_retries": (3, int, ()),
    # deterministic chaos spec (analysis/faultinject.py), e.g.
    # "kill@iteration=3;corrupt@snapshot=2"; env LGBM_TPU_FAULTS wins
    "tpu_fault_spec": ("", str, ()),
    # snapshot / continue
    "snapshot_freq": (-1, int, ("save_period",)),
    "input_model": ("", str, ("model_input", "model_in")),
    "output_model": ("LightGBM_model.txt", str, ("model_output", "model_out")),
    # gpu compat (accepted, ignored)
    "gpu_platform_id": (-1, int, ()),
    "gpu_device_id": (-1, int, ()),
    "gpu_use_dp": (False, bool, ()),
    "num_gpu": (1, int, ()),
}

OBJECTIVE_ALIASES: Dict[str, str] = {
    "regression": "regression",
    "regression_l2": "regression",
    "l2": "regression",
    "mean_squared_error": "regression",
    "mse": "regression",
    "l2_root": "regression",
    "root_mean_squared_error": "regression",
    "rmse": "regression",
    "regression_l1": "regression_l1",
    "l1": "regression_l1",
    "mean_absolute_error": "regression_l1",
    "mae": "regression_l1",
    "huber": "huber",
    "fair": "fair",
    "poisson": "poisson",
    "quantile": "quantile",
    "mape": "mape",
    "mean_absolute_percentage_error": "mape",
    "gamma": "gamma",
    "tweedie": "tweedie",
    "binary": "binary",
    "multiclass": "multiclass",
    "softmax": "multiclass",
    "multiclassova": "multiclassova",
    "multiclass_ova": "multiclassova",
    "ova": "multiclassova",
    "ovr": "multiclassova",
    "xentropy": "xentropy",
    "cross_entropy": "xentropy",
    "xentlambda": "xentlambda",
    "cross_entropy_lambda": "xentlambda",
    "lambdarank": "lambdarank",
    "rank_xendcg": "rank_xendcg",
    "xendcg": "rank_xendcg",
    "xe_ndcg": "rank_xendcg",
    "xe_ndcg_mart": "rank_xendcg",
    "xendcg_mart": "rank_xendcg",
    "custom": "custom",
    "none": "custom",
    "null": "custom",
    "na": "custom",
}

METRIC_ALIASES: Dict[str, str] = {
    "l1": "l1", "mean_absolute_error": "l1", "mae": "l1", "regression_l1": "l1",
    "l2": "l2", "mean_squared_error": "l2", "mse": "l2", "regression_l2": "l2",
    "regression": "l2",
    "rmse": "rmse", "root_mean_squared_error": "rmse", "l2_root": "rmse",
    "quantile": "quantile",
    "mape": "mape", "mean_absolute_percentage_error": "mape",
    "huber": "huber",
    "fair": "fair",
    "poisson": "poisson",
    "gamma": "gamma",
    "gamma_deviance": "gamma_deviance",
    "tweedie": "tweedie",
    "ndcg": "ndcg", "lambdarank": "ndcg", "rank_xendcg": "ndcg",
    "xendcg": "ndcg", "xe_ndcg": "ndcg", "xe_ndcg_mart": "ndcg", "xendcg_mart": "ndcg",
    "map": "map", "mean_average_precision": "map",
    "auc": "auc",
    "average_precision": "average_precision",
    "binary_logloss": "binary_logloss", "binary": "binary_logloss",
    "binary_error": "binary_error",
    "auc_mu": "auc_mu",
    "multi_logloss": "multi_logloss", "multiclass": "multi_logloss",
    "softmax": "multi_logloss", "multiclassova": "multi_logloss",
    "multiclass_ova": "multi_logloss", "ova": "multi_logloss", "ovr": "multi_logloss",
    "multi_error": "multi_error",
    "cross_entropy": "cross_entropy", "xentropy": "cross_entropy",
    "cross_entropy_lambda": "cross_entropy_lambda", "xentlambda": "cross_entropy_lambda",
    "kullback_leibler": "kldiv", "kldiv": "kldiv",
    "none": "none", "na": "none", "null": "none", "custom": "none",
}

# Parameters accepted (for reference drop-in compatibility) but NOT implemented
# yet. Setting one to a non-default value warns loudly so a user migrating from
# the reference is never silently handed a different model (the reference
# rejects inconsistent configs outright, src/io/config.cpp:286). Entries are
# removed from this set as the corresponding feature lands.
UNIMPLEMENTED_PARAMS: Dict[str, str] = {
    "pre_partition": "pre-partitioned distributed data",
}

# alias -> canonical param name
_ALIAS_TABLE: Dict[str, str] = {}
for _name, (_d, _t, _aliases) in PARAMS.items():
    _ALIAS_TABLE[_name] = _name
    for _a in _aliases:
        _ALIAS_TABLE[_a] = _name


def alias_table() -> Dict[str, str]:
    return dict(_ALIAS_TABLE)


def _coerce(name: str, value: Any, typ: type) -> Any:
    if value is None:
        return None
    if name == "objective" and callable(value):
        return value  # custom objective function passes through untouched
    if typ is bool:
        if isinstance(value, str):
            return value.lower() in ("true", "1", "+", "yes")
        return bool(value)
    if typ is int:
        return int(value)
    if typ is float:
        return float(value)
    if typ is str:
        return str(value)
    return value


class Config:
    """Resolved parameter set (reference: struct Config, include/LightGBM/config.h:39)."""

    def __init__(self, params: Optional[Dict[str, Any]] = None):
        self._explicit: set = set()
        for name, (default, _typ, _aliases) in PARAMS.items():
            setattr(self, name, copy.copy(default))
        if params:
            self.set(params)

    def set(self, params: Dict[str, Any]) -> None:
        # resolve aliases first: explicit canonical name wins over aliases
        # (reference behavior: Config::KeepFirstValues in src/io/config.cpp)
        resolved: Dict[str, Any] = {}
        unknown: Dict[str, Any] = {}
        for key, value in params.items():
            canon = _ALIAS_TABLE.get(key)
            if canon is None:
                unknown[key] = value
                continue
            if canon in resolved and key != canon:
                continue  # first occurrence / canonical wins
            if canon in resolved and key == canon:
                resolved[canon] = value
                continue
            resolved[canon] = value
        for key, value in resolved.items():
            default, typ, _ = PARAMS[key]
            try:
                setattr(self, key, _coerce(key, value, typ))
            except (TypeError, ValueError) as e:
                log.fatal(f"Bad value {value!r} for parameter {key}: {e}")
            self._explicit.add(key)
        for key in unknown:
            log.warning(f"Unknown parameter: {key}")
        if "tpu_autotune" in resolved and \
                self.tpu_autotune.lower() not in ("off", "0", "false"):
            log.warning(f"tpu_autotune={self.tpu_autotune} is retired and "
                        "has no effect: engines resolve from platform and "
                        "shape (engines/registry.py)")
        for key in resolved:
            feature = UNIMPLEMENTED_PARAMS.get(key)
            if feature is None:
                continue
            default = PARAMS[key][0]
            value = getattr(self, key)
            # 0/0.0 are meaningful values and must still warn (they compare
            # equal to False), so use identity checks for the "unset" sentinels
            unset = value is None or value == "" or value is False
            if value != default and not unset:
                log.warning(
                    f"Parameter {key}={value!r} is accepted for compatibility "
                    f"but {feature} is NOT implemented yet — it has no "
                    "effect; results will differ from the reference LightGBM")
        self._check_consistency()

    def is_explicit(self, name: str) -> bool:
        return name in self._explicit

    def get(self, name: str, default: Any = None) -> Any:
        """Dict-style parameter access used across the objective/metric/boosting
        layers; falls back to ``default`` when the value is unset (None)."""
        value = getattr(self, name, None)
        return default if value is None else value

    def _check_consistency(self) -> None:
        # objective canonicalization (reference: ParseObjectiveAlias, config.h)
        obj = self.objective
        if obj is None or (isinstance(obj, str) and obj.lower() in OBJECTIVE_ALIASES):
            if isinstance(obj, str):
                self.objective = OBJECTIVE_ALIASES[obj.lower()]
        elif callable(obj):
            pass  # custom objective function
        else:
            log.fatal(f"Unknown objective: {obj!r}")
        # boosting alias: goss as boosting type rewrites to sample strategy
        # (reference: config.cpp:119-145)
        if self.boosting == "goss":
            self.boosting = "gbdt"
            self.data_sample_strategy = "goss"
        if self.boosting not in ("gbdt", "gbrt", "dart", "rf", "random_forest"):
            log.fatal(f"Unknown boosting type: {self.boosting}")
        if self.boosting == "gbrt":
            self.boosting = "gbdt"
        if self.boosting == "random_forest":
            self.boosting = "rf"
        if self.objective in ("multiclass", "multiclassova") and self.num_class <= 1:
            log.fatal("Number of classes should be specified and greater than 1 for multiclass training")
        if self.objective not in ("multiclass", "multiclassova") and self.is_explicit("num_class") and self.num_class != 1:
            log.fatal("Number of classes must be 1 for non-multiclass training")
        if self.bagging_freq > 0 and (self.bagging_fraction >= 1.0 or self.bagging_fraction <= 0.0) \
                and self.data_sample_strategy == "bagging" and not self.bagging_by_query:
            self.bagging_freq = 0
        if self.early_stopping_round < 0:
            self.early_stopping_round = 0
        if self.num_leaves < 2:
            self.num_leaves = 2
        if self.max_bin < 2:
            log.fatal("max_bin should be >= 2")
        if self.verbosity is not None:
            log.set_verbosity(self.verbosity)
        # metric list resolution
        self.metric = resolve_metrics(self.metric, self.objective)

    def to_dict(self) -> Dict[str, Any]:
        return {name: getattr(self, name) for name in PARAMS}


def default_metric_for_objective(objective: Any) -> Optional[str]:
    if not isinstance(objective, str):
        return None
    table = {
        "regression": "l2",
        "regression_l1": "l1",
        "huber": "huber",
        "fair": "fair",
        "poisson": "poisson",
        "quantile": "quantile",
        "mape": "mape",
        "gamma": "gamma",
        "tweedie": "tweedie",
        "binary": "binary_logloss",
        "multiclass": "multi_logloss",
        "multiclassova": "multi_logloss",
        "xentropy": "cross_entropy",
        "xentlambda": "cross_entropy_lambda",
        "lambdarank": "ndcg",
        "rank_xendcg": "ndcg",
    }
    return table.get(objective)


def resolve_metrics(metric: Any, objective: Any) -> List[str]:
    """Resolve the ``metric`` parameter into a canonical list."""
    if metric is None or metric == "" or metric == []:
        m = default_metric_for_objective(objective)
        return [m] if m else []
    if isinstance(metric, str):
        metric = [m.strip() for m in metric.split(",") if m.strip()]
    out: List[str] = []
    for m in metric:
        if not isinstance(m, str):
            continue
        canon = METRIC_ALIASES.get(m.lower())
        if canon is None:
            log.warning(f"Unknown metric: {m}")
            continue
        if canon == "none":
            return []
        if canon not in out:
            out.append(canon)
    return out
