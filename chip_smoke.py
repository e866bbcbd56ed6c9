"""chip_smoke.py — the quickest proof that lightgbm_tpu still starts on the chip.

    python chip_smoke.py                # one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4      # data- and voting-parallel, 4 chips

One process, one JAX import, no child. It drives the normal entry points
(``lgb.Dataset`` / ``lgb.train`` / ``Booster.update`` / ``predict`` /
``save_model`` / ``serve``) at the higgs width the repo benches (28
features, 255 leaves, 255 bins, data from ``bench.make_higgs_like``), proves
the TPU path was the one taken (compact grower, fused Mosaic kernel, a
``tpu_custom_call`` in the compiled step program), and checks what comes out
against independent references. Every phase prints one JSON line; any
failed check raises, so the run cannot end with exit code 0. The last line
of stdout is ``{"ok": true, "device": {...}}`` with the device as JAX
reports it. Without a TPU it exits 2 and prints no result.

Every timing printed here is a SMOKE FIGURE (one cold run, no repeats), not a
benchmark row; it goes into no BENCH_* file. The four-chip phase stays at
its 4,194,304 rows: what bounds a data-parallel job (2^24 - 1 rows a shard
for the f32 shard-local counts, 2^31 - 1 in all for the int32 row ids and
the counts summed across shards) is measured at size by the benchmark's cell
``criteo_dp4_train`` (40,000,000 rows over four chips), not here.

Rehearsal on the CPU (no chip time; ``--rehearsal`` is the explicit opt-in
that swaps the Mosaic kernel for Pallas interpret mode and shrinks rows,
leaves and iterations — never a quiet platform check):

    JAX_PLATFORMS=cpu python chip_smoke.py --rehearsal
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python chip_smoke.py --chips 4 --rehearsal
"""
import argparse
import json
import logging
import os
import sys
import tempfile
import time

FEATURES, LEAVES, MAX_BIN = 28, 255, 255
PUBLISHED_ROWS = 10_500_000       # Higgs, docs/Experiments.rst (BASELINE.md)
MIN_ROWS = 1_000_000              # rows may be cut (printed), widths never

# AUC floors on the held-out slice ("the run has learned something"). The
# full-size floor sits 0.02 under what 8 iterations reach on this generator
# (0.8495 held out, 1M rows, CPU backend: PERF.md PR 24); the rehearsal
# floor only has to beat chance clearly at 3 iterations of 15 leaves.
AUC_FLOOR, AUC_FLOOR_REHEARSAL = 0.83, 0.65
# trained (device engine, f32 sums in tree order) vs the same model reloaded
# from its text (host path, float64 sum of the same leaf values): the
# README's save/load round-trip bound
ROUNDTRIP_TOL = 1e-6
# served vs direct: same engine, same tree order; only the row rung differs
SERVE_TOL = 1e-6
# device TreeSHAP vs the host twin: the f32 tolerance tests/test_device_serving
# pins for the contrib endpoint
CONTRIB_TOL = 2e-5
# data-parallel vs serial after a few iterations: every histogram entry is an
# f32 sum regrouped across 4 shards by the psum (~1e-7 relative), leaf values
# inherit it; the bound tests/test_distributed.py holds the same pair to
PARALLEL_RTOL, PARALLEL_ATOL = 1e-4, 1e-5


def emit(phase, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


class StderrLog(logging.Handler):
    """The package's log lines go to stderr (stdout carries the JSON lines
    only); warnings — where the logged structural fall-backs announce
    themselves — are kept and printed as the ``warnings`` phase."""

    def __init__(self):
        super().__init__()
        self.warnings = []

    def emit(self, record):
        msg = record.getMessage()
        sys.stderr.write(msg + "\n")
        if record.levelno >= logging.WARNING:
            self.warnings.append(msg)


def check(ok, what):
    if not ok:
        raise AssertionError(f"chip_smoke: {what}")


def base_params(rehearsal, leaves):
    params = {
        "objective": "binary", "num_leaves": leaves, "max_bin": MAX_BIN,
        "learning_rate": 0.1, "min_data_in_leaf": 100, "verbosity": 1,
    }
    if rehearsal:
        # the same grower and kernel at toy rows: compact is forced (auto
        # needs >= 65536 rows) and the kernel runs in Pallas interpret mode
        params.update(tpu_grower="compact", tpu_fused="on",
                      tpu_fused_interpret=True, tpu_fused_block=128,
                      min_data_in_leaf=20)
    return params


def train(lgb, params, ds, iters):
    """lgb.train for the first iteration, Booster.update for the rest; each
    waits for the device. Returns (booster, first_s, per_iter_s, lowerings
    after the first iteration, cache counters)."""
    from lightgbm_tpu.analysis import guards
    with guards.cache_counter() as cache:
        t0 = time.perf_counter()
        bst = lgb.train(params, ds, num_boost_round=1,
                        keep_training_booster=True)
        bst._gbdt.train_score.block_until_ready()
        first_s = time.perf_counter() - t0
        per_iter = []
        with guards.compile_counter() as steady:
            for _ in range(iters):
                t0 = time.perf_counter()
                bst.update()
                bst._gbdt.train_score.block_until_ready()
                per_iter.append(time.perf_counter() - t0)
    return bst, first_s, per_iter, steady.lowerings, cache


def step_program_text(bst, key):
    """Compiled HLO text of a train-step program, as recorded by the
    booster under LGBM_TPU_COMM_ACCOUNTING=1 (boosting/gbdt._comm_capture)."""
    g = bst._gbdt
    check(key in g._comm_hlo,
          f"step program {key!r} was not captured (have {sorted(g._comm_hlo)})")
    check(len(g._comm_hlo_history[key]) == 1,
          f"step program {key!r} re-lowered "
          f"{len(g._comm_hlo_history[key]) - 1} time(s)")
    return g._comm_hlo[key]


def resolved_engine(bst):
    """The engine fields of the booster's GrowerParams as resolved."""
    g = bst._gbdt
    gp = g.grower_params
    return {
        "grower": "compact" if g._use_compact else "masked",
        "entry": g._engine_resolution.entry_id,
        "fused_block": gp.fused_block, "fused_dual": gp.fused_dual,
        "hist_impl": gp.hist_impl, "hist_mbatch": gp.hist_mbatch,
        "hist_layout": gp.hist_layout, "hist_overlap": gp.hist_overlap,
        "step_buckets": gp.step_buckets, "quant_hist": gp.quant_hist,
        "bin_pack4": gp.bin_pack4,
    }


def engine_proof(bst, rehearsal, phase="engine"):
    """Which engine trained: resolved fields + their sources, the
    structural clamps, and the kernel in the compiled step."""
    from lightgbm_tpu.ops.fused_split import fused_block_cap
    g = bst._gbdt
    gp, res = g.grower_params, g._engine_resolution
    requested = int(g.config.get("tpu_fused_block", 512))
    fields = resolved_engine(bst)
    text = step_program_text(bst, "compact_step_k0") if g._use_compact else ""
    emit(phase, rehearsal=rehearsal, resolved=fields, sources=res.sources,
         fallbacks={
             "fused_block_requested": requested,
             "fused_block_vmem_cap": fused_block_cap(
                 g._fused_clamp_ctx["num_cols"], gp.hist_mbatch,
                 hist_layout=gp.hist_layout) if g._fused_clamp_ctx else None,
             "note": "engines/registry.clamp_fused_block shrinks the block "
                     "to the scoped-VMEM cap; sublane layout needs B <= 64"},
         tpu_custom_call_in_step="tpu_custom_call" in text,
         step_program_bytes=len(text))
    check(g._use_compact, "the compact grower is not in use")
    check(gp.fused_block > 0, "the fused kernel is off (fused_block == 0)")
    if rehearsal:
        check(gp.fused_interpret, "rehearsal must run the kernel interpreted")
    else:
        check(not gp.fused_interpret, "the kernel ran in interpret mode")
        check("tpu_custom_call" in text,
              "no tpu_custom_call in the compiled step program")
    return text


def first_tree_splits(bst):
    """(split_feature, threshold) lines of Tree=0 in the model text."""
    block = bst.model_to_string().split("Tree=0\n", 1)[1].split("\n\n", 1)[0]
    kv = dict(line.split("=", 1) for line in block.splitlines() if "=" in line)
    return kv["split_feature"], kv["threshold"]


# --------------------------------------------------------------- one chip
def run_one_chip(args, lgb, np, tmp):
    import jax
    from sklearn.metrics import roc_auc_score

    from bench import make_higgs_like
    from lightgbm_tpu.analysis import guards
    rh = args.rehearsal
    rows = args.rows or (8192 if rh else PUBLISHED_ROWS)
    if not rh:
        check(rows >= MIN_ROWS, f"--rows below {MIN_ROWS}: cut rows no further")
    leaves = 15 if rh else LEAVES
    iters = 2 if rh else 7
    holdout = 2048 if rh else 200_000
    n_predict = 3000 if rh else 1_000_000
    warm_rows = 1024 if rh else 4096

    t0 = time.perf_counter()
    X, y = make_higgs_like(rows + holdout, FEATURES, seed=args.seed)
    Xh, yh, X, y = X[rows:], y[rows:], X[:rows], y[:rows]
    params = base_params(rh, leaves)
    params["tpu_serve_endpoints"] = "predict,leaf,contrib"
    datagen_s = time.perf_counter() - t0

    # ---- train
    t0 = time.perf_counter()
    ds = lgb.Dataset(X, label=y, params=params)
    ds.construct()
    construct_s = time.perf_counter() - t0
    bst, first_s, per_iter, lowerings, cache = train(lgb, params, ds, iters)
    auc = float(roc_auc_score(yh, bst.predict(Xh)))
    floor = AUC_FLOOR_REHEARSAL if rh else AUC_FLOOR
    emit("train", smoke_figures_not_benchmark=True, rehearsal=rh,
         rows=rows, published_rows=PUBLISHED_ROWS,
         rows_cut=None if rows == PUBLISHED_ROWS else
         f"{rows} of {PUBLISHED_ROWS} rows (widths unchanged)",
         features=FEATURES, num_leaves=leaves, max_bin=MAX_BIN,
         seed=args.seed, datagen_s=round(datagen_s, 2),
         construct_s=round(construct_s, 2),
         first_iteration_s=round(first_s, 2),
         per_iteration_s=[round(t, 4) for t in per_iter],
         iterations=1 + iters, lowerings_after_warmup=lowerings,
         compile_cache={"dir": jax.config.jax_compilation_cache_dir,
                        "from_env": bool(os.environ.get(
                            guards.CACHE_DIR_ENV)),
                        "requests": cache.requests, "hits": cache.hits},
         holdout_rows=holdout, holdout_auc=round(auc, 5), auc_floor=floor)
    check(lowerings == 0, f"{lowerings} lowering(s) after the first iteration")
    check(auc >= floor, f"held-out AUC {auc:.4f} below the floor {floor}")

    # ---- engine proof
    engine_proof(bst, rh)

    # ---- predict: device engine vs the same model reloaded from its text
    Xp = X[:n_predict]
    t0 = time.perf_counter()
    p_dev = bst.predict(Xp)
    predict_first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    p_dev2 = bst.predict(Xp)
    predict_s = time.perf_counter() - t0
    model_path = os.path.join(tmp, "model.txt")
    bst.save_model(model_path)
    p_host = lgb.Booster(model_file=model_path).predict(Xp)
    diff = float(np.abs(p_dev - p_host).max())
    emit("predict", rows=len(Xp), engine=str(bst._gbdt._predict_cfg()[2]),
         trees=bst.num_trees(), first_call_s=round(predict_first_s, 2),
         second_call_s=round(predict_s, 2), finite=bool(
             np.isfinite(p_dev).all()), max_abs_diff_vs_reloaded=diff,
         tolerance=ROUNDTRIP_TOL)
    check(p_dev.shape == (len(Xp),) and np.isfinite(p_dev).all(),
          "predict returned a wrong shape or non-finite values")
    check(np.array_equal(p_dev, p_dev2), "predict is not repeatable")
    check(diff <= ROUNDTRIP_TOL, f"predict differs from the reloaded model "
          f"by {diff} > {ROUNDTRIP_TOL}")

    # ---- serve: mixed sizes through the coalescer, equal to direct calls
    sizes = [1, 7, 64, 300, 1000, warm_rows] * 6
    rng = np.random.RandomState(args.seed + 1)
    starts = [int(rng.randint(0, len(Xp) - s)) for s in sizes]
    t0 = time.perf_counter()
    srv = bst.serve(warm_max_rows=warm_rows, queue_max=1 << 16,
                    deadline_ms=120_000.0)
    warm_s = time.perf_counter() - t0
    try:
        health = srv.health()
        with guards.compile_counter() as steady:
            t0 = time.perf_counter()
            futs = [srv.submit(Xp[a:a + s]) for a, s in zip(starts, sizes)]
            f_leaf = srv.submit_leaf(Xp[:64])
            f_contrib = srv.submit_contrib(Xp[:64])
            outs = [f.result(timeout=300) for f in futs]
            leaf = f_leaf.result(timeout=300)
            contrib = f_contrib.result(timeout=300)
            serve_s = time.perf_counter() - t0
        stats = srv.stats
    finally:
        srv.close(drain=True)
    closed = srv.health()
    x32 = Xp[:64].astype(np.float32)
    d_pred = max(float(np.abs(o - bst.predict(
        Xp[a:a + s].astype(np.float32))).max())
        for o, a, s in zip(outs, starts, sizes))
    d_contrib = float(np.abs(
        contrib - bst.predict(x32, pred_contrib=True)).max())
    leaf_equal = bool(np.array_equal(leaf, bst.predict(x32, pred_leaf=True)))
    emit("serve", requests=len(sizes) + 2, sizes=sorted(set(sizes)),
         endpoints=health["endpoints"], warm_rungs=health["warm_rungs"],
         warm_s=round(warm_s, 2), answered_in_s=round(serve_s, 3),
         lowerings_after_warm=steady.lowerings,
         ticks=stats["ticks"], max_abs_diff_predict=d_pred,
         predict_tolerance=SERVE_TOL, leaf_equal=leaf_equal,
         max_abs_diff_contrib=d_contrib, contrib_tolerance=CONTRIB_TOL,
         worker_alive_after_close=closed["worker_alive"])
    check(health["ready"], f"server was not ready after warm: {health}")
    check(steady.lowerings == 0,
          f"{steady.lowerings} lowering(s) while serving a warm ladder")
    check(d_pred <= SERVE_TOL, f"served predict differs by {d_pred}")
    check(leaf_equal, "served pred_leaf differs from the direct call")
    check(d_contrib <= CONTRIB_TOL, f"served contrib differs by {d_contrib}")
    check(closed["closed"] and not closed["worker_alive"],
          "the server did not stop cleanly")


# -------------------------------------------------------------- four chips
def compare_parallel(np, name, serial, par, X, step_key, asks_for=None):
    """The checks that prove ``par`` really trained across four devices and
    agrees with ``serial``. ``asks_for``: a collective the program itself
    must request (StableHLO name) — what the compiler makes of it is
    printed: the v5e compiler decomposes small reduce-scatters and
    all-gathers into all-reduces."""
    import jax

    from lightgbm_tpu.analysis.hlo import collective_bytes
    g = par._gbdt
    check(g.mesh is not None and g.mesh.devices.size == 4,
          f"{name}: the mesh does not hold four devices ({g.mesh})")
    arrays = {"binned": g.binned, "train_score": g.train_score}
    if g._use_compact:
        arrays["work"] = g._compact["work"]
    placed = {k: len(a.sharding.device_set) for k, a in arrays.items()}
    check(all(n == 4 for n in placed.values()),
          f"{name}: training arrays are not spread over four devices: "
          f"{placed}")
    text = step_program_text(par, step_key)
    coll = {k: v for k, v in collective_bytes(text).items() if v}
    check(coll.get("count", 0) > 0,
          f"{name}: no collective in the compiled step program")
    if asks_for:
        check(asks_for in g.aot_lower_program(step_key).as_text(),
              f"{name}: the step program does not ask for {asks_for}")
    same_first = first_tree_splits(serial) == first_tree_splits(par)
    sample = X[:100_000]
    ps, pp = serial.predict(sample), par.predict(sample)
    diff = float(np.abs(ps - pp).max())
    emit(name, devices=jax.device_count(), mesh=list(g.mesh.devices.shape),
         device_set_sizes=placed, resolved=resolved_engine(par),
         sources=g._engine_resolution.sources, asks_for=asks_for,
         compiled_collective_bytes=coll,
         tpu_custom_call_in_step="tpu_custom_call" in text,
         first_tree_equal=same_first, max_abs_diff_scores=diff,
         rtol=PARALLEL_RTOL, atol=PARALLEL_ATOL)
    check(same_first, f"{name}: first tree differs from the serial model")
    check(np.allclose(pp, ps, rtol=PARALLEL_RTOL, atol=PARALLEL_ATOL),
          f"{name}: scores differ from serial by {diff}")


def run_four_chips(args, lgb, np):
    import jax

    from bench import make_higgs_like
    rh = args.rehearsal
    check(jax.device_count() == 4,
          f"--chips 4 needs exactly four devices, found {jax.device_count()}"
          " (rehearsal: XLA_FLAGS=--xla_force_host_platform_device_count=4)")

    def fit(rows, leaves, iters, learners):
        X, y = make_higgs_like(rows, FEATURES, seed=args.seed)
        params = base_params(rh, leaves)
        ds = lgb.Dataset(X, label=y, params=params)
        ds.construct()
        out = {}
        for learner in learners:
            p = dict(params, tree_learner=learner)
            if learner == "voting":
                p.pop("tpu_grower", None)   # rehearsal forces compact
            bst, first_s, per_iter, lowerings, _ = train(
                lgb, p, ds, iters - 1)
            emit(f"train_{learner}", smoke_figures_not_benchmark=True,
                 rehearsal=rh, rows=rows, features=FEATURES,
                 num_leaves=leaves, max_bin=MAX_BIN, iterations=iters,
                 first_iteration_s=round(first_s, 2),
                 per_iteration_s=[round(t, 4) for t in per_iter],
                 lowerings_after_warmup=lowerings)
            check(lowerings == 0, f"{learner}: {lowerings} lowering(s) "
                                  "after the first iteration")
            out[learner] = bst
        return X, out

    # serial on one device vs data-parallel on four: each shard is far
    # above the 65,536-row compact threshold
    X, b = fit(args.rows or (8192 if rh else 4_194_304),
               15 if rh else LEAVES, 2 if rh else 3, ("serial", "data"))
    check(b["serial"]._gbdt.mesh is None, "serial run built a mesh")
    engine_proof(b["serial"], rh, phase="engine_serial")
    engine_proof(b["data"], rh, phase="engine_data")
    compare_parallel(np, "data_vs_serial", b["serial"], b["data"], X,
                     "compact_step_k0", asks_for="reduce_scatter")
    del b

    # voting-parallel: the masked grower (O(N x leaves)), so a short run.
    # GSPMD partitions its step, so its histograms take the XLA einsum
    # (sources.hist_impl == "gspmd"); at 28 features the default top_k=20
    # elects every feature, i.e. the exact data-parallel histogram
    X, b = fit(4096 if rh else 262_144, 7 if rh else 31, 2,
               ("serial", "voting"))
    check(not b["voting"]._gbdt._use_compact,
          "voting is expected on the masked grower")
    compare_parallel(np, "voting_vs_serial", b["serial"], b["voting"], X,
                     "step")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU rehearsal: interpret-mode kernel, toy sizes")
    ap.add_argument("--rows", type=int, default=0,
                    help=f"training rows (default {PUBLISHED_ROWS}; "
                         f">= {MIN_ROWS} on the chip)")
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    # the booster records its compiled step programs under this (existing)
    # switch; the engine proof reads the kernel and the collectives there
    os.environ["LGBM_TPU_COMM_ACCOUNTING"] = "1"
    import jax
    import numpy as np

    dev = jax.devices()[0]      # a backend that cannot start raises here
    if args.rehearsal:
        if dev.platform != "cpu":
            print("chip_smoke: --rehearsal is the CPU rehearsal "
                  f"(JAX_PLATFORMS=cpu); platform is {dev.platform!r}",
                  file=sys.stderr)
            return 2
    elif dev.platform != "tpu":
        print(f"chip_smoke: JAX found no accelerator (platform "
              f"{dev.platform!r}); the CPU rehearsal is asked for with "
              "--rehearsal", file=sys.stderr)
        return 2

    import lightgbm_tpu as lgb
    from lightgbm_tpu.analysis.guards import (checkout_cache_dir,
                                              configure_compile_cache)
    from lightgbm_tpu.utils.log import register_logger
    log = StderrLog()
    logger = logging.getLogger("chip_smoke")
    logger.addHandler(log)
    logger.setLevel(logging.INFO)
    logger.propagate = False
    register_logger(logger)
    configure_compile_cache(checkout_cache_dir())
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        if args.chips == 4:
            run_four_chips(args, lgb, np)
        else:
            run_one_chip(args, lgb, np, tmp)
    emit("warnings", messages=log.warnings)
    emit("done", wall_s=round(time.perf_counter() - t0, 1),
         rehearsal=args.rehearsal)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
