"""Benchmark: boosting iterations/sec on a Higgs-like binary problem, one chip.

Reference baseline (BASELINE.md): LightGBM CPU trains Higgs (10.5M rows x 28
features, num_leaves=255, 500 iters) at ~3.84 iters/s on 2x Xeon E5-2690v4
(docs/Experiments.rst:113). This bench runs the same FULL configuration —
binary logloss, 28 dense float features, 10.5M rows, 255 leaves, 255 bins —
on the TPU chip the driver exposes (round 1 ran a 10x-smaller config; the
compact grower made the full shape tractable, see ops/grower_compact.py).

Env knobs (BENCH_ROWS/FEATURES/NUM_LEAVES/MAX_BIN/ITERS/WARMUP) scale it
down for quick runs.

Prints ONE json line: {"metric", "value", "unit", "vs_baseline"}.
"""
import json
import os
import sys
import time

import numpy as np

def _cli_override(flag, default):
    """``--rows 5e5``-style CLI overrides (the env knobs predate them).
    The row override exists so scaled-down runs are explicit in the
    command line AND normalized: every recorded shape now carries a
    rows/s column, so a 500k-row Allstate number is never quoted next to
    the reference's full 13.2M-row wall without a per-row figure.

    Runs at import time (bench.py is also imported for its dataset
    makers), so a missing or unparseable value must not crash the host
    process — it warns and keeps the default."""
    if flag not in sys.argv:
        return default
    idx = sys.argv.index(flag)
    try:
        return int(float(sys.argv[idx + 1]))
    except (IndexError, ValueError):
        sys.stderr.write(f"[bench] ignoring {flag}: expected a numeric "
                         "value after the flag\n")
        return default


ROWS = _cli_override("--rows", int(float(os.environ.get("BENCH_ROWS",
                                                        10_500_000))))
FEATURES = int(os.environ.get("BENCH_FEATURES", 28))
NUM_LEAVES = int(os.environ.get("BENCH_NUM_LEAVES", 255))
MAX_BIN = int(os.environ.get("BENCH_MAX_BIN", 255))
ITERS = int(os.environ.get("BENCH_ITERS", 15))
WARMUP = int(os.environ.get("BENCH_WARMUP", 2))
BASELINE_ITERS_PER_SEC = 3.84  # Higgs-10.5M CPU, docs/Experiments.rst:113


def _clear_backend_cache(jax_mod):
    """Drop jax's (possibly partially-populated) backend cache.

    When backend discovery brings the CPU client up first and the TPU
    client then fails, xla_bridge has already cached ``_backends={'cpu'}``
    before raising — a plain ``jax.devices()`` retry would return that
    CPU backend. Clearing forces a genuine re-init on the next attempt;
    :func:`_require_device` additionally refuses a CPU device however it
    was reached."""
    if getattr(jax_mod, "__name__", None) != "jax":
        return      # test doubles manage their own state
    # private reach, kept: jax 0.9.0 has no public way to drop the
    # backend cache; an import error here must surface, not skip the clear
    from jax._src import xla_bridge
    xla_bridge._clear_backends()


# transient backend-init / device-enumeration failure signatures. The
# canonical list lives in lightgbm_tpu.parallel.multihost.TRANSIENT_ERRORS
# (shared with the collective watchdog's retry classifier).
from lightgbm_tpu.parallel.multihost import (  # noqa: E402
    TRANSIENT_ERRORS as _TRANSIENT_BACKEND_ERRORS)


def _init_backend_with_retry(jax_mod, attempts=None, base_delay_s=5.0):
    """Return the default device, retrying transient backend-init AND
    device-enumeration failures.

    On a machine where the chip belongs to one process this loop cannot
    make a chip appear — at best it waits out a previous process that is
    still releasing it, at worst it delays a real failure by the sum of
    its delays. It stays because tests/test_hazard_fixes.py pins its
    classification and back-off until bench.py is restructured into
    cells (ROADMAP S1/D5). Each retry clears the backend cache first (see
    _clear_backend_cache) so the re-init is real. Non-transient errors
    re-raise immediately; the last transient attempt re-raises too, and
    main() converts the raise into a structured failure stub."""
    if attempts is None:
        # env override rounded + re-guarded, never trusted raw (same
        # convention as LGBM_TPU_FUSED_BS): a 0/negative/garbage value
        # must not turn the retry loop into a silent None return
        try:
            attempts = int(os.environ.get("BENCH_INIT_ATTEMPTS", 5))
        except ValueError:
            sys.stderr.write("[bench] ignoring non-numeric "
                             "BENCH_INIT_ATTEMPTS; using 5 attempts\n")
            attempts = 5
    attempts = max(1, attempts)
    for attempt in range(attempts):
        try:
            _fire_fault("backend_init", attempt=attempt + 1)
            devices = jax_mod.devices()
            if not devices:
                raise RuntimeError(
                    "device enumeration returned an empty device list")
            return devices[0]
        except Exception as err:  # noqa: BLE001 - classified below
            msg = str(err)
            transient = any(t in msg for t in _TRANSIENT_BACKEND_ERRORS)
            if not transient or attempt == attempts - 1:
                raise
            delay = base_delay_s * (2 ** attempt)
            sys.stderr.write(
                f"[bench] backend init failed (attempt {attempt + 1}/"
                f"{attempts}): {msg.splitlines()[0][:200]}; retrying in "
                f"{delay:.0f}s\n")
            _clear_backend_cache(jax_mod)
            time.sleep(delay)


#: the explicit opt-in for running a bench stage on the CPU backend (a
#: rehearsal of the control flow; its rows say ``platform: cpu`` and are
#: not device numbers)
CPU_REHEARSAL_FLAG = "--cpu-rehearsal"


def _require_device(jax_mod):
    """The device every bench stage runs on: a TPU, or — only when the
    command line carries ``--cpu-rehearsal`` — the CPU. Any other
    outcome exits non-zero, however the backend was reached (first try
    or after a retry): a bench that finds no chip fails, it does not
    fall back."""
    dev = _init_backend_with_retry(jax_mod)
    sys.stderr.write(f"[bench] backend platform: {dev.platform}\n")
    if dev.platform != "tpu" and not (
            dev.platform == "cpu" and CPU_REHEARSAL_FLAG in sys.argv):
        raise SystemExit(
            f"[bench] platform is {dev.platform!r}, not 'tpu': refusing to "
            f"measure (pass {CPU_REHEARSAL_FLAG} to rehearse the control "
            "flow on the CPU backend)")
    return dev


def _configure_cache():
    """The repo's one compile-cache rule (analysis/guards.py):
    JAX_COMPILATION_CACHE_DIR when set, else <checkout>/.jax_cache."""
    from lightgbm_tpu.analysis.guards import (checkout_cache_dir,
                                              configure_compile_cache)
    configure_compile_cache(checkout_cache_dir())


def _fire_fault(site, **ctx):
    """Chaos hook (lightgbm_tpu/analysis/faultinject.py): lets the
    fault-injection tests exercise the bench's backend-retry and
    checkpoint-resume paths deterministically. A no-op when the package
    is absent (bench.py stays runnable standalone) or no spec is armed."""
    try:
        from lightgbm_tpu.analysis.faultinject import active_plan
    except ImportError:  # pragma: no cover - standalone bench
        return
    active_plan().fire(site, **ctx)


def _resumable_update_loop(bst, make_booster, target_iters, ckpt_dir,
                           ckpt_freq=5, keep=2, max_retries=5,
                           base_delay_s=5.0):
    """Advance ``bst`` to ``target_iters`` total iterations, checkpointing
    every ``ckpt_freq`` and RESUMING from the latest snapshot after a
    transient backend death instead of restarting from iteration 0 (the
    r05/r06 death mode the init-retry loop alone could not close: a run
    that died mid-boosting lost every completed iteration). A failure
    that keeps recurring with NO forward progress gives up after
    ``max_retries`` resume attempts (with exponential backoff between
    them) so a persistently-down backend falls through to the structured
    failure stub instead of busy-looping. Returns the (possibly rebuilt)
    booster at ``target_iters``."""
    from lightgbm_tpu.io import checkpoint as ckpt_mod
    retries, last_progress = 0, -1
    while bst.current_iteration() < target_iters:
        try:
            _fire_fault("bench_update", iteration=bst.current_iteration() + 1)
            bst.update()
            done = bst.current_iteration()
            if ckpt_dir and done % ckpt_freq == 0:
                bst.save_checkpoint(ckpt_dir, keep=keep)
        except Exception as err:  # noqa: BLE001 - classified below
            msg = str(err)
            transient = any(t in msg for t in _TRANSIENT_BACKEND_ERRORS)
            if not ckpt_dir or not transient:
                raise
            reached = bst.current_iteration()
            if reached > last_progress:
                retries, last_progress = 0, reached
            retries += 1
            if retries > max_retries:
                sys.stderr.write(
                    f"[bench] giving up after {max_retries} resume "
                    f"attempts with no progress past iteration "
                    f"{last_progress}\n")
                raise
            delay = base_delay_s * (2 ** (retries - 1))
            sys.stderr.write(
                f"[bench] transient failure mid-run at iteration "
                f"{reached}: {msg.splitlines()[0][:200]}; resuming from "
                f"checkpoint in {delay:.0f}s "
                f"(attempt {retries}/{max_retries})\n")
            time.sleep(delay)
            bst = make_booster()
            state = ckpt_mod.load_latest(ckpt_dir)
            if state is not None:
                try:
                    bst._restore_checkpoint(state)
                except ValueError as verr:
                    sys.stderr.write(f"[bench] ignoring incompatible "
                                     f"checkpoint: {verr}\n")
            sys.stderr.write(f"[bench] resumed at iteration "
                             f"{bst.current_iteration()}\n")
    return bst


def _emit_failure_stub(stage: str, err: BaseException) -> None:
    """Print a STRUCTURED failure row and record it in BENCH_SHAPES.json.

    The driver records the bench's one-line JSON; before round 6 a
    backend that never came up raised straight through and the BENCH_r0x
    row was silently absent (the r05 gap). Now the row always exists —
    with ``value: null`` and the error inline — and the process still
    exits nonzero so automation sees the failure."""
    first_line = str(err).splitlines()[0][:300] if str(err) else repr(err)
    payload = {
        "stage": stage,
        "error": first_line,
        "error_type": type(err).__name__,
        "time": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    try:
        _record_shape("last_failure", payload)
    except Exception as rec_err:  # noqa: BLE001 - the stub must not sink
        sys.stderr.write(f"[bench] failed to record failure stub: "
                         f"{rec_err}\n")
    print(json.dumps({
        "metric": f"bench-failed ({stage})",
        "value": None,
        "unit": "iters/sec/chip",
        "vs_baseline": None,
        "error": first_line,
    }))


def _timed_mean(fn, *args, reps=10):
    """THE warm-up/rep timing discipline for fixed-rep microbench cells
    (2 warm calls cover compile + cache fill, then the mean of ``reps``
    back-to-back dispatches with one trailing sync). Every fixed-rep
    section shares this helper so a change to the discipline cannot make
    recorded BENCH_SHAPES cells inconsistent across sections."""
    fn(*args).block_until_ready()
    fn(*args).block_until_ready()
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
    out.block_until_ready()
    return (time.time() - t0) / reps


def make_higgs_like(n, f, seed=7):
    """Dense float features + nonlinear binary target (Higgs-shaped)."""
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    w1 = rng.randn(f) / np.sqrt(f)
    w2 = rng.randn(f) / np.sqrt(f)
    logits = X @ w1 + 0.7 * np.abs(X @ w2) - 0.4 + 0.5 * rng.randn(n)
    y = (logits > 0).astype(np.float64)
    return X, y


def make_allstate_like(n, f, card=8, seed=7):
    """Sparse one-hot blocks (Allstate F=4228 shape) — exercises EFB.

    Generated group by group to avoid a dense [n, f] float64 intermediate."""
    rng = np.random.RandomState(seed)
    X = np.zeros((n, f), np.float32)
    logits = 0.5 * rng.randn(n)
    off = 0
    while off < f:
        w = min(card, f - off)           # remainder becomes a smaller group
        cats = rng.randint(0, w, size=n)
        X[np.arange(n), off + cats] = 1.0
        wg = rng.randn(w) * 0.3
        logits += wg[cats]
        off += w
    y = (logits > 0).astype(np.float64)
    return X, y


def make_msltr_like(n, f, docs_per_query=120, seed=7):
    """MS-LTR-shaped ranking data: graded labels 0-4, query groups
    (BASELINE.md MS-LTR row: 2.27M docs x 137 features,
    ref docs/Experiments.rst:117)."""
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    w = rng.randn(f) / np.sqrt(f)
    rel = X @ w + 0.8 * rng.randn(n)
    # graded relevance by global quantiles
    qs = np.quantile(rel, [0.55, 0.75, 0.9, 0.97])
    y = np.digitize(rel, qs).astype(np.float64)
    n_q = n // docs_per_query
    group = np.full(n_q, docs_per_query, np.int64)
    rest = n - n_q * docs_per_query
    if rest:
        group = np.concatenate([group, [rest]])
    return X, y, group


def _record_shape(key, payload):
    rec_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_SHAPES.json")
    rec = {}
    if os.path.exists(rec_path):
        with open(rec_path) as fh:
            rec = json.load(fh)
    rec[key] = payload
    with open(rec_path, "w") as fh:
        json.dump(rec, fh, indent=1, sort_keys=True)


def run_hist_microbench(print_json=True):
    """BENCH_HIST_MICRO=1: the tentpole's speed claim, measured directly —
    the quantized int8 one-hot contraction (int8 x int8 -> int32,
    preferred_element_type=int32) vs the fp32-HIGHEST one-hot einsum it
    replaces, on the SAME [N, F] x B histogram shape and channel count.
    Records BENCH_SHAPES.json["hist_micro"] with both timings and the
    speedup (acceptance: >= 2x on TPU)."""
    import functools

    import jax
    import jax.numpy as jnp

    dev = _require_device(jax)
    from lightgbm_tpu.ops.histogram import histogram_block

    n = int(float(os.environ.get("BENCH_HIST_ROWS", 1 << 20)))
    f = int(os.environ.get("BENCH_HIST_FEATURES", 28))
    b = int(os.environ.get("BENCH_HIST_BINS", 256))
    reps = int(os.environ.get("BENCH_HIST_REPS", 10))
    rng = np.random.RandomState(0)
    binned = jnp.asarray(rng.randint(0, b, (n, f)).astype(np.uint8))
    ch_f32 = jnp.asarray(rng.randn(n, 4).astype(np.float32))
    codes = rng.randint(-8, 9, (n, 4)).astype(np.int8)
    codes[:, 2:] = 1                       # count channels
    ch_int8 = jnp.asarray(codes)

    # f32 baseline pinned to the chunked fp32-HIGHEST einsum (the exact
    # path the int8 pipeline replaces); the int path uses the same auto
    # dispatch the trainer uses (Mosaic int8 kernel on TPU, XLA on CPU)
    f32_fn = jax.jit(lambda bn, ch: histogram_block(bn, ch, b, impl="xla"))
    int_fn = jax.jit(lambda bn, ch: histogram_block(bn, ch, b, impl="auto"))

    def bench_one(fn, ch):
        return _timed_mean(fn, binned, ch, reps=reps)

    t_f32 = bench_one(f32_fn, ch_f32)
    t_int = bench_one(int_fn, ch_int8)
    speedup = t_f32 / t_int
    sys.stderr.write(
        f"[bench-hist] platform={dev.platform} shape=[{n}, {f}] B={b} "
        f"f32-HIGHEST={t_f32 * 1e3:.2f}ms int8={t_int * 1e3:.2f}ms "
        f"speedup={speedup:.2f}x\n")

    # batched-M sweep (tpu_hist_mbatch): K row blocks per one-hot
    # contraction -> M = 8K MXU rows (ops/fused_split.py hist_flush);
    # per-K timings of both channel layouts land in BENCH_SHAPES.json
    mb_sweep = {}
    for kb in (1, 8, 16):
        fn_k = jax.jit(functools.partial(
            histogram_block, num_bins=b, impl="auto", mbatch=kb))
        t_kf = bench_one(fn_k, ch_f32)
        t_ki = bench_one(fn_k, ch_int8)
        mb_sweep[str(kb)] = {
            "f32_ms": round(t_kf * 1e3, 3),
            "int8_ms": round(t_ki * 1e3, 3),
            "int8_rows_per_sec": round(n / t_ki),
        }
        sys.stderr.write(
            f"[bench-hist] mbatch={kb}: f32={t_kf * 1e3:.2f}ms "
            f"int8={t_ki * 1e3:.2f}ms ({n / t_ki / 1e6:.1f} Mrows/s)\n")
    layout_sweep = _run_layout_sweep(jax, dev, n, f, reps)
    _record_shape("hist_micro", {
        "platform": dev.platform, "rows": n, "features": f, "bins": b,
        "f32_highest_ms": round(t_f32 * 1e3, 3),
        "int8_ms": round(t_int * 1e3, 3),
        "int8_speedup": round(speedup, 3),
        "mbatch_sweep": mb_sweep,
        "layout_sweep": layout_sweep,
    })
    if print_json:
        print(json.dumps({
            "metric": f"hist-micro [{n // 1024}k x {f}] B={b} int8 speedup",
            "value": round(speedup, 3),
            "unit": "x vs fp32-HIGHEST einsum",
            "vs_baseline": round(speedup / 2.0, 3),  # acceptance target 2x
        }))


def _run_layout_sweep(jax, dev, n, f, reps):
    """{u8, pack4} x {lane, sublane} x {f32, int8, int16-narrowed} at a
    pack4-eligible shape (B=16).

    Every cell records rows/s plus its speedup vs the u8-lane-f32 cell of
    the SAME shape, so "which engine wins where" is a table lookup, not
    folklore. Cells whose engine needs a TPU backend (the sublane Mosaic
    layout off-TPU) record a skip marker instead of silently vanishing —
    a missing cell reads as "covered", a marked one as "not measured
    here". Narrowed cells use quant_max=9 (num_grad_quant_bins=8 + the
    stochastic-rounding +1)."""
    import functools

    import jax.numpy as jnp
    from lightgbm_tpu.ops.histogram import histogram_block

    b = 16                      # pack4- and sublane-eligible bin width
    qmax = 9
    rng = np.random.RandomState(1)
    binned = rng.randint(0, b, (n, f)).astype(np.uint8)
    from lightgbm_tpu.io.dataset import pack4_matrix
    packed = pack4_matrix(binned)   # the trainer's canonical nibble order
    codes = rng.randint(-qmax // 2, qmax // 2 + 1, (n, 4)).astype(np.int8)
    codes[:, 1] = rng.randint(0, qmax, n)       # hess codes >= 0
    codes[:, 2:] = 1
    ch = {"f32": jnp.asarray(rng.randn(n, 4).astype(np.float32)),
          "int8": jnp.asarray(codes), "int16n": jnp.asarray(codes)}
    bins = {"u8": jnp.asarray(binned), "pack4": jnp.asarray(packed)}
    on_tpu = dev.platform == "tpu"

    cells = {}
    base_rps = None
    for pk in ("u8", "pack4"):
        for lay in ("lane", "sublane"):
            for eng in ("f32", "int8", "int16n"):
                key = f"{pk}-{lay}-{eng}"
                if lay == "sublane" and eng == "int16n":
                    cells[key] = {"skipped": "the narrowed engine is "
                                             "XLA-side; register layout "
                                             "does not apply"}
                    continue
                if lay == "sublane" and not on_tpu:
                    cells[key] = {"skipped": "sublane is a Mosaic layout; "
                                             "needs a TPU backend"}
                    continue
                kw = dict(num_bins=b,
                          impl="pallas" if lay == "sublane" else "auto",
                          layout=lay,
                          packed4_features=f if pk == "pack4" else 0)
                if eng == "int16n":
                    kw.update(acc_bits=16, quant_max=qmax)
                fn = jax.jit(functools.partial(histogram_block, **kw))
                dt = _timed_mean(fn, bins[pk], ch[eng], reps=reps)
                rps = n / dt
                cells[key] = {"ms": round(dt * 1e3, 3),
                              "rows_per_sec": round(rps)}
                if key == "u8-lane-f32":
                    base_rps = rps
                sys.stderr.write(f"[bench-hist] {key}: {dt * 1e3:.2f}ms "
                                 f"({rps / 1e6:.1f} Mrows/s)\n")
    if base_rps:
        for key, cell in cells.items():
            if "rows_per_sec" in cell:
                cell["speedup_vs_f32"] = round(
                    cell["rows_per_sec"] / base_rps, 3)
    quant_cells = {k: c.get("speedup_vs_f32") for k, c in cells.items()
                   if ("int8" in k or "int16n" in k)
                   and c.get("speedup_vs_f32")}
    best_q = max(quant_cells, key=quant_cells.get) if quant_cells else None
    if best_q:
        sys.stderr.write(
            f"[bench-hist] best quantized/narrowed cell: {best_q} "
            f"({quant_cells[best_q]}x vs u8-lane-f32)\n")
    return {"platform": dev.platform, "rows": n, "features": f, "bins": b,
            "quant_max": qmax, "baseline_cell": "u8-lane-f32",
            "cells": cells, "best_quantized_cell": best_q,
            "best_quantized_speedup": quant_cells.get(best_q)
            if best_q else None}


_CONTRIB_CPU_BASELINE_QPS = 18.0  # single-row pred_contrib on the CPU
                                  # LightGBM reference (ISSUE 20)


def _contrib_qps_row(g, binned_all):
    """pred_contrib throughput row for BENCH_SHAPES["predict_micro"]:
    the per-row UNWIND loop kernel (tpu_shap_tables=off) raced against
    the precomputed-table kernel (tpu_shap_tables=on), both through the
    real serving entry (predict_contrib_padded). Rows/s is the QPS of
    row-sized requests, compared against the 18 QPS CPU baseline. A
    failure emits the structured stub and returns the error row rather
    than sinking the whole predict stage."""
    n = int(float(os.environ.get("BENCH_CONTRIB_ROWS", 1000)))
    req = binned_all[:n]
    row = {"rows": n, "cpu_baseline_qps": _CONTRIB_CPU_BASELINE_QPS}
    try:
        for label, mode in (("loop", "off"), ("tables", "on")):
            g.config.set({"tpu_shap_tables": mode})
            g._shap_tables_cache = None
            fn = (lambda: np.asarray(
                g.predict_contrib_padded(req)).sum())
            t1 = time.time()
            fn()  # warm: table build + compile land here
            once = time.time() - t1
            reps = max(1, min(5, int(2.0 / max(once, 1e-9))))
            t1 = time.time()
            for _ in range(reps):
                fn()
            dt = (time.time() - t1) / reps
            row[label + "_s"] = round(dt, 4)
            row[label + "_rows_per_sec"] = round(n / dt, 1)
            sys.stderr.write(
                f"[bench-predict] contrib/{label} N={n}: "
                f"{dt * 1e3:.1f}ms ({n / dt:.0f} rows/s)\n")
    except Exception as err:  # noqa: BLE001 - keep the predict row
        row["error"] = f"{type(err).__name__}: {err}"
        _emit_failure_stub("predict-contrib", err)
    finally:
        g.config.set({"tpu_shap_tables": "auto"})
        g._shap_tables_cache = None
    if row.get("tables_rows_per_sec") and row.get("loop_rows_per_sec"):
        row["tables_speedup"] = round(
            row["tables_rows_per_sec"] / row["loop_rows_per_sec"], 2)
        row["qps_vs_cpu_baseline"] = round(
            row["tables_rows_per_sec"] / _CONTRIB_CPU_BASELINE_QPS, 1)
    return row


def run_predict_microbench(print_json=True):
    """BENCH_PREDICT=1: races every serving engine per shape — the
    depth-batched walk ("batched"), the pre-change serial tree scan
    ("scan"), the level-order heap relayout ("level"), and the level
    engine over int8 quantized leaf slabs ("qleaf") — measured end to
    end at the gbdt serving entry on already-binned requests.

    Sweeps batch sizes {1k, 10k, 100k, 1M} x tree counts {100, 500}
    (255-leaf trees) and records, per cell, rows/s for every engine
    plus the compile events each leg spent across its whole sweep — the
    old path compiles one program per (T, N) shape, the bucketed
    engines one per (row rung, tree bucket). Acceptance (ISSUE 5):
    >= 5x rows/s at T=500, N=100k on the CPU backend. A pred_contrib
    QPS row (UNWIND loop kernel vs precomputed tables, vs the 18 QPS
    CPU baseline) rides along. Results land in
    BENCH_SHAPES.json["predict_micro"].

    Trees are real (trained on a Higgs-like shape); larger tree counts
    tile the trained base model — traversal cost per tree is
    structure-dependent, not value-dependent, so tiling preserves the
    measured work while keeping the bench's training phase short.
    """
    import jax

    dev = _require_device(jax)
    import lightgbm_tpu as lgb
    from lightgbm_tpu.analysis import guards

    train_rows = int(float(os.environ.get("BENCH_PREDICT_TRAIN_ROWS",
                                          30_000)))
    feats = int(os.environ.get("BENCH_FEATURES", 28))
    leaves = int(os.environ.get("BENCH_NUM_LEAVES", 255))
    base_trees = int(os.environ.get("BENCH_PREDICT_BASE_TREES", 50))
    tree_sweep = [int(t) for t in os.environ.get(
        "BENCH_PREDICT_TREES", "100,500").split(",")]
    rows_sweep = [int(float(t)) for t in os.environ.get(
        "BENCH_PREDICT_ROWS", "1000,10000,100000,1000000").split(",")]
    budget_s = float(os.environ.get("BENCH_PREDICT_BUDGET_S", 120.0))
    if any(t % base_trees for t in tree_sweep):
        raise SystemExit("BENCH_PREDICT_TREES entries must be multiples of "
                         f"BENCH_PREDICT_BASE_TREES ({base_trees})")

    X, y = make_higgs_like(train_rows, feats)
    params = {
        "objective": "binary", "num_leaves": leaves, "max_bin": 255,
        "learning_rate": 0.1, "min_data_in_leaf": 20, "verbosity": -1,
        "stop_check_freq": 10_000,
    }
    t0 = time.time()
    bst = lgb.train(params, lgb.Dataset(X, label=y, params=params),
                    base_trees)
    g = bst._gbdt
    g._flush_trees()
    sys.stderr.write(f"[bench-predict] trained {len(g.models)} x "
                     f"{leaves}-leaf trees in {time.time() - t0:.1f}s "
                     f"(depth {g._models_max_depth(g.models)})\n")
    base_models = list(g.models)

    rng = np.random.RandomState(3)
    n_max = max(rows_sweep)
    Xq = rng.randn(min(n_max, 1 << 20), feats).astype(np.float32)
    binned_all = g.bin_matrix(np.resize(Xq, (n_max, feats)))

    def timed(fn, n_rows):
        t1 = time.time()
        fn()
        once = time.time() - t1
        reps = max(1, min(5, int(2.0 / max(once, 1e-9))))
        t1 = time.time()
        for _ in range(reps):
            fn()
        dt = (time.time() - t1) / reps
        return dt, n_rows / dt

    # Engine legs raced per shape cell. "batched" is the depth-batched
    # walk, "scan" the pre-change serial tree loop, "level" the
    # breadth-first heap relayout, "qleaf" the level engine over int8
    # quantized leaf slabs (the compiled-forest serving stack). A leg
    # that dies records a structured per-engine error and the others
    # keep racing — the row is never silently absent.
    engine_legs = (
        ("batched", {"tpu_predict_engine": "batched"}),
        ("scan", {"tpu_predict_engine": "scan"}),
        ("level", {"tpu_predict_engine": "level"}),
        ("qleaf", {"tpu_predict_engine": "level",
                   "tpu_leaf_quant": "int8"}),
    )
    cells = {}
    compile_events = {}
    engine_errors = {}
    for engine, overrides in engine_legs:
        g.config.set(dict({"tpu_leaf_quant": "off"}, **overrides))
        try:
            with guards.compile_counter() as cc:
                for t_count in tree_sweep:
                    g.models = base_models * (t_count // base_trees)
                    g._invalidate_device_trees()
                    skip_rest = False
                    for n in sorted(rows_sweep):
                        key = f"t{t_count}_n{n}"
                        cell = cells.setdefault(key, {"trees": t_count,
                                                      "rows": n})
                        if skip_rest:
                            cell[engine + "_s"] = None
                            continue
                        req = binned_all[:n]
                        fn = (lambda: np.asarray(
                            g.predict_raw_device(req)).sum())
                        dt, rps = timed(fn, n)
                        cell[engine + "_s"] = round(dt, 4)
                        cell[engine + "_rows_per_sec"] = round(rps)
                        sys.stderr.write(
                            f"[bench-predict] {engine} T={t_count} "
                            f"N={n}: {dt * 1e3:.1f}ms "
                            f"({rps / 1e6:.2f} Mrows/s)\n")
                        # the serial scan is O(T*L*N); stop a sweep leg
                        # that would blow the budget and record the gap
                        # honestly
                        if dt * 10 > budget_s:
                            skip_rest = True
            compile_events[engine] = cc.lowerings
        except Exception as err:  # noqa: BLE001 - race the other legs
            engine_errors[engine] = f"{type(err).__name__}: {err}"
            _emit_failure_stub(f"predict-{engine}", err)
    g.config.set({"tpu_predict_engine": "batched",
                  "tpu_leaf_quant": "off"})
    g.models = base_models
    g._invalidate_device_trees()

    for cell in cells.values():
        if cell.get("scan_s") and cell.get("batched_s"):
            cell["speedup"] = round(cell["scan_s"] / cell["batched_s"], 2)
        for eng in ("level", "qleaf"):
            if cell.get(eng + "_s") and cell.get("batched_s"):
                cell[eng + "_vs_batched"] = round(
                    cell["batched_s"] / cell[eng + "_s"], 3)
    t_top = max(tree_sweep)
    accept = cells.get(f"t{t_top}_n100000", {}).get("speedup")
    sys.stderr.write(
        f"[bench-predict] compile events: "
        + " ".join(f"{k}={v}" for k, v in compile_events.items())
        + f"; T={t_top} N=100k speedup={accept}x\n")
    contrib = _contrib_qps_row(g, binned_all)
    _record_shape("predict_micro", {
        "platform": dev.platform, "leaves": leaves,
        "train_rows": train_rows, "features": feats,
        "cells": cells, "compile_events": compile_events,
        "engine_errors": engine_errors or None,
        "contrib": contrib,
        "t500_n100k_speedup": accept,
    })
    if print_json:
        print(json.dumps({
            "metric": f"predict-micro {t_top}x{leaves}-leaf trees "
                      "N=100k engine speedup",
            "value": accept,
            "unit": "x vs serial tree scan",
            "vs_baseline": round((accept or 0) / 5.0, 3),  # acceptance 5x
        }))


def run_serving_bench(print_json=True):
    """BENCH_SERVING=1: sustained-QPS sweep through the micro-batch
    coalescer (lightgbm_tpu/serving/) with mixed request sizes.

    Open-loop offered load: BENCH_SERVING_THREADS client threads pace
    submissions to each BENCH_SERVING_QPS level for
    BENCH_SERVING_DURATION_S, cycling BENCH_SERVING_SIZES rows per
    request, WITHOUT waiting for responses — so queue pressure (and load
    shedding) is real. Per level: p50/p99 end-to-end latency (submit ->
    completion, from the ServeFuture timestamps), achieved QPS,
    shed/timeout rates. The whole traffic phase runs post-warmup under a
    compile counter — the serving steady state must lower NOTHING
    (compile_events_steady == 0 is the acceptance gate from ISSUE 9).
    Results land in BENCH_SHAPES.json["serving"]; a failure emits the
    structured stub row like every other stage."""
    import jax

    dev = _require_device(jax)
    import lightgbm_tpu as lgb
    from lightgbm_tpu.analysis import guards
    from lightgbm_tpu.serving import ServerOverloaded, ServingTimeout

    train_rows = int(float(os.environ.get("BENCH_SERVING_TRAIN_ROWS",
                                          20_000)))
    feats = int(os.environ.get("BENCH_FEATURES", 28))
    leaves = int(os.environ.get("BENCH_SERVING_LEAVES", 63))
    rounds = int(os.environ.get("BENCH_SERVING_TREES", 20))
    ladder = os.environ.get("BENCH_SERVING_BUCKETS", "256,1024,4096")
    tick_ms = float(os.environ.get("BENCH_SERVING_TICK_MS", 2.0))
    deadline_ms = float(os.environ.get("BENCH_SERVING_DEADLINE_MS", 2000.0))
    queue_max = int(os.environ.get("BENCH_SERVING_QUEUE_MAX", 16384))
    duration_s = float(os.environ.get("BENCH_SERVING_DURATION_S", 3.0))
    threads = int(os.environ.get("BENCH_SERVING_THREADS", 8))
    qps_levels = [int(float(q)) for q in os.environ.get(
        "BENCH_SERVING_QPS", "100,300,1000").split(",")]
    sizes = [int(s) for s in os.environ.get(
        "BENCH_SERVING_SIZES", "1,8,64,256").split(",")]

    endpoints = [e.strip() for e in os.environ.get(
        "BENCH_SERVING_ENDPOINTS", "predict,leaf,contrib").split(",")
        if e.strip()]
    featurize_mode = os.environ.get("BENCH_SERVING_FEATURIZE", "device")

    X, y = make_higgs_like(train_rows, feats)
    params = {
        "objective": "binary", "num_leaves": leaves, "max_bin": 63,
        "learning_rate": 0.1, "min_data_in_leaf": 20, "verbosity": -1,
        "stop_check_freq": 10_000, "tpu_predict_buckets": ladder,
        "tpu_serve_endpoints": ",".join(endpoints),
        "tpu_serve_featurize": featurize_mode,
    }
    t0 = time.time()
    bst = lgb.train(params, lgb.Dataset(X, label=y, params=params), rounds)
    sys.stderr.write(f"[bench-serving] trained {rounds} x {leaves}-leaf "
                     f"trees in {time.time() - t0:.1f}s\n")

    server = bst.serve(tick_ms=tick_ms, queue_max=queue_max,
                       deadline_ms=deadline_ms)
    warm = server.registry.warm_stats()
    sys.stderr.write(f"[bench-serving] warm: rungs={warm['rungs']} "
                     f"in {warm['seconds']}s ({warm['lowerings']} "
                     f"lowerings)\n")

    # featurize attribution: host seconds vs device seconds for one
    # top-rung batch — the hoist ISSUE 13 claims, as a recorded number.
    # Host = the bin_columns sweep predict_serving used to run per tick;
    # device = the jitted raw->binned program (ops/device_bin.py), timed
    # blocked so it is device work, not dispatch.
    import jax as _jax
    import threading as _threading
    rng = np.random.RandomState(5)
    inner = bst._gbdt
    top_rung = int(max(warm["rungs"]))
    fprobe = rng.randn(top_rung, feats).astype(np.float32)
    reps = int(os.environ.get("BENCH_SERVING_FEATURIZE_REPS", 20))
    _jax.block_until_ready(inner.featurize_rung(fprobe))     # warm
    t0 = time.perf_counter()
    for _ in range(reps):
        inner.bin_matrix(fprobe)
    host_s = (time.perf_counter() - t0) / reps
    t0 = time.perf_counter()
    for _ in range(reps):
        _jax.block_until_ready(inner.featurize_rung(fprobe))
    dev_s = (time.perf_counter() - t0) / reps
    featurize_row = {
        "rows": top_rung, "mode": featurize_mode,
        "featurize_host_seconds": round(host_s, 6),
        "featurize_device_seconds": round(dev_s, 6),
        "host_over_device": round(host_s / max(dev_s, 1e-9), 3),
    }
    sys.stderr.write(f"[bench-serving] featurize {top_rung} rows: "
                     f"host {host_s*1e3:.2f}ms vs device program "
                     f"{dev_s*1e3:.2f}ms\n")

    pool = rng.randn(max(sizes), feats).astype(np.float32)

    def _run_level(srv, endpoint, qps):
        """One open-loop offered-load level against ``srv``; returns the
        recorded cell (shared by the main sweep and the drift-overhead
        comparison below)."""
        futs, sheds, misc_errors = [], [0], [0]
        mu = _threading.Lock()
        t_end = time.monotonic() + duration_s
        interval = threads / max(qps, 1)

        def client(idx):
            k = idx
            nxt = time.monotonic()
            while True:
                now = time.monotonic()
                if now >= t_end:
                    return
                if now < nxt:
                    time.sleep(min(nxt - now, 0.01))
                    continue
                nxt += interval
                size = sizes[k % len(sizes)]
                k += threads
                try:
                    f = srv.submit(pool[:size], kind=endpoint)
                    with mu:
                        futs.append(f)
                except ServerOverloaded:
                    with mu:
                        sheds[0] += 1
                except Exception:  # noqa: BLE001 - counted below
                    with mu:
                        misc_errors[0] += 1

        ts = [_threading.Thread(target=client, args=(i,))
              for i in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        # settle: every admitted request completes or times out
        lat, timeouts, failed, rows_done = [], 0, 0, 0
        for f in futs:
            try:
                f.result()
                lat.append(f.latency_s)
                rows_done += f.n
            except ServingTimeout:
                timeouts += 1
            except Exception:  # noqa: BLE001 - recorded as failure
                failed += 1
        offered = len(futs) + sheds[0] + misc_errors[0]
        lat_ms = np.asarray(lat) * 1e3 if lat else np.array([])
        return {
            "offered_qps": round(offered / duration_s, 1),
            "achieved_qps": round(len(lat) / duration_s, 1),
            # rows actually served, not completed-count x mean size:
            # shedding is size-biased (big submits shed first), which
            # would otherwise overstate rows/s exactly under overload
            "rows_per_sec": round(rows_done / duration_s),
            "p50_ms": round(float(np.percentile(lat_ms, 50)), 2)
            if lat else None,
            "p99_ms": round(float(np.percentile(lat_ms, 99)), 2)
            if lat else None,
            "shed_rate": round(sheds[0] / max(offered, 1), 4),
            "timeout_rate": round(timeouts / max(offered, 1), 4),
            "failed": failed + misc_errors[0],
        }

    levels = {}
    with guards.compile_counter() as steady_cc:
        # per-endpoint levels: the same open-loop sweep drives each
        # enabled endpoint (predict / leaf / contrib) through the shared
        # coalescer ladder
        for endpoint, qps in [(e, q) for e in endpoints
                              for q in qps_levels]:
            cell = _run_level(server, endpoint, qps)
            cell["endpoint"] = endpoint
            key = (str(qps) if endpoint == "predict"
                   else f"{endpoint}@{qps}")   # predict keeps the legacy key
            levels[key] = cell
            # same schema as the training rows: when BENCH_METRICS_PATH is
            # armed, each level also lands in the unified metrics stream
            # (shed-rate beside compile counts — scripts/obs reads both)
            if os.environ.get("BENCH_METRICS_PATH"):
                from lightgbm_tpu.obs import metrics as obs_metrics
                s = obs_metrics.stream_for(os.environ["BENCH_METRICS_PATH"])
                if s is not None:
                    s.emit("serving_level", qps=qps, endpoint=endpoint,
                           **cell)
            sys.stderr.write(
                f"[bench-serving] {endpoint} qps={qps}: achieved="
                f"{cell['achieved_qps']} p50={cell['p50_ms']}ms "
                f"p99={cell['p99_ms']}ms shed={cell['shed_rate']:.1%} "
                f"timeout={cell['timeout_rate']:.1%}\n")
    server.close(drain=True)
    stats = server.stats
    sys.stderr.write(f"[bench-serving] steady compile events: "
                     f"{steady_cc.lowerings} (must be 0); "
                     f"coalescer stats: {stats}\n")
    top = levels[str(qps_levels[-1])]

    # drift/SLO overhead (ISSUE 14): re-run the recorded top predict
    # level with the serving-quality monitors ARMED — sustained QPS and
    # p99 with observation on vs off, so the "observe" pillar's cost is
    # a recorded number, and the monitors' own zero-recompile contract
    # is re-proven under load. A failure here stubs structurally
    # (stage "serving-drift") without losing the main serving row.
    drift_row = None
    if os.environ.get("BENCH_SERVING_DRIFT", "1") != "0":
        try:
            top_qps = qps_levels[-1]
            flush_every = int(os.environ.get("BENCH_SERVING_DRIFT_FLUSH",
                                             50))
            srv_on = bst.serve(tick_ms=tick_ms, queue_max=queue_max,
                               deadline_ms=deadline_ms,
                               drift_flush_every=flush_every,
                               slo_ms=deadline_ms / 2)
            try:
                with guards.compile_counter() as drift_cc:
                    cell_on = _run_level(srv_on, "predict", top_qps)
                mon = srv_on.observer.drift
                keys = ("achieved_qps", "rows_per_sec", "p50_ms",
                        "p99_ms")
                drift_row = {
                    "qps": top_qps, "flush_every": flush_every,
                    "off": {k: top.get(k) for k in keys},
                    "on": {k: cell_on.get(k) for k in keys},
                    "p99_overhead_ms": (
                        round(cell_on["p99_ms"] - top["p99_ms"], 2)
                        if cell_on.get("p99_ms") is not None
                        and top.get("p99_ms") is not None else None),
                    "flushes": mon.flushes,
                    "host_syncs": mon.host_syncs,
                    "max_psi": mon.gauges().get("max_psi"),
                    "slo": srv_on.observer.slo.snapshot(),
                    "compile_events_steady": drift_cc.lowerings,
                }
            finally:
                srv_on.close(drain=True)
            sys.stderr.write(
                f"[bench-serving] drift_overhead @ {top_qps} qps: "
                f"p99 {top.get('p99_ms')}ms off -> "
                f"{cell_on.get('p99_ms')}ms on "
                f"({drift_row['flushes']} flushes, "
                f"{drift_row['compile_events_steady']} steady "
                f"compiles)\n")
        except Exception as err:  # noqa: BLE001 - stub, keep the main row
            _emit_failure_stub("serving-drift", err)
            drift_row = None

    _record_shape("serving", {
        "platform": dev.platform, "trees": rounds, "leaves": leaves,
        "features": feats, "ladder": warm["rungs"],
        "endpoints": endpoints,
        "tick_ms": tick_ms, "deadline_ms": deadline_ms,
        "queue_max_rows": queue_max, "sizes": sizes,
        "duration_s": duration_s, "levels": levels,
        "warmup": warm,
        "featurize": featurize_row,
        "drift_overhead": drift_row,
        "compile_events_steady": steady_cc.lowerings,
        "coalescer": stats,
    })
    if print_json:
        print(json.dumps({
            "metric": f"serving p99 @ {qps_levels[-1]} qps "
                      f"(mixed sizes {sizes})",
            "value": top["p99_ms"],
            "unit": "ms",
            # acceptance: 0 steady-state compiles; encode it in the row
            "vs_baseline": steady_cc.lowerings,
        }))


def run_ranking_bench():
    """Lambdarank at MS-LTR scale: pair-block chunking + NDCG under load."""
    import jax
    dev = _require_device(jax)
    _configure_cache()
    import lightgbm_tpu as lgb

    rows = int(float(os.environ.get("BENCH_ROWS", 2_270_000)))
    feats = int(os.environ.get("BENCH_FEATURES", 137))
    iters = int(os.environ.get("BENCH_ITERS", 10))
    X, y, group = make_msltr_like(rows, feats)
    params = {
        "objective": "lambdarank", "metric": "ndcg", "eval_at": [10],
        "num_leaves": int(os.environ.get("BENCH_NUM_LEAVES", 255)),
        "max_bin": int(os.environ.get("BENCH_MAX_BIN", 255)),
        "learning_rate": 0.1, "min_data_in_leaf": 50, "verbosity": -1,
        "stop_check_freq": 10_000,
    }
    ds = lgb.Dataset(X, label=y, group=group, params=params)
    bst = lgb.Booster(params, ds)
    t0 = time.time()
    for _ in range(WARMUP):
        bst.update()
    bst._gbdt._flush_trees()
    warm = time.time() - t0
    t0 = time.time()
    for _ in range(iters):
        bst.update()
    bst._gbdt._flush_trees()
    dt = time.time() - t0
    (_, name, ndcg, _), = bst.eval_train()
    sys.stderr.write(f"[bench-ranking] rows={rows} features={feats} "
                     f"warmup={warm:.1f}s train({iters})={dt:.1f}s "
                     f"{name}={ndcg:.5f}\n")
    _record_shape("ranking", {
        "platform": dev.platform,
        "rows": rows, "features": feats, "leaves": params["num_leaves"],
        "iters_per_sec": round(iters / dt, 3),
        "rows_per_sec": round(rows * iters / dt),
        "ndcg": round(float(ndcg), 5),
    })
    # MS-LTR CPU baseline: ref Experiments.rst:117 xgb_hist/LightGBM table
    # does not publish iters/sec for MS-LTR; report absolute throughput
    print(json.dumps({
        "metric": f"synthetic-msltr{rows // 1_000_000}M-"
                  f"{params['num_leaves']}leaf lambdarank throughput",
        "value": round(iters / dt, 3),
        "unit": "iters/sec/chip",
        "vs_baseline": round(float(ndcg), 5),
    }))


def _record_scaling_ledger(jax, trace_dir, shape, iters_per_sec,
                           timed_iters):
    """BENCH_LEDGER=1: record the scaling-efficiency block
    (obs/ledger.py) into COMM_ACCOUNTING.json (+ BENCH_MULTICHIP_PATH
    when set). The round's profiler trace under ``trace_dir`` is no
    longer reduced here (the hand-written reader is gone), so the block
    carries no ``measured_vs_model``. Best-effort — the ledger must
    never sink a bench round that already measured its throughput."""
    try:
        from lightgbm_tpu.obs import ledger as obs_ledger
        analysis = None
        n_chips = len(jax.devices())
        contract_mode = os.environ.get(
            "BENCH_LEDGER_CONTRACT",
            "data_scatter" if n_chips > 1 else "serial_compact")
        contract = obs_ledger.load_contract(contract_mode)
        comm_path = os.environ.get(
            "BENCH_COMM_ACCOUNTING",
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "COMM_ACCOUNTING.json"))
        block = obs_ledger.ledger_block(
            shape, n_chips, iters_per_sec, analysis=analysis,
            contract=contract, steps=timed_iters,
            prior_rows=obs_ledger.prior_rows(comm_path, shape))
        key = f"{shape}_x{n_chips}"
        obs_ledger.record(comm_path, key, block)
        mc_path = os.environ.get("BENCH_MULTICHIP_PATH", "")
        if mc_path:
            obs_ledger.record(mc_path, key, block)
        mvm = block.get("measured_vs_model", {})
        sys.stderr.write(
            f"[bench] ledger[{key}]: efficiency="
            f"{block['scaling'][-1].get('efficiency')} comm_fraction="
            f"{mvm.get('measured', {}).get('comm_fraction')} -> "
            f"{comm_path}\n")
    except Exception as err:  # noqa: BLE001 - never sink the bench row
        sys.stderr.write(f"[bench] ledger failed: {err}\n")


def _bench_stage() -> str:
    """The ONE env-precedence chain both the dispatcher and the failure
    stub key on — a new bench mode added here is automatically labeled
    correctly in "last_failure" rows."""
    if os.environ.get("BENCH_HIST_MICRO", "") == "1":
        return "hist-micro"
    if os.environ.get("BENCH_PREDICT", "") == "1":
        return "predict-micro"
    if os.environ.get("BENCH_SERVING", "") == "1":
        return "serving"
    if os.environ.get("BENCH_RANKING", "") == "1":
        return "ranking"
    return "train"


def main():
    """Dispatch wrapper: any unhandled failure — the backend never coming
    up after retries, an OOM mid-run — emits a structured stub row
    (value null + the error inline, also recorded in BENCH_SHAPES.json
    "last_failure") before re-raising, so the BENCH_r0x row is never
    silently absent (the r05 gap)."""
    stage = _bench_stage()
    try:
        return _main(stage)
    except BaseException as err:
        if isinstance(err, (KeyboardInterrupt, SystemExit)):
            raise
        _emit_failure_stub(stage, err)
        raise


def _main(stage=None):
    stage = stage or _bench_stage()
    if stage == "hist-micro":
        return run_hist_microbench()
    if stage == "predict-micro":
        return run_predict_microbench()
    if stage == "serving":
        return run_serving_bench()
    if stage == "ranking":
        return run_ranking_bench()
    import jax
    dev = _require_device(jax)
    # persistent compile cache: the full-config tree program takes ~2 min to
    # compile cold; warm runs of the bench (and of users' jobs) skip it
    _configure_cache()

    import lightgbm_tpu as lgb
    from lightgbm_tpu.analysis.guards import cache_counter, compile_counter

    if dev.platform == "tpu" and not os.environ.get("BENCH_SKIP_HIST_MICRO"):
        # cheap (~seconds): every TPU bench run refreshes the int8-vs-f32
        # histogram microbench record alongside the training throughput.
        # Not caught: a kernel the chip refuses fails the run
        run_hist_microbench(print_json=False)
    sparse = os.environ.get("BENCH_SPARSE", "") == "1"
    if sparse:
        X, y = make_allstate_like(ROWS, FEATURES)
    else:
        X, y = make_higgs_like(ROWS, FEATURES)

    # unified telemetry (ISSUE 10): the per-iteration metrics stream is
    # the ONE source the BENCH row's counters come from — the booster
    # emits cumulative phase-keyed compile counts per update, bench adds
    # window marks, and obs/summarize.bench_counters diffs them (the
    # inline compile_counter guards below stay as the fallback when the
    # stream is absent)
    metrics_path = os.environ.get(
        "BENCH_METRICS_PATH",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     ".bench_metrics.jsonl"))
    from lightgbm_tpu.obs import metrics as obs_metrics
    from lightgbm_tpu.obs import summarize as obs_summarize
    mstream = obs_metrics.stream_for(metrics_path)

    def _mark(name):
        from lightgbm_tpu.analysis import guards as _g
        if mstream is not None:
            mstream.emit("mark", name=name,
                         compiles=_g.phase_compile_counts(),
                         cache=_g.global_cache_counts())

    params = {
        "objective": "binary",
        "metric": "auc",
        "num_leaves": NUM_LEAVES,
        "max_bin": MAX_BIN,
        "learning_rate": 0.1,
        "min_data_in_leaf": 100,
        "verbosity": -1,
        # bench runs sync-free; one stop check at the end
        "stop_check_freq": 10_000,
        "tpu_metrics_path": metrics_path,
    }
    if sparse:
        # binary one-hot features: a small sample fully determines the bins,
        # and the host-side mapper loop over F=4228 dominates construct time
        params["bin_construct_sample_cnt"] = 20_000
    t0 = time.time()
    ds = lgb.Dataset(X, label=y, params=params)
    ds.construct()
    construct_s = time.time() - t0

    # resume-aware long rounds (BENCH_CHECKPOINT_DIR): the booster
    # checkpoints every BENCH_CHECKPOINT_FREQ iterations and a transient
    # backend death mid-run — or a fresh bench invocation after a process
    # death — resumes from the last snapshot instead of iteration 0
    ckpt_dir = os.environ.get("BENCH_CHECKPOINT_DIR", "")
    ckpt_freq = max(1, int(os.environ.get("BENCH_CHECKPOINT_FREQ", "5")))

    def make_booster():
        return lgb.Booster(params, ds)

    bst = make_booster()
    if ckpt_dir:
        from lightgbm_tpu.io import checkpoint as ckpt_mod
        state = ckpt_mod.load_latest(ckpt_dir)
        if state is not None:
            try:
                bst._restore_checkpoint(state)
                sys.stderr.write(f"[bench] resumed from checkpoint at "
                                 f"iteration {bst.current_iteration()}\n")
            except ValueError as err:  # stale dir from a different shape
                sys.stderr.write(f"[bench] ignoring incompatible "
                                 f"checkpoint in {ckpt_dir}: {err}\n")
    t_run0 = time.time()
    t0 = time.time()
    _mark("warmup_start")
    # count warmup lowerings + persistent-cache lookups: with the step
    # ladder (tpu_step_buckets) compile_events is the O(1) rung budget, and
    # a warm compile cache shows cache hits == requests (backend compile
    # skipped) — the compile-time win lands in the BENCH row, not just it/s
    with compile_counter() as warm_cc, cache_counter() as warm_cache:
        if ckpt_dir:
            warm_from = bst.current_iteration()
            bst = _resumable_update_loop(bst, make_booster, WARMUP,
                                         ckpt_dir, ckpt_freq)
            if bst.current_iteration() == warm_from \
                    and warm_from < WARMUP + ITERS:
                # the restore already covered WARMUP, so the loop above
                # performed 0 updates and nothing lowered yet — run ONE
                # update inside the warm window so the step-program
                # compiles land in warmup_seconds/compile_events instead
                # of the timed loop (compile_events_steady must stay 0,
                # and iters/sec must not absorb compile time). A restore
                # that already covers the FULL run gets no extra update:
                # the timed loop will do 0 updates and the row records
                # 0.0 with the stderr note, not a model one iteration
                # longer than the config declares
                bst.update()
            elif bst.current_iteration() >= WARMUP + ITERS:
                sys.stderr.write("[bench] checkpoint already covers the "
                                 "full run; timed loop will perform 0 "
                                 "updates (stale BENCH_CHECKPOINT_DIR?)\n")
        else:
            for _ in range(WARMUP):
                bst.update()
        bst._gbdt._flush_trees()
    warmup_s = time.time() - t0
    _mark("warmup_end")

    # scaling-efficiency ledger (BENCH_LEDGER=1, obs/ledger.py): the
    # timed loop runs under a full profiler trace_session so the
    # device-time analytics can measure the collective durations the
    # byte model only predicts — the measured_vs_model block lands in
    # COMM_ACCOUNTING.json (and BENCH_MULTICHIP_PATH when set) with the
    # round, attribution built in
    import contextlib
    ledger_on = os.environ.get("BENCH_LEDGER", "") == "1"
    ledger_trace_dir = None
    ledger_session = contextlib.nullcontext()
    if ledger_on:
        from lightgbm_tpu.obs import spans as obs_spans
        ledger_trace_dir = os.environ.get(
            "BENCH_TRACE_DIR",
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         ".bench_trace"))
        ledger_session = obs_spans.trace_session(ledger_trace_dir, "full")
    t0 = time.time()
    timed_from = bst.current_iteration()
    with ledger_session:
        with compile_counter() as steady_cc:
            if ckpt_dir:
                bst = _resumable_update_loop(bst, make_booster,
                                             WARMUP + ITERS,
                                             ckpt_dir, ckpt_freq)
            else:
                for _ in range(ITERS):
                    bst.update()
            bst._gbdt._flush_trees()  # materialize: device work finishes
    train_s = time.time() - t0
    _mark("steady_end")
    # the unified-schema counters: derived from the metrics stream (the
    # booster's cumulative per-iteration records + the marks above); the
    # inline counters remain the fallback for a missing/partial stream.
    # Gated on THIS run's stream being live — a stale file from a prior
    # invocation would otherwise hand the row the old run's numbers
    stream_row = (obs_summarize.bench_counters(metrics_path)
                  if mstream is not None else None) or {}
    if stream_row:
        sys.stderr.write(
            f"[bench] counters from metrics stream {metrics_path}: "
            f"{json.dumps(stream_row)}\n")

    # rate over the updates ACTUALLY performed this invocation: a resumed
    # round runs fewer than ITERS in the timed loop, and dividing by the
    # nominal count would record inflated throughput in the BENCH_r0x row
    timed_iters = bst.current_iteration() - timed_from
    if ckpt_dir and timed_iters < ITERS:
        sys.stderr.write(f"[bench] timed loop ran {timed_iters}/{ITERS} "
                         "updates (checkpoint resume); rate uses the "
                         "actual count\n")
    iters_per_sec = (timed_iters / train_s) if timed_iters > 0 else 0.0
    # AUC sanity on the training data (separability check, not a quality bench)
    auc = None
    sample = slice(0, min(ROWS, 200_000))
    try:
        from sklearn.metrics import roc_auc_score
        auc = float(roc_auc_score(y[sample], bst.predict(X[sample])))
    except Exception:
        pass

    # time-to-accuracy: wall clock from construct start (construct + compile
    # + train + eval) until AUC >= TTA_AUC on a 200k train slice — makes
    # compile/construct latency visible next to steady-state it/s. The 0.84
    # default target is higgs-specific; other shapes skip TTA unless
    # BENCH_TTA_AUC is set explicitly
    has_tta = ("BENCH_TTA_AUC" in os.environ or not sparse) \
        and not os.environ.get("LGBM_TPU_FUSED_HIST_DEBUG")
    tta_target = float(os.environ.get("BENCH_TTA_AUC", 0.84))
    wall_to_auc = None
    if auc is not None and has_tta:
        cur = auc
        extra = 0
        while cur < tta_target and extra < 300:
            for _ in range(15):
                bst.update()
            bst._gbdt._flush_trees()
            extra += 15
            from sklearn.metrics import roc_auc_score
            cur = float(roc_auc_score(y[sample], bst.predict(X[sample])))
        if cur >= tta_target:
            wall_to_auc = round(construct_s + (time.time() - t_run0), 1)

    # warmup minus two steady-state iterations approximates compile+cache time
    compile_s = max(0.0, warmup_s - WARMUP / max(iters_per_sec, 1e-9))
    sys.stderr.write(
        f"[bench] device={dev} rows={ROWS} features={FEATURES} "
        f"leaves={NUM_LEAVES} bins={MAX_BIN}\n"
        f"[bench] construct={construct_s:.1f}s warmup({WARMUP})={warmup_s:.1f}s "
        f"compile~={compile_s:.1f}s train({ITERS})={train_s:.1f}s auc={auc}\n"
        f"[bench] compile events: warmup={warm_cc.lowerings} "
        f"(backend={warm_cc.backend_compiles}) steady={steady_cc.lowerings}; "
        f"cache {warm_cache.hits}/{warm_cache.requests} hit\n")
    if os.environ.get("LGBM_TPU_FUSED_HIST_DEBUG"):
        # hist-debug runs produce INVALID results; never record them
        sys.stderr.write("[bench] hist-debug mode: NOT recording shapes\n")
        return
    shape = "allstate" if sparse else "higgs"
    if MAX_BIN != 255:
        # low-bin runs (the reference's GPU learner defaults to 63 bins,
        # docs/GPU-Performance.rst:133) record under their own key
        shape = f"{shape}-b{MAX_BIN}"
    if ledger_on and ledger_trace_dir:
        # same shape key as BENCH_SHAPES so ledger rows and throughput
        # rows join on it
        _record_scaling_ledger(jax, ledger_trace_dir, shape,
                               iters_per_sec, timed_iters)
    # every run also records its result in BENCH_SHAPES.json so the sparse
    # and ranking shape numbers live in files, not prose (run the other
    # shapes via BENCH_SPARSE=1 / BENCH_RANKING=1)
    _record_shape(shape, {
        "platform": dev.platform,
        "rows": ROWS, "features": FEATURES, "leaves": NUM_LEAVES,
        "bins": MAX_BIN, "iters_per_sec": round(iters_per_sec, 3),
        # normalized per-row throughput: rows scanned per second of
        # boosting (iterations x rows) — comparable across row counts
        "rows_per_sec": round(ROWS * iters_per_sec),
        "construct_s": round(construct_s, 1),
        "compile_s": round(compile_s, 1), "auc": auc,
        "wall_to_auc_s": wall_to_auc,
        "wall_to_auc_target": tta_target,
        # compile-time ladder accounting (ISSUE 8) via the unified metrics
        # stream (ISSUE 10): distinct programs lowered during warmup (the
        # rung budget under tpu_step_buckets) WITH phase attribution,
        # steady-state lowerings (must be 0), and persistent-cache
        # hit/miss so warm-cache rounds are distinguishable
        "warmup_seconds": stream_row.get("warmup_seconds",
                                         round(warmup_s, 1)),
        "compile_events": stream_row.get("compile_events",
                                         warm_cc.lowerings),
        "compile_events_by_phase": stream_row.get("compile_events_by_phase"),
        "compile_events_steady": stream_row.get("compile_events_steady",
                                                steady_cc.lowerings),
        "compile_cache": stream_row.get(
            "compile_cache", {"requests": warm_cache.requests,
                              "hits": warm_cache.hits,
                              "misses": warm_cache.misses}),
        "metrics_stream": metrics_path if stream_row else None,
        # BENCH_LEDGER rounds time the loop UNDER a full profiler
        # session (the ledger needs the trace): per-op tracing overhead
        # loads the number, so the row says so — comparing a ledgered
        # round's it/s against untraced history would be a silent lie
        **({"profiler_loaded": True} if ledger_on else {}),
    })
    print(json.dumps({
        "metric": f"synthetic-{shape}{ROWS // 1_000_000}M-"
                  f"{NUM_LEAVES}leaf boosting throughput",
        "value": round(iters_per_sec, 3),
        "unit": "iters/sec/chip",
        "vs_baseline": round(iters_per_sec / BASELINE_ITERS_PER_SEC, 3),
        "warmup_seconds": stream_row.get("warmup_seconds",
                                         round(warmup_s, 1)),
        "compile_events": stream_row.get("compile_events",
                                         warm_cc.lowerings),
        "compile_cache_hits": stream_row.get(
            "compile_cache", {}).get("hits", warm_cache.hits),
        "compile_cache_misses": stream_row.get(
            "compile_cache", {}).get("misses", warm_cache.misses),
    }))


if __name__ == "__main__":
    main()
