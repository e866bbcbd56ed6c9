"""Startup microbench autotuner: measured per-shape engine selection.

``tpu_autotune=off|first_run|always`` (default ``first_run``): at
``_setup_train`` the registry's eligible sweep candidates
(engines/registry.py, {xla, pallas} x {lane, sublane} x batched-M
depth) are each timed on a small strided sample of the REAL binned
matrix — a few histogram builds per candidate, ``block_until_ready``,
under the ``autotune`` obs span and compile phase — and the winner
becomes the shape-class's decision. The decision PERSISTS to a JSON
cache (``tpu_autotune_cache``, atomic write-temp-rename like
obs/ledger.py), so a repeat run with the same shape-class and backend
resolves with ZERO microbenches and zero extra compiles; bench.py
copies the recorded sweep tables into ``BENCH_SHAPES.json["autotune"]``
(``BENCH_AUTOTUNE=1``) and ``scripts/autotune`` runs the same sweep
offline.

Arming rules (the part that keeps tier-1 and every CPU run inert by
default):

* ``off`` — never; the registry resolves pure heuristics (the escape
  hatch the parity tests diff against).
* ``first_run`` (default) — armed when the user set ``tpu_autotune``
  explicitly, OR implicitly on a real TPU backend for shapes of at
  least :data:`MIN_AUTOTUNE_ROWS` rows (tiny shapes gain nothing and
  the default must not tax small jobs or the CPU test suite). A cache
  hit skips the sweep.
* ``always`` — re-sweep even over a cache hit (perf investigations).

Multi-process runs never sweep locally: per-rank timings would elect
different winners and desync every collective — they read the shared
cache (same decision on every rank) or fall back to heuristics with a
warning pointing at ``scripts/autotune``.

The sweep runs strictly BEFORE the steady-state window: its compiles
land in the ``autotune`` phase (guards.compile_phase) and the
0-recompile/0-d2h steady-state guard holds with autotune armed
(tests/test_registry.py).
"""
from __future__ import annotations

import json
import os
import tempfile
import time
from typing import Any, Dict, List, Optional, Tuple

from ..utils import log
from . import registry

#: cache schema version (consumers key on it before trusting fields)
CACHE_VERSION = 1

#: rows the microbench samples from the real binned matrix (strided)
SWEEP_SAMPLE_ROWS = 1 << 14

#: timed repetitions per candidate (after one warm/compile call)
SWEEP_REPS = 3

#: implicit-arming row floor: below this the engine choice is noise and
#: the DEFAULT first_run mode stays inert (explicit tpu_autotune
#: settings arm at any size — tests and perf experiments opt in)
MIN_AUTOTUNE_ROWS = 1 << 16

MODES = ("off", "first_run", "always")

#: module-level sweep counter — tests pin "exactly one microbench on a
#: fresh cache, zero on the warm rerun" against it
SWEEPS_RUN = 0


def resolve_mode(cfg) -> str:
    """Validate ``tpu_autotune``; unknown values warn and fall back to
    the ``first_run`` default."""
    mode = str(registry._get(cfg, "tpu_autotune", "first_run")
               or "first_run").lower()
    if mode in ("0", "false"):
        mode = "off"
    if mode not in MODES:
        log.warning(f"tpu_autotune={mode!r} is not one of "
                    f"{'|'.join(MODES)}; using first_run")
        return "first_run"
    return mode


def cache_path(cfg) -> str:
    """``tpu_autotune_cache``, or the per-user default location."""
    path = str(registry._get(cfg, "tpu_autotune_cache", "") or "")
    if path:
        return path
    return os.path.join(os.path.expanduser("~"), ".cache",
                        "lightgbm_tpu", "autotune.json")


def cache_key(platform: str, sclass: str) -> str:
    return f"{platform}/{sclass}"


def load_cache(path: str) -> Dict[str, Any]:
    """Tolerant cache read: a missing, torn, or wrong-version file is an
    EMPTY cache (the sweep re-runs and rewrites it), never an error."""
    if not path or not os.path.exists(path):
        return {}
    try:
        with open(path, encoding="utf-8") as fh:
            data = json.load(fh)
    except (OSError, json.JSONDecodeError, ValueError):
        log.warning(f"tpu_autotune_cache {path} is unreadable/corrupt; "
                    "treating it as empty (the microbench will re-run "
                    "and rewrite it)")
        return {}
    if not isinstance(data, dict) \
            or data.get("version") != CACHE_VERSION \
            or not isinstance(data.get("entries"), dict):
        log.warning(f"tpu_autotune_cache {path} has an unknown schema; "
                    "treating it as empty")
        return {}
    return data


def store_decision(path: str, key: str, block: Dict[str, Any]) -> None:
    """Merge one shape-class decision into the cache file atomically
    (write-temp-rename, the obs/ledger.py discipline — a killed run
    must never leave a torn cache)."""
    data = load_cache(path)
    if not data:
        data = {"version": CACHE_VERSION, "entries": {}}
    data["entries"][key] = block
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
    try:
        with os.fdopen(fd, "w", encoding="utf-8") as fh:
            json.dump(data, fh, indent=1, sort_keys=True)
            fh.write("\n")
        os.replace(tmp, path)
    except BaseException:
        # BaseException, not OSError: a serializer TypeError or a
        # SimulatedKill mid-dump must not orphan the temp file (R012)
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def sweep_tables(path: str) -> Dict[str, Any]:
    """Every recorded decision block, keyed by ``platform/shape-class``
    — what bench.py copies into BENCH_SHAPES.json["autotune"]."""
    return dict(load_cache(path).get("entries", {}))


def _time_candidate(fn, *args, reps: int = SWEEP_REPS) -> float:
    """One warm call (compile + cache fill), then the mean of ``reps``
    back-to-back dispatches with one trailing sync — the bench.py
    _timed_mean discipline. Module-level so the fast-lane tests stub it
    (the REAL timed sweep lives in the slow lane)."""
    fn(*args).block_until_ready()
    t0 = time.perf_counter()
    out = None
    for _ in range(reps):
        out = fn(*args)
    out.block_until_ready()
    return (time.perf_counter() - t0) / max(1, reps)


def run_sweep(sample, num_bins: int,
              candidates: List[registry.Candidate],
              reps: int = SWEEP_REPS, quant: bool = False,
              pack4: bool = False
              ) -> Tuple[Optional[Dict[str, Any]], List[Dict[str, Any]]]:
    """Time every candidate on ``sample`` (host [n, F] bin codes);
    returns ``(winner_knobs_or_None, table)``. Runs under the
    ``autotune`` span and compile phase so device traces and compile
    counters attribute the startup work honestly.

    ``quant``/``pack4`` make the measurement match the engine path the
    shape-class actually trains on: quant classes time int8 code
    channels through the int8 -> int32 contraction (fp32 relative
    speeds do not transfer — that difference is the quant path's whole
    premise), pack4 classes time nibble-packed blocks through the
    in-loop unpack."""
    global SWEEPS_RUN
    import numpy as np

    import jax

    from ..analysis.guards import compile_phase
    from ..obs.spans import span
    from ..ops.histogram import histogram_block

    SWEEPS_RUN += 1
    sample = np.ascontiguousarray(sample)
    n = int(sample.shape[0])
    rng = np.random.RandomState(0)
    table: List[Dict[str, Any]] = []
    packed_features = 0
    if pack4:
        from ..io.dataset import pack4_matrix
        packed_features = int(sample.shape[1])
        sample = pack4_matrix(sample)
    with span("autotune"), compile_phase("autotune"):
        import jax.numpy as jnp
        binned = jnp.asarray(sample)
        if quant:
            codes = rng.randint(-8, 9, (n, 4)).astype(np.int8)
            codes[:, 1] = rng.randint(0, 9, n)      # hess codes >= 0
            codes[:, 2:] = 1                        # count channels
            channels = jnp.asarray(codes)
        else:
            channels = jnp.asarray(rng.randn(n, 4).astype(np.float32))

        def build(cand):
            def hist(b, c):
                with span("autotune"):
                    return histogram_block(
                        b, c, num_bins=num_bins, impl=cand.entry.impl,
                        mbatch=cand.mbatch, layout=cand.entry.layout,
                        packed4_features=packed_features)
            return jax.jit(hist)

        for cand in candidates:
            row: Dict[str, Any] = {
                "candidate": cand.key, "entry": cand.entry.id,
                "hist_impl": cand.entry.impl,
                "hist_layout": cand.entry.layout,
                "hist_mbatch": cand.mbatch,
            }
            try:
                dt = _time_candidate(build(cand), binned, channels,
                                     reps=reps)
            except Exception as err:
                # a candidate the backend refuses must stop the run, not
                # lose the race to the XLA einsum with exit code 0
                raise RuntimeError(
                    f"tpu_autotune: candidate {cand.key} failed on "
                    f"bins{tuple(binned.shape)} {binned.dtype} x channels"
                    f"{tuple(channels.shape)} {channels.dtype}, "
                    f"num_bins={num_bins}") from err
            row["ms"] = round(dt * 1e3, 4)
            row["rows_per_sec"] = round(n / max(dt, 1e-12))
            table.append(row)
    if not table:
        return None, table
    best = min(table, key=lambda r: r["ms"])
    winner = {"entry": best["entry"], "hist_impl": best["hist_impl"],
              "hist_layout": best["hist_layout"],
              "hist_mbatch": best["hist_mbatch"]}
    return winner, table


def _multiproc() -> bool:
    import jax
    return jax.process_count() > 1


def _all_swept_knobs_pinned(cfg) -> bool:
    """User/env own every knob the sweep can decide — the microbench
    could not influence anything, so startup pays nothing for it."""
    mbatch = registry._explicit(cfg, "tpu_hist_mbatch") \
        or bool(os.environ.get("LGBM_TPU_HIST_MBATCH", ""))
    layout = registry._explicit(cfg, "tpu_hist_layout") and \
        str(registry._get(cfg, "tpu_hist_layout", "auto")
            or "auto").lower() not in ("", "auto")
    impl = registry._explicit(cfg, "tpu_hist_impl") and \
        str(registry._get(cfg, "tpu_hist_impl", "auto")
            or "auto").lower() not in ("", "auto")
    return mbatch and layout and impl


def decision_block(winner, table, platform: str, sclass: str,
                   rows_sampled: int, reps: int) -> Dict[str, Any]:
    """The cache-entry schema — ONE construction site shared by
    :func:`decision_for` and the offline CLI (engines/cli.py), so a
    schema change cannot fork between the two writers."""
    return {"winner": winner, "table": table, "platform": platform,
            "shape_class": sclass, "rows_sampled": int(rows_sampled),
            "reps": int(reps),
            "recorded": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                      time.gmtime())}


def decision_for(cfg, shape: registry.DatasetShape, platform: str,
                 sample_provider=None, allow_sweep: bool = True
                 ) -> Tuple[Optional[Dict[str, Any]], bool]:
    """The autotuner's half of ``registry.resolve``: ``(winner_knobs or
    None, swept_now)``. Explicit user knobs never reach here per-knob —
    the resolve order applies the decision only below user/env."""
    mode = resolve_mode(cfg)
    if mode == "off" or shape is None:
        return None, False
    armed = registry._explicit(cfg, "tpu_autotune") or (
        registry.on_tpu(platform) and shape.rows >= MIN_AUTOTUNE_ROWS)
    if not armed:
        return None, False
    if _all_swept_knobs_pinned(cfg):
        return None, False
    path = cache_path(cfg)
    key = cache_key(platform, registry.shape_class(shape))
    cached = load_cache(path).get("entries", {}).get(key)
    if cached is not None and mode != "always":
        return cached.get("winner"), False
    if not allow_sweep or sample_provider is None:
        return (cached or {}).get("winner"), False
    if _multiproc():
        log.warning(
            "tpu_autotune: multi-process run with no cached decision "
            f"for {key} — per-rank microbenches would elect divergent "
            "winners and desync the collectives, so the heuristic "
            "defaults apply; record a decision offline with "
            "scripts/autotune (or a single-host run) into "
            f"{path} first")
        return (cached or {}).get("winner"), False
    candidates = registry.sweep_candidates(shape, platform)
    if not candidates:
        return None, False
    n = min(int(shape.rows), SWEEP_SAMPLE_ROWS)
    sample = sample_provider(n)
    winner, table = run_sweep(
        sample, int(shape.num_bins), candidates,
        quant=shape.quant,
        # pack4 nibble-packs only where every stored column fits a
        # nibble; the common padded width is the available proxy here
        pack4=shape.pack4 and int(shape.num_bins) <= 16)
    block = decision_block(winner, table, platform,
                           registry.shape_class(shape),
                           sample.shape[0], SWEEP_REPS)
    try:
        store_decision(path, key, block)
    except OSError as err:
        log.warning(f"tpu_autotune: cannot persist the decision to "
                    f"{path} ({err}); this run still uses the measured "
                    "winner, the next run will re-bench")
    return winner, True


def serving_decision_for(cfg, sclass: str, platform: Optional[str] = None,
                         runners_provider=None, allow_sweep: bool = True
                         ) -> Tuple[Optional[Dict[str, Any]], bool]:
    """The autotuner's serving half (registry.resolve_serving_engine's
    ``auto`` rung): ``(winner or None, raced_now)``.

    ``runners_provider()`` returns ``({engine_id: zero-arg dispatch},
    rows)`` — each dispatch runs the REAL stacked trees over a small
    rung (gbdt._serving_race_runners), so the race measures the actual
    serving programs, not a synthetic proxy. Decisions persist to the
    same atomic autotune cache under the ``serve-*`` shape class; the
    arming rules mirror :func:`decision_for` (explicit ``tpu_autotune``
    arms everywhere, TPU platforms arm implicitly, multi-process never
    races locally)."""
    global SWEEPS_RUN
    mode = resolve_mode(cfg)
    if mode == "off":
        return None, False
    platform = platform or registry.current_platform()
    armed = registry._explicit(cfg, "tpu_autotune") \
        or registry.on_tpu(platform)
    if not armed:
        return None, False
    path = cache_path(cfg)
    key = cache_key(platform, sclass)
    cached = load_cache(path).get("entries", {}).get(key)
    if cached is not None and mode != "always":
        return cached.get("winner"), False
    if not allow_sweep or runners_provider is None or _multiproc():
        return (cached or {}).get("winner"), False
    runners, rows = runners_provider()
    if not runners:
        return None, False
    from ..analysis.guards import compile_phase
    from ..obs.spans import span
    SWEEPS_RUN += 1
    table: List[Dict[str, Any]] = []
    with span("autotune"), compile_phase("autotune"):
        for eng, fn in runners.items():
            row: Dict[str, Any] = {"candidate": f"serve_{eng}",
                                   "serve_engine": eng}
            try:
                dt = _time_candidate(fn)
            except Exception as err:
                raise RuntimeError(
                    f"tpu_autotune: serving candidate serve_{eng} failed "
                    f"on {sclass} at {rows} rows") from err
            row["ms"] = round(dt * 1e3, 4)
            row["rows_per_sec"] = round(rows / max(dt, 1e-12))
            table.append(row)
    best = min(table, key=lambda r: r["ms"])
    winner = {"serve_engine": best["serve_engine"]}
    block = decision_block(winner, table, platform, sclass, rows,
                           SWEEP_REPS)
    try:
        store_decision(path, key, block)
    except OSError as err:
        log.warning(f"tpu_autotune: cannot persist the serving "
                    f"decision to {path} ({err}); this run still uses "
                    "the measured winner, the next run will re-race")
    return winner, True
