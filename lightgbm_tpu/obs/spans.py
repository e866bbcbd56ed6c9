"""Phase-named spans: one API, two faces (device trace names + host timing).

The reference attributes wall time to every training phase through its
``USE_TIMETAG`` ``Common::Timer`` registry (include/LightGBM/utils/log.h:
``global_timer.Print()`` at process exit). On TPU that design splits in
two, because the two interesting clocks live in different places:

* **Device time** belongs to the profiler. A span entered while jax is
  TRACING wraps the region in ``jax.named_scope``, so the lowered HLO ops
  carry the phase name and the Perfetto/TensorBoard trace that
  ``tpu_trace_dir`` emits shows ``hist_build`` / ``split_scan`` /
  ``collective_reduce`` lanes instead of a wall of fused ops. This costs
  nothing at runtime — the scope only exists at trace time.
* **Host time** belongs to the orchestration loop. A span entered outside
  tracing (checkpoint writes, serve ticks, warmup rungs) wraps the region
  in ``jax.profiler.TraceAnnotation`` and accumulates wall time into the
  per-phase table that :mod:`..obs.summarize` prints — the
  ``Common::Timer::Print`` analogue. Host timing around ASYNC dispatch
  measures dispatch, not device work (tpulint R009 exists to keep naive
  timing out of jit-reachable code); host spans are therefore placed only
  at the declared tick sites, where the host genuinely blocks.

Zero-cost-when-disabled contract: with no trace session active,
``span(name)`` outside tracing returns one shared no-op context manager —
two attribute reads, no allocation. Enablement comes from
:func:`trace_session` (the ``tpu_trace_dir``/``tpu_trace_mode`` context
engine.train holds for the whole run): ``mode="full"`` starts a real
``jax.profiler.trace`` AND enables host spans; ``mode="annotations"``
enables the spans (device names + host phase table) without the profiler
— the cheap always-on-able flavor.

Span taxonomy (every name a device program or tick site carries):

========================  ==================================================
``binning``               io/binning.bin_columns — raw values -> bin codes
                          (dataset construct AND the serve-time bin_matrix)
``gradient``              objective gradients/hessians for the iteration
``hist_build``            per-leaf histogram accumulation (all engines)
``collective_reduce``     psum/psum_scatter of histograms over the mesh
``split_scan``            best-split scan over the histogram bins
``partition``             row partition / routing after a split
``checkpoint_write``      io/checkpoint.write_snapshot atomic tick
``predict_warmup``        one serving-ladder rung warm (basic.py)
``serve_tick``            one coalescer micro-batch device dispatch
``autotune``              the startup engine microbench sweep
                          (engines/autotune.py — strictly pre-steady-state)
========================  ==================================================
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, Iterator, Optional, Set

import jax

#: the complete phase-name taxonomy (tests assert a traced+served run
#: touches every one of these). Canonical copy lives in obs/tracing.py
#: (jax-free, so scripts/obs can attribute trace phases with no backend);
#: re-exported here because spans is the producer side of the same names.
from .tracing import SPAN_TAXONOMY  # noqa: E402,F401

_TRACE_MODES = ("full", "annotations")

_mu = threading.Lock()
_enabled = 0                      # nesting count of enabling sessions
_seen: Set[str] = set()           # span names entered (host) or traced
_seen_n: Dict[str, int] = {}      # per-name entry counts (for per-run
#                                   deltas: names are a SET, so a rerun
#                                   of the same spans is invisible to
#                                   set difference — counts are not)
_phase_s: Dict[str, float] = {}   # host-span wall seconds by name
_phase_n: Dict[str, int] = {}     # host-span entry counts by name


def _mark_seen(name: str) -> None:
    _seen.add(name)
    _seen_n[name] = _seen_n.get(name, 0) + 1


def _trace_state_clean() -> bool:
    # no catch: if jax moves this again, span() must fail loudly — a
    # swallowed AttributeError here once left every device program
    # without its phase name (jax.core.trace_state_clean, gone in 0.9)
    return jax.core.trace_ctx.is_top_level()


class _NullSpan:
    """Shared no-op span (the disabled fast path)."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NULL = _NullSpan()


class _TracedSpan:
    """Span entered under a jax trace: pure ``named_scope``.

    Runs only at trace time — the name is baked into the lowered ops'
    metadata (the profiler groups device time under it) and costs nothing
    when the compiled program executes. Recording into the seen-set here
    is the honest signal that the DEVICE PROGRAM carries the name, not
    merely that host code passed by.
    """

    __slots__ = ("_scope",)

    def __init__(self, name: str):
        with _mu:
            _mark_seen(name)
        self._scope = jax.named_scope(name)

    def __enter__(self) -> "_TracedSpan":
        self._scope.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        self._scope.__exit__(*exc)
        return False


class _HostSpan:
    """Span entered on the host: profiler annotation + phase-time entry."""

    __slots__ = ("_name", "_ann", "_t0")

    def __init__(self, name: str):
        self._name = name
        self._ann = jax.profiler.TraceAnnotation(name)

    def __enter__(self) -> "_HostSpan":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        with _mu:
            _mark_seen(self._name)
            _phase_s[self._name] = _phase_s.get(self._name, 0.0) + dt
            _phase_n[self._name] = _phase_n.get(self._name, 0) + 1
        return False


def span(name: str):
    """The phase span for ``name`` — see the module docstring.

    Under tracing: a ``named_scope`` (always, enablement aside — trace
    time is the only chance to name the device ops, and it is free at
    runtime). On the host: a timing+annotation span when a trace session
    is active, else the shared no-op.
    """
    if not _trace_state_clean():
        return _TracedSpan(name)
    if _enabled:
        return _HostSpan(name)
    return _NULL


def annotations_enabled() -> bool:
    return bool(_enabled)


def active_sessions() -> int:
    """Currently-entered ``trace_session`` nesting depth. The resource
    witness (guards.resource_witness) reads this: a scope that exits
    with a higher depth than it entered leaked a profiler session."""
    with _mu:
        return int(_enabled)


def enable_annotations() -> None:
    global _enabled
    with _mu:
        _enabled += 1


def disable_annotations() -> None:
    global _enabled
    with _mu:
        _enabled = max(0, _enabled - 1)


def seen_spans() -> Set[str]:
    """Span names observed so far (traced into a program, or entered on
    the host inside a session)."""
    with _mu:
        return set(_seen)


def phase_times() -> Dict[str, Dict[str, float]]:
    """Host-span wall time by phase: ``{name: {seconds, count}}``.

    Process-cumulative — per-RUN tables come from
    :func:`phase_times_since` (engine.train snapshots at run start so
    two runs in one process don't double-count each other's seconds)."""
    with _mu:
        return {k: {"seconds": _phase_s[k], "count": _phase_n.get(k, 0)}
                for k in sorted(_phase_s)}


def phase_times_since(baseline: Dict[str, Dict[str, float]]
                      ) -> Dict[str, Dict[str, float]]:
    """The phase-time delta accumulated after ``baseline`` (a prior
    :func:`phase_times` snapshot); zero-delta phases are dropped."""
    out: Dict[str, Dict[str, float]] = {}
    for name, cur in phase_times().items():
        base = baseline.get(name, {})
        secs = cur["seconds"] - float(base.get("seconds", 0.0))
        cnt = cur["count"] - int(base.get("count", 0))
        if secs > 0.0 or cnt > 0:
            out[name] = {"seconds": secs, "count": cnt}
    return out


def seen_counts() -> Dict[str, int]:
    """Per-name span entry counts (the per-run-delta baseline shape)."""
    with _mu:
        return dict(_seen_n)


def seen_since(baseline: Dict[str, int]) -> Set[str]:
    """Span names entered after ``baseline`` (a prior
    :func:`seen_counts` snapshot) — a set difference over names would
    miss reruns of the same spans, counts do not."""
    with _mu:
        return {k for k, n in _seen_n.items()
                if n > int(baseline.get(k, 0))}


def reset() -> None:
    """Clear the seen-set and the phase-time table (test isolation)."""
    with _mu:
        _seen.clear()
        _seen_n.clear()
        _phase_s.clear()
        _phase_n.clear()


def resolve_trace_mode(mode) -> str:
    """Validate ``tpu_trace_mode``; unknown values warn and fall back to
    ``full`` (the pre-knob behavior of ``tpu_trace_dir``)."""
    m = str(mode or "full").strip().lower() or "full"
    if m not in _TRACE_MODES:
        from ..utils import log
        log.warning(f"unrecognized tpu_trace_mode={mode!r} "
                    f"(one of {_TRACE_MODES}); using 'full'")
        return "full"
    return m


@contextlib.contextmanager
def trace_session(trace_dir: Optional[str] = None,
                  mode: str = "full") -> Iterator[None]:
    """One telemetry session: spans enabled for the block, and (in
    ``full`` mode with a directory) a ``jax.profiler.trace`` written to
    ``trace_dir``.

    This is the ``tpu_trace_dir`` context engine.train holds around the
    WHOLE training loop — as a context manager, so the profiler trace is
    closed on every error path (the raw ``__enter__``-then-``finally``
    wiring it replaces leaked the trace if setup raised before the try).
    ``mode="annotations"`` enables span names (device-trace metadata +
    the host phase table) without paying for a full profiler trace.
    """
    mode = resolve_trace_mode(mode)
    profiler = None
    enable_annotations()
    try:
        if trace_dir and mode == "full":
            profiler = jax.profiler.trace(str(trace_dir))
            profiler.__enter__()
        try:
            yield
        finally:
            if profiler is not None:
                profiler.__exit__(None, None, None)
    finally:
        disable_annotations()
