"""Phase-named spans: one API, two faces (device trace names + host records).

The reference attributes wall time to every training phase through its
``USE_TIMETAG`` ``Common::Timer`` registry (include/LightGBM/utils/log.h:
``global_timer.Print()`` at process exit). On TPU that design splits in
two, because the two interesting clocks live in different places:

* **Device time** belongs to the profiler. A span entered while jax is
  TRACING wraps the region in ``jax.named_scope``, so the lowered HLO ops
  carry the phase name in their op metadata. The chip's profile does NOT
  name its events by that scope: an ``XLA Ops`` event is named by its HLO
  line (``%fusion.3 = ...``, ``%fused_split_step.17 = ...``), and the
  scope path is metadata one click down in XProf. Only the fused kernel's
  two call sites are told apart by event name (``ops/fused_split.py``
  names each); device seconds by scope are reduced nowhere yet (PERF.md,
  section 7).
* **Host time** belongs to the orchestration loop. A span entered outside
  tracing appends one ``span`` record to the flight ring (obs/flight.py)
  — ``name``, ``t0``/``t1`` on ``time.perf_counter()``, ``parent`` (the
  enclosing host span, from a thread-local stack), ``iteration`` (the
  booster's ``iter_`` inside ``Booster.update()``, else None) — feeds the
  per-phase table that :func:`phase_times` returns, and enters a
  ``jax.profiler.TraceAnnotation`` of the same name: free with no
  profiler listening, and with one it puts the span on the trace's host
  timeline beside the device's, for whoever opens the xplane in XProf or
  Perfetto. Host timing around ASYNC dispatch measures dispatch, not
  device work (tpulint R009 keeps naive timing out of jit-reachable
  code): of the update loop's spans only ``flush_trees`` blocks on the
  device; its child ``step_wait`` is the wait for the step, not host
  work, and what is left of it the copy of the trees' arrays.

Which host spans record. The spans of set-up and of the update loop
(:data:`ALWAYS_ON`) record whenever they are entered: the ring is on by
default, ``Booster.update()`` writes its ``iteration`` event there anyway,
and an update enters seven of them (a dict and a locked append each:
microseconds against an iteration; ``bag``, ``gradient`` and
``rank_grads`` make it eight to ten where the run samples rows or calls
a gradient program of its own). Every other host span (the serving
ticks, ``checkpoint_write``) records only inside a
:func:`trace_session` and is otherwise one shared no-op — a coalescer
ticking thousands of times a second would turn the ring over and push
out the compile and iteration events a post-mortem wants.
``trace_session`` is the ``tpu_trace_dir``/``tpu_trace_mode`` context
engine.train holds for the whole run: ``mode="full"`` starts a real
``jax.profiler.trace`` too, ``mode="annotations"`` does not.

Inside ``Booster.update()`` the ``iteration`` span also holds the
update's counters (:func:`bump`): ``dispatches`` (calls of the booster's
own jitted programs: the step, the gradients), ``host_syncs`` (entries
into sites that block on the device) and ``d2h_bytes``; the closing
``iteration`` event reports them (``GBDT._obs_iteration_tick``), and
with them what a ranking objective's layout makes the gradient program
compute: ``rank_slots`` (padded [queries, length] slots over all length
classes), ``rank_docs``, ``rank_slots_per_doc``, ``rank_classes``; and
what a data-parallel step is made of: ``shards``, ``rows_per_shard``,
``collectives`` and ``collective_bytes`` (the collective instructions of
the step's compiled text and their bytes, each instruction once); and
which path a quantized-gradient step runs: ``quant_hist`` (1: int8 codes
into int32 histograms; 0: the dequantising f32 shim), ``quant_bins``,
``quant_renew`` (1: leaves renewed from the true gradients); and
``hist_levels``, the levels of the one-hot the step's fused kernel
contracts (2: bin = 64 hi + lo; 1: the whole stride; 0: the kernel is off);
``carry_bytes``, the bytes of VMEM a carried row's byte takes in its ring
carries (1: packed bytes; 0: the kernel is off); and ``record_write`` (1: the ``record_write`` kernel writes the step's
per-row columns into the records; 0: XLA's lane-slice update does).

The update's seconds by phase ride the same event. Every span that
closes inside an update adds its seconds to a per-update table beside
the counters, under its own name (a parent's seconds hold its
children's: ``flush_trees`` holds ``step_wait``; ``update_tick``, which
closes after the event is written, is in no table); the closing event
carries the table as ``phase_s`` and, as ``cpu_s``, the CPU seconds the
updating thread was given (``time.thread_time()``): host-only reads. A
phase whose wall seconds are far over the update's ``cpu_s`` was spent
waiting (for the device in ``step_wait``; for a blocked launch, or for
the scheduler, in ``step_dispatch`` or ``step_args``); ``cpu_s`` near
``seconds`` is Python's own work. :class:`SlowUpdates` reads them where
it matters: an update that took :data:`SLOW_FACTOR` times the median of
the booster's recent updates writes one ``slow_iteration`` record and
one warning with its ``phase_s``, ``cpu_s``, the compiles and the
collector's passes since the update before it.

Span taxonomy (every name a device program or tick site carries):

========================  ==================================================
``binning``               io/binning.bin_columns — raw values -> bin codes
                          (dataset construct AND the serve-time bin_matrix)
``gradient``              objective gradients/hessians for the iteration
                          (named scope in the program; host face where the
                          gradient program is called)
``hist_build``            per-leaf histogram accumulation (all engines)
``collective_reduce``     psum/psum_scatter of histograms over the mesh
``split_scan``            best-split scan over the histogram bins
``partition``             row partition / routing after a split
``quant_discretize``      use_quantized_grad: the gradients' scales and
                          their integer codes (boosting/gbdt.py, in the
                          step; on the host where the masked grower's
                          shim runs ahead of it)
``quant_renew``           quant_train_renew_leaf: every leaf's value from
                          the true gradients of its rows, in the step
``checkpoint_write``      io/checkpoint.write_snapshot atomic tick
``predict_warmup``        one serving-ladder rung warm (basic.py)
``serve_tick``            one coalescer micro-batch device dispatch
``import``                ``import lightgbm_tpu`` (stamped, not entered)
``construct``             all of ``Dataset.construct()``; children
                          ``find_bins`` (the sample and the boundaries)
                          and ``binning``
``booster_init``          ``Booster.__init__`` with a train set, past its
                          ``construct``; children ``to_device`` (the
                          binned matrix's first move to the device, in
                          ``GBDT._setup_train``) and, under a ranking
                          objective, ``rank_layout`` (the queries grouped
                          into length classes, each class's index, gains
                          and inverse max DCG: objectives.py)
``compact_setup``         ``_setup_compact_state`` (first update); under a
                          mesh its child ``shard_rows``: every device packs
                          the rows it holds into its own records
``build_step``            ``_build_compact_step_fn`` / ``_build_step_fn``
``iteration``             all of ``Booster.update()``; children ``bag``
                          (only where the strategy draws or reuses a
                          bag), ``gradient`` (``rank_grads`` where the
                          program called is the ranking gradients' own,
                          ahead of the compact step), ``step_args`` (one
                          per tree: the step's arguments, from the bag's
                          mask to the per-tree keys), ``step_dispatch``
                          (one per call of the jitted step),
                          ``valid_scores``, ``flush_trees`` (where the
                          loop blocks: its child ``step_wait`` is the
                          wait for the step that grew the pending trees,
                          the rest the copy of their arrays to the
                          host), ``decode_trees`` (the ``HostTree``
                          objects, the copied list, the stop check),
                          ``update_tick`` (the update's own telemetry,
                          last in it: the sampled rank-stats probe and
                          the closing ``iteration`` event)
========================  ==================================================
"""
from __future__ import annotations

import collections
import contextlib
import gc
import statistics
import threading
import time
from typing import Any, Deque, Dict, Iterator, List, Optional, Set, Tuple

import jax

from . import flight
#: the complete phase-name taxonomy (tests assert a traced+served run
#: touches every one of these). Canonical copy lives in obs/tracing.py
#: (jax-free, so scripts/obs can name phases with no backend);
#: re-exported here because spans is the producer side of the same names.
from .tracing import SPAN_TAXONOMY  # noqa: E402,F401

_TRACE_MODES = ("full", "annotations")

#: host spans that record with no session active: set-up and the update
#: loop (module docstring: which host spans record)
ALWAYS_ON = frozenset((
    "import", "construct", "find_bins", "binning", "to_device",
    "booster_init", "rank_layout", "compact_setup", "shard_rows",
    "build_step",
    "iteration", "bag", "gradient", "rank_grads", "step_args",
    "step_dispatch", "valid_scores", "flush_trees", "step_wait",
    "decode_trees", "update_tick"))

#: the update's counters (:func:`bump`), as its ``iteration`` event and
#: the metrics stream's record carry them
COUNTERS = ("dispatches", "host_syncs", "d2h_bytes")

#: a slow update reports itself (:class:`SlowUpdates`): one that took at
#: least SLOW_FACTOR times the median of the booster's previous updates
#: with the same ``host_syncs`` count, the last SLOW_WINDOW of them, once
#: there are SLOW_MIN
SLOW_FACTOR = 3.0
SLOW_WINDOW = 32
SLOW_MIN = 8

#: per-thread: the stack of open host spans (``stack``) and, inside
#: Booster.update(), that update's state (``update``)
_local = threading.local()


class _Update:
    """What the ``iteration`` span holds for the update it wraps: the
    booster's iter_, the update's counters, its seconds by phase and the
    thread's CPU clock as the update found it."""

    __slots__ = ("iteration", "counters", "phases", "cpu0")

    def __init__(self, iteration: int):
        self.iteration = iteration
        self.counters: Dict[str, int] = {}
        self.phases: Dict[str, float] = {}
        self.cpu0 = time.thread_time()

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
    """Span entered on the host: ring record + phase-table entry +
    profiler annotation. ``iteration`` (given by ``Booster.update``
    alone) makes this the update's span: the spans inside it carry that
    number, :func:`bump` counts into it, and each span that closes
    inside it adds its seconds to the update's own table
    (:func:`update_phases`)."""

    __slots__ = ("_name", "_ann", "_t0", "_parent", "_iteration", "_outer")

    def __init__(self, name: str, iteration: Optional[int] = None):
        self._name = name
        self._ann = jax.profiler.TraceAnnotation(name)
        self._iteration = iteration

    def __enter__(self) -> "_HostSpan":
        if self._iteration is not None:
            self._outer = getattr(_local, "update", None)
            _local.update = _Update(int(self._iteration))
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        self._parent = stack[-1] if stack else None
        stack.append(self._name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        _local.stack.pop()
        update = getattr(_local, "update", None)
        record(self._name, self._t0, t1, self._parent,
               update.iteration if update is not None else None)
        if self._iteration is not None:
            _local.update = self._outer
        elif update is not None:
            update.phases[self._name] = (update.phases.get(self._name, 0.0)
                                         + t1 - self._t0)
        return False


def record(name: str, t0: float, t1: float, parent: Optional[str] = None,
           iteration: Optional[int] = None) -> None:
    """One finished host span: a ``span`` record in the flight ring and
    an entry in the phase table. What a host span's exit calls; called
    directly where a span is stamped and not entered (``import``)."""
    flight.note("span", name=name, t0=t0, t1=t1, parent=parent,
                iteration=iteration)
    with _mu:
        _mark_seen(name)
        _phase_s[name] = _phase_s.get(name, 0.0) + (t1 - t0)
        _phase_n[name] = _phase_n.get(name, 0) + 1


def span(name: str, iteration: Optional[int] = None):
    """The phase span for ``name`` — see the module docstring.

    Under tracing: a ``named_scope`` (always, enablement aside — trace
    time is the only chance to name the device ops, and it is free at
    runtime). On the host: a recording span when the name is one of
    :data:`ALWAYS_ON` or a trace session is active, else the shared
    no-op.
    """
    if not _trace_state_clean():
        return _TracedSpan(name)
    if _enabled or name in ALWAYS_ON:
        return _HostSpan(name, iteration)
    return _NULL


def bump(counter: str, n: int = 1) -> None:
    """Add ``n`` to one of the current update's counters. Outside
    ``Booster.update()`` there is no update to count for and nothing is
    counted: a flush from ``predict`` is not an update's sync."""
    update = getattr(_local, "update", None)
    if update is not None:
        update.counters[counter] = update.counters.get(counter, 0) + n


def update_counters() -> Dict[str, int]:
    """The counters of the update this thread is inside (zeros outside
    one): what ``GBDT._obs_iteration_tick`` writes into its event."""
    update = getattr(_local, "update", None)
    counted = update.counters if update is not None else {}
    return {name: counted.get(name, 0) for name in COUNTERS}


def update_phases() -> Tuple[Dict[str, float], float]:
    """(``phase_s``, ``cpu_s``) of the update this thread is inside: the
    seconds of every span closed in it so far, by name, and the CPU
    seconds the thread was given since the update began
    (``time.thread_time()``: a thread that waits or is descheduled earns
    none). Empty and 0.0 outside an update. Host-only reads."""
    update = getattr(_local, "update", None)
    if update is None:
        return {}, 0.0
    return ({name: round(s, 6) for name, s in update.phases.items()},
            round(time.thread_time() - update.cpu0, 6))


class SlowUpdates:
    """One booster's watch for the update that stalls (module docstring).

    ``check`` is called once an update, from its closing tick, with what
    the tick already holds. An update is held against the median of the
    booster's previous updates *with the same* ``host_syncs`` *count*:
    one that flushes waits for the device and the others (under
    ``stop_check_freq`` > 1) only dispatch. Where it took
    :data:`SLOW_FACTOR` times that median, ``check`` returns the
    ``slow_iteration`` record's fields: ``iteration``, ``seconds``,
    ``median_s``, ``phase_s``, ``cpu_s``, ``compiles`` (lowerings and
    backend compiles since the previous tick) and ``gc_collections``
    (the collector's passes per generation since the previous tick).
    Host-only reads; a median of at most :data:`SLOW_WINDOW` floats."""

    def __init__(self) -> None:
        self._recent: Dict[int, Deque[float]] = {}
        # (lowerings, backend compiles, the collector's passes by
        # generation) as the previous tick read them
        self._last: Optional[List[int]] = None

    def check(self, iteration: int, seconds: float, host_syncs: int,
              phase_s: Dict[str, float], cpu_s: float
              ) -> Optional[Dict[str, Any]]:
        from ..analysis import guards
        counts = guards.phase_compile_counts()
        now = [counts["lowerings"], counts["backend_compiles"]] + [
            g["collections"] for g in gc.get_stats()]
        last, self._last = self._last or now, now
        recent = self._recent.setdefault(
            host_syncs, collections.deque(maxlen=SLOW_WINDOW))
        median = statistics.median(recent) if len(recent) >= SLOW_MIN \
            else None
        recent.append(seconds)
        if median is None or seconds < SLOW_FACTOR * median:
            return None
        return {
            "iteration": iteration, "seconds": round(seconds, 6),
            "median_s": round(median, 6), "phase_s": phase_s,
            "cpu_s": cpu_s,
            "compiles": {"lowerings": now[0] - last[0],
                         "backend_compiles": now[1] - last[1]},
            "gc_collections": [a - b for a, b in zip(now[2:], last[2:])]}


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
    """Span names observed so far (traced into a program, or recorded
    on the host)."""
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
