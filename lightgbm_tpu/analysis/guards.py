"""Runtime guard rails: recompile and host-transfer assertions.

The static pass (analysis/tpulint.py) catches hazard *patterns*; these
guards catch the *behavior* — they wrap a steady-state region (e.g. 5
post-warmup boosting iterations) and fail loudly if jax compiles anything
or an array is materialized on the host inside it.

``compile_counter``
    Counts compilations via ``jax.monitoring`` duration events.
    ``lowerings`` (jaxpr->MLIR) increments on every in-memory cache miss —
    including ones served by the persistent compilation cache, which skips
    only the backend compile — so it is the honest "did jit re-trace"
    signal. ``backend_compiles`` counts actual XLA compiles. Counts are
    also keyed by the active ``compile_phase()`` (train step / predict
    warmup / serving) in ``by_phase``, and a process-lifetime listener
    (``install_global_compile_listener``) feeds the same attribution to
    the obs/ metrics plane and the flight recorder.

``no_host_transfers``
    Patches the Python-level host-materialization funnels on
    ``jax.Array`` (``_value``, ``__array__``, ``item``, ``tolist``,
    ``__float__``/``__int__``/``__bool__``/``__index__``) to raise
    ``HostTransferError`` at the offending call site, and additionally
    arms ``jax.transfer_guard_device_to_host("disallow")``, which is
    enforced natively on real device backends.

    ``np.asarray(arr)`` on the CPU backend reaches the buffer zero-copy
    through the C-level buffer protocol WITHOUT touching any ``jax.Array``
    method — so the numpy entry points themselves
    (``np.asarray``/``np.array``/``np.ascontiguousarray``/
    ``np.asanyarray``) are wrapped too: a ``jax.Array`` as the top-level
    argument raises inside the guard. Residual caveat: a direct C-level
    consumer (``memoryview(arr)``, third-party C extensions taking the
    buffer) still bypasses Python entirely — only the native transfer
    guard on TPU and the static pass (R001) see those.

``api_race_sanitizer``
    The runtime half of tpulint R007: while armed, every
    ``@read_locked``/``@write_locked`` public ``Booster``/``Dataset``
    method reports entry/exit (from *inside* the lock, utils/rwlock.py),
    and any overlap — a writer concurrent with anything, on the same
    object, from another thread — is recorded as a race. A correctly
    locked program records nothing; a bypassed or missing lock (the
    seeded mutation in tests/test_concurrency.py) lights it up.

All are plain context managers usable directly or as pytest fixtures
(wired in tests/conftest.py).
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import sys
import threading
import time
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import jax
from jax import monitoring

from ..utils import rwlock as _rwlock

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_REQUEST_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

#: duration event -> the ``kind`` of the flight ring's ``compile`` record.
#: The first two are also counted (CompileCount); a backend compile's
#: interval encloses its cache retrieval's, and a jit traced inside
#: another's trace would nest likewise (only outermost traces are
#: recorded), so a reader takes unions and never sums across kinds.
_COMPILE_KINDS = {_TRACE_EVENT: "traces", _LOWER_EVENT: "lowerings",
                  _BACKEND_EVENT: "backend_compiles",
                  _CACHE_RETRIEVAL_EVENT: "cache_retrievals"}

#: jax.Array methods/properties through which host materialization funnels
_FUNNELS = ("_value", "__array__", "item", "tolist", "__float__",
            "__int__", "__bool__", "__index__", "__complex__")

#: numpy entry points that can materialize a CPU-backend jax.Array
#: zero-copy via the C buffer protocol, bypassing every patched method
_NP_FUNNELS = ("asarray", "array", "ascontiguousarray", "asanyarray")


class HostTransferError(AssertionError):
    """An array was materialized on the host inside a guarded region."""


#: thread-local compile-phase stack (jax compiles synchronously on the
#: calling thread, so the phase at event time attributes the compile)
_phase_local = threading.local()

#: phase recorded when no compile_phase() scope is active
DEFAULT_PHASE = "other"


def current_compile_phase() -> str:
    stack = getattr(_phase_local, "stack", None)
    return stack[-1] if stack else DEFAULT_PHASE


@contextlib.contextmanager
def compile_phase(name: str) -> Iterator[None]:
    """Attribute compile events inside the block to ``name``.

    The phase key behind ``CompileCount.by_phase`` and the metrics
    plane: ``train_step`` wraps boosting iterations, ``predict_warmup``
    wraps the serving-ladder warm, ``serving`` wraps coalescer ticks —
    so a BENCH row (or a flight dump) says WHERE a compile happened
    instead of reporting one global count. Nests; the innermost wins."""
    stack = getattr(_phase_local, "stack", None)
    if stack is None:
        stack = _phase_local.stack = []
    stack.append(str(name))
    try:
        yield
    finally:
        stack.pop()


@dataclasses.dataclass
class CompileCount:
    lowerings: int = 0
    backend_compiles: int = 0
    #: phase -> {"lowerings": n, "backend_compiles": m} (see compile_phase)
    by_phase: dict = dataclasses.field(default_factory=dict)

    def bump(self, kind: str, phase: str) -> None:
        setattr(self, kind, getattr(self, kind) + 1)
        slot = self.by_phase.setdefault(
            phase, {"lowerings": 0, "backend_compiles": 0})
        slot[kind] += 1

    def snapshot(self) -> dict:
        return {"lowerings": self.lowerings,
                "backend_compiles": self.backend_compiles,
                "by_phase": {p: dict(v) for p, v in self.by_phase.items()}}

    def assert_no_compiles(self, what: str = "guarded region") -> None:
        if self.lowerings or self.backend_compiles:
            raise AssertionError(
                f"{what}: expected zero recompilations, saw "
                f"{self.lowerings} lowering(s) and "
                f"{self.backend_compiles} backend compile(s) — a shape, "
                "dtype, or static-arg value changed after warmup "
                f"(by phase: {self.by_phase})")


@contextlib.contextmanager
def _monitoring_listener(callback, register, unregister):
    """Register a jax.monitoring listener for the duration of the block."""
    register(callback)
    try:
        yield
    finally:
        unregister(callback)


@contextlib.contextmanager
def compile_counter() -> Iterator[CompileCount]:
    """Count jit compilations inside the ``with`` block.

    Usage::

        with compile_counter() as cc:
            for _ in range(5):
                bst.update()
        cc.assert_no_compiles("post-warmup boosting")
    """
    counts = CompileCount()

    def _on_event(event: str, duration_secs: float = 0.0, **kw) -> None:
        if event == _LOWER_EVENT:
            counts.bump("lowerings", current_compile_phase())
        elif event == _BACKEND_EVENT:
            counts.bump("backend_compiles", current_compile_phase())

    with _monitoring_listener(
            _on_event, monitoring.register_event_duration_secs_listener,
            monitoring.unregister_event_duration_listener):
        yield counts


@dataclasses.dataclass
class CacheCount:
    """Persistent-compile-cache lookups observed inside a guarded region."""
    requests: int = 0
    hits: int = 0

    @property
    def misses(self) -> int:
        return self.requests - self.hits


@contextlib.contextmanager
def cache_counter() -> Iterator[CacheCount]:
    """Count persistent-compilation-cache lookups inside the ``with`` block.

    ``requests`` counts backend compiles that consulted the cache
    (``/jax/compilation_cache/compile_requests_use_cache``), ``hits`` the
    ones served from it. A warm cache (``tpu_compile_cache_dir`` pointed
    at a previous run's directory, fresh process) shows hits == requests:
    lowering still happens, the XLA backend compile is skipped. Counts
    stay zero when no cache dir is configured."""
    counts = CacheCount()

    def _on_event(event: str, **kw) -> None:
        if event == _CACHE_REQUEST_EVENT:
            # jax emits the request event on EVERY backend compile, cache
            # dir or not — only count consultations of a real cache, so
            # cache-disabled runs read 0/0 instead of all-miss
            if jax.config.jax_compilation_cache_dir:
                counts.requests += 1
        elif event == _CACHE_HIT_EVENT:
            counts.hits += 1

    with _monitoring_listener(_on_event, monitoring.register_event_listener,
                              monitoring.unregister_event_listener):
        yield counts


#: jax reads this into ``jax_compilation_cache_dir`` at import; where it
#: is set, no code in the repository names another directory
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"


def checkout_cache_dir() -> str:
    """``<checkout>/.jax_cache``: the fixed default for the entry points
    that want a cache without being told where (chip_smoke.py, bench.py,
    scripts/, the test suite). The path is part of jax's cache key, so it
    is never a temp name, pid or timestamp; .gitignore lists it."""
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache(cache_dir, min_compile_secs: float = 0.0) -> bool:
    """THE place the persistent compilation cache is pointed somewhere.

    One rule: if ``JAX_COMPILATION_CACHE_DIR`` is set the cache lives
    there and ``cache_dir`` is ignored (jax already read the variable);
    otherwise it lives in ``cache_dir`` — the user's
    ``tpu_compile_cache_dir``, or :func:`checkout_cache_dir` for the
    repo's own entry points. The admission thresholds drop so every step
    program qualifies (jax's default 1 s floor rejects most CPU-backend
    programs); the test suite passes a small floor to keep thousands of
    trivial programs off the disk. Changing the directory after a
    compile already ran re-arms jax's once-per-task cache-enable
    decision via ``reset_cache``. Returns True when a cache directory is
    active, False for an empty/unset ``cache_dir`` (no-op)."""
    path = str(cache_dir or "").strip()
    if not path:
        return False
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_secs))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if os.environ.get(CACHE_DIR_ENV):
        return True
    if jax.config.jax_compilation_cache_dir != path:
        from jax.experimental.compilation_cache import (
            compilation_cache as _cc)
        jax.config.update("jax_compilation_cache_dir", path)
        _cc.reset_cache()  # drop the cached is-cache-used decision
    return True


# -- process-lifetime compile accounting (the obs/ metrics plane) ----------
#: cumulative phase-keyed counts, fed by ONE permanently-registered
#: listener (install_global_compile_listener); the metrics stream emits
#: these as cumulative snapshots so any two records diff cleanly
_global_compiles = CompileCount()
_global_cache = CacheCount()
_global_listener_installed = False
_global_mu = threading.Lock()


def install_global_compile_listener() -> None:
    """Register the always-on compile/cache listeners (idempotent).

    Unlike :func:`compile_counter` (a scoped guard), this feeds the
    process-lifetime counters behind :func:`phase_compile_counts` and
    records each compile into the flight recorder, phase-keyed — so a
    post-mortem dump shows WHAT compiled right before a death, and the
    metrics plane reports attribution without any guard being armed.
    Besides lowerings and backend compiles the ring gets jaxpr traces
    (outermost only) and persistent-cache retrievals, each with the
    ``compile_phase()`` it fell in, the function's name where jax gives
    one, and ``t0``/``t1`` on ``time.perf_counter()``.
    Cost: one python callback per compile event (compiles are rare by
    contract — the whole repo is built around zero steady-state
    compiles)."""
    global _global_listener_installed
    with _global_mu:
        if _global_listener_installed:
            return
        _global_listener_installed = True

    def _on_duration(event: str, duration_secs: float = 0.0, **kw) -> None:
        kind = _COMPILE_KINDS.get(event)
        if kind is None:
            return
        # jax reports a duration as it ends: t1 is now, on the clock of
        # the host spans (obs/spans.py), so a reader places both on one
        # timeline; the ring's wall-clock t stays for the dumps
        t1 = time.perf_counter()
        if event == _TRACE_EVENT and not jax.core.trace_ctx.is_top_level():
            # a jit traced inside another jit's trace (every jnp function
            # is one): hundreds a step, all inside the outermost's interval
            return
        phase = current_compile_phase()
        if event in (_LOWER_EVENT, _BACKEND_EVENT):
            with _global_mu:
                _global_compiles.bump(kind, phase)
        from ..obs import flight
        flight.note("compile", kind=kind, phase=phase,
                    seconds=round(float(duration_secs), 4),
                    t0=t1 - float(duration_secs), t1=t1,
                    fun=kw.get("fun_name"))

    def _on_event(event: str, **kw) -> None:
        if event == _CACHE_REQUEST_EVENT:
            if jax.config.jax_compilation_cache_dir:
                with _global_mu:
                    _global_cache.requests += 1
        elif event == _CACHE_HIT_EVENT:
            with _global_mu:
                _global_cache.hits += 1

    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)


def phase_compile_counts() -> dict:
    """Cumulative process-lifetime compile counts, phase-keyed (zeros
    until :func:`install_global_compile_listener` ran)."""
    with _global_mu:
        return _global_compiles.snapshot()


def global_cache_counts() -> dict:
    """Cumulative persistent-compile-cache counters (same caveat)."""
    with _global_mu:
        return {"requests": _global_cache.requests,
                "hits": _global_cache.hits,
                "misses": _global_cache.misses}


#: shared device-enumeration probe state: a wedged backend pins exactly
#: ONE blocked thread process-wide (periodic readiness polling reuses the
#: in-flight enumeration), and once a backend has come up enumeration is
#: jax's cached lookup, called inline with no thread at all
_device_probe = {"mu": threading.Lock(), "thread": None, "box": None,
                 "initialized": False}


def device_healthcheck(deadline_s: float = 5.0) -> dict:
    """Device-reachability probe for serving health endpoints.

    Returns ``{"ok", "platform", "device_count", "error"}`` without ever
    raising — and without ever HANGING: ``jax.devices()`` on a fresh
    process synchronously initializes the backend, which on a wedged TPU
    runtime blocks for the full init timeout (the BENCH_r05 death mode;
    on some hosts plugin discovery never returns at all). The first
    enumeration therefore runs in a single SHARED daemon thread waited
    on for ``deadline_s``: a blown deadline reports ``ok: False``, and
    every later probe re-waits on the SAME blocked thread instead of
    leaking one watchdog worker per poll. After one successful
    enumeration the backend is cached and the probe calls inline.
    ``deadline_s <= 0`` disables the watchdog (may block)."""

    def _summarize(devices):
        if not devices:
            return {"ok": False, "platform": None, "device_count": 0,
                    "error": "device enumeration returned an empty list"}
        _device_probe["initialized"] = True
        return {"ok": True, "platform": devices[0].platform,
                "device_count": len(devices), "error": None}

    def _failure(err):
        msg = str(err).splitlines()[0][:200] if str(err) else repr(err)
        return {"ok": False, "platform": None, "device_count": 0,
                "error": msg}

    if _device_probe["initialized"] or not deadline_s or deadline_s <= 0:
        try:
            return _summarize(jax.devices())
        except Exception as err:  # noqa: BLE001 - probe must not raise
            return _failure(err)
    with _device_probe["mu"]:
        thread, box = _device_probe["thread"], _device_probe["box"]
        if thread is None or not thread.is_alive():
            # no probe in flight (fresh, or the last one finished and was
            # consumed): start one
            box = {"done": threading.Event()}

            def _enumerate(b=box):
                try:
                    b["devices"] = jax.devices()
                except BaseException as err:  # noqa: BLE001 - reported
                    b["error"] = err
                finally:
                    b["done"].set()

            thread = threading.Thread(target=_enumerate, daemon=True,
                                      name="lgbm-tpu-device-probe")
            _device_probe["thread"], _device_probe["box"] = thread, box
            thread.start()
    if not box["done"].wait(deadline_s):
        return {"ok": False, "platform": None, "device_count": 0,
                "error": f"device enumeration still blocked after "
                         f"{deadline_s:.0f}s (backend init wedged)"}
    if "error" in box:
        return _failure(box["error"])
    return _summarize(box.get("devices"))


@contextlib.contextmanager
def no_host_transfers() -> Iterator[None]:
    """Raise ``HostTransferError`` on any device->host materialization.

    See the module docstring for the CPU buffer-protocol caveat.
    """
    # private reach, kept: the concrete class whose methods materialize
    # on the host has no public name (jax.Array is only its abstract
    # base); present in the installed jax 0.9.0, and an import error here
    # fails the guard loudly rather than disarming it
    from jax._src import array as _array_mod

    cls = _array_mod.ArrayImpl
    saved = {}

    def _wrap(name, orig):
        if isinstance(orig, property):
            @property
            def guard_prop(self):
                raise HostTransferError(
                    f"jax.Array.{name} materialized an array on the host "
                    "inside a no_host_transfers() region")
            return guard_prop

        def guard(self, *a, **k):
            raise HostTransferError(
                f"jax.Array.{name}() materialized an array on the host "
                "inside a no_host_transfers() region")
        return guard

    for name in _FUNNELS:
        orig = getattr(cls, name, None)
        if orig is None:
            continue
        saved[name] = orig
        setattr(cls, name, _wrap(name, orig))

    # the np.asarray buffer-protocol path materializes the array without
    # calling ANY jax.Array method on CPU; guard the numpy entry points
    # for direct jax.Array arguments (nested containers still route
    # through the patched __array__ above)
    import numpy as _np

    def _np_wrap(name, orig):
        def guard(a, *args, **kw):
            if isinstance(a, cls):
                raise HostTransferError(
                    f"np.{name}() materialized a jax.Array on the host "
                    "inside a no_host_transfers() region (C buffer-protocol "
                    "path)")
            return orig(a, *args, **kw)
        return guard

    np_saved = {}
    for name in _NP_FUNNELS:
        orig = getattr(_np, name, None)
        if orig is None:  # pragma: no cover - numpy always has these
            continue
        np_saved[name] = orig
        setattr(_np, name, _np_wrap(name, orig))
    try:
        with jax.transfer_guard_device_to_host("disallow"):
            yield
    finally:
        for name, orig in saved.items():
            setattr(cls, name, orig)
        for name, orig in np_saved.items():
            setattr(_np, name, orig)


class ApiRaceError(AssertionError):
    """Unsynchronized concurrent access to a shared API object."""


class ApiRaceSanitizer:
    """Detector for concurrent unsynchronized ``Booster``/``Dataset`` use.

    Holds a table of (object, thread) -> current access kind, fed by the
    rwlock decorators. Because the hooks run while the API lock is held,
    a working lock admits no overlap; overlaps therefore mean the lock
    was bypassed, replaced, or a method skipped its decorator. Detector
    mode records races in ``.races`` without blocking the offending
    thread; ``raise_on_race=True`` turns the first overlap into an
    immediate ``ApiRaceError`` at the second accessor's call site.
    """

    def __init__(self, raise_on_race: bool = False):
        self.races: List[str] = []
        self.raise_on_race = raise_on_race
        self._mu = threading.Lock()
        # id(obj) -> {thread_id: [kind, depth, method]}
        self._held = {}

    def enter(self, obj, kind: str, method: str):
        me = threading.get_ident()
        key = id(obj)
        with self._mu:
            holds = self._held.setdefault(key, {})
            mine = holds.get(me)
            if mine is not None:
                mine[1] += 1            # same-thread nesting is not a race
                return (key, me)
            clash = next(
                (f"{type(obj).__name__}.{method} [{kind}] in thread {me} "
                 f"overlaps {type(obj).__name__}.{m} [{k}] in thread {t}"
                 for t, (k, _, m) in holds.items()
                 if kind == "write" or k == "write"), None)
            if clash is not None:
                self.races.append(clash)
                if self.raise_on_race:
                    # the access does not proceed (the wrapper's exit_ is
                    # never reached), so do NOT register the hold — a
                    # phantom entry would indict every later accessor
                    raise ApiRaceError(clash)
            holds[me] = [kind, 1, method]
            return (key, me)

    def exit_(self, token) -> None:
        key, me = token
        with self._mu:
            holds = self._held.get(key, {})
            mine = holds.get(me)
            if mine is None:
                return
            mine[1] -= 1
            if mine[1] <= 0:
                del holds[me]

    def assert_no_races(self, what: str = "guarded region") -> None:
        if self.races:
            raise ApiRaceError(
                f"{what}: {len(self.races)} unsynchronized concurrent "
                "API access(es):\n  " + "\n  ".join(self.races[:10]))


@contextlib.contextmanager
def api_race_sanitizer(raise_on_race: bool = False
                       ) -> Iterator[ApiRaceSanitizer]:
    """Arm the API race detector for the ``with`` block.

    Usage::

        with api_race_sanitizer() as san:
            ... threads hammering booster.predict()/update() ...
        san.assert_no_races("concurrent predict")
    """
    san = ApiRaceSanitizer(raise_on_race=raise_on_race)
    prev = _rwlock.get_sanitizer()
    _rwlock.set_sanitizer(san)
    try:
        yield san
    finally:
        _rwlock.set_sanitizer(prev)


@contextlib.contextmanager
def steady_state_guard(what: str = "guarded region"
                       ) -> Iterator[CompileCount]:
    """Combined guard: zero recompiles AND zero host transfers.

    Asserts on clean exit; an exception from the body propagates as-is.
    """
    with compile_counter() as counts:
        with no_host_transfers():
            yield counts
    counts.assert_no_compiles(what)


# ---------------------------------------------------------------------------
# lock-order witness — the runtime half of tpulint R011


class LockOrderError(AssertionError):
    """A lock-order cycle was observed across threads at runtime."""


def _witness_stack(skip: int = 2, depth: int = 12) -> Tuple[str, ...]:
    """Cheap ``file.py:line`` stack (innermost first), skipping the
    witness/lock machinery frames — captured on every outer acquisition,
    so no ``traceback`` formatting."""
    frames: List[str] = []
    try:
        f = sys._getframe(skip)
    except ValueError:              # pragma: no cover - shallow stack
        return ()
    while f is not None and len(frames) < depth:
        fname = f.f_code.co_filename
        base = os.path.basename(fname)
        if base not in ("threading.py", "rwlock.py", "guards.py"):
            frames.append(f"{base}:{f.f_lineno} in {f.f_code.co_name}")
        f = f.f_back
    return tuple(frames)


class LockOrderWitness:
    """Per-thread held-lock stacks merged into a global order graph.

    Locks are identified by their *creation site* name, not instance id:
    every ``ServeFuture._mu`` is the same node, so a per-request lock
    family cannot spuriously self-cycle (same-name pairs are skipped —
    they are either re-entrant or independent instances), while a real
    A->B / B->A inversion between two lock families is caught no matter
    which instances exhibit it. Each first-seen edge keeps the acquiring
    thread's stacks for both locks; a cycle closing in the graph records
    the full loop with both witness stacks and fails
    ``assert_no_cycles``.
    """

    def __init__(self):
        # a RAW lock, created before lock_witness() patches the factories
        self._mu = threading.Lock()
        # thread id -> [(id(obj), name, side, stack), ...]
        self._held: Dict[int, List[tuple]] = {}
        # (held name, acquired name) -> (held stack, acquired stack)
        self.edges: Dict[Tuple[str, str], Tuple[Tuple[str, ...],
                                                Tuple[str, ...]]] = {}
        self.cycles: List[str] = []
        self.acquires = 0

    # -- hooks (called by rwlock + the patched stdlib factories) -------
    def note_acquire(self, obj, name: str, side: str) -> None:
        me = threading.get_ident()
        stack = _witness_stack()
        with self._mu:
            self.acquires += 1
            held = self._held.setdefault(me, [])
            for _hid, hname, _hside, hstack in held:
                if hname == name:
                    continue        # same family: re-entrant/per-instance
                if (hname, name) not in self.edges:
                    self.edges[(hname, name)] = (hstack, stack)
                    if self._reaches(name, hname):
                        self._record_cycle(hname, name)
            held.append((id(obj), name, side, stack))

    def note_release(self, obj) -> None:
        me = threading.get_ident()
        with self._mu:
            held = self._held.get(me, ())
            for i in range(len(held) - 1, -1, -1):
                if held[i][0] == id(obj):
                    del held[i]
                    return

    # -- cycle machinery (callers hold self._mu) -----------------------
    def _reaches(self, src: str, dst: str) -> bool:
        seen = {src}
        frontier = [src]
        while frontier:
            node = frontier.pop()
            for (a, b) in self.edges:
                if a == node and b not in seen:
                    if b == dst:
                        return True
                    seen.add(b)
                    frontier.append(b)
        return False

    def _path(self, src: str, dst: str) -> List[str]:
        prev: Dict[str, str] = {}
        frontier = [src]
        seen = {src}
        while frontier:
            node = frontier.pop(0)
            for (a, b) in self.edges:
                if a == node and b not in seen:
                    prev[b] = a
                    if b == dst:
                        path = [dst]
                        while path[-1] != src:
                            path.append(prev[path[-1]])
                        return list(reversed(path))
                    seen.add(b)
                    frontier.append(b)
        return [src, dst]           # pragma: no cover - _reaches said yes

    def _record_cycle(self, hname: str, name: str) -> None:
        loop = [hname] + self._path(name, hname)
        lines = [f"lock-order cycle observed: "
                 f"{' -> '.join([hname, name])} closes "
                 f"{' -> '.join(loop)}"]
        for a, b in zip(loop, loop[1:]):
            hstack, astack = self.edges.get((a, b), ((), ()))
            lines.append(f"  edge {a} -> {b}:")
            lines.append(f"    {a} held at: "
                         + (" <- ".join(hstack[:6]) or "<?>"))
            lines.append(f"    {b} acquired at: "
                         + (" <- ".join(astack[:6]) or "<?>"))
        self.cycles.append("\n".join(lines))

    def assert_no_cycles(self, what: str = "guarded region") -> None:
        if self.cycles:
            raise LockOrderError(
                f"{what}: {len(self.cycles)} lock-order cycle(s) "
                "observed:\n" + "\n".join(self.cycles[:4]))


class _WitnessedLock:
    """threading.Lock wrapper reporting outer acquire/release."""

    def __init__(self, inner, name: str):
        self._inner = inner
        self._name = name

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        ok = self._inner.acquire(blocking, timeout)
        if ok:
            w = _active_lock_witness
            if w is not None:
                w.note_acquire(self, self._name, "excl")
        return ok

    def release(self) -> None:
        w = _active_lock_witness
        if w is not None:
            w.note_release(self)
        self._inner.release()

    def locked(self) -> bool:
        return self._inner.locked()

    def __enter__(self):
        return self.acquire()

    def __exit__(self, *exc):
        self.release()
        return False

    def __getattr__(self, attr):
        # Condition() wires _release_save/_acquire_restore/_is_owned
        # straight to the inner lock: cv.wait() releases without a
        # witness note, so the held entry persists while the thread is
        # BLOCKED in wait — it records no edges there, harmless
        return getattr(self._inner, attr)


class _WitnessedRLock(_WitnessedLock):
    """Re-entrant variant: only depth 0<->1 transitions are noted."""

    def __init__(self, inner, name: str):
        super().__init__(inner, name)
        self._local = threading.local()

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        ok = self._inner.acquire(blocking, timeout)
        if ok:
            depth = getattr(self._local, "depth", 0)
            self._local.depth = depth + 1
            if depth == 0:
                w = _active_lock_witness
                if w is not None:
                    w.note_acquire(self, self._name, "excl")
        return ok

    def release(self) -> None:
        depth = getattr(self._local, "depth", 1)
        self._local.depth = depth - 1
        if depth == 1:
            w = _active_lock_witness
            if w is not None:
                w.note_release(self)
        self._inner.release()


#: the armed witness; wrappers outliving the block (daemon threads still
#: holding references) go quiet once this resets to None
_active_lock_witness: Optional[LockOrderWitness] = None


@contextlib.contextmanager
def lock_witness() -> Iterator[LockOrderWitness]:
    """Arm the runtime lock-order witness for the ``with`` block.

    Patches the ``threading.Lock``/``threading.RLock`` factories so
    locks *created inside the block* report outer acquisitions with
    their creation site as the graph node name (``Condition()`` picks up
    the patched RLock automatically), and arms the RWLock/Mutex hooks in
    utils/rwlock.py for the API locks and ``GBDT._trees_mu`` (those
    report at their own level, so their internals — and any lock created
    from rwlock.py or this module — stay unwrapped). Pre-existing stdlib
    locks are invisible; construct the server/registry under the witness.

    Usage::

        with lock_witness() as w:
            ... threads hammering serve()/deploy()/save_checkpoint() ...
        w.assert_no_cycles("16-thread serving")
    """
    global _active_lock_witness
    w = LockOrderWitness()
    saved_lock, saved_rlock = threading.Lock, threading.RLock

    def _site() -> str:
        f = sys._getframe(2)        # the factory's caller
        while f is not None and \
                os.path.basename(f.f_code.co_filename) == "threading.py":
            f = f.f_back
        if f is None:               # pragma: no cover - always has one
            return "<unknown>"
        return f"{os.path.basename(f.f_code.co_filename)}:{f.f_lineno}"

    def make_lock():
        site = _site()
        inner = saved_lock()
        if site.startswith(("rwlock.py", "guards.py")):
            return inner            # witnessed at the RWLock/Mutex level
        return _WitnessedLock(inner, f"Lock@{site}")

    def make_rlock():
        site = _site()
        inner = saved_rlock()
        if site.startswith(("rwlock.py", "guards.py")):
            return inner
        return _WitnessedRLock(inner, f"RLock@{site}")

    prev_rw = _rwlock.get_witness()
    threading.Lock = make_lock
    threading.RLock = make_rlock
    _rwlock.set_witness(w)
    _active_lock_witness = w
    try:
        yield w
    finally:
        _active_lock_witness = None
        _rwlock.set_witness(prev_rw)
        threading.Lock = saved_lock
        threading.RLock = saved_rlock


# ======================================================================
# resource-leak witness — the runtime half of tpulint R012, exactly as
# lock_witness is the runtime half of R011

class ResourceLeakError(AssertionError):
    """A guarded scope exited with live resources it did not enter with."""


#: thread-name prefixes of deliberate process-lifetime holds (anchored
#: in tpulint.allow on the static side): the shared device probe and the
#: multihost deadline watchdog, which outlives its scope BY DESIGN when
#: a deadline fires
_WITNESS_THREAD_EXEMPT = ("lgbm-tpu-device-probe", "lgbm-tpu-watchdog")

#: extra jit/program-cache size probes: callables returning an int; the
#: witness sums them into the ``jit_cache`` delta (drift's accumulator
#: factories register lazily below — register yours if you add a keyed
#: program cache, and make it pass R012's bound check first)
_witness_cache_probes: List[Callable[[], int]] = []


def register_witness_cache_probe(probe: Callable[[], int]) -> None:
    _witness_cache_probes.append(probe)


def _witness_threads() -> Dict[int, str]:
    return {t.ident: t.name for t in threading.enumerate()
            if t.is_alive() and t.ident is not None
            and not t.name.startswith(_WITNESS_THREAD_EXEMPT)}


def _witness_fds() -> Optional[frozenset]:
    try:
        return frozenset(os.listdir("/proc/self/fd"))
    except OSError:                 # pragma: no cover - non-procfs OS
        return None


def _witness_sessions() -> int:
    spans = sys.modules.get("lightgbm_tpu.obs.spans")
    return int(spans.active_sessions()) if spans is not None else 0


def _witness_jit_cache() -> int:
    total = 0
    # only modules ALREADY imported are probed: the witness must never
    # be the thing that pulls a subsystem (and its compiles) in
    drift = sys.modules.get("lightgbm_tpu.obs.drift")
    if drift is not None:
        for name in ("_bin_accum_fn", "_score_accum_fn"):
            fn = getattr(drift, name, None)
            if fn is not None and hasattr(fn, "cache_info"):
                total += int(fn.cache_info().currsize)
    for probe in _witness_cache_probes:
        try:
            total += int(probe())
        except Exception:           # noqa: BLE001 - probes must not kill
            pass
    return total


class ResourceWitness:
    """Snapshot of live resources at arm time; ``assert_no_leaks``
    re-snapshots (polling, releases are asynchronous — a shutdown
    serve_forever thread takes a poll interval to exit) and raises
    ResourceLeakError naming every thread/fd/session/cache delta."""

    def __init__(self):
        self._base_threads = _witness_threads()
        self._base_fds = _witness_fds()
        self._base_sessions = _witness_sessions()
        self._base_jit_cache = _witness_jit_cache()

    def deltas(self) -> Dict[str, object]:
        """Current growth over the baseline (leaked thread NAMES, new fd
        count, session and cache-size deltas); empty dict == clean."""
        out: Dict[str, object] = {}
        threads = _witness_threads()
        leaked = [name for ident, name in threads.items()
                  if ident not in self._base_threads]
        if leaked:
            out["threads"] = sorted(leaked)
        fds = _witness_fds()
        if fds is not None and self._base_fds is not None:
            grown = len(fds - self._base_fds) - \
                len(self._base_fds - fds)
            if grown > 0:
                out["fds"] = grown
        sessions = _witness_sessions() - self._base_sessions
        if sessions > 0:
            out["sessions"] = sessions
        cache = _witness_jit_cache() - self._base_jit_cache
        if cache > 0:
            out["jit_cache"] = cache
        return out

    def assert_no_leaks(self, what: str = "guarded scope",
                        settle_s: float = 5.0) -> None:
        deadline = time.monotonic() + float(settle_s)
        deltas = self.deltas()
        while deltas and time.monotonic() < deadline:
            time.sleep(0.05)
            deltas = self.deltas()
        if deltas:
            parts = []
            if "threads" in deltas:
                parts.append("live threads not in the baseline: "
                             + ", ".join(deltas["threads"]))
            if "fds" in deltas:
                parts.append(f"{deltas['fds']} more open fd(s)")
            if "sessions" in deltas:
                parts.append(f"{deltas['sessions']} still-entered trace "
                             "session(s)")
            if "jit_cache" in deltas:
                parts.append(f"retained-program caches grew by "
                             f"{deltas['jit_cache']} entries")
            raise ResourceLeakError(
                f"resource leak across {what}: " + "; ".join(parts)
                + ". Every acquisition must release on ALL paths "
                "(tpulint R012) — close/join/stop in a finally, or fix "
                "the owner's close() to be release-complete.")


@contextlib.contextmanager
def resource_witness() -> Iterator[ResourceWitness]:
    """Arm the resource-leak witness for the ``with`` block.

    The dynamic complement of ``scripts/tpulint resources`` (R012):
    snapshots live threads, open fds, entered trace sessions, and
    retained-program cache sizes at entry; ``assert_no_leaks`` proves
    the scope gave everything back. Warm caches and construct
    long-lived fixtures BEFORE arming — the witness measures the scope,
    not process history.

    Usage::

        with resource_witness() as w:
            server = PredictionServer(bst)
            ... kill/hang chaos ...
            server.close()
        w.assert_no_leaks("serving chaos drill")
    """
    yield ResourceWitness()
