"""Flight recorder: a bounded ring of structured events, dumped on death.

The r05 bench run died with nothing attributable on disk — the retry loop
had host-side prints, the device had a profiler nobody had armed, and the
post-mortem was archaeology over stderr. This module is the black box
that makes the NEXT failure ship its own post-mortem: production code
records cheap structured events into a bounded in-memory ring
(``tpu_flight_buffer`` entries; a dict append under a lock, no I/O, no
device access), and the ring is dumped as JSONL

* on ``TrainingInterrupted`` / any crash escaping engine.train,
* on a blown model hot-swap (serving/registry.py),
* at every checkpoint tick (so even a SIGKILL leaves the ring as of the
  last durable snapshot).

Events recorded by the shipped hooks: host spans (``span`` records:
``name``, ``t0``/``t1`` on ``time.perf_counter()``, ``parent``,
``iteration`` — obs/spans.py), iteration ticks with the update's
counters, compile events (phase-keyed and on the same ``t0``/``t1``
clock, via analysis/guards), persistent-cache hits/misses,
collective-program byte accounting (analysis/hlo.py, when
LGBM_TPU_COMM_ACCOUNTING=1), fault-injection fires, collective deadline /
transient-retry outcomes, checkpoint writes, serving swaps and worker
restarts, and the serving-quality plane (obs/drift.py): drift_flush
summaries, hysteresis-gated drift_detected / drift_cleared — the
machine-readable refit trigger of ROADMAP 4 — and slo_burn /
slo_burn_cleared transitions.

Dump location, first match wins: explicit ``path=``, the
``LGBM_TPU_FLIGHT_PATH`` env var, ``<dump_dir>/flight_<pid>.jsonl`` when
a dump dir was configured (engine.train points it at
``tpu_checkpoint_dir``), else ``lgbm_tpu_flight_<pid>.jsonl`` in the
working directory. The first line of a dump is a header record
(``event: "flight_dump"``) carrying the reason and ring stats; every
subsequent line is one event, oldest first — ``scripts/obs`` pretty-
prints either.
"""
from __future__ import annotations

import collections
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

#: default ring capacity when no config has been seen (tpu_flight_buffer)
DEFAULT_CAPACITY = 512

#: directory for the last-resort cwd fallback dump path. Empty = the
#: working directory (production default); the test suite points it at
#: a tmpdir so stray dumps can never pollute a checkout (conftest.py).
_FALLBACK_DIR = ""


def _process_rank() -> Optional[int]:
    """This process's rank when running multi-process, else None."""
    try:
        import jax
        if jax.process_count() > 1:
            return int(jax.process_index())
    except Exception:  # noqa: BLE001 - jax absent/uninitialized: single
        pass
    return None


class FlightRecorder:
    """Thread-safe bounded event ring with JSONL dumps."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self._mu = threading.Lock()
        self._capacity = int(capacity)
        self._ring: collections.deque = collections.deque(
            maxlen=max(self._capacity, 1))
        self._seq = 0
        self._dump_dir: Optional[str] = None

    # -- configuration -------------------------------------------------------
    @property
    def capacity(self) -> int:
        return self._capacity

    def configure(self, capacity: Optional[int] = None,
                  dump_dir: Optional[str] = None) -> None:
        """Resize the ring / set the default dump directory. Existing
        events are kept (newest-first retention on shrink). Capacity 0
        disables recording entirely."""
        with self._mu:
            if capacity is not None and int(capacity) != self._capacity:
                self._capacity = int(capacity)
                self._ring = collections.deque(
                    self._ring, maxlen=max(self._capacity, 1))
            if dump_dir:
                self._dump_dir = str(dump_dir)

    # -- recording (hot path) ------------------------------------------------
    def record(self, event: str, **fields: Any) -> None:
        """Append one event. Cheap by contract: a dict build and a locked
        deque append — safe from any thread, including serving workers.
        A zero-capacity recorder drops everything."""
        if self._capacity <= 0:
            return
        with self._mu:
            self._seq += 1
            rec = {"seq": self._seq, "t": round(time.time(), 6),
                   "event": event}
            rec.update(fields)
            self._ring.append(rec)

    def events(self) -> List[Dict[str, Any]]:
        with self._mu:
            return list(self._ring)

    def dropped(self) -> int:
        """Records the ring has let go since process start (or the last
        ``clear``). A reader that sums over the ring checks this first:
        with records dropped, a sum over what is left is a short sum."""
        with self._mu:
            return max(0, self._seq - len(self._ring))

    def clear(self) -> None:
        with self._mu:
            self._ring.clear()
            self._seq = 0

    # -- dumping -------------------------------------------------------------
    @staticmethod
    def _rank_suffix() -> str:
        """``_rankN`` on EVERY multihost rank (rank 0 included) — dump
        destinations are often shared (env path identical on every rank,
        checkpoint dir on a shared filesystem, pids colliding across
        containers), ranks must not clobber each other's post-mortems,
        and ``scripts/obs merge`` interleaves the per-rank files back
        into one cross-rank timeline by this tag. Single-host paths
        stay exactly as configured."""
        rank = _process_rank()
        return "" if rank is None else f"_rank{rank}"

    def _resolve_path(self, path: Optional[str]) -> str:
        rank = self._rank_suffix()
        if path:
            return str(path)
        env = os.environ.get("LGBM_TPU_FLIGHT_PATH", "")
        if env:
            if rank:
                root, ext = os.path.splitext(env)
                return f"{root}{rank}{ext}"
            return env
        if self._dump_dir:
            return os.path.join(self._dump_dir,
                                f"flight{rank}_{os.getpid()}.jsonl")
        return os.path.join(
            _FALLBACK_DIR, f"lgbm_tpu_flight{rank}_{os.getpid()}.jsonl")

    def dump(self, reason: str, path: Optional[str] = None,
             extra: Optional[Dict[str, Any]] = None) -> Optional[str]:
        """Write the ring as JSONL; returns the path, or None.

        Best-effort by design: a dump runs on failure paths (crash
        handlers, blown swaps) and must never raise — a post-mortem that
        kills the post-mortem writer helps nobody. A DISABLED recorder
        (capacity 0, the documented ``tpu_flight_buffer=0`` off switch)
        writes nothing at all: "0 disables" must not keep littering
        checkpoint dirs with header-only files at every tick."""
        if self._capacity <= 0:
            return None
        try:
            with self._mu:
                events = list(self._ring)
                dropped = max(0, self._seq - len(events))
            out = self._resolve_path(path)
            d = os.path.dirname(out)
            if d:
                os.makedirs(d, exist_ok=True)
            with open(out, "w", encoding="utf-8") as fh:
                header = {"event": "flight_dump", "reason": reason,
                          "t": round(time.time(), 6), "pid": os.getpid(),
                          "rank": _process_rank(),
                          "capacity": self._capacity,
                          "events": len(events),
                          "dropped": dropped}
                if extra:
                    header.update(extra)
                fh.write(json.dumps(header, default=str) + "\n")
                for rec in events:
                    fh.write(json.dumps(rec, default=str) + "\n")
            return out
        except Exception:  # noqa: BLE001 - never raise from a post-mortem
            return None


#: the process-wide recorder every shipped hook feeds
_RECORDER = FlightRecorder()


def recorder() -> FlightRecorder:
    return _RECORDER


def note(event: str, **fields: Any) -> None:
    """Record one event into the process recorder (the production hook)."""
    _RECORDER.record(event, **fields)


def configure(capacity: Optional[int] = None,
              dump_dir: Optional[str] = None) -> None:
    _RECORDER.configure(capacity=capacity, dump_dir=dump_dir)


def dump(reason: str, path: Optional[str] = None,
         extra: Optional[Dict[str, Any]] = None) -> Optional[str]:
    return _RECORDER.dump(reason, path=path, extra=extra)


def read_dump(path: str) -> List[Dict[str, Any]]:
    """Parse a dump (header + events). Tolerates a torn tail line — the
    dump may have raced a dying process; everything parseable is kept."""
    from .metrics import read_stream
    return read_stream(path)
