"""Per-rank runtime attribution: who is slow, who waits at the collective.

The reference's distributed learners account communication per rank by
hand around their socket/MPI ``Allreduce``/``ReduceScatter``
(``src/network/network.cpp``); on a TPU pod the collectives are inside
the compiled step, every rank runs the same program, and a single slow
host (preempted neighbor, thermal throttle, input stall) silently sets
the pace of the whole pod — the collectives make everyone wait for the
slowest arrival. This module makes that visible:

* **Sampled timers** (``tpu_rank_stats_every``): at the sampled
  iterations only, the booster brackets its update with
  ``block_until_ready`` (true step wall, collective wait included) and
  times one *collective arrival probe* — between samples nothing is
  timed, blocked, or published, so the steady-state 0-recompile /
  0-host-transfer guard holds off-sample by construction.
* **The probe**: multi-process ranks time their arrival skew at a
  coordination-service KV barrier (the same ``wait_at_barrier`` plumbing
  ``mesh.sync_barrier`` uses — works on every backend, including the
  2-process CPU dryrun); single-process meshes time a pre-compiled
  scalar ``psum`` over the device mesh instead. Either way the number is
  "how long did this rank wait for its slowest peer", the quantity the
  in-step ``psum``/``psum_scatter`` sites experience.
* **Publish + aggregate**: each rank publishes its per-sample payload
  (step seconds, per-iteration wall, collective wait, a heartbeat
  timestamp) through the coordination-service KV. Rank 0 gathers all
  ranks, computes median / p99 / max-over-ranks, and flags stragglers —
  a rank whose iteration wall exceeds ``tpu_straggler_factor`` x its
  peers' concurrent median (so a global slowdown flags nobody and a
  persistent straggler keeps being flagged; with no peers reporting,
  the rolling self-history median is the fallback base) — into the
  flight recorder and the metrics stream.
  A rank whose payload never arrives within the deadline is reported as
  ``rank_missing`` with its last-heartbeat age.

Flight dumps are rank-tagged (``..._rank<k>.jsonl``, obs/flight.py) and
``scripts/obs merge`` interleaves them into one cross-rank timeline.
"""
from __future__ import annotations

import json
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from . import flight

#: KV key namespace (coordination service); run-scoped below
_KV_PREFIX = "lgbm_tpu_rs"

#: rolling window of cross-rank medians the straggler compare uses
_WINDOW = 32

#: process-wide run counter: every rank constructs its RankStats in the
#: same program order (one per training run), so the counter agrees
#: across the pod and keeps two runs' KV keys from colliding
_run_seq = 0
_run_mu = threading.Lock()


def _median(xs: List[float]) -> float:
    s = sorted(xs)
    n = len(s)
    if n == 0:
        return 0.0
    mid = n // 2
    return s[mid] if n % 2 else 0.5 * (s[mid - 1] + s[mid])


def _p99(xs: List[float]) -> float:
    s = sorted(xs)
    if not s:
        return 0.0
    idx = max(0, min(len(s) - 1, int(-(-99 * len(s) // 100)) - 1))
    return s[idx]


class RankStats:
    """Sampled per-rank step/collective-wait attribution (one per run).

    ``kv``/``rank``/``world`` are injectable for tests; production wiring
    (boosting/gbdt.py ``_setup_train``) lets them default to the live
    jax process topology and coordination client.
    """

    def __init__(self, every: int, straggler_factor: float = 3.0,
                 mesh=None, deadline_s: float = 30.0, stream=None,
                 kv=None, rank: Optional[int] = None,
                 world: Optional[int] = None):
        global _run_seq
        self.every = max(1, int(every))
        self.factor = float(straggler_factor)
        self.deadline_s = float(deadline_s) if deadline_s > 0 else 30.0
        self._stream = stream
        if rank is None or world is None:
            try:
                import jax
                rank = jax.process_index() if rank is None else rank
                world = jax.process_count() if world is None else world
            except Exception:  # noqa: BLE001 - no backend: single rank
                rank, world = rank or 0, world or 1
        self.rank = int(rank)
        self.world = int(world)
        if kv is None and self.world > 1:
            from ..parallel.multihost import kv_client
            kv = kv_client()
        self._kv = kv
        with _run_mu:
            _run_seq += 1
            self._run = _run_seq
        self._mu = threading.Lock()
        self._last_t: Optional[float] = None
        self._last_iter: Optional[int] = None
        self._medians: deque = deque(maxlen=_WINDOW)
        self._last_seen: Dict[int, float] = {}
        self._latest: Dict[str, Any] = {}
        self.straggler_events = 0
        self._probe_fn = None
        self._probe_arg = None
        if self._kv is None and mesh is not None:
            self._build_probe(mesh)

    # -- collective arrival probe -------------------------------------------
    def _build_probe(self, mesh) -> None:
        """Pre-compile the scalar-psum probe OUTSIDE the steady-state
        region (construction time), so sampled probes lower nothing."""
        try:
            import jax
            import jax.numpy as jnp
            import numpy as np
            from ..parallel.mesh import row_sharding
            ndev = len(mesh.devices.ravel())
            if ndev <= 1:
                return
            arg = jax.device_put(np.ones(ndev, np.float32),
                                 row_sharding(mesh))
            fn = jax.jit(lambda x: jnp.sum(x))
            jax.block_until_ready(fn(arg))      # warm: compile here
            self._probe_fn, self._probe_arg = fn, arg
        except Exception:  # noqa: BLE001 - probe is optional attribution
            self._probe_fn = self._probe_arg = None

    def _barrier_step(self, iteration: int) -> None:
        """Arrive at the sample barrier for ``iteration`` (every rank
        calls this at the same sampled iterations; the KV timeout
        bounds a dead peer)."""
        self._kv.wait_at_barrier(
            f"{_KV_PREFIX}_{self._run}_bar_{iteration}",
            int(self.deadline_s * 1000))

    def _kv_arrival_wait(self, iteration: int) -> float:
        # DECLARED R009 tick site (allowlisted): the sampled
        # collective-wait timer — the KV barrier blocks by nature (no
        # device dispatch to block_until_ready on), and the elapsed wall
        # IS the measurement: how long this rank waited for its slowest
        # peer to arrive, the skew the in-step psum sites experience
        t0 = time.perf_counter()
        try:
            self._barrier_step(iteration)
        except Exception:  # noqa: BLE001 - dead peer: the timeout is the wait
            pass
        return time.perf_counter() - t0

    def _probe_wait(self) -> float:
        import jax
        t0 = time.perf_counter()
        jax.block_until_ready(self._probe_fn(self._probe_arg))
        return time.perf_counter() - t0

    def collective_wait(self, iteration: int) -> float:
        """Timed arrival at the collective, per the module docstring."""
        if self._kv is not None:
            return self._kv_arrival_wait(iteration)
        if self._probe_fn is not None:
            return self._probe_wait()
        return 0.0

    # -- sampling ------------------------------------------------------------
    def due(self, iteration: int) -> bool:
        return iteration > 0 and iteration % self.every == 0

    def sample_step(self, iteration: int, step_s: float) -> None:
        """One sampled tick: publish this rank's numbers; aggregate on
        rank 0. ``step_s`` is the block_until_ready-bracketed update
        wall the caller measured (basic.py, the anchored tick site)."""
        now = time.perf_counter()
        if self._last_t is not None and iteration > (self._last_iter or 0):
            iter_s = (now - self._last_t) / (iteration - self._last_iter)
        else:
            iter_s = step_s
        wait_s = self.collective_wait(iteration)
        payload = {
            "rank": self.rank, "iteration": int(iteration),
            "step_s": round(step_s, 6), "iter_s": round(iter_s, 6),
            "wait_s": round(wait_s, 6),
            # the heartbeat: rank 0 ages it when a later payload never
            # arrives (preempted peer vs merely slow)
            "hb": round(time.time(), 6),
        }
        flight.note("rank_sample", **payload)
        self._publish(payload)
        if self.rank == 0:
            self._aggregate(iteration, payload)
        # re-stamp AFTER the sampling overhead: the barrier wait and the
        # rank-0 KV gather must not leak into the next window's
        # iteration wall — the rank that WAITED for a straggler would
        # otherwise be flagged as the next sample's straggler
        self._last_t, self._last_iter = time.perf_counter(), iteration

    # -- KV plumbing ---------------------------------------------------------
    def _key(self, iteration: int, rank: int) -> str:
        return f"{_KV_PREFIX}/{self._run}/{iteration}/{rank}"

    def _publish(self, payload: Dict[str, Any]) -> None:
        if self._kv is None or self.rank == 0:
            return
        try:
            self._kv.key_value_set(self._key(payload["iteration"],
                                             self.rank),
                                   json.dumps(payload))
        except Exception:  # noqa: BLE001 - attribution must not kill training
            pass

    def _gather(self, iteration: int) -> Dict[int, Dict[str, Any]]:
        out = {}
        if self._kv is None:
            return out
        # ONE shared deadline for the whole gather, not a fresh one per
        # rank: with k preempted ranks a per-rank budget would stall
        # rank 0's sampled update k x deadline_s — long enough to trip
        # the engine's own collective watchdog on a self-inflicted wait
        budget_end = time.perf_counter() + self.deadline_s
        for r in range(1, self.world):
            remaining_ms = int((budget_end - time.perf_counter()) * 1000)
            if remaining_ms <= 0:
                break
            try:
                raw = self._kv.blocking_key_value_get(
                    self._key(iteration, r), remaining_ms)
                out[r] = json.loads(raw)
            except Exception:  # noqa: BLE001 - missing rank reported below
                continue
        return out

    # -- rank-0 aggregation --------------------------------------------------
    def _aggregate(self, iteration: int,
                   own: Dict[str, Any]) -> Dict[str, Any]:
        ranks: Dict[int, Dict[str, Any]] = {0: own}
        ranks.update(self._gather(iteration))
        now = time.time()
        for r, p in ranks.items():
            self._last_seen[r] = float(p.get("hb", now))
        missing = [r for r in range(self.world) if r not in ranks]
        for r in missing:
            age = now - self._last_seen.get(r, now)
            flight.note("rank_missing", rank=r, iteration=iteration,
                        heartbeat_age_s=round(age, 3))
        # the attribution quantity: the slowest of (blocked step wall,
        # per-iteration loop wall) — host-side stalls between updates
        # (input pipeline, a hung callback) pace the pod just as surely
        # as a slow device step
        slow = {r: max(float(p.get("step_s", 0.0)),
                       float(p.get("iter_s", 0.0)))
                for r, p in ranks.items()}
        med = _median(list(slow.values()))
        rolling = _median(list(self._medians) + [med])
        self._medians.append(med)
        # a rank is a straggler when it exceeds the factor x its PEERS'
        # concurrent median — peers, not the pod median, so a global
        # slowdown (shared input stall) flags nobody, and a PERSISTENT
        # straggler keeps getting flagged (a rolling pod median would
        # absorb its inflated samples and go quiet after a few ticks).
        # With no peers reporting (single process, or every other rank
        # missing) the rolling self-history median is the fallback base,
        # so a single-process hang still shows.
        stragglers = []
        for r, s in slow.items():
            others = [v for q, v in slow.items() if q != r]
            base = _median(others) if others else rolling
            if base > 0.0 and s > self.factor * base:
                stragglers.append(r)
        stragglers.sort()
        agg = {
            "iteration": int(iteration),
            "ranks_reporting": len(ranks),
            "world": self.world,
            "median_s": round(med, 6),
            "rolling_median_s": round(rolling, 6),
            "p99_s": round(_p99(list(slow.values())), 6),
            "max_s": round(max(slow.values()), 6),
            "max_rank": max(slow, key=lambda r: slow[r]),
            "wait_median_s": round(_median(
                [float(p.get("wait_s", 0.0)) for p in ranks.values()]), 6),
            "wait_max_s": round(max(
                float(p.get("wait_s", 0.0)) for p in ranks.values()), 6),
            "stragglers": stragglers,
            "missing": missing,
        }
        with self._mu:
            self._latest = dict(agg)
            self._latest["per_rank"] = {str(r): ranks[r] for r in ranks}
        for r in stragglers:
            self.straggler_events += 1
            flight.note("straggler", rank=r, iteration=iteration,
                        slow_s=round(slow[r], 6),
                        rolling_median_s=round(rolling, 6),
                        factor=self.factor)
        if self._stream is not None:
            self._stream.emit("rank_stats", **agg)
        return agg

    # -- consumers -----------------------------------------------------------
    def latest_tree(self) -> Dict[str, Any]:
        """The last aggregate (rank 0) or this rank's config — the
        training MetricsServer's ``rank_stats`` subtree."""
        with self._mu:
            out = dict(self._latest)
        out.setdefault("world", self.world)
        out["rank"] = self.rank
        out["every"] = self.every
        out["straggler_factor"] = self.factor
        out["straggler_events"] = self.straggler_events
        return out
