"""lightgbm_tpu.obs — unified telemetry: spans, flight recorder, metrics.

One observability subsystem spanning training, collectives, and serving
(the reproduction's answer to the reference's ``USE_TIMETAG``
``Common::Timer`` registry plus the ops tooling it never had):

* :mod:`.spans` — phase-named spans (``span("hist_build")``):
  ``jax.named_scope`` under trace, so DEVICE programs carry the phase
  names in their op metadata; on the host one ``span`` record in the
  flight ring (name, t0, t1, parent, iteration) + the phase table +
  a ``TraceAnnotation``. Set-up and the update loop record always, the
  serving ticks inside a ``trace_session``, which owns the
  ``tpu_trace_dir``/``tpu_trace_mode`` knobs.
* :mod:`.flight` — bounded ring of structured events (host spans,
  iteration ticks with the update's counters, phase-keyed compile
  events on the spans' clock, collective byte accounting, fault fires,
  deadline/retry outcomes), dumped as JSONL on ``TrainingInterrupted``,
  on a blown hot-swap, and at checkpoint ticks (``tpu_flight_buffer``).
  Read in-process by the benchmark's reducers.
* :mod:`.metrics` — per-iteration JSONL stream (``tpu_metrics_path``;
  bench.py derives its BENCH-row counters from it) and a pull-based
  Prometheus-text endpoint served from PredictionServer
  (``--metrics-port`` on ``scripts/serve``). stdlib HTTP, no new deps.
* :mod:`.summarize` — ``scripts/obs``: per-phase host time share, the
  per-iteration table, compile / collective totals from any of the
  above artifacts (the ``Common::Timer::Print`` analogue), jax-free;
  subcommands ``merge`` (cross-rank flight-dump timeline) and ``drift``.
* :mod:`.tracing` — the span taxonomy (the names), jax-free. Device
  time is the benchmark's to reduce (``benchmarks/trace.py``).
* :mod:`.ranks` — per-rank runtime attribution: sampled step /
  collective-wait timers published over the coordination-service KV,
  rank-0 median/p99/max aggregation + straggler flags
  (``tpu_rank_stats_every`` / ``tpu_straggler_factor``).
* :mod:`.ledger` — scaling-efficiency ledger: per-chip throughput
  efficiency vs the 1-chip row + measured-vs-modeled comm accounting
  recorded into MULTICHIP/COMM_ACCOUNTING.json (bench BENCH_LEDGER=1).
* :mod:`.drift` — serving-quality observability (ROADMAP 4's "observe"
  pillar): on-device per-feature bin-occupancy + raw-margin drift
  monitors flushed on a cadence (``tpu_drift_flush_every``) with
  hysteresis-gated PSI ``drift_detected`` events, per-request latency
  attribution histograms, and the multi-window SLO burn-rate tracker
  (``tpu_serve_slo_ms`` / ``tpu_serve_slo_target``). Module level is
  numpy-only; jax loads lazily inside the device accumulate builders.

This ``__init__`` stays jax-free too (``spans`` and ``ranks`` are the
only jax-touching modules and are imported lazily), so ``scripts/obs``
runs without a backend.
"""
from __future__ import annotations

from . import drift, flight, ledger, metrics, summarize, tracing  # noqa: F401

__all__ = ["drift", "flight", "ledger", "metrics", "summarize", "tracing",
           "spans", "ranks", "configure"]


def __getattr__(name):
    # lazy: spans/ranks import jax; offline consumers (scripts/obs)
    # never pay. importlib (not `from . import`) — the from-form probes
    # this very __getattr__ before importing, which recurses
    if name in ("spans", "ranks"):
        import importlib
        return importlib.import_module(f".{name}", __name__)
    raise AttributeError(name)


def configure(config) -> "metrics.MetricsStream | None":
    """Arm the process-wide telemetry from a resolved config: flight-ring
    capacity (``tpu_flight_buffer``), default dump dir
    (``tpu_checkpoint_dir``), the global phase-keyed compile listener,
    and the ``tpu_metrics_path`` stream (returned; None when unset).

    Called from ``GBDT.__init__`` — one call per booster, idempotent."""
    cap = config.get("tpu_flight_buffer", None)
    dump_dir = str(config.get("tpu_checkpoint_dir", "") or "") or None
    flight.configure(capacity=None if cap is None else int(cap),
                     dump_dir=dump_dir)
    from ..analysis import guards
    guards.install_global_compile_listener()
    # multihost: tpu_metrics_path is typically a shared filesystem (the
    # same deployment contract as tpu_checkpoint_dir, where only process
    # 0 writes) — every rank opening the one stream would truncate and
    # interleave it. Rank 0 writes; the others run streamless.
    import jax
    if jax.process_count() > 1 and jax.process_index() != 0:
        return None
    return metrics.stream_for(config.get("tpu_metrics_path", ""))
