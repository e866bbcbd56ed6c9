"""The chip's idle time in the traced window, by the program span the
host was in, per iteration.

The program's spans are records of its flight ring on
``time.perf_counter()`` (records.py); the device's operations are on the
profiler's clock. The benchmark's own ``update`` spans are on both:
``run["spans"]`` holds them as the host's clock read them,
``run["profile"]["host_spans"]`` as the trace holds them. The offset
between the two clocks is the median of trace time less host time over
those spans' starts and ends (:func:`ring_to_trace`); where the two
lists differ in length, or the offsets spread by more than
``MAX_SPREAD_S``, the clocks cannot be matched and nothing is read.

Each idle gap of a device inside the window (``trace.gaps`` on the
clipped operations) is cut at the edges of the program's spans, moved
onto the trace's clock, and each piece goes to the innermost span that
covers it (the one that started last; ``trace.span_at``), or to no
span. ``reduce`` gives the seconds under the spans named in ``spans``
(``rest=True``: under every other span or none: the window's idle that
those names do not hold), a chip's mean, over the iterations. The four metrics that read
this partition the window's idle: they add up to ``device.idle_pct`` x
window / iterations.

Silent where update_phase is (no records, a ring that dropped, a program
that does not name the update's phases), without a profile, and where
the clocks cannot be matched.
"""
import json
import statistics

from ..trace import clipped, gaps, span_at
from .update_phase import window_spans

#: offsets between the clocks, over one window's update spans, further
#: apart than this do not give one offset
MAX_SPREAD_S = 0.2e-3


def ring_to_trace(run, window="update"):
    """{"offset_s", "spread_s", "updates"}: what to add to a time on the
    host's clock to have it on the trace's; None where the benchmark's
    window spans are not the same number on both."""
    host = [(s, e) for n, s, e in run["spans"] if n == window]
    traced = [(s, e) for n, s, e in run["profile"]["host_spans"]
              if n == window]
    if not host or len(host) != len(traced):
        return None
    offsets = [t - h for hs, ts in zip(sorted(host), sorted(traced))
               for h, t in zip(hs, ts)]
    return {"offset_s": statistics.median(offsets),
            "spread_s": max(offsets) - min(offsets), "updates": len(host)}


def idle_by_span(profile, spans):
    """{span name: idle seconds, summed over devices}: each gap of each
    device cut at the edges of ``spans`` ((name, start, end) on the
    trace's clock), each piece under its innermost span
    (``trace.span_at``: ``between_spans`` where none covers it)."""
    lo, hi = profile["window"]
    program = {"host_spans": [s for s in spans if s[2] > lo and s[1] < hi]}
    edges = sorted({t for _, s, e in program["host_spans"] for t in (s, e)})
    out = {}
    for events in profile["devices"].values():
        for g0, g1 in gaps(clipped(events, profile["window"]),
                           profile["window"]):
            cuts = [g0] + [t for t in edges if g0 < t < g1] + [g1]
            for a, b in zip(cuts, cuts[1:]):
                name = span_at(program, 0.5 * (a + b))
                out[name] = out.get(name, 0.0) + (b - a)
    return out


def window_idle(run):
    """The window's idle seconds by program span, read once a run and
    kept on it (four metrics read it); the clocks' offset and spread go
    to the run's earlier output lines. None where nothing can be read."""
    if "idle_by_span" not in run:
        run["idle_by_span"] = None
        profile, found = run.get("profile"), window_spans(run)
        if profile and profile["devices"] and found is not None:
            clocks = ring_to_trace(run)
            print(json.dumps({"ring_to_trace": clocks}), flush=True)
            if clocks is not None and clocks["spread_s"] <= MAX_SPREAD_S:
                off = clocks["offset_s"]
                run["idle_by_span"] = idle_by_span(
                    profile, [(n, s + off, e + off)
                              for n, s, e, _, _ in found[0]])
    return run["idle_by_span"]


def reduce(run, spans, rest=False):
    idle = window_idle(run)
    if idle is None or not run["iterations"]:
        return None
    took = sum(t for name, t in idle.items() if (name in spans) != rest)
    return took / len(run["profile"]["devices"]) / run["iterations"]
