"""The program's own records, read in-process, and the interval
arithmetic the reducers on them share.

The program writes what it does into one ring (``lightgbm_tpu/obs/
flight.py``): a ``span`` record per host span (``name``, ``t0``, ``t1``,
``parent``, ``iteration``), a ``compile`` record per jaxpr trace,
lowering, backend compile and persistent-cache retrieval (``kind``,
``t0``, ``t1``), an ``iteration`` record per ``Booster.update()`` with the
update's counters (``t1``). ``t0`` and ``t1`` are on
``time.perf_counter()``, the clock of the benchmark's own spans
(``run["spans"]``), so a reducer picks the records of the first round or
of the window by the benchmark's intervals. The reducers run in the
program's process after the booster is freed; the ring is module state
and is still there.

*Records* here are a plain dict, so that the reducers can be tried on a
hand-made one (``run["records"]``; the harness gives none and the ring is
read):

    {"spans": [(name, t0, t1, parent, iteration), ...],
     "compiles": [(kind, t0, t1), ...],
     "iterations": [{"t1": ..., "dispatches": ..., ...}, ...]}

``load`` gives None where there is nothing sound to read: a program
without such records (a commit before them), or a ring that has dropped
records since the process started (a sum over the rest would be short).
"""
from .trace import union_seconds


def load(run):
    if "records" in run:
        return run["records"]
    from lightgbm_tpu.obs import flight
    ring = flight.recorder()
    dropped = getattr(ring, "dropped", None)
    if dropped is None or dropped():
        return None
    events = ring.events()
    spans = [(e["name"], e["t0"], e["t1"], e.get("parent"),
              e.get("iteration")) for e in events if e["event"] == "span"]
    if not spans:
        return None
    return {"spans": spans,
            "compiles": [(e["kind"], e["t0"], e["t1"]) for e in events
                         if e["event"] == "compile" and "t1" in e],
            "iterations": [e for e in events
                           if e["event"] == "iteration" and "t1" in e]}


def intervals(run, name):
    """The benchmark's own spans of that name: [(t0, t1)]."""
    return [(s, e) for n, s, e in run["spans"] if n == name]


def within(records, spans):
    """The span or compile records that lie inside one of ``spans``."""
    return [r for r in records
            if any(s <= r[1] and r[2] <= e for s, e in spans)]


def seconds(records):
    """Seconds covered by at least one of the records: nested records
    count once."""
    return union_seconds([(r[0], r[1], r[2]) for r in records])


def seconds_outside(records, others):
    """Seconds that ``records`` cover and ``others`` do not."""
    return seconds(list(records) + list(others)) - seconds(others)
