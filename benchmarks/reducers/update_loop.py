"""What the update loop cost the host in the window, per iteration, from
the program's own records (records.py):

``host_s``   the ``iteration`` spans (all of ``Booster.update()``) inside
             the benchmark's window spans, less their ``flush_trees``
             children (the wait for the device): python and dispatch time
a counter    the mean of that counter (``dispatches``, ``host_syncs``,
             ``d2h_bytes``) over the window's ``iteration`` events
"""
from .. import records


def reduce(run, what, window="update"):
    rec = records.load(run)
    updates = records.intervals(run, window)
    if rec is None or not updates:
        return None
    if what == "host_s":
        spans = records.within(rec["spans"], updates)
        its = [s for s in spans if s[0] == "iteration"]
        if not its:
            return None
        waits = [s for s in spans
                 if s[0] == "flush_trees" and s[3] == "iteration"]
        return records.seconds_outside(its, waits) / len(its)
    ticks = [e for e in rec["iterations"]
             if any(s <= e["t1"] <= t for s, t in updates) and what in e]
    if not ticks:
        return None
    return sum(e[what] for e in ticks) / len(ticks)
