"""Seconds of one phase of ``Booster.update()`` in the window, per
iteration, from the program's own records (records.py): the program's
host spans named ``span`` that lie inside the benchmark's window spans,
less their children named in ``less`` (``"*"``: less every span entered
directly inside one of them, so that ``span`` = ``iteration`` gives the
time of an update that no span names), over the window's ``iteration``
spans.

``flush_trees`` less ``step_wait`` is the copy of a tree's arrays to the
host without the wait for the step; ``iteration`` less ``"*"`` is the
closure check of the update's spans: with ``step_args``,
``step_dispatch``, ``decode_trees``, ``update_tick`` and the gradient
and bag spans it adds up to ``entry.host_s_per_iter``.

Silent where there is nothing sound to read: no records or a ring that
dropped (records.py), no ``iteration`` span in the window, or a program
that does not name the update's phases yet (:func:`window_spans`).
"""
from .. import records

#: a program that names the update's phases enters this span in every
#: update, once per tree; one whose window holds none is a commit before
#: them, and reads as nothing, not as zero seconds
MARKER = "step_args"


def window_spans(run, window="update"):
    """(the program's span records inside the benchmark's ``window``
    spans, the ``iteration`` records among them), or None where a
    reducer of the update's phases has nothing sound to read."""
    rec = records.load(run)
    updates = records.intervals(run, window)
    if rec is None or not updates:
        return None
    spans = records.within(rec["spans"], updates)
    its = [s for s in spans if s[0] == "iteration"]
    if not its or not any(s[0] == MARKER and s[3] == "iteration"
                          for s in spans):
        return None
    return spans, its


def reduce(run, span, less=(), window="update"):
    found = window_spans(run, window)
    if found is None:
        return None
    spans, its = found
    mine = [s for s in spans if s[0] == span]
    if less == "*":
        inside = [s for s in spans if s[3] == span]
    else:
        inside = [s for s in spans if s[0] in less and s[3] == span]
    return records.seconds_outside(mine, inside) / len(its)
