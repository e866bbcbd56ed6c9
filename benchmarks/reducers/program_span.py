"""Seconds the program spent in its host spans named ``span`` (with
``parent``: only those entered directly inside a span of that name),
summed over the run. Read from the program's own records (records.py);
silent where the program keeps none."""
from .. import records


def reduce(run, span, parent=None):
    rec = records.load(run)
    if rec is None:
        return None
    took = [t1 - t0 for name, t0, t1, par, _ in rec["spans"]
            if name == span and (parent is None or par == parent)]
    return sum(took) if took else None
