"""A count the run took from one of the program's counters (``counter``)."""


def reduce(run, counter):
    return run["counters"].get(counter)
