"""Seconds the benchmark's own clock spent in one of its spans (``span``),
summed over the run. Read from the host clock, traced or not."""


def reduce(run, span):
    took = [e - s for name, s, e in run["spans"] if name == span]
    return sum(took) if took else None
