"""Device self time, per iteration, of the operations that run inside a
host span named ``span`` before that span's first operation matching
``pattern`` (a regular expression): what the device does in an update
before the step's first kernel call. With a second program ahead of the
step (the ranking gradients) that is this program, and the step's few
operations ahead of its first kernel call with it; the profile keeps
operation names only, and XLA's own (``sort``, ``fusion``, ``scatter``)
say nothing of the program they belong to.

An operation counts where it starts inside the span and ends no later
than the first match starts; an enclosing operation that ends later (a
``while`` around the kernel calls) does not count, nor does anything in a
span that holds no match. Averaged over chips and divided by the
iterations; None where no span holds a match."""
import re

from ..trace import clipped, self_times


def reduce(run, pattern, span="update"):
    profile = run.get("profile")
    if not profile or not profile["devices"] or not run["iterations"]:
        return None
    hit = re.compile(pattern)
    spans = [(s, e) for n, s, e in profile["host_spans"] if n == span]
    total, seen = 0.0, False
    for events in profile["devices"].values():
        events = clipped(events, profile["window"])
        for lo, hi in spans:
            inside = [ev for ev in events if lo <= ev[1] < hi]
            first = min((s for n, s, _ in inside if hit.search(n)),
                        default=None)
            if first is None:
                continue
            seen = True
            total += sum(own for _, own in self_times(
                [ev for ev in inside if ev[2] <= first]))
    if not seen:
        return None
    return total / len(profile["devices"]) / run["iterations"]
