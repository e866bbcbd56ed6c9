"""Device self time of the operations whose name matches ``pattern`` (a
regular expression; ``outside`` true takes the others instead), in the
traced window, averaged over chips and divided by the iterations."""
import re

from ..trace import clipped, self_times


def seconds(run, pattern, outside=False):
    profile = run.get("profile")
    if not profile or not profile["devices"]:
        return None
    hit = re.compile(pattern)
    total, seen = 0.0, False
    for events in profile["devices"].values():
        for name, own in self_times(clipped(events, profile["window"])):
            if bool(hit.search(name)) != bool(outside):
                total += own
                seen = True
    return total / len(profile["devices"]) if seen else None


def reduce(run, pattern, outside=False):
    total = seconds(run, pattern, outside)
    if total is None or not run["iterations"]:
        return None
    return total / run["iterations"]
