"""How unevenly the chips share the operations whose name matches
``pattern``: over the chips, (largest - smallest) of their self seconds
in the traced window over the chips' mean, in percent. One chip, or none
that ran such an operation, gives nothing."""
import re

from ..trace import clipped, self_times


def reduce(run, pattern):
    profile = run.get("profile")
    if not profile or len(profile["devices"]) < 2:
        return None
    hit = re.compile(pattern)
    per_chip = [sum(own for name, own in
                    self_times(clipped(events, profile["window"]))
                    if hit.search(name))
                for events in profile["devices"].values()]
    mean = sum(per_chip) / len(per_chip)
    if mean <= 0.0:
        return None
    return 100.0 * (max(per_chip) - min(per_chip)) / mean
