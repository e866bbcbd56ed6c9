"""Share of the traced window, in percent, in which no operation ran on
the chip: 1 - union of the operations' intervals over the window, averaged
over chips."""
from ..trace import busy_seconds


def reduce(run):
    profile = run.get("profile")
    if not profile or not profile["devices"]:
        return None
    lo, hi = profile["window"]
    busy = busy_seconds(profile)
    return 100.0 * (1.0 - sum(busy.values()) / len(busy) / (hi - lo))
