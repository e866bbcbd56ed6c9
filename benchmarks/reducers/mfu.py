"""The whole step's share of the chip's peak, in percent: the window's
histogram contraction count (work.py) over the traced window's length
times the bf16 peak (peaks.json) times the chips."""


def reduce(run):
    profile = run.get("profile")
    if not profile or not profile["devices"] or not run.get("work"):
        return None
    lo, hi = profile["window"]
    chips = len(profile["devices"])
    return 100.0 * run["work"]["flops"] / (
        (hi - lo) * chips * run["peak"]["bf16_flops_per_s"])
