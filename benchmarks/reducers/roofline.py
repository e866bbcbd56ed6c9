"""A kernel's share of its roofline, in percent: the least time the chip
could take for the work the window's trees stand for (work.py, against
peaks.json: the larger of operations over peak FLOP/s and bytes over peak
bytes/s), over the device seconds of the operations matching ``pattern``.
Silent where the trace shows no such operation."""
from ..work import least_seconds
from .device_self_time import seconds


def reduce(run, pattern):
    took = seconds(run, pattern)
    if not took or not run.get("work"):
        return None
    least, _ = least_seconds(run["work"], run["peak"])
    return 100.0 * least / took
