"""From the profiler's trace to plain intervals, and the arithmetic on
them that the reducers share.

A *profile* here is a plain dict, so that the reducers can be tried on a
hand-made one:

    {"window": (start_s, end_s),
     "devices": {"/device:TPU:0": [(name, start_s, end_s), ...]},
     "host_spans": [(name, start_s, end_s), ...]}

``devices`` holds each chip's operations (the line ``XLA Ops`` of its
plane); an enclosing operation, such as a ``while``, spans its body's.
``host_spans`` are the benchmark's own spans, written into the same trace
by ``jax.profiler.TraceAnnotation``; the window runs from the first
span's start to the last span's end.
"""
import glob
import os

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


def op_name(text):
    """The profile prints an operation as its whole HLO line,
    ``%fused_split.17 = (...) custom-call(...)``: its name is what stands
    before the ``=``."""
    return text.split(" = ", 1)[0].lstrip("%")


def load(trace_dir, span_names):
    """The newest trace under ``trace_dir`` as a profile dict."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (op_name(e.name), e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend((e.name, e.start_ns * 1e-9,
                              (e.start_ns + e.duration_ns) * 1e-9)
                             for e in line.events if e.name in span_names)
    spans.sort(key=lambda s: s[1])
    if not spans:
        raise ValueError("the trace holds none of the benchmark's spans")
    return {"window": (spans[0][1], max(s[2] for s in spans)),
            "devices": devices, "host_spans": spans}


def clipped(events, window):
    lo, hi = window
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def union_seconds(events):
    """Seconds covered by at least one interval."""
    total, end = 0.0, float("-inf")
    for _, s, e in sorted(events, key=lambda ev: ev[1]):
        if s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def self_times(events):
    """[(name, self seconds)] per event: its duration less what the events
    it encloses cover. Events on one line nest and do not cross."""
    order = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    out, stack = [], []                 # stack: [name, end, self]
    for name, s, e in order:
        while stack and stack[-1][1] <= s:
            out.append((stack[-1][0], stack[-1][2]))
            stack.pop()
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    out.extend((name, own) for name, _, own in stack)
    return out


def gaps(events, window):
    """[(start, end)] inside the window that no interval covers."""
    out, at = [], window[0]
    for _, s, e in sorted(events, key=lambda ev: ev[1]):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if window[1] > at:
        out.append((at, window[1]))
    return out


def busy_seconds(profile):
    """Per device, the seconds of the window in which an operation ran."""
    return {name: union_seconds(clipped(events, profile["window"]))
            for name, events in profile["devices"].items()}


def span_at(profile, t):
    """The benchmark's span that covers instant ``t``: the innermost."""
    best = None
    for name, s, e in profile["host_spans"]:
        if s <= t < e and (best is None or s >= best[1]):
            best = (name, s)
    return best[0] if best else "between_spans"


def breakdown(profile, top=10):
    """The device operations with most self time, and the idle time by
    the host span that covered it with the longest single gaps."""
    ops, idle, single = {}, {}, []
    for events in profile["devices"].values():
        inside = clipped(events, profile["window"])
        for name, own in self_times(inside):
            ops[name] = ops.get(name, 0.0) + own
        for s, e in gaps(inside, profile["window"]):
            rest = e - s
            for name, lo, hi in profile["host_spans"]:
                inside_span = max(0.0, min(e, hi) - max(s, lo))
                idle[name + ".total"] = (idle.get(name + ".total", 0.0)
                                         + inside_span)
                rest -= inside_span
            idle["between_spans.total"] = (
                idle.get("between_spans.total", 0.0) + rest)
            single.append((span_at(profile, 0.5 * (s + e)) + ".longest",
                           e - s))
    n_dev = max(1, len(profile["devices"]))
    by_time = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    totals = sorted(idle.items(), key=lambda kv: -kv[1])
    single.sort(key=lambda kv: -kv[1])
    gaps_out = (totals + single)[:top]
    return {"device_ops": [[n, t / n_dev] for n, t in by_time],
            "idle_gaps": [[n, t / n_dev] for n, t in gaps_out]}
