"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

runs one cell once on the machine it is started on and prints, as the last
line of standard output, one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` with ``--trace 1``),
then ``compared``: each number the comparison read, beside its limit.
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result. README.md says how the files beside this
one make up a cell.
"""
import time

PROCESS_START = time.perf_counter()

import argparse        # noqa: E402
import contextlib      # noqa: E402
import importlib       # noqa: E402
import json            # noqa: E402
import logging         # noqa: E402
import os              # noqa: E402
import shutil          # noqa: E402
import sys             # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
PACKAGE = os.path.basename(HERE)
SPAN_NAMES = ("update",)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_cell(name):
    cell = load_json("workloads", name + ".json")
    return cell, load_json("configs", cell["config"] + ".json")


def part(name):
    """A module of the benchmark, by its dotted name under this directory
    (the repository's root is on ``sys.path``: ``main`` puts it there)."""
    return importlib.import_module(f"{PACKAGE}.{name}")


def module(kind, name):
    return part(f"{kind}.{name}")


class Env:
    """What the harness lends a traffic kind: the clock's spans, the
    profiler around the window, notes for the earlier output lines."""

    def __init__(self, trace_dir):
        self.trace_dir = trace_dir
        self.spans, self.notes = [], {}
        self.setup_s = self.memory_peak = None
        self._tracing = False

    def generator(self, name):
        return module("generators", name)

    @contextlib.contextmanager
    def span(self, name):
        import jax
        t0 = time.perf_counter()
        with (jax.profiler.TraceAnnotation(name) if self._tracing
              else contextlib.nullcontext()):
            yield
        self.spans.append((name, t0, time.perf_counter()))

    def note(self, key, value):
        self.notes[key] = value
        print(json.dumps({key: value}, default=str), flush=True)

    def setup_done(self):
        self.setup_s = time.perf_counter() - PROCESS_START

    @contextlib.contextmanager
    def window(self, trace):
        if not trace:
            yield
            return
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self._tracing = True
        try:
            yield
        finally:
            self._tracing = False
            jax.profiler.stop_trace()

    def read_memory_peak(self):
        import jax
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.local_devices()]
        self.note("memory_peak_bytes_per_device", peaks)
        self.memory_peak = max(peaks)


class CacheMisses(logging.Handler):
    """Names of the programs the persistent compile cache did not hold,
    from jax's own log lines: the counters give only their number."""

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.names = []

    def emit(self, record):
        if "CACHE MISS" in str(record.msg) and record.args:
            self.names.append(str(record.args[0]))
        elif record.levelno >= logging.WARNING:
            print(record.getMessage(), file=sys.stderr)

    @classmethod
    def listen(cls):
        handler = cls()
        log = logging.getLogger("jax._src.compiler")
        log.addHandler(handler)
        log.setLevel(logging.DEBUG)
        log.propagate = False
        return handler


def per_layer_metrics(names, run):
    out = {}
    for name in names:
        spec = load_json("metrics", name + ".json")
        value = module("reducers", spec["reducer"]).reduce(
            run, **spec.get("args", {}))
        if value is not None:
            out[name] = {"value": value, "unit": spec["unit"]}
    return out


def drive(name, seed, seconds, trace, scratch=None, files=None):
    """Drive the cell's traffic once. ``files`` is a (cell, configuration)
    pair to run in place of the cell's own files (the tests' toy sizes,
    the control's parameters). Returns (env, what the traffic returned,
    cell, configuration)."""
    import jax
    cell, config = files or load_cell(name)
    scratch = scratch or os.path.join(ROOT, ".bench_scratch")
    env = Env(os.path.join(scratch, "trace", name))
    env.note("device_kind", jax.devices()[0].device_kind)
    misses = CacheMisses.listen()
    out = module("traffic", cell["traffic"]).run(
        env, cell, config, seed, seconds, trace)
    out["spans"] = env.spans
    env.note("compile_cache_misses", misses.names)
    env.note("spans_s", [[n, round(e - s, 3)] for n, s, e in env.spans
                         if n not in SPAN_NAMES])
    return env, out, cell, config


def run_cell(name, seed, seconds, trace, bench, scratch=None, files=None):
    """One run of one cell, past the look for a chip. ``bench`` is
    BENCHMARK.json's content. Returns the result line's object."""
    import jax
    correct, tracing, work = part("correct"), part("trace"), part("work")
    env, out, cell, config = drive(name, seed, seconds, trace, scratch,
                                   files)
    dev = jax.devices()[0]

    if trace:
        peaks = load_json("peaks.json")
        if dev.device_kind not in peaks:
            raise KeyError(f"no peaks for device_kind {dev.device_kind!r}")
        out["peak"] = peaks[dev.device_kind]
        out["profile"] = tracing.load(env.trace_dir, SPAN_NAMES)
        trees = correct.parse_trees(out["produced"]["model_text"])
        first = out["produced"]["first_window_tree"]
        out["work"] = work.window_work(
            trees[first:first + out["iterations"]],
            config["sizes"]["features"], int(config["params"]["max_bin"]))
        env.note("work", out["work"])
        wanted = [m["name"] for m in bench["per_layer"]
                  if name in m.get("workloads", [name])]
        metrics = per_layer_metrics(wanted, out)
    else:
        values = dict(out["end_to_end"], setup_s=env.setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in bench["end_to_end"]
                   if name in m.get("workloads", [name])
                   and values.get(m["name"]) is not None}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": env.memory_peak}
    result = {"attempted": out["attempted"], "failed": out["failed"],
              "metrics": metrics, "device": device}
    if trace:
        busy = tracing.busy_seconds(out["profile"])
        lo, hi = out["profile"]["window"]
        device["busy_s"] = sum(busy.values()) / max(1, len(busy))
        device["window_s"] = hi - lo
        result["breakdown"] = tracing.breakdown(out["profile"])
        shutil.rmtree(env.trace_dir, ignore_errors=True)
    out.pop("profile", None)

    # the comparison comes last: the window is closed, the peak is read,
    # the program's state is freed
    t0 = time.perf_counter()
    readings = correct.reference_readings(out["produced"], out["data"],
                                          config)
    ok, rows = correct.judge(readings, cell["limits"])
    env.note("reference_s", round(time.perf_counter() - t0, 2))
    env.note("read_and_not_compared", {n: v for n, v in readings.items()
                                       if n not in cell["limits"]})
    result = {"correct": bool(ok and out["failed"] == 0
                              and out["iterations"] > 0), **result}
    result["compared"] = {n: {"value": v, "limit": lim}
                          for n, v, lim in rows}
    for n, v, lim in rows:
        print(f"compared {n}: {v} (limit {lim})", file=sys.stderr)
    sys.stderr.flush()
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell, _ = load_cell(args.workload)
    try:
        import lightgbm_tpu  # noqa: F401  (the system under test)
    except ImportError as err:
        print(f"benchmark: the program is not in this checkout ({err})",
              file=sys.stderr)
        return 3
    import jax
    devices = jax.devices()     # a backend that cannot start raises here
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"benchmark: cell {args.workload} asks for {cell['chips']} TPU "
              f"chip(s); JAX found {len(devices)} x {devices[0].platform}",
              file=sys.stderr)
        return 2

    from lightgbm_tpu.analysis import guards
    from lightgbm_tpu.utils.log import register_logger
    logger = logging.getLogger("benchmark.program")
    logger.addHandler(logging.StreamHandler(sys.stderr))
    logger.setLevel(logging.INFO)
    logger.propagate = False
    register_logger(logger)
    guards.configure_compile_cache(guards.checkout_cache_dir())
    print(json.dumps({"compile_cache_dir":
                      jax.config.jax_compilation_cache_dir}),
          file=sys.stderr, flush=True)

    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), bench)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
