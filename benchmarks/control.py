"""The control and the planted faults, read on the chip at a cell's size.

    python3 benchmarks/control.py --workload <cell> --seeds 11,12,13

Not part of a benchmark run. For each seed it drives the cell's own
traffic through a window of three iterations and prints, one JSON line a
seed, every number the comparison reads:

``sound``        the program as the configuration states (a lower reading)
``control``      the plain reference in the program's place, its gradients
                 and leaf sums taken in bfloat16 where the configuration
                 states float32
``program_control``  where the cell's file names ``control_params``: the
                 program run again with its own lower-precision path on
``half_batch``   the reference in the program's place with every other
                 row left out of gradients and sums
``altered_leaf`` one leaf value of the window's first tree changed by 1%

PERF.md keeps the readings; the limits in workloads/*.json were set from
them.
"""
import argparse
import copy
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import correct, run as bench_run  # noqa: E402


class HalfBatch:
    """An objective whose gradients leave every other row out."""

    def __init__(self, objective):
        self.objective = objective

    def prepare(self, label, group, params):
        return self.objective.prepare(label, group, params)

    def gradients(self, state, score, dtype):
        import jax.numpy as jnp
        g, h = self.objective.gradients(state, score, dtype)
        keep = (jnp.arange(g.shape[0]) % 2 == 0)
        return g * keep, h * keep


def altered_leaf(produced, by=0.01):
    """``produced`` with one leaf value of the window's first tree scaled
    by 1 + ``by``, in the model text."""
    text = produced["model_text"]
    head, tail = text.split(f"Tree={produced['first_window_tree']}\n", 1)
    before, rest = tail.split("leaf_value=", 1)
    line, after = rest.split("\n", 1)
    values = line.split()
    values[len(values) // 2] = repr(float(values[len(values) // 2])
                                    * (1 + by))
    tail = before + "leaf_value=" + " ".join(values) + "\n" + after
    return dict(produced,
                model_text=head + f"Tree={produced['first_window_tree']}\n"
                + tail)


def readings_for_seed(name, seed):
    import jax.numpy as jnp
    cell, config = bench_run.load_cell(name)
    cell = copy.deepcopy(cell)
    cell.setdefault("traffic_params", {})["max_iterations"] = (
        correct.CHECKED_TREES)
    _, out, _, _ = bench_run.drive(name, seed, 1e9, False,
                                   files=(cell, config))
    objective = bench_run.module("reference", config["params"]["objective"])
    produced, data = out["produced"], out["data"]
    got = {"seed": seed,
           "sound": correct.reference_readings(produced, data, config),
           "control": correct.reference_readings(
               produced, data, config, precision=jnp.bfloat16),
           "half_batch": correct.reference_readings(
               produced, data, config, objective=HalfBatch(objective)),
           "altered_leaf": correct.reference_readings(
               altered_leaf(produced), data, config)}
    del out
    if cell.get("control_params"):
        lowered = copy.deepcopy(config)
        lowered["params"].update(cell["control_params"])
        _, out, _, _ = bench_run.drive(name, seed, 1e9, False,
                                       files=(cell, lowered))
        got["program_control"] = correct.reference_readings(
            out["produced"], out["data"], config)
    return got


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: JAX found no TPU", file=sys.stderr)
        return 2
    from lightgbm_tpu.analysis import guards
    guards.configure_compile_cache(guards.checkout_cache_dir())
    for seed in (int(s) for s in args.seeds.split(",")):
        print("READINGS " + json.dumps(
            readings_for_seed(args.workload, seed)),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
