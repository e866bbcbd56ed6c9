"""The span taxonomy: the phase names the program's spans may carry.

Jax-free, so ``scripts/obs`` and offline readers can name phases without
a backend; ``obs/spans.py`` (the producer of the names) re-exports it.
The device-time table that used to be reduced here from a profiler
artifact is the benchmark's now (``benchmarks/trace.py`` on
``jax.profiler.ProfileData``); the program keeps only the names.
"""
from __future__ import annotations

from typing import Optional, Tuple

#: every name a device program or a host tick site carries. Device
#: phases (``named_scope`` at trace time) and serving ticks first, then
#: the host spans of set-up and of the update loop (obs/spans.py says
#: which of them record always). None may be ``update``: the benchmark
#: takes host events of that name as its window.
SPAN_TAXONOMY = (
    "binning", "gradient", "hist_build", "collective_reduce", "split_scan",
    "partition", "quant_discretize", "quant_renew", "checkpoint_write",
    "predict_warmup", "serve_tick", "featurize", "contrib",
    "import", "construct", "find_bins", "to_device", "booster_init",
    "rank_layout", "compact_setup", "shard_rows", "build_step",
    "iteration", "bag", "rank_grads", "step_args", "step_dispatch",
    "valid_scores", "flush_trees", "step_wait", "decode_trees",
    "update_tick",
)


def phase_of(scoped_name: str) -> Optional[str]:
    """First taxonomy token appearing in a scoped op name, scanned in
    path order so the OUTERMOST phase scope wins (``.../hist_build/
    jit(cumsum)/...`` is hist_build even if an inner scope matches
    another token)."""
    best: Tuple[int, Optional[str]] = (len(scoped_name) + 1, None)
    for token in SPAN_TAXONOMY:
        i = scoped_name.find(token)
        if i >= 0 and i < best[0]:
            best = (i, token)
    return best[1]
