"""One part of the first ``lgb.train`` round, from the program's own
records (records.py). The parts do not overlap, and with the step's first
execution (the first ``flush_trees``) they make up the round:

``init``          the spans ``booster_init``, ``compact_setup`` and
                  ``build_step`` inside the round, less the compile
                  events inside them
``trace_lower``   jaxpr traces and lowerings to MLIR
``cache_retrieval``  reads of the persistent compile cache (with the
                  deserialisation: jax times both as one)
``backend``       backend compiles less the cache retrievals inside them
``unattributed``  the round's wall (the benchmark's span ``round``) less
                  what the program's records cover inside it: what the
                  spans do not yet explain. ``iteration`` is left out of
                  the cover: it is all of an update and would explain
                  everything by enclosing it

The three compile parts take the events from the start of the round to
the start of the window (the warm-up updates should add none). Nested
intervals count once (``trace.union_seconds``)."""
from .. import records

INIT_SPANS = ("booster_init", "compact_setup", "build_step")
ENVELOPE = "iteration"
COMPILE_KINDS = {"trace_lower": (("traces", "lowerings"), ()),
                 "cache_retrieval": (("cache_retrievals",), ()),
                 "backend": (("backend_compiles",), ("cache_retrievals",))}


def reduce(run, part, round="first_iter", window="update"):
    rec = records.load(run)
    rounds = records.intervals(run, round)
    if rec is None or not rounds:
        return None
    if part in COMPILE_KINDS:
        starts = [s for s, _ in records.intervals(run, window)]
        until = min(starts) if starts else float("inf")
        events = records.within(rec["compiles"], [(rounds[0][0], until)])
        kinds, less = COMPILE_KINDS[part]
        return records.seconds_outside(
            [e for e in events if e[0] in kinds],
            [e for e in events if e[0] in less])
    spans = records.within(rec["spans"], rounds)
    compiles = records.within(rec["compiles"], rounds)
    if part == "init":
        return records.seconds_outside(
            [s for s in spans if s[0] in INIT_SPANS], compiles)
    if part == "unattributed":
        cover = [s for s in spans if s[0] != ENVELOPE]
        wall = sum(e - s for s, e in rounds)
        return wall - records.seconds(cover + compiles)
    raise ValueError(f"no part {part!r} of the first round")
