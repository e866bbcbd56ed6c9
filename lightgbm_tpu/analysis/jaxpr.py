"""What a traced program moves by index: its ``gather``, ``scatter`` and
``sort`` equations, read from the jaxpr and everything it nests. A TPU
makes a gather or a scatter an indexed access at a time, so their count
is the cost of carrying values from one order to another, which an
HLO fusion's name does not show."""
from __future__ import annotations

import math
from typing import Dict, Iterator, List, Tuple


def _nested(jaxpr, trips: int) -> Iterator[Tuple[object, int]]:
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold,
    with the times a ``scan`` around it repeats it."""
    for eqn in jaxpr.eqns:
        yield eqn, trips
        inner_trips = trips * int(eqn.params.get("length", 1)
                                  if eqn.primitive.name == "scan" else 1)
        for value in eqn.params.values():
            for item in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(item, "jaxpr", item)
                if hasattr(inner, "eqns"):
                    yield from _nested(inner, inner_trips)


def indexed_moves(jaxpr) -> List[Dict]:
    """One record for each ``gather``, ``scatter*`` and ``sort`` equation:
    ``op`` (``gather`` / ``scatter`` / ``sort``), ``shape`` (the gathered
    output's, the scattered updates', the sorted operands') and
    ``accesses``: the index vectors it reads, times the trips of the
    ``scan`` around it; 0 for a sort, which moves by compare and
    exchange."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    found = []
    for eqn, trips in _nested(jaxpr, 1):
        name = eqn.primitive.name
        if name == "gather":
            op, shape = "gather", eqn.outvars[0].aval.shape
        elif name.startswith("scatter"):
            op, shape = "scatter", eqn.invars[2].aval.shape
        elif name == "sort":
            op, shape = "sort", eqn.invars[0].aval.shape
        else:
            continue
        # a gather's and a scatter's second operand is its indices, the
        # last axis the index vector
        accesses = 0 if op == "sort" else trips * math.prod(
            eqn.invars[1].aval.shape[:-1])
        found.append({"op": op, "shape": tuple(shape), "accesses": accesses})
    return found
