"""R009 — host-clock timing around async device dispatch.

jax dispatch is asynchronous: a jitted call returns as soon as the work is
enqueued, so ``t1 - t0`` around it measures DISPATCH, not device time —
off by orders of magnitude, silently. The honest options are (a) time at
a declared tick site where the host genuinely blocks (a flush, a
materializing ``np.asarray``, an explicit ``block_until_ready``), or
(b) let the profiler do it (obs/spans.py: phase-named device traces under
``tpu_trace_dir``).

Two checks:

* **(a) timing in jit-reachable code**: any host-clock read
  (``time.time``/``perf_counter``/``monotonic``/``process_time``/
  ``timeit.default_timer``, alias-aware) inside a jit-reachable function
  is a finding — under trace it bakes a trace-time constant; between
  dispatches it lies. So is the manual span-close pattern
  (``s = span(...)`` then ``s.stop()``/``.close()``/``.__exit__()``):
  obs spans in traced code must be ``with``-scoped named scopes, never
  hand-timed.
* **(b) tick-site pinning** (any function, reachable or not): a function
  that reads a host clock AND dispatches device work (a call whose name
  contains ``step``/``train``/``predict``/``serve``/``grow``) without
  ``block_until_ready`` in the same body is timing async dispatch. The
  declared tick sites — ``Booster.update``'s metrics tick,
  ``warm_predict_ladder``'s warmup stats, and the sampled
  collective-wait timer (``obs/ranks.py``), all of which knowingly
  measure the host loop — carry allowlist anchors; a new unreviewed
  timing site fails tier-1 until justified.
"""
from __future__ import annotations

import ast
from typing import List, Optional, Set

from .base import (Finding, ModuleInfo, PackageInfo, Rule, call_name,
                   dotted_name)

#: host-clock reads (module attr names); time.sleep is NOT a clock read
_TIME_ATTRS = {"time", "perf_counter", "perf_counter_ns", "monotonic",
               "monotonic_ns", "process_time", "process_time_ns"}
_TIMEIT_ATTRS = {"default_timer"}

#: call-name fragments that mean "this dispatches device work here"
_DISPATCHY = ("step", "train", "predict", "serve", "grow")

#: manual span-close spellings (the with-statement form never matches)
_SPAN_CLOSERS = {"stop", "end", "close", "__exit__"}

#: blocking materializers that make host timing honest in the same body
_BLOCKERS = {"block_until_ready"}

def _is_clock_call(module: ModuleInfo, node: ast.Call) -> Optional[str]:
    """The canonical clock name for a Call node, or None."""
    name = call_name(node)
    if name is None:
        return None
    if "." in name:
        head, _, attr = name.partition(".")
        if "." in attr:
            return None
        target = module.imports.get(head)
        if target is None and head in ("time", "timeit"):
            target = (head, None)
        if target is None or target[1] is not None:
            return None
        mod = target[0]
        if mod == "time" and attr in _TIME_ATTRS:
            return f"time.{attr}"
        if mod == "timeit" and attr in _TIMEIT_ATTRS:
            return f"timeit.{attr}"
        return None
    target = module.imports.get(name)
    if target is None:
        return None
    mod, sym = target
    if mod == "time" and sym in _TIME_ATTRS:
        return f"time.{sym}"
    if mod == "timeit" and sym in _TIMEIT_ATTRS:
        return f"timeit.{sym}"
    return None


def _span_locals(fn) -> Set[str]:
    """Local names assigned from a ``span(...)`` call."""
    out: Set[str] = set()
    for n in fn.own_nodes():
        if isinstance(n, ast.Assign) and isinstance(n.value, ast.Call):
            cname = call_name(n.value)
            if cname and cname.rsplit(".", 1)[-1] == "span":
                for t in n.targets:
                    if isinstance(t, ast.Name):
                        out.add(t.id)
    return out


def _dispatchy_call(node: ast.Call) -> Optional[str]:
    name = call_name(node)
    if name is None:
        return None
    base = name.rsplit(".", 1)[-1].lower()
    if any(frag in base for frag in _DISPATCHY):
        return name
    return None


class TimingRule(Rule):
    code = "R009"
    title = "host-clock timing around async dispatch"

    def check(self, module: ModuleInfo, package: PackageInfo
              ) -> List[Finding]:
        out: List[Finding] = []
        reachable = {id(f) for f in package.reachable_functions(module)}
        for fn in module.functions.values():
            jit_reachable = id(fn) in reachable
            spans = _span_locals(fn)
            clock_node = None
            clock_name = None
            dispatch_name = None
            blocked = False
            for node in fn.own_nodes():
                if not isinstance(node, ast.Call):
                    continue
                cname = _is_clock_call(module, node)
                if cname is not None:
                    if clock_node is None:
                        clock_node, clock_name = node, cname
                    if jit_reachable:
                        out.append(self.finding(
                            module, node, fn.qualname,
                            f"{cname}() in jit-reachable code: async "
                            "dispatch makes host timing a lie (and under "
                            "trace it bakes a constant); time at a "
                            "declared tick site or use obs/spans device "
                            "traces (tpu_trace_dir)"))
                    continue
                if isinstance(node.func, ast.Attribute) \
                        and node.func.attr in _SPAN_CLOSERS \
                        and isinstance(node.func.value, ast.Name) \
                        and node.func.value.id in spans:
                    if jit_reachable:
                        out.append(self.finding(
                            module, node, fn.qualname,
                            f"manual span close "
                            f"(.{node.func.attr}() on a span(...) local) "
                            "in jit-reachable code: spans under trace "
                            "must be with-scoped named scopes; host "
                            "timing here measures dispatch, not device "
                            "work"))
                    continue
                name = call_name(node)
                if name is not None and \
                        name.rsplit(".", 1)[-1] in _BLOCKERS:
                    blocked = True
                    continue
                if dispatch_name is None:
                    dispatch_name = _dispatchy_call(node)
            # (b) tick-site pinning: clock + dispatch, no blocker
            if not jit_reachable and clock_node is not None \
                    and dispatch_name is not None and not blocked:
                out.append(self.finding(
                    module, clock_node, fn.qualname,
                    f"{clock_name}() times around {dispatch_name}() "
                    "without block_until_ready: async dispatch makes the "
                    "measurement a lie. Declared tick sites (the "
                    "Booster.update metrics tick, warm_predict_ladder) "
                    "carry allowlist anchors; block, or move the timing "
                    "to a tick site / the device trace"))
        return out
