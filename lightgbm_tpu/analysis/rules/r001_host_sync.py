"""R001 — host synchronization inside jit-reachable code.

``float()``, ``.item()``, ``.tolist()``, ``np.asarray``/``np.array`` and
``jax.device_get`` on a traced value force a device->host round trip: under
trace they either raise (``TracerArrayConversionError``) or, worse, silently
bake a trace-time constant into the compiled program; called between jitted
steps they serialize the dispatch pipeline (each one drains the device
queue; see boosting/gbdt.py stop_check_freq). The gbdt train step and the ops/
growers are the protected hot paths.

Python casts (``float``/``int``/``bool``) are only flagged when an argument
references a traced name — trace-time conversion of host config constants
(e.g. ``float(obj.renew_alpha)`` on a closed-over host object) is fine.

Two checkpoint-era sub-checks (the snapshot subsystem, io/checkpoint.py):

* file I/O (``open``/``os.fsync``/``pickle.dump``/``np.save``/...) in
  jit-reachable code — a snapshot write reachable from a traced program
  is both a host sync AND a trace-time constant bake; snapshots belong in
  the host training loop, at ``tpu_checkpoint_freq`` ticks;
* any function that BOTH pickles state and writes/fsyncs a file is pinned
  as a **snapshot-writer site** regardless of reachability: such a
  function blocks on a device fetch + fsync wherever it is called from,
  so every call site must be a deliberate tick. The shipped writer
  (``io/checkpoint.py::write_snapshot``) carries the allowlist entry;
  a new unreviewed writer fails tier-1 until justified.
"""
from __future__ import annotations

import ast
from typing import List

from .base import (Finding, ModuleInfo, PackageInfo, Rule, call_name,
                   expr_references, traced_names)

_ALWAYS = {"jax.device_get", "jax.block_until_ready"}
_SYNC_METHODS = {"item", "tolist", "block_until_ready",
                 "copy_to_host_async"}
_TRACED_CASTS = {"float", "int", "bool", "complex",
                 "np.asarray", "np.array", "numpy.asarray", "numpy.array"}
#: file/serialization I/O that must never be reachable from a traced
#: program (each call is a host sync at best, a baked trace-time constant
#: at worst)
_FILE_IO = {"open", "os.fdopen", "os.fsync", "os.replace",
            "pickle.dump", "pickle.dumps",
            "np.save", "np.savez", "numpy.save", "numpy.savez",
            "json.dump"}
#: the snapshot-writer structural signature: serializes state AND syncs
#: it to a file in the same function
_SNAP_SERIALIZE = {"pickle.dump", "pickle.dumps"}
_SNAP_FILE_SINK = {"open", "os.fdopen", "os.fsync"}


class HostSyncRule(Rule):
    code = "R001"
    title = "host sync in jit-reachable code"

    def check(self, module: ModuleInfo, package: PackageInfo
              ) -> List[Finding]:
        out: List[Finding] = []
        for fn in package.reachable_functions(module):
            traced = traced_names(fn, package)
            for node in fn.own_nodes():
                if not isinstance(node, ast.Call):
                    continue
                name = call_name(node)
                if name in _ALWAYS:
                    out.append(self.finding(
                        module, node, fn.qualname,
                        f"{name}() in jit-reachable code forces a "
                        "device->host sync (or bakes a trace-time "
                        "constant)"))
                elif name in _FILE_IO:
                    out.append(self.finding(
                        module, node, fn.qualname,
                        f"{name}() in jit-reachable code — checkpoint/"
                        "snapshot file I/O is a host sync; snapshot at "
                        "tpu_checkpoint_freq ticks in the host training "
                        "loop (io/checkpoint.py), never under trace"))
                elif name in _TRACED_CASTS and any(
                        expr_references(a, traced) for a in node.args):
                    out.append(self.finding(
                        module, node, fn.qualname,
                        f"{name}() on a traced value in jit-reachable "
                        "code — host sync / TracerArrayConversionError"))
                elif (isinstance(node.func, ast.Attribute)
                      and node.func.attr in _SYNC_METHODS
                      and not (name or "").startswith(("np.", "numpy."))):
                    out.append(self.finding(
                        module, node, fn.qualname,
                        f".{node.func.attr}() in jit-reachable code "
                        "materializes the array on the host"))
        out.extend(self._snapshot_writers(module))
        return out

    def _snapshot_writers(self, module: ModuleInfo) -> List[Finding]:
        """Pin every pickle-and-write-to-file function, reachable or not:
        a snapshot writer blocks its caller on serialization + fsync, so
        each one must be a reviewed, deliberate snapshot-tick path (the
        shipped io/checkpoint.py writer is allowlisted)."""
        out: List[Finding] = []
        for fn in module.functions.values():
            serialize = sink = None
            for node in fn.own_nodes():
                if not isinstance(node, ast.Call):
                    continue
                name = call_name(node)
                if name in _SNAP_SERIALIZE and serialize is None:
                    serialize = node
                elif name in _SNAP_FILE_SINK and sink is None:
                    sink = node
            if serialize is not None and sink is not None:
                out.append(self.finding(
                    module, serialize, fn.qualname,
                    "snapshot-writer site (pickles state AND writes/"
                    "fsyncs a file): blocks on a host materialization + "
                    "fsync wherever called — keep off the jit hot path; "
                    "the deliberate snapshot tick carries an allowlist "
                    "entry (io/checkpoint.py::write_snapshot)"))
        return out
