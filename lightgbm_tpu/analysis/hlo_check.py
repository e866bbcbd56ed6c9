"""hlo_check — post-lowering contract verification of the step programs.

PR 1's tpulint checks hazard *patterns* in Python source; the runtime
guards check *behavior* counters. This pass closes the remaining gap: the
claims the repo makes about its COMPILED programs — which collectives a
learner mode is allowed to emit (reduce-scatter, not a full-histogram
all-reduce, when ``tpu_hist_scatter`` is on), that the jitted step moves
zero bytes between host and device, that every integer histogram
contraction carries ``preferred_element_type=int32`` (an s8 dot that
keeps an s8 accumulator silently wraps at ±127), and that the program
stays byte-for-byte stable across iterations (recompile detection at the
HLO level, not just the event counter) — were previously asserted by
hand-read HLO. Here they are **contract files**
(``analysis/contracts/*.json``), one per learner mode, verified
mechanically against the lowered text on any backend (the tier-1 gate
runs on CPU; the same programs are what dryrun_multichip records into
COMM_ACCOUNTING.json).

Contract schema (one JSON object per mode)::

    {
      "mode": "data_scatter",
      "description": "...",
      "params":  {...},          # Booster params reproducing the program
      "num_devices": 8,          # mesh size the program was lowered for
      "program": "compact_step_k0",   # key in GBDT._comm_hlo
      "collectives": {
        "allow":   ["reduce-scatter", "all-gather", "all-reduce"],
        "require": ["reduce-scatter"],
        "max_bytes": {"all-reduce": 16, ...}   # per-kind byte budgets
      },
      "forbid_host_ops": true,   # no infeed/outfeed/send/recv/callbacks
      "int_dot_s32": true,       # narrow-int dots must accumulate in s32
      "require_integer_dot": false,  # quant mode: the int path must be live
      "stable_fingerprint": true,
      "measured": {...},         # collective_bytes() at generation time —
                                 #   scripts/verify_contracts.py diffs this
      "measured_baseline": {...},# overlap modes only: the overlap=off
                                 #   lowering's accounting — every kind's
                                 #   bytes must MATCH "measured" (overlap
                                 #   hides latency, never adds traffic)
      "memory": {                # per-mesh static per-chip HBM budget
        "8": {"budget_bytes": ..., "estimate_bytes": ...,   # (ISSUE 15;
              "headroom_bytes": ..., ...}},                 # memory.py walk)
      "spmd": {                  # per-mesh collective inventory+schedule
        "4": {"collectives": [...],          # recorded by scripts/tpulint
              "schedule": [[kind, B], ...]}} # spmd --update (spmd_check.py)
    }

The harness half (``capture_mode``) trains a tiny Booster with
``LGBM_TPU_COMM_ACCOUNTING=1`` so ``boosting/gbdt.py`` records the
compiled step text (and re-lowers on any argument-signature change —
``_comm_hlo_history``); it imports jax lazily so the checking half stays
importable from ``scripts/tpulint``'s backend-free stub.

CLI: ``scripts/tpulint hlo [--update] [mode ...]``; tier-1 runs the same
gate in tests/test_hlo_check.py.
"""
from __future__ import annotations

import dataclasses
import json
import os
import re
from typing import Dict, List, Optional, Sequence

from . import memory
from .hlo import (HOST_CUSTOM_CALL_MARKERS, HOST_OPS, INT_NARROW,
                  collective_bytes, fingerprint, parse_instructions)

_CUSTOM_CALL_TARGET_RE = re.compile(r'custom_call_target="([^"]*)"')

CONTRACTS_DIR = os.path.join(os.path.dirname(__file__), "contracts")

#: integer element types an MXU-friendly accumulator may use
_INT_ACCUM = ("s32", "s64", "u32", "u64")
_INT_ALL = INT_NARROW + _INT_ACCUM


@dataclasses.dataclass(frozen=True)
class ContractFinding:
    contract: str
    check: str        # collectives | host-ops | int-dot | fingerprint | ...
    message: str

    def render(self) -> str:
        return f"[{self.contract}] {self.check}: {self.message}"


# ---------------------------------------------------------------------------
# mode templates: the static half of each contract. `params` must rebuild the
# exact steady-state step program; measured budgets are filled by --update.
# ---------------------------------------------------------------------------
_BASE = {"objective": "binary", "num_leaves": 7, "max_bin": 15,
         "min_data_in_leaf": 2, "verbosity": -1}

MODE_TEMPLATES: Dict[str, dict] = {
    "serial_compact": {
        "description": "single-chip compact grower: a pure on-device step "
                       "— no collectives, no host traffic",
        "params": dict(_BASE, tpu_grower="compact"),
        "num_devices": 1,
        "program": "compact_step_k0",
        "require": [],
        "require_integer_dot": False,
        "problem": {"n": 509, "f": 8, "seed": 0},
    },
    "data_scatter": {
        "description": "data-parallel compact grower with the feature-axis "
                       "reduce-scatter histogram reduction "
                       "(tpu_hist_scatter): the full-histogram all-reduce "
                       "is budgeted down to the best-split sync bytes",
        "params": dict(_BASE, tpu_grower="compact", tree_learner="data",
                       tpu_hist_scatter="on"),
        "num_devices": 8,
        "program": "compact_step_k0",
        "require": ["reduce-scatter"],
        "require_integer_dot": False,
        "problem": {"n": 509, "f": 8, "seed": 0},
    },
    "voting": {
        "description": "voting-parallel learner (PV-Tree): top-k elected "
                       "histograms reduce, so collective bytes stay far "
                       "below the full-F data-parallel exchange",
        "params": dict(_BASE, tree_learner="voting", top_k=2),
        "num_devices": 8,
        "program": "step",
        "require": ["all-reduce"],
        "require_integer_dot": False,
        "problem": {"n": 509, "f": 64, "seed": 1},
    },
    "quant_int8": {
        "description": "quantized-gradient int8 histogram pipeline: every "
                       "narrow-int contraction must accumulate in int32 "
                       "(preferred_element_type) and the integer dot path "
                       "must actually be live",
        "params": dict(_BASE, tpu_grower="compact", use_quantized_grad=True,
                       num_grad_quant_bins=16, quant_train_renew_leaf=True),
        "num_devices": 1,
        "program": "compact_step_k0",
        "require": [],
        "require_integer_dot": True,
        "problem": {"n": 509, "f": 8, "seed": 0},
    },
    # -- engine-registry entry contracts (engines/registry.py) ----------
    # One contract per non-exempt registry entry, the entry id in the
    # filename (registry_contract_findings enumerates the coverage):
    # a new engine entry cannot land without either a contract here or
    # a justified contract_exempt on the entry. xla_lane pins the
    # registry's fully-concretized serial program — every engine knob
    # explicit (no "auto" left for the trace-time dispatch) — so a drift
    # in how the registry threads its resolution into
    # GrowerParams shows up as contract drift, not just a perf change.
    "xla_lane": {
        "description": "engine-registry entry xla_lane: the chunked "
                       "one-hot einsum engine with every knob "
                       "concretized through registry.resolve "
                       "(tpu_hist_impl=xla, lane layout, batched-M 8) "
                       "on the serial compact step — "
                       "no collectives, no host traffic",
        "params": dict(_BASE, tpu_grower="compact", tpu_hist_impl="xla",
                       tpu_hist_layout="lane", tpu_hist_mbatch=8,
                       tpu_autotune="off"),
        "num_devices": 1,
        "program": "compact_step_k0",
        "require": [],
        "require_integer_dot": False,
        "problem": {"n": 509, "f": 8, "seed": 0},
    },
    # -- async histogram-collective overlap (tpu_hist_overlap) ----------
    # The overlap modes carry a ``baseline_params`` override: --update
    # captures the overlap=off program too and records its accounting as
    # ``measured_baseline``; check_overlap_parity then fails the gate if
    # ANY collective kind moves different bytes with overlap on — overlap
    # hides latency, it never adds traffic (only the collective COUNT may
    # grow: one reduce per feature group instead of one for the slab).
    # ``async_twins`` admits the corresponding ``-start`` ops with the
    # same byte budgets: the CPU backend lowers the group collectives
    # synchronously (measured start-bytes 0), an async backend splits
    # each into a -start/-done pair that overlaps the next group's
    # contraction — the same schedule freedom the grouping exists for.
    "data_scatter_overlap": {
        "description": "data-parallel compact grower, reduce-scatter "
                       "histograms, tpu_hist_overlap=on: the owned "
                       "feature slice reduces in 2 groups, each group's "
                       "collective issued while the next group still "
                       "contracts — byte budgets identical to the "
                       "single-collective baseline, only the count grows",
        "params": dict(_BASE, tpu_grower="compact", tree_learner="data",
                       tpu_hist_scatter="on", tpu_hist_overlap="on"),
        "baseline_params": {"tpu_hist_overlap": "off"},
        "num_devices": 8,
        "program": "compact_step_k0",
        "require": ["reduce-scatter"],
        "require_integer_dot": False,
        "async_twins": True,
        # 16 features / 8 shards = 2 owned columns per shard — the
        # smallest problem where the 2-group split is live
        "problem": {"n": 509, "f": 16, "seed": 0},
    },
    "voting_overlap": {
        "description": "voting-parallel learner, tpu_hist_overlap=on: the "
                       "2k elected histograms reduce in 2 groups, one "
                       "cross-shard all-reduce per group pipelined under "
                       "the next group's gather — same elected bytes as "
                       "the single all-reduce baseline",
        "params": dict(_BASE, tree_learner="voting", top_k=2,
                       tpu_hist_overlap="on"),
        "baseline_params": {"tpu_hist_overlap": "off"},
        "num_devices": 8,
        "program": "step",
        "require": ["all-reduce"],
        "require_integer_dot": False,
        "async_twins": True,
        "problem": {"n": 509, "f": 64, "seed": 1},
    },
}

MODES = tuple(MODE_TEMPLATES)

# ---------------------------------------------------------------------------
# serving-engine contracts (engines/registry.SERVING_ENTRIES): the predict
# program each serving engine compiles, lowered AOT at a ladder rung
# (GBDT.aot_lower_serving) instead of comm-captured from a training step.
# One file per non-exempt serving entry, the entry id in the filename —
# registry_contract_findings enumerates the coverage exactly like the
# histogram entries. serve_qleaf is exempt: it shares these two programs'
# shapes (only the leaf-slab dtype narrows) and is pinned by its RECORDED
# error bound + tests/test_level_engine.py instead.
# ---------------------------------------------------------------------------
_SERVE_BASE = dict(_BASE, tpu_autotune="off", max_depth=5)

SERVING_TEMPLATES: Dict[str, dict] = {
    "serve_walk": {
        "description": "serving engine serve_walk: the depth-batched "
                       "pointer walk (predict_raw_batched) at the "
                       "smallest ladder rung — per depth step one packed "
                       "node-record gather + one bin gather, no "
                       "collectives, no host traffic",
        "engine": "walk",
        "params": dict(_SERVE_BASE, tpu_predict_engine="walk"),
        "program": "predict_raw_batched",
        "problem": {"n": 509, "f": 8, "seed": 0},
    },
    "serve_level": {
        "description": "serving engine serve_level: the level-order heap "
                       "relayout (predict_raw_level) at the smallest "
                       "ladder rung — depth step d reads the contiguous "
                       "[Tb, 2^d] slab of the complete-binary-heap "
                       "records, unrolled over the exact tree depth",
        "engine": "level",
        "params": dict(_SERVE_BASE, tpu_predict_engine="level"),
        "program": "predict_raw_level",
        "problem": {"n": 509, "f": 8, "seed": 0},
    },
}

SERVING_MODES = tuple(SERVING_TEMPLATES)


def contract_path(mode: str) -> str:
    return os.path.join(CONTRACTS_DIR, f"{mode}.json")


def load_contract(mode: str) -> dict:
    with open(contract_path(mode)) as fh:
        return json.load(fh)


# The XLA memory estimate for the same program differs across XLA builds
# and host layouts (padding/fusion decisions shift estimate_bytes by
# ~30%), so the drift fingerprint keeps only the *contracted* quantities
# — the sticky budget and the exact argument/output byte counts — and
# drops the estimate-derived fields. check_memory still enforces
# estimate <= budget against the LIVE lowering, so a real regression
# fails the gate; it just no longer fails tier-1 on a host change.
_MEM_ESTIMATE_KEYS = ("estimate_bytes", "headroom_bytes")


def drift_fingerprint(contract: dict) -> dict:
    """A copy of ``contract`` with host-dependent memory-estimate fields
    normalized out, for byte-exact drift comparison."""
    out = dict(contract)
    mem = contract.get("memory")
    if isinstance(mem, dict):
        out["memory"] = {
            nd: {k: v for k, v in blk.items()
                 if k not in _MEM_ESTIMATE_KEYS}
            if isinstance(blk, dict) else blk
            for nd, blk in mem.items()}
    return out


# ---------------------------------------------------------------------------
# checking half (pure text; no jax)
# ---------------------------------------------------------------------------
def check_collectives(hlo_text: str, contract: dict) -> List[ContractFinding]:
    name = contract["mode"]
    spec = contract.get("collectives", {})
    allow = set(spec.get("allow", []))
    require = set(spec.get("require", []))
    budgets = spec.get("max_bytes", {})
    acct = collective_bytes(hlo_text)
    out: List[ContractFinding] = []
    observed = {k: v for k, v in acct.items()
                if k not in ("total", "count") and v > 0}
    for kind, nbytes in sorted(observed.items()):
        if kind not in allow:
            out.append(ContractFinding(
                name, "collectives",
                f"forbidden collective '{kind}' ({nbytes} B) in the step "
                f"program — allowed inventory: {sorted(allow) or 'none'}. "
                "If the learner's comm protocol deliberately changed, "
                "regenerate contracts (scripts/verify_contracts.py "
                "--update) and justify in the PR"))
        elif kind in budgets and nbytes > budgets[kind]:
            out.append(ContractFinding(
                name, "collectives",
                f"'{kind}' moves {nbytes} B > budget {budgets[kind]} B — "
                "e.g. a histogram all-reduce reappearing next to the "
                "reduce-scatter path doubles cross-chip traffic silently"))
    for kind in sorted(require - set(observed)):
        out.append(ContractFinding(
            name, "collectives",
            f"required collective '{kind}' is missing — the mode's "
            "comm-reduction claim (README/COMM_ACCOUNTING.json) no longer "
            "holds for this program"))
    return out


def check_host_ops(hlo_text: str, contract: dict) -> List[ContractFinding]:
    if not contract.get("forbid_host_ops", True):
        return []
    name = contract["mode"]
    out: List[ContractFinding] = []
    for instr in parse_instructions(hlo_text):
        if instr.opcode in HOST_OPS:
            out.append(ContractFinding(
                name, "host-ops",
                f"'{instr.opcode}' at HLO line {instr.line}: the jitted "
                "step must keep a 0-d2h steady state — host traffic here "
                "serializes every iteration on the transfer"))
        elif instr.opcode == "custom-call":
            # match the TARGET only — the raw line also carries metadata
            # like source_file=".../site-packages/jax/..." whose 'python'
            # substring would false-positive on every benign custom-call
            m = _CUSTOM_CALL_TARGET_RE.search(instr.raw)
            target = (m.group(1) if m else "").lower()
            if any(marker in target for marker in HOST_CUSTOM_CALL_MARKERS):
                out.append(ContractFinding(
                    name, "host-ops",
                    f"host-callback custom-call '{target}' at HLO line "
                    f"{instr.line}: a Python callback inside the step "
                    "program round-trips to the host every iteration"))
    return out


def check_int_dots(hlo_text: str, contract: dict) -> List[ContractFinding]:
    name = contract["mode"]
    out: List[ContractFinding] = []
    saw_integer_dot = False
    for instr in parse_instructions(hlo_text):
        if instr.opcode != "dot":
            continue
        op_dtypes = [d for d, _ in instr.operand_shapes]
        res_dtypes = [d for d, _ in instr.result_shapes]
        if op_dtypes and all(d in _INT_ALL for d in op_dtypes) \
                and all(d in _INT_ACCUM for d in res_dtypes):
            saw_integer_dot = True
        if contract.get("int_dot_s32", True):
            narrow = [d for d in op_dtypes + res_dtypes if d in INT_NARROW]
            if narrow and not all(d in _INT_ACCUM for d in res_dtypes):
                out.append(ContractFinding(
                    name, "int-dot",
                    f"dot at HLO line {instr.line} contracts "
                    f"{'/'.join(op_dtypes)} into {'/'.join(res_dtypes)} — "
                    "an int8/int16 matmul without "
                    "preferred_element_type=int32 wraps its sums at the "
                    "narrow-type bound (ops/histogram.py contract)"))
    if contract.get("require_integer_dot") and not saw_integer_dot:
        out.append(ContractFinding(
            name, "int-dot",
            "no integer-accumulating dot found — the quantized int8 "
            "histogram path is not live in this program (fell back to the "
            "dequantized f32 shim?)"))
    return out


def check_overlap_parity(contract: dict,
                         measured: Optional[dict] = None
                         ) -> List[ContractFinding]:
    """Overlap never adds traffic: with ``measured_baseline`` present
    (the overlap=off lowering of the same mode), every collective kind
    must move exactly the bytes the baseline moves — grouping a
    histogram reduce splits ONE collective into N, it must not grow,
    shrink, or re-route what crosses the links. The collective COUNT is
    exempt (one reduce per feature group IS the mechanism).

    ``measured`` is the LIVE capture's accounting (verify_mode passes
    it); without it the check degrades to diffing the two stored fields
    of the checked-in contract, which cannot see current-lowering
    drift."""
    base = contract.get("measured_baseline")
    if not base:
        return []
    name = contract["mode"]
    cur = measured if measured is not None \
        else contract.get("measured", {})
    out: List[ContractFinding] = []
    # "total" is the sum of the kinds — diffing it too would report every
    # drift twice
    for kind in sorted((set(base) | set(cur)) - {"count", "total"}):
        if cur.get(kind, 0) != base.get(kind, 0):
            out.append(ContractFinding(
                name, "overlap-bytes",
                f"'{kind}' moves {cur.get(kind, 0)} B with overlap on vs "
                f"{base.get(kind, 0)} B in the overlap=off baseline — "
                "tpu_hist_overlap must hide collective latency without "
                "changing collective traffic (same addends per element, "
                "same bytes per link)"))
    return out


def check_fingerprint(history: Sequence[str],
                      contract: dict) -> List[ContractFinding]:
    name = contract["mode"]
    if not contract.get("stable_fingerprint", True) or len(history) <= 1:
        return []
    prints = [fingerprint(t) for t in history]
    detail = ("identical program re-lowered (argument signature changed)"
              if len(set(prints)) == 1 else
              f"program CHANGED across lowerings: {prints}")
    return [ContractFinding(
        name, "fingerprint",
        f"step program was lowered {len(history)} times during the "
        f"steady-state run — {detail}. A stable step must compile once; "
        "a shape/dtype/static-arg flip after warmup recompiles every "
        "change (guards.compile_counter sees the event, this names the "
        "program)")]


def check_memory(hlo_text: str, contract: dict) -> List[ContractFinding]:
    """Native-mesh memory budget: the contract's ``memory`` block (ISSUE
    15) records a per-chip peak-HBM budget + estimate per mesh key; the
    mode's own lowering is checked against its native mesh here (the
    flight meshes are spmd_check's job). An estimate above budget is a
    memory regression; budgets only move by deliberate edit."""
    name = contract["mode"]
    key = str(contract.get("num_devices", 1))
    block = contract.get("memory", {}).get(key)
    if not block:
        return []
    est = memory.estimate(hlo_text)
    budget = int(block["budget_bytes"])
    if est.peak_bytes <= budget:
        return []
    top = ", ".join(f"{n}={memory.render_bytes(b)}"
                    for n, b in est.largest[:3])
    return [ContractFinding(
        name, "memory",
        f"mesh {key}: static per-chip peak "
        f"{memory.render_bytes(est.peak_bytes)} exceeds the recorded "
        f"{memory.render_bytes(budget)} budget (largest buffers: {top}) "
        "— the step program's resident footprint regressed; shrink it "
        "or raise budget_bytes deliberately (scripts/tpulint spmd "
        "--update keeps budgets sticky)")]


def check_hlo(hlo_text: str, contract: dict) -> List[ContractFinding]:
    """All single-program checks against one contract."""
    return (check_collectives(hlo_text, contract)
            + check_host_ops(hlo_text, contract)
            + check_int_dots(hlo_text, contract)
            + check_memory(hlo_text, contract))


def registry_contract_findings(entries=None,
                               serving_entries=None
                               ) -> List[ContractFinding]:
    """Per-registry-entry contract coverage (engines/registry.py).

    Every engine entry must either name contracts — known modes with a
    checked-in file, at least one filename carrying the entry id — or
    carry a ``contract_exempt`` justification. For histogram entries the
    exemption is only admissible for TPU-only engines (``requires_tpu``):
    the CPU contract harness cannot lower Mosaic kernels, everything
    else MUST be pinned. Serving entries (SERVING_ENTRIES) additionally
    admit an exemption that names the parity test pinning them (a
    ``tests/`` path in the justification) — serve_qleaf shares the
    walk/level program shapes and is pinned by its recorded error bound
    instead of a third identical contract. A new engine cannot land
    without one or the other (tier-1 runs this via
    scripts/verify_contracts.py and tests/test_hlo_check.py)."""
    if entries is None:
        from ..engines.registry import ENTRIES as entries
        if serving_entries is None:
            from ..engines.registry import \
                SERVING_ENTRIES as serving_entries
    serving = tuple(serving_entries or ())
    known_modes = set(MODE_TEMPLATES) | set(SERVING_TEMPLATES)
    out: List[ContractFinding] = []
    for entry in tuple(entries) + serving:
        is_serving = entry in serving
        if entry.contract_exempt:
            admissible = entry.requires_tpu or (
                is_serving and "tests/" in entry.contract_exempt)
            if not admissible:
                out.append(ContractFinding(
                    entry.id, "registry",
                    "contract_exempt is only admissible for TPU-only "
                    "engines (the CPU harness cannot lower Mosaic "
                    "kernels) or for serving entries whose exemption "
                    "names the tests/ parity file pinning them; "
                    "otherwise check in a contract "
                    "(scripts/verify_contracts.py --update)"))
            continue
        if not entry.contracts:
            out.append(ContractFinding(
                entry.id, "registry",
                "registry entry has neither an HLO contract nor a "
                "contract_exempt justification — a new engine cannot "
                "land unpinned; add a MODE_TEMPLATE + contract file "
                "named after the entry id and regenerate "
                "(scripts/verify_contracts.py --update)"))
            continue
        if not any(entry.id in mode for mode in entry.contracts):
            out.append(ContractFinding(
                entry.id, "registry",
                f"none of its contracts {list(entry.contracts)} carry "
                "the entry id in the filename — per-entry enumeration "
                "needs the id visible in analysis/contracts/"))
        for mode in entry.contracts:
            if mode not in known_modes:
                out.append(ContractFinding(
                    entry.id, "registry",
                    f"contract mode '{mode}' has no MODE_TEMPLATE or "
                    "SERVING_TEMPLATE — the harness cannot regenerate "
                    "or verify it"))
            elif not os.path.exists(contract_path(mode)):
                out.append(ContractFinding(
                    entry.id, "registry",
                    f"contract file {contract_path(mode)} is missing — "
                    "run scripts/verify_contracts.py --update"))
            else:
                # per-entry mesh enumeration (ISSUE 15): each contract
                # must carry a verified memory block for every mesh the
                # entry declares
                have = set(load_contract(mode).get("memory", {}))
                for mesh in getattr(entry, "meshes", ()):
                    if mesh not in have:
                        out.append(ContractFinding(
                            entry.id, "registry",
                            f"contract '{mode}' has no memory block "
                            f"for declared mesh '{mesh}' (have "
                            f"{sorted(have) or 'none'}) — regenerate "
                            "(scripts/verify_contracts.py --update, or "
                            "scripts/tpulint spmd --update for flight "
                            "meshes)"))
    return out


# ---------------------------------------------------------------------------
# harness half (imports jax + the package lazily)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class CapturedMode:
    mode: str
    program: str
    hlo_text: str
    history: List[str]
    all_programs: Dict[str, str]
    #: the trained GBDT — spmd_check's AOT-relowering hooks
    #: (aot_lower_program / flight_row_dims) hang off it
    gbdt: object = None


def _tiny_problem(n: int, f: int, seed: int):
    import numpy as np
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = ((X[:, 0] + 0.5 * X[:, 1] + 0.2 * rng.randn(n)) > 0)
    return X, y.astype(np.float64)


def capture_mode(mode: str, template: Optional[dict] = None,
                 iterations: int = 4) -> CapturedMode:
    """Train a tiny Booster in ``mode`` and return its step-program HLO.

    Requires an initialized jax backend with >= the mode's device count
    (the tier-1 conftest provisions 8 virtual CPU devices; the CLI path
    sets XLA_FLAGS before first import).
    """
    import jax

    import lightgbm_tpu as lgb

    t = template or MODE_TEMPLATES[mode]
    platform = jax.devices()[0].platform
    if platform != "cpu":
        # the checked-in contracts are CPU lowerings; diffing a TPU/GPU
        # program against them would report meaningless drift
        raise RuntimeError(
            f"hlo_check contracts are CPU-backend lowerings, but this "
            f"process's jax backend is '{platform}' — run via "
            "scripts/tpulint hlo (which forces the CPU platform before "
            "jax initializes)")
    need = t.get("num_devices", 1)
    if len(jax.devices()) < need:
        raise RuntimeError(
            f"mode '{mode}' needs {need} devices, have "
            f"{len(jax.devices())} (set "
            "XLA_FLAGS=--xla_force_host_platform_device_count)")
    X, y = _tiny_problem(**t["problem"])
    prev = os.environ.get("LGBM_TPU_COMM_ACCOUNTING")
    os.environ["LGBM_TPU_COMM_ACCOUNTING"] = "1"
    try:
        bst = lgb.Booster(dict(t["params"]), lgb.Dataset(X, label=y))
        for _ in range(iterations):
            bst.update()
    finally:
        if prev is None:
            os.environ.pop("LGBM_TPU_COMM_ACCOUNTING", None)
        else:
            os.environ["LGBM_TPU_COMM_ACCOUNTING"] = prev
    g = bst._gbdt
    key = t["program"]
    if key not in g._comm_hlo:
        raise RuntimeError(
            f"mode '{mode}': step program '{key}' was not captured "
            f"(have {sorted(g._comm_hlo)}) — the learner dispatched a "
            "different step path than the contract expects")
    return CapturedMode(mode, key, g._comm_hlo[key],
                        list(g._comm_hlo_history.get(key, [])),
                        dict(g._comm_hlo), gbdt=g)


def verify_mode(mode: str, contract: Optional[dict] = None,
                captured: Optional[CapturedMode] = None
                ) -> List[ContractFinding]:
    """Lower the mode's program and verify it against its contract."""
    contract = contract or load_contract(mode)
    captured = captured or capture_mode(mode)
    findings = check_hlo(captured.hlo_text, contract)
    findings += check_fingerprint(captured.history, contract)
    # parity against the CURRENT lowering, not the contract's own stored
    # measurement — a backend upgrade that reshapes the overlap
    # collectives must fail this gate, not wait for --update
    findings += check_overlap_parity(
        contract, measured=collective_bytes(captured.hlo_text))
    return findings


def build_contract(mode: str, captured: Optional[CapturedMode] = None
                   ) -> dict:
    """Measure the mode's program and emit its contract dict (--update)."""
    t = MODE_TEMPLATES[mode]
    captured = captured or capture_mode(mode)
    acct = collective_bytes(captured.hlo_text)
    observed = sorted(k for k, v in acct.items()
                      if k not in ("total", "count") and v > 0)
    budgets = {k: acct[k] for k in observed}
    if t.get("async_twins"):
        # admit the -start half of each observed collective at the same
        # byte budget: async backends split every group reduce into a
        # -start/-done pair (the overlap the grouping exists for); the
        # sync CPU lowering just never uses the allowance
        for k in observed:
            if not k.endswith("-start"):
                budgets.setdefault(f"{k}-start", acct[k])
    contract = {
        "mode": mode,
        "description": t["description"],
        "params": t["params"],
        "num_devices": t["num_devices"],
        "program": t["program"],
        "collectives": {
            "allow": sorted(budgets),
            "require": list(t["require"]),
            "max_bytes": budgets,
        },
        "forbid_host_ops": True,
        "int_dot_s32": True,
        "require_integer_dot": bool(t["require_integer_dot"]),
        "stable_fingerprint": True,
        "measured": {k: v for k, v in sorted(acct.items())},
    }
    if "baseline_params" in t:
        bt = dict(t, params=dict(t["params"], **t["baseline_params"]))
        base_cap = capture_mode(mode, bt)
        contract["measured_baseline"] = {
            k: v for k, v in sorted(collective_bytes(
                base_cap.hlo_text).items())}
    # memory block (ISSUE 15): the native-mesh per-chip budget+estimate,
    # with any previously recorded budget kept STICKY and any additional
    # mesh keys (the spmd flight matrix) and spmd schedule blocks
    # preserved verbatim — those are re-recorded by scripts/tpulint
    # spmd --update, not here
    prior: dict = {}
    if os.path.exists(contract_path(mode)):
        prior = load_contract(mode)
    native = str(t["num_devices"])
    mem = dict(prior.get("memory", {}))
    mem[native] = memory.contract_block(
        captured.hlo_text, prior=prior.get("memory", {}).get(native))
    contract["memory"] = mem
    if "spmd" in prior:
        contract["spmd"] = prior["spmd"]
    return contract


def capture_serving(mode: str) -> str:
    """Train a tiny Booster and AOT-lower ``mode``'s serving-engine
    predict program at the smallest ladder rung (GBDT.aot_lower_serving
    — abstract inputs, nothing transferred). Returns the compiled HLO
    text. CPU-backend only, like :func:`capture_mode`."""
    import jax

    import lightgbm_tpu as lgb

    t = SERVING_TEMPLATES[mode]
    platform = jax.devices()[0].platform
    if platform != "cpu":
        raise RuntimeError(
            f"serving contracts are CPU-backend lowerings, but this "
            f"process's jax backend is '{platform}' — run via "
            "scripts/tpulint hlo")
    X, y = _tiny_problem(**t["problem"])
    bst = lgb.Booster(dict(t["params"]), lgb.Dataset(X, label=y))
    for _ in range(4):
        bst.update()
    return bst._gbdt.aot_lower_serving(t["engine"]).compile().as_text()


def build_serving_contract(mode: str, hlo_text: Optional[str] = None
                           ) -> dict:
    """Measure a serving engine's program and emit its contract dict.

    Same checking schema as the step-program contracts (collectives
    inventory — empty: a single-chip serving dispatch must move zero
    cross-chip bytes — host ops, int-dot accumulators, sticky memory
    budget); ``stable_fingerprint`` is off because the program is
    lowered AOT once, not captured across iterations."""
    t = SERVING_TEMPLATES[mode]
    hlo_text = hlo_text if hlo_text is not None else capture_serving(mode)
    acct = collective_bytes(hlo_text)
    prior: dict = {}
    if os.path.exists(contract_path(mode)):
        prior = load_contract(mode)
    return {
        "mode": mode,
        "description": t["description"],
        "params": t["params"],
        "engine": t["engine"],
        "num_devices": 1,
        "program": t["program"],
        "collectives": {"allow": [], "require": [], "max_bytes": {}},
        "forbid_host_ops": True,
        "int_dot_s32": True,
        "require_integer_dot": False,
        "stable_fingerprint": False,
        "measured": {k: v for k, v in sorted(acct.items())},
        "memory": {"1": memory.contract_block(
            hlo_text, prior=prior.get("memory", {}).get("1"))},
    }


def verify_serving_contracts(modes: Sequence[str] = SERVING_MODES,
                             update: bool = False,
                             check_drift: bool = True
                             ) -> List[ContractFinding]:
    """The serving half of the contract gate: every serving engine's
    program re-lowered and verified (or re-recorded with ``update``)
    against ``analysis/contracts/serve_*.json``."""
    findings: List[ContractFinding] = []
    for mode in modes:
        hlo_text = capture_serving(mode)
        fresh = build_serving_contract(mode, hlo_text)
        if update:
            os.makedirs(CONTRACTS_DIR, exist_ok=True)
            with open(contract_path(mode), "w") as fh:
                json.dump(fresh, fh, indent=1, sort_keys=True)
                fh.write("\n")
        if not os.path.exists(contract_path(mode)):
            findings.append(ContractFinding(
                mode, "missing",
                f"no checked-in contract at {contract_path(mode)} — run "
                "scripts/verify_contracts.py --update"))
            continue
        contract = load_contract(mode)
        findings += check_hlo(hlo_text, contract)
        fresh_fp = drift_fingerprint(fresh)
        contract_fp = drift_fingerprint(contract)
        if check_drift and not update and fresh_fp != contract_fp:
            drift = sorted(k for k in set(fresh_fp) | set(contract_fp)
                           if fresh_fp.get(k) != contract_fp.get(k))
            findings.append(ContractFinding(
                mode, "drift",
                f"regenerated serving contract differs from the "
                f"checked-in file in {drift} — the engine's program "
                "shape drifted; if intended, rerun "
                "scripts/verify_contracts.py --update and review the "
                "diff"))
    return findings


def verify_contracts(modes: Sequence[str] = MODES, update: bool = False,
                     check_drift: bool = True) -> List[ContractFinding]:
    """The full gate: every registry entry covered, every mode verified,
    and the regenerated measurement diffed against the checked-in
    contract (silent comm-shape drift fails tier-1; ``update=True``
    rewrites the files instead)."""
    findings: List[ContractFinding] = []
    for mode in modes:
        captured = capture_mode(mode)
        fresh = build_contract(mode, captured)
        if update:
            os.makedirs(CONTRACTS_DIR, exist_ok=True)
            with open(contract_path(mode), "w") as fh:
                json.dump(fresh, fh, indent=1, sort_keys=True)
                fh.write("\n")
        if not os.path.exists(contract_path(mode)):
            findings.append(ContractFinding(
                mode, "missing",
                f"no checked-in contract at {contract_path(mode)} — run "
                "scripts/verify_contracts.py --update"))
            continue
        contract = load_contract(mode)
        findings += verify_mode(mode, contract, captured)
        fresh_fp = drift_fingerprint(fresh)
        contract_fp = drift_fingerprint(contract)
        if check_drift and not update and fresh_fp != contract_fp:
            drift = sorted(k for k in set(fresh_fp) | set(contract_fp)
                           if fresh_fp.get(k) != contract_fp.get(k))
            findings.append(ContractFinding(
                mode, "drift",
                f"regenerated contract differs from the checked-in file "
                f"in {drift} — comm/program shape drifted; if intended, "
                "rerun scripts/verify_contracts.py --update and review "
                "the diff"))
    # the serving-engine programs ride the same gate (their modes are
    # SERVING_TEMPLATES, captured via aot_lower_serving)
    findings += verify_serving_contracts(update=update,
                                         check_drift=check_drift)
    # per-registry-entry coverage AFTER the update loop, so --update can
    # create a new entry's contract file in the same invocation
    findings += registry_contract_findings()
    return findings


def main(argv: Optional[Sequence[str]] = None) -> int:
    """CLI body for ``scripts/tpulint hlo`` / scripts/verify_contracts.py.

    Must run before jax initializes a backend elsewhere in the process:
    it forces the CPU platform with enough virtual devices for every
    requested mode.
    """
    import argparse
    ap = argparse.ArgumentParser(
        prog="tpulint hlo",
        description="verify the learner-mode HLO contracts on the CPU "
                    "backend (no TPU required)")
    ap.add_argument("modes", nargs="*", default=list(MODES),
                    help=f"modes to verify (default: all of {list(MODES)})")
    ap.add_argument("--update", action="store_true",
                    help="regenerate analysis/contracts/*.json from the "
                         "current lowering instead of failing on drift")
    args = ap.parse_args(argv)
    modes = args.modes or list(MODES)
    unknown = [m for m in modes if m not in MODE_TEMPLATES]
    if unknown:
        print(f"hlo_check: unknown mode(s) {unknown}; "
              f"known: {list(MODES)}")
        return 2

    # jax reads JAX_PLATFORMS/XLA_FLAGS at IMPORT time, and importing this
    # module already pulled the package (and jax) in — so the pre-import
    # env lives in ONE place, scripts/tpulint's hlo branch (which
    # scripts/verify_contracts.py execs). Here only the post-import
    # platform override remains (the same move as tests/conftest.py); the
    # virtual device count cannot be raised after backend init, so
    # capture_mode raises an actionable error if too few devices exist.
    try:
        import jax
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass   # backend already initialized elsewhere; device check below

    findings = verify_contracts(modes, update=args.update)
    for f in findings:
        print(f.render())
    if args.update and not findings:
        print(f"hlo_check: contracts regenerated for {list(modes)}")
    if not findings:
        print(f"hlo_check: {len(modes)} contract(s) verified clean")
    return 1 if findings else 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
