"""R004 — Pallas/Mosaic kernel contract checks.

The fused kernels carry hard contracts that the compiler cannot check for
the caller (ops/fused_split.py module docstring):

  * block sizes must be 32-multiples — Mosaic's DMA checker needs offsets
    provably divisible by the sublane tiling; a literal that is not a
    32-multiple fails at runtime on device only.
  * environment overrides must not flow into a block size raw: the
    automatic derivation rounds to 32 and re-checks the scoped-VMEM
    estimate, and an unvalidated ``int(os.environ[...])`` bypasses both
    (the seed case: LGBM_TPU_FUSED_BS, boosting/gbdt.py — ADVICE r5 #3).
    An assignment whose target looks like a block size and whose value
    reads ``os.environ`` must go through a validating helper (a call with
    "valid" or "round" in its name) or inline ``// 32`` rounding.
  * ``fused_split`` callers must pass ``num_rows`` so the kernel's
    ``pad >= block_size`` contract is enforced statically instead of
    silently clamping rows away (ADVICE r5 #2; the raise lives in
    ops/fused_split.py).
  * batched-M pending rings (round 6, ops/fused_split.py hist_flush):
    a constant ``mbatch`` must keep 8*mbatch within the 128 MXU rows,
    and ``mbatch x block_size`` VMEM residency (the transposed block
    slots, their channel slots, and the flush's one-hot, evaluated for
    both the bf16 and int8 channel layouts) must stay
    under the scoped-VMEM ring budget — the arithmetic lives in
    ops/fused_split.py fused_ring_bytes and is evaluated here at the
    minimum 128-byte record width.
  * a kernel that stages histogram blocks into a pending ring (writes
    to a ``pend*`` buffer keyed off ``mbatch``) must drain the
    ``pushes % mbatch`` remainder: without a drain function carrying
    that modulo, the last partial batch is silently dropped and every
    histogram whose block count is not a multiple of K is wrong.
  * bins-on-sublanes layout contracts (round 6): a constant
    ``hist_layout="sublane"`` needs ``num_bins <= 64`` (bins lie along
    sublanes; wider counts cannot group features into the 128 MXU rows),
    and the pending-ring VMEM budget is evaluated under BOTH layouts
    (since PR 33 both stage the same transposed operands,
    ops/fused_split.py fused_ring_bytes). The formula takes the RECORD
    width as its
    ``num_cols`` — under RowLayout.packed4 that width is already the
    nibble-packed one, so packing tightens the bound instead of
    escaping it.
  * pack4 nibble extraction (round 6): a right-shift that selects a
    nibble (``>> 4`` or ``>> ((f & 1) * 4)``-shaped) from a packed bin
    byte must mask the result with ``& 0xF`` — without the mask the
    neighbour feature's high nibble rides along and every downstream
    compare (one-hot, routing predicate) silently mismatches on half
    the rows (ops/fused_split.py bin_row is the canonical site).
  * engine-registry ownership (round 12): histogram-engine selection
    lives in ONE place, ``lightgbm_tpu/engines/``. Outside that package,
    (a) a ``GrowerParams(...)`` / ``._replace(...)`` call setting an
    engine knob (``hist_impl``/``hist_layout``/``hist_mbatch``/
    ``fused_block``) from anything but a registry resolution (a value
    mentioning ``resolved``/``resolution``/``registry``), (b) a
    function choosing between engine-impl constants (assigning or
    returning two or more of ``"xla"``/``"pallas"``/``"fused"``), and
    (c) a histogram call pinning a constant ``impl=``/``layout=`` are
    all findings — a hardcoded engine choice silently bypasses the
    registry's user/env override order. The one sanctioned escape hatch
    is ``ops/histogram.py::_resolve_impl`` (allowlist-anchored): the
    trace-time per-call-width dispatch that runs when the registry hands
    ``"auto"`` through.
  * serving-engine contract coverage (round 20): every serving
    ``EngineEntry`` (``id`` starting with ``serve``) must either name an
    HLO contract id (``contracts=("serve_walk",)`` — verified by
    hlo_check.verify_serving_contracts against
    analysis/contracts/<mode>.json) or carry a non-empty
    ``contract_exempt`` justification that names the pinning test
    (``tests/...``). An uncovered serving entry ships a compiled
    program nothing re-verifies — host callbacks or stray collectives
    in the serving path would land silently.
  * quantized-leaf scales must ship their recorded bound (round 20):
    the quantized slab is only safe to serve because
    ``quantize_leaves`` returns an exact max-score-error bound next to
    the scale. An unpack that discards the bound
    (``slab, scale = quantize_leaves(...)`` or a ``_`` third target),
    or a hand-rolled symmetric int8 scale (``amax / 127``) in a
    function that never assigns a ``bound``/``err`` value, serves
    quantized scores with no recorded accuracy contract.
"""
from __future__ import annotations

import ast
from typing import List

from .base import (Finding, ModuleInfo, PackageInfo, Rule, call_name,
                   dotted_name)

_BLOCK_KWARGS = {"block_size", "bs", "fused_block"}
_MBATCH_KWARGS = {"mbatch", "hist_mbatch"}
_MBATCH_MAX = 16          # 8K <= 128 MXU rows

# engine-registry ownership (sub-checks (a)-(c) in the docstring)
_ENGINE_KWARGS = {"hist_impl", "hist_layout", "hist_mbatch", "fused_block"}
_ENGINE_CONSTS = {"xla", "pallas", "fused"}
_ENGINE_CALL_KWARGS = {"impl", "hist_impl", "layout", "hist_layout"}
_REGISTRY_TOKENS = ("resolv", "registry")


def _is_registry_module(module: ModuleInfo) -> bool:
    """True for the engine-registry package itself (the one place
    engine-selection policy may live)."""
    path = module.path.replace("\\", "/")
    return "/engines/" in path or path.startswith("engines/") \
        or (module.dotted or "").startswith("lightgbm_tpu.engines")


def _mentions_registry(node: ast.AST) -> bool:
    """A value expression sourced from a registry resolution: it
    references a name/attribute/call mentioning ``resolv*``/``registry``
    (``resolved.hist_impl``, ``engine_registry.clamp_fused_block(...)``,
    a local named ``resolved_bs``)."""
    for n in ast.walk(node):
        if isinstance(n, ast.Name) and \
                any(t in n.id.lower() for t in _REGISTRY_TOKENS):
            return True
        if isinstance(n, ast.Attribute) and \
                any(t in n.attr.lower() for t in _REGISTRY_TOKENS):
            return True
    return False


def _target_is_blocky(name: str) -> bool:
    low = name.lower()
    return "block" in low or low in ("bs", "bs_", "fused_bs") \
        or low.endswith("_bs") or low.startswith("bs_")


def _reads_environ(node: ast.AST) -> bool:
    return any(dotted_name(n) == "os.environ"
               for n in ast.walk(node) if isinstance(n, ast.Attribute))


def _has_validation(node: ast.AST) -> bool:
    for n in ast.walk(node):
        if isinstance(n, ast.Call):
            name = (call_name(n) or "").rsplit(".", 1)[-1].lower()
            if "valid" in name or "round" in name or "clamp" in name:
                return True
        if isinstance(n, ast.BinOp) and isinstance(n.op, ast.FloorDiv) \
                and isinstance(n.right, ast.Constant) and n.right.value == 32:
            return True
    return False


class PallasContractRule(Rule):
    code = "R004"
    title = "Pallas kernel contract checks"

    def check(self, module: ModuleInfo, package: PackageInfo
              ) -> List[Finding]:
        out: List[Finding] = []
        func_of = _FuncIndex(module)
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Call):
                out.extend(self._check_call(module, node, func_of))
                out.extend(self._check_serving_entry(
                    module, node, func_of))
                if not _is_registry_module(module):
                    out.extend(self._check_engine_kwargs(
                        module, node, func_of))
                    out.extend(self._check_engine_call_consts(
                        module, node, func_of))
            elif isinstance(node, ast.Assign):
                out.extend(self._check_env_assign(module, node, func_of))
                out.extend(self._check_quant_unpack(module, node, func_of))
        for fn in module.functions.values():
            out.extend(self._check_defaults(module, fn))
            out.extend(self._check_quant_scale(module, fn))
            if not _is_registry_module(module):
                out.extend(self._check_engine_chooser(module, fn))
        out.extend(self._check_ring_drain(module))
        out.extend(self._check_nibble_masks(module, func_of))
        return out

    # -- serving-engine contract coverage (round 20) --------------------
    def _check_serving_entry(self, module, node: ast.Call, func_of
                             ) -> List[Finding]:
        """A serving ``EngineEntry`` (id starting with "serve") must name
        an HLO contract id or carry a contract_exempt justification that
        points at the pinning test (a ``tests/`` path); otherwise the
        entry ships a compiled serving program nothing re-verifies."""
        name = (call_name(node) or "").rsplit(".", 1)[-1]
        if name != "EngineEntry":
            return []
        eid = None
        for kw in node.keywords:
            if kw.arg == "id" and isinstance(kw.value, ast.Constant) \
                    and isinstance(kw.value.value, str):
                eid = kw.value.value
        if node.args and eid is None \
                and isinstance(node.args[0], ast.Constant) \
                and isinstance(node.args[0].value, str):
            eid = node.args[0].value
        if not eid or not eid.startswith("serve"):
            return []
        contracts_ok = exempt_ok = exempt_present = False
        for kw in node.keywords:
            if kw.arg == "contracts":
                if isinstance(kw.value, (ast.Tuple, ast.List)) \
                        and kw.value.elts:
                    contracts_ok = True
                elif not isinstance(kw.value, (ast.Tuple, ast.List)):
                    contracts_ok = True     # computed value: trust it
            elif kw.arg == "contract_exempt":
                if isinstance(kw.value, ast.Constant) \
                        and isinstance(kw.value.value, str):
                    exempt_present = bool(kw.value.value.strip())
                    exempt_ok = "tests/" in kw.value.value
                else:
                    exempt_present = exempt_ok = True  # computed: trust
        if contracts_ok or exempt_ok:
            return []
        what = ("its contract_exempt justification does not name the "
                "pinning test (a tests/ path)") if exempt_present else \
               ("it names no HLO contract id and carries no "
                "contract_exempt justification")
        return [self.finding(
            module, node, func_of(node),
            f"serving EngineEntry {eid!r}: {what} — every serving "
            "engine either ships a verified HLO contract "
            "(analysis/contracts/<mode>.json, checked by "
            "verify_serving_contracts) or a contract_exempt string "
            "naming the parity test that pins its output")]

    # -- quantized-leaf recorded bound (round 20) -----------------------
    def _check_quant_unpack(self, module, node: ast.Assign, func_of
                            ) -> List[Finding]:
        """``quantize_leaves`` returns (slab, scale, bound); an unpack
        that drops or discards the bound serves quantized scores with no
        recorded accuracy contract."""
        if not (isinstance(node.value, ast.Call)
                and (call_name(node.value) or "").rsplit(".", 1)[-1]
                == "quantize_leaves"):
            return []
        if len(node.targets) != 1 \
                or not isinstance(node.targets[0], ast.Tuple):
            return []                      # whole-tuple capture: bound kept
        elts = node.targets[0].elts
        dropped = len(elts) < 3 or (
            isinstance(elts[2], ast.Name) and elts[2].id == "_")
        if not dropped:
            return []
        return [self.finding(
            module, node, func_of(node),
            "quantize_leaves unpack discards the recorded "
            "max-score-error bound — the bound is the accuracy contract "
            "the quantized slab ships (leaf_quant_bound); keep it next "
            "to the scale instead of serving quantized scores blind")]

    def _check_quant_scale(self, module, fn) -> List[Finding]:
        """A hand-rolled symmetric int8 leaf scale (an assignment to a
        ``*scale*`` name whose value divides by 127) in a function that
        never assigns a ``bound``/``err`` value has no recorded error
        bound at all — the seed shape quantize_leaves exists to
        prevent."""
        site = None
        records_bound = False
        for n in fn.own_nodes():
            if not isinstance(n, ast.Assign):
                continue
            names = [t.id for t in n.targets if isinstance(t, ast.Name)]
            names += [e.id for t in n.targets
                      if isinstance(t, ast.Tuple)
                      for e in t.elts if isinstance(e, ast.Name)]
            if any("bound" in m.lower() or "err" in m.lower()
                   for m in names):
                records_bound = True
            if site is None and any("scale" in m.lower() for m in names) \
                    and self._divides_by_127(n.value):
                site = n
        if site is None or records_bound:
            return []
        return [self.finding(
            module, site, fn.qualname,
            "symmetric int8 leaf scale computed without a recorded "
            "error bound: nothing in this function assigns a "
            "bound/err value, so the quantized slab ships with no "
            "accuracy contract — use quantize_leaves (slab, scale, "
            "bound) or record the per-tree worst-case dequantization "
            "error next to the scale")]

    @staticmethod
    def _divides_by_127(value: ast.AST) -> bool:
        for n in ast.walk(value):
            if isinstance(n, ast.BinOp) and isinstance(n.op, ast.Div) \
                    and isinstance(n.right, ast.Constant) \
                    and isinstance(n.right.value, (int, float)) \
                    and float(n.right.value) == 127.0:
                return True
        return False

    # -- engine-registry ownership (round 12) ---------------------------
    def _check_engine_kwargs(self, module, node: ast.Call, func_of
                             ) -> List[Finding]:
        """(a) GrowerParams(hist_*=...) / ._replace(hist_*=...) outside
        lightgbm_tpu/engines must source the value from a registry
        resolution — anything else re-opens a second selection site."""
        name = (call_name(node) or "").rsplit(".", 1)[-1]
        if name not in ("GrowerParams", "_replace"):
            return []
        out: List[Finding] = []
        for kw in node.keywords:
            if kw.arg in _ENGINE_KWARGS and \
                    not _mentions_registry(kw.value):
                out.append(self.finding(
                    module, kw.value, func_of(node),
                    f"{name}({kw.arg}=...) outside lightgbm_tpu/engines "
                    "selects a histogram engine knob away from the "
                    "registry — populate it from a registry.resolve "
                    "Resolution (user > env > platform and shape) so "
                    "the override order cannot be bypassed"))
        return out

    def _check_engine_call_consts(self, module, node: ast.Call, func_of
                                  ) -> List[Finding]:
        """(c) a histogram DISPATCH call (histogram_block / histogram —
        the funnels the registry's resolution threads through) pinning
        ``impl=``/``layout=`` to a constant hardcodes an engine choice;
        direct engine-callable calls (pallas_histogram) stay under the
        existing block/sublane contracts."""
        name = (call_name(node) or "").rsplit(".", 1)[-1]
        if name not in ("histogram_block", "histogram"):
            return []
        out: List[Finding] = []
        for kw in node.keywords:
            if kw.arg in _ENGINE_CALL_KWARGS and \
                    isinstance(kw.value, ast.Constant) and \
                    isinstance(kw.value.value, str) and \
                    kw.value.value != "auto":
                out.append(self.finding(
                    module, kw.value, func_of(node),
                    f"{name}({kw.arg}={kw.value.value!r}): constant "
                    "engine selection outside lightgbm_tpu/engines — "
                    "thread the registry-resolved value (GrowerParams) "
                    "through instead of pinning the engine at the "
                    "callsite"))
        return out

    def _check_engine_chooser(self, module, fn) -> List[Finding]:
        """(b) a function assigning/returning >= 2 distinct engine-impl
        constants IS an engine-selection policy site; outside the
        registry that policy is unowned (the ops/histogram.py
        _resolve_impl trace-time escape hatch carries the one allowlist
        anchor)."""
        consts = set()
        first = None
        for n in fn.own_nodes():
            vals = []
            if isinstance(n, ast.Return) and n.value is not None:
                vals = [n.value]
            elif isinstance(n, ast.Assign):
                vals = [n.value]
            for v in vals:
                if isinstance(v, ast.IfExp):
                    vals.extend([v.body, v.orelse])
                    continue
                if isinstance(v, ast.Constant) and v.value in _ENGINE_CONSTS:
                    consts.add(v.value)
                    first = first or n
        if len(consts) < 2:
            return []
        return [self.finding(
            module, first or fn.node, fn.qualname,
            f"function selects between engine impls {sorted(consts)} "
            "outside lightgbm_tpu/engines — engine-selection policy "
            "belongs to the registry (engines/registry.py), where the "
            "user/env override order applies; the only sanctioned "
            "exception is the trace-time dispatch in ops/histogram.py "
            "_resolve_impl (allowlisted)")]

    def _check_call(self, module, node: ast.Call, func_of) -> List[Finding]:
        name = (call_name(node) or "").rsplit(".", 1)[-1]
        out: List[Finding] = []
        if name not in ("fused_split", "pallas_call", "pallas_histogram"):
            return out
        for kw in node.keywords:
            if kw.arg in _BLOCK_KWARGS and \
                    isinstance(kw.value, ast.Constant) and \
                    isinstance(kw.value.value, int) and \
                    kw.value.value % 32 != 0:
                out.append(self.finding(
                    module, kw.value, func_of(node),
                    f"{name}({kw.arg}={kw.value.value}): block sizes "
                    "must be 32-multiples (Mosaic DMA sublane "
                    "alignment)"))
        if name == "fused_split" and not any(
                kw.arg == "num_rows" for kw in node.keywords):
            out.append(self.finding(
                module, node, func_of(node),
                "fused_split call without num_rows= — the "
                "pad >= block_size contract cannot be checked "
                "statically and a short pad silently drops tail rows"))
        out.extend(self._check_sublane(module, node, func_of, name))
        out.extend(self._check_mbatch(module, node, func_of, name))
        return out

    def _check_sublane(self, module, node: ast.Call, func_of,
                       name: str) -> List[Finding]:
        """Constant-foldable bins-on-sublanes block-shape contract: a
        sublane layout with num_bins > 64 cannot group features into the
        128 MXU rows (ops/pallas_histogram.py _SUBLANE_MAX_BINS)."""
        layout = bins = None
        for kw in node.keywords:
            if kw.arg in ("hist_layout", "layout") and \
                    isinstance(kw.value, ast.Constant):
                layout = kw.value.value
            elif kw.arg == "num_bins" and \
                    isinstance(kw.value, ast.Constant) and \
                    isinstance(kw.value.value, int):
                bins = kw.value.value
        if layout != "sublane":
            return []
        if bins is None and name == "pallas_histogram" \
                and len(node.args) >= 3 \
                and isinstance(node.args[2], ast.Constant) \
                and isinstance(node.args[2].value, int):
            bins = node.args[2].value
        if bins is None or bins <= 64:
            return []
        return [self.finding(
            module, node, func_of(node),
            f"{name}(hist_layout='sublane', num_bins={bins}): the "
            "bins-on-sublanes layout supports num_bins <= 64 — wider bin "
            "counts leave no room to group features into the 128 MXU "
            "rows (bins lie along sublanes)")]

    def _check_mbatch(self, module, node: ast.Call, func_of,
                      name: str) -> List[Finding]:
        """Constant-foldable batched-M contracts: MXU-row bound + the
        pending ring's scoped-VMEM budget (both channel layouts)."""
        mb = bs = None
        layouts = ("lane",)             # the parameter default
        for kw in node.keywords:
            if kw.arg in ("hist_layout",) and \
                    isinstance(kw.value, ast.Constant):
                # constant layout: charge that layout's formula; a traced/
                # computed layout charges both (conservative)
                layouts = ((kw.value.value,)
                           if kw.value.value in ("lane", "sublane")
                           else ("lane", "sublane"))
            if isinstance(kw.value, ast.Constant) and \
                    isinstance(kw.value.value, int):
                if kw.arg in _MBATCH_KWARGS:
                    mb = kw.value.value
                elif kw.arg in _BLOCK_KWARGS:
                    bs = kw.value.value
            elif kw.arg == "hist_layout" and \
                    not isinstance(kw.value, ast.Constant):
                layouts = ("lane", "sublane")
        if mb is None:
            return []
        out: List[Finding] = []
        if not 1 <= mb <= _MBATCH_MAX:
            out.append(self.finding(
                module, node, func_of(node),
                f"{name}(mbatch={mb}): the batched-M depth must stay in "
                f"[1, {_MBATCH_MAX}] — 8*mbatch output rows must fit the "
                "128 MXU rows (ops/fused_split.py hist_flush)"))
            return out
        if name == "fused_split" and bs is not None:
            from ...ops.fused_split import (_VMEM_RING_BUDGET,
                                            fused_ring_bytes)
            # minimum 128-byte record width (packed4 layouts are NARROWER,
            # so this floor covers them); evaluated for both channel
            # dtypes AND both register layouts
            worst = max(
                fused_ring_bytes(bs, 128, mb, quant=q, hist_layout=hl)
                for q in (False, True) for hl in layouts)
            if worst > _VMEM_RING_BUDGET:
                out.append(self.finding(
                    module, node, func_of(node),
                    f"{name}(block_size={bs}, mbatch={mb}): the pending "
                    f"ring needs >= {worst >> 20}MB of scoped VMEM "
                    f"(budget {_VMEM_RING_BUDGET >> 20}MB) even at the "
                    "minimum record width — derive the block size via "
                    "fused_block_cap(num_cols, mbatch)"))
        return out

    def _check_ring_drain(self, module) -> List[Finding]:
        """A kernel that stages histogram blocks into a pending ring
        (writes a ``pend*`` buffer keyed off ``mbatch``) must drain the
        ``pushes % mbatch`` remainder somewhere in the module: a drain
        function carrying ``lax.rem(_, mbatch)`` / ``_ % mbatch``."""
        stagers = []
        has_drain = False
        for fname, fn in module.functions.items():
            writes_pend = any(
                isinstance(n, ast.Assign) and any(
                    isinstance(t, ast.Subscript)
                    and isinstance(t.value, ast.Name)
                    and t.value.id.startswith("pend")
                    for t in n.targets)
                for n in ast.walk(fn.node))
            uses_mbatch = any(
                isinstance(n, ast.Name) and n.id in _MBATCH_KWARGS
                for n in ast.walk(fn.node))
            if writes_pend and uses_mbatch:
                stagers.append(fn)
            if "drain" in fname.lower() and self._has_mbatch_rem(fn.node):
                has_drain = True
        if not stagers or has_drain:
            return []
        fn = stagers[0]
        return [self.finding(
            module, fn.node, fn.qualname,
            "pending-ring staging without a remainder drain: no 'drain' "
            "function computes pushes % mbatch, so the last partial "
            "batch of staged histogram blocks is silently dropped "
            "whenever the block count is not a multiple of mbatch")]

    # names whose reads plausibly hold a PACKED bin byte (two features
    # per byte): the detector scopes to these so unrelated bit twiddling
    # (word-index shifts, radix unpacks) stays out of view
    _PACKY = ("pack", "nibble", "byte")

    def _check_nibble_masks(self, module, func_of) -> List[Finding]:
        """pack4 unpack sites must mask: ``X >> 4`` (or the dynamic
        ``X >> ((f & 1) * 4)`` form) on a packed bin byte without an
        ``& 0xF`` around it leaves the neighbour feature's nibble in the
        result — flagged unless the shift sits under a BitAnd with 15."""
        parents = {}
        for node in ast.walk(module.tree):
            for child in ast.iter_child_nodes(node):
                parents[child] = node
        out: List[Finding] = []
        for node in ast.walk(module.tree):
            if not (isinstance(node, ast.BinOp)
                    and isinstance(node.op, ast.RShift)):
                continue
            if not self._is_nibble_shift(node.right):
                continue
            if not self._touches_packed(module, node, parents):
                continue
            if self._masked_with_0xf(node, parents):
                continue
            out.append(self.finding(
                module, node, func_of(node),
                "pack4 nibble extract without the & 0xF mask: the shift "
                "selects a nibble from a packed bin byte, but the "
                "neighbour feature's nibble survives in the high bits — "
                "every downstream bin compare silently mismatches "
                "(mask the result with & 0xF)"))
        return out

    @staticmethod
    def _is_nibble_shift(rhs: ast.AST) -> bool:
        """Shift amounts that select a nibble: the constant 4, or an
        expression multiplying by 4 (the ``(f & 1) * 4`` dynamic form)."""
        if isinstance(rhs, ast.Constant):
            return rhs.value == 4
        for n in ast.walk(rhs):
            if isinstance(n, ast.BinOp) and isinstance(n.op, ast.Mult):
                for side in (n.left, n.right):
                    if isinstance(side, ast.Constant) and side.value == 4:
                        return True
        return False

    def _touches_packed(self, module, node: ast.BinOp, parents) -> bool:
        """Scope: the shifted value's name mentions a packed-byte source,
        or the enclosing function is a pack4 helper."""
        for n in ast.walk(node.left):
            if isinstance(n, ast.Name) and \
                    any(t in n.id.lower() for t in self._PACKY):
                return True
            if isinstance(n, ast.Attribute) and \
                    any(t in n.attr.lower() for t in self._PACKY):
                return True
        cur = node
        while cur in parents:
            cur = parents[cur]
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and any(t in cur.name.lower()
                            for t in ("pack", "nibble", "bin_col",
                                      "bin_row")):
                return True
        return False

    @staticmethod
    def _masked_with_0xf(node: ast.AST, parents) -> bool:
        """True when an ancestor BitAnd masks with 15 (`& 0xF`, including
        the dtype-wrapped `& jnp.uint8(0x0F)` form)."""
        def is_0xf(n: ast.AST) -> bool:
            if isinstance(n, ast.Constant) and n.value == 15:
                return True
            return (isinstance(n, ast.Call) and len(n.args) == 1
                    and isinstance(n.args[0], ast.Constant)
                    and n.args[0].value == 15)

        cur = node
        while cur in parents:
            parent = parents[cur]
            if isinstance(parent, ast.BinOp) and \
                    isinstance(parent.op, ast.BitAnd) and \
                    (is_0xf(parent.left) or is_0xf(parent.right)):
                return True
            if not isinstance(parent, (ast.BinOp, ast.UnaryOp)):
                break
            cur = parent
        return False

    @staticmethod
    def _has_mbatch_rem(fn_node: ast.AST) -> bool:
        for n in ast.walk(fn_node):
            if isinstance(n, ast.BinOp) and isinstance(n.op, ast.Mod) \
                    and isinstance(n.right, ast.Name) \
                    and n.right.id in _MBATCH_KWARGS:
                return True
            if isinstance(n, ast.Call) and \
                    (call_name(n) or "").endswith("rem") and \
                    len(n.args) == 2 and isinstance(n.args[1], ast.Name) \
                    and n.args[1].id in _MBATCH_KWARGS:
                return True
        return False

    def _check_env_assign(self, module, node: ast.Assign, func_of
                          ) -> List[Finding]:
        targets = [t.id for t in node.targets if isinstance(t, ast.Name)]
        if not any(_target_is_blocky(t) for t in targets):
            return []
        if not _reads_environ(node.value) or _has_validation(node.value):
            return []
        return [self.finding(
            module, node, func_of(node),
            f"block size '{targets[0]}' taken raw from os.environ — "
            "round to a 32-multiple and re-check the scoped-VMEM "
            "estimate before accepting an override")]

    def _check_defaults(self, module, fn) -> List[Finding]:
        out: List[Finding] = []
        args = fn.node.args
        pos = args.posonlyargs + args.args
        defaults = [None] * (len(pos) - len(args.defaults)) \
            + list(args.defaults)
        pairs = list(zip(pos, defaults)) \
            + list(zip(args.kwonlyargs, args.kw_defaults))
        for param, default in pairs:
            if param.arg in _BLOCK_KWARGS and \
                    isinstance(default, ast.Constant) and \
                    isinstance(default.value, int) and \
                    default.value % 32 != 0:
                out.append(self.finding(
                    module, default, fn.qualname,
                    f"default {param.arg}={default.value} is not a "
                    "32-multiple (Mosaic DMA sublane alignment)"))
        return out


class _FuncIndex:
    """Map an AST node to its enclosing function qualname (by line span)."""

    def __init__(self, module: ModuleInfo):
        self.spans = []
        for fn in module.functions.values():
            end = getattr(fn.node, "end_lineno", fn.node.lineno)
            self.spans.append((fn.node.lineno, end, fn.qualname))
        self.spans.sort(key=lambda s: (s[0], -s[1]))

    def __call__(self, node: ast.AST) -> str:
        line = getattr(node, "lineno", 0)
        best = "<module>"
        for lo, hi, qual in self.spans:
            if lo <= line <= hi:
                best = qual            # innermost wins (sorted outer-first)
        return best
