"""Engine registry: the ONE owner of histogram-engine selection.

Through round 11 the engine knob space — {fused, pallas, xla-einsum} x
batched-M depth x block size x {lane, sublane} layout x learner mode —
was resolved by five ``_pick_*`` helpers spread through
``boosting/gbdt.py``, plus env overrides (``LGBM_TPU_FUSED_BS``,
``LGBM_TPU_HIST_MBATCH``) and per-op defaults. This module collapses
all of it behind one table (:data:`ENTRIES`) and one callsite
(:func:`resolve`), the way the reference resolves col-wise vs row-wise
histogram dispatch from ONE decision point at ``InitTrain``
(``dataset.h:727``) — and, like the reference, the decision can be
*measured* instead of guessed: the startup microbench autotuner
(``engines/autotune.py``, ``tpu_autotune``) times the eligible entries
on a slice of the real binned data and records the winner per
shape-class.

Resolve order, per knob (the contract every test in
tests/test_registry.py pins)::

    user explicit > env override > autotune cache > heuristic default

Registry entries carry their HLO-contract id: ``scripts/
verify_contracts.py`` enumerates contracts per entry (the entry id is
in the contract filename), so a new engine cannot land without either
a checked-in contract or a justified ``contract_exempt`` (TPU-only
Mosaic kernels, which the CPU contract harness cannot lower — their
parity is pinned by the cross-engine bit-identity tests instead).

tpulint R004 enforces the ownership: ``GrowerParams(hist_*=...)`` or a
direct engine-callable choice outside this package is a finding; the
one sanctioned escape hatch is ``ops/histogram.py::_resolve_impl``
(allowlist-anchored), the trace-time dispatch that keeps the measured
per-width heuristic when the registry hands ``"auto"`` through
(``tpu_autotune=off`` / no cache).

Module level is jax-free; functions that need a backend import jax
lazily.
"""
from __future__ import annotations

import os
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

from ..utils import log

#: platforms with a Mosaic backend — the ONE copy; every "is this a
#: TPU" question in the package goes through :func:`on_tpu`
TPU_PLATFORMS = ("tpu",)

#: batched-M depths the autotuner sweeps for the STANDALONE engines (the
#: Mosaic kernel's window partition and the XLA einsum's chunk widening:
#: M = 8K MXU rows, K <= 16). The default (8) leads so a tie resolves to
#: today's behavior, not to an arbitrary cell.
MBATCH_CANDIDATES = (8, 16, 1)

#: the fused kernel's own depth (ops/fused_split.py hist_flush), which it
#: no longer inherits from the standalone engines. On the chip (higgs,
#: 10.5M x 28, block 384; PERF.md section 6, PR 29) K = 1 / 2 / 4 train
#: at 0.932 / 0.939 / 0.949 s an iteration — the ring buys no speed — and
#: K = 6 / 7 / 8 / 16 at 6.6 / 8.3 / 9.7 / 25.3 s: once a flush spans
#: K x block >= 2048 rows every streamed row costs 60-96 ns more. The
#: standalone kernel's race goes the other way (k8 0.340 ms against k1
#: 0.445 ms). 2 and not 1, for 0.7% of an iteration: each flush adds
#: two blocks' partial sums to the f32 accumulator at once, half as many
#: roundings, and at 63 bins (164k rows a bin at the root) that halves
#: the worst leaf's hessian error (3.4e-4 at K = 1 against the
#: benchmark's 4e-4 limit; 1.8e-4 at K = 2, 0.9e-4 at K = 8). One value
#: for every shape the chip has measured (255 and 63 bins, int8
#: channels, F = 137); a shape that wants another gets it in
#: :func:`resolve_mbatch` from what the shape shows (``quant``, layout,
#: width), not from a new knob.
FUSED_MBATCH = 2


class DatasetShape(NamedTuple):
    """The static dataset facts engine selection keys on."""
    rows: int
    features: int
    num_bins: int
    mode: str = "serial"          # serial | data | voting | feature
    quant: bool = False           # use_quantized_grad (int8 channels)
    pack4: bool = False           # tpu_bin_pack4 (nibble-packed bins)
    #: the step is partitioned by GSPMD (the masked grower under a mesh:
    #: voting, feature, and every data-parallel configuration the compact
    #: grower does not take). Nothing partitions a Mosaic call — lowering
    #: one inside a GSPMD-sharded jit is refused ("Mosaic kernels cannot
    #: be automatically partitioned. Please wrap the call in a
    #: shard_map"; met compiling the voting step for a 4-chip v5e mesh) —
    #: so these shapes take the XLA einsum, which GSPMD splits over rows.
    #: The compact grower's step runs under shard_map and keeps the kernels
    gspmd: bool = False
    #: the compact grower takes this run (False: the masked grower on one
    #: chip — small data, a stochastic or caller-supplied objective).
    #: Only the compact grower fuses; not part of the shape class
    compact: bool = True


class EngineEntry(NamedTuple):
    """One histogram engine the registry can select.

    ``contracts`` names the ``analysis/contracts/<mode>.json`` files
    that pin this entry's steady-state step program (at least one file
    name must contain the entry id); ``contract_exempt`` is the
    mandatory justification when no CPU contract can exist (TPU-only
    Mosaic kernels). ``sweepable`` entries are timed standalone by the
    autotuner; the fused kernel is selected structurally (it replaces
    the partition+histogram streams and its binding constraint is the
    scoped-VMEM validator, :func:`clamp_fused_block`). It inherits the
    winning LAYOUT, which threads into its ``hist_flush``, and not the
    winning depth: what wins a standalone 16k-row race (8) loses a factor
    of ten inside the fused walk, so a fused entry runs
    :data:`FUSED_MBATCH` unless the user or the environment names a depth.
    """
    id: str
    impl: str                     # hist_impl fed to ops/histogram dispatch
    layout: str                   # lane | sublane
    fused: bool
    description: str
    contracts: Tuple[str, ...] = ()
    contract_exempt: str = ""
    max_bins: int = 256           # eligibility bound on the bin width
    requires_tpu: bool = False
    sweepable: bool = True
    #: mesh shapes (spmd_check keys: "1", "8", "4x2") every contract of
    #: this entry must carry a verified `memory` block for — the
    #: per-entry slice of the pod flight check (analysis/spmd_check.py);
    #: hlo_check.registry_contract_findings enumerates the coverage
    meshes: Tuple[str, ...] = ("1",)


ENTRIES: Tuple[EngineEntry, ...] = (
    EngineEntry(
        "xla_lane", "xla", "lane", False,
        "chunked one-hot einsum (fp32-HIGHEST / int8 -> s32), lane "
        "layout — runs on every backend",
        contracts=("xla_lane",)),
    EngineEntry(
        "pallas_lane", "pallas", "lane", False,
        "standalone Mosaic one-hot kernel, bins along lanes "
        "(ops/pallas_histogram.py)",
        contract_exempt="Mosaic kernels cannot lower on the CPU "
                        "contract harness; cross-engine bit-identity "
                        "is pinned by tests/test_ops.py and "
                        "tests/test_hist_mbatch.py",
        requires_tpu=True),
    EngineEntry(
        "pallas_sublane", "pallas", "sublane", False,
        "standalone Mosaic kernel, bins along sublanes (B <= 64: the "
        "one-hot compare fills the register tile)",
        contract_exempt="Mosaic kernels cannot lower on the CPU "
                        "contract harness; layout bit-identity is "
                        "pinned by tests/test_pack4_train.py",
        max_bins=64, requires_tpu=True),
    EngineEntry(
        "fused_lane", "auto", "lane", True,
        "fused partition+histogram Mosaic kernel (ops/fused_split.py), "
        "lane-layout hist_flush",
        contract_exempt="Mosaic kernels cannot lower on the CPU "
                        "contract harness; parity is pinned by "
                        "tests/test_fused.py leaf-count identity",
        requires_tpu=True, sweepable=False),
    EngineEntry(
        "fused_sublane", "auto", "sublane", True,
        "fused Mosaic kernel with the bins-on-sublanes hist_flush "
        "(B <= 64)",
        contract_exempt="Mosaic kernels cannot lower on the CPU "
                        "contract harness; layout bit-identity is "
                        "pinned by tests/test_pack4_train.py",
        max_bins=64, requires_tpu=True, sweepable=False),
)


#: serving-only inference engines (ROADMAP 4): same registry contract
#: as the histogram entries — an HLO contract id in the filename or a
#: justified exemption (tpulint R004 enforces it), selected through the
#: same resolve order by :func:`resolve_serving_engine`.
SERVING_ENTRIES: Tuple[EngineEntry, ...] = (
    EngineEntry(
        "serve_walk", "walk", "lane", False,
        "depth-batched pointer walk (ops/predict.py "
        "predict_raw_batched): one packed node-record gather over "
        "[Tb, L-1] per depth step",
        contracts=("serve_walk",), sweepable=True),
    EngineEntry(
        "serve_level", "level", "lane", False,
        "level-order heap relayout (predict_raw_level): depth step d "
        "reads the contiguous [Tb, 2^d] per-level slab; buckets deeper "
        "than tpu_level_depth_cap keep the walk",
        contracts=("serve_level",), sweepable=True),
    EngineEntry(
        "serve_qleaf", "qleaf", "lane", False,
        "quantized leaf slab (tpu_leaf_quant=int8|f16) over the "
        "resolved walk/level router: narrow leaf gather + per-tree "
        "dequant scale, with a recorded max-score-error bound",
        contract_exempt="shares the serve_walk/serve_level step "
                        "program shape (only the leaf-slab dtype "
                        "narrows); score deviation is pinned by the "
                        "RECORDED bound and "
                        "tests/test_level_engine.py",
        sweepable=True),
)

#: tpu_predict_engine spellings the serving resolver accepts
SERVING_ENGINE_VALUES = ("batched", "walk", "level", "scan", "auto")


class Candidate(NamedTuple):
    """One autotune sweep cell: an engine entry at a batched-M depth."""
    entry: EngineEntry
    mbatch: int

    @property
    def key(self) -> str:
        return f"{self.entry.id}-k{self.mbatch}"


class Resolution(NamedTuple):
    """The registry's answer: every engine knob, with provenance.

    ``sources`` maps knob -> one of ``user`` / ``env`` / ``autotune`` /
    ``default`` so logs and tests can see WHICH rung of the resolve
    order produced each value (``hist_impl`` may also read ``gspmd`` and
    ``hist_mbatch`` ``fused``: the structural answers). ``hist_mbatch``
    is the depth the run's histograms are built at: the fused kernel's
    under a fused entry, the standalone engines' otherwise.
    """
    entry_id: str
    fused_block: int
    hist_impl: str
    hist_mbatch: int
    hist_layout: str
    hist_overlap: int
    step_buckets: bool
    sources: Dict[str, str]
    shape_class: Optional[str] = None
    autotuned: bool = False
    # the raw autotune winner this resolution applied (None = none):
    # reset_parameter re-resolves against THIS, not a cache re-read —
    # the in-run engine choice must survive an unwritable cache and
    # must never flip because the file changed under a live run
    decision: Optional[Dict[str, Any]] = None


# ---------------------------------------------------------------------------
# shape classes
# ---------------------------------------------------------------------------
def _rung(x: int) -> int:
    """Power-of-two rung (>= 1) — shape classes bucket like the step
    ladder does, so near-identical datasets share one decision."""
    return 1 << max(0, (max(1, int(x)) - 1).bit_length())


def shape_class(shape: DatasetShape) -> str:
    """Canonical shape-class key: learner mode + row/feature rungs +
    exact bin width + dtype/layout markers. The autotune cache and
    BENCH_SHAPES["autotune"] both key on it."""
    tags = ""
    if shape.quant:
        tags += "-quant"
    if shape.pack4:
        tags += "-pack4"
    if shape.gspmd:
        tags += "-gspmd"
    return (f"{shape.mode}-r{_rung(shape.rows)}-f{_rung(shape.features)}"
            f"-b{int(shape.num_bins)}{tags}")


def current_platform() -> str:
    """The active jax backend platform. A backend that fails to
    initialise raises here: it must never read as a CPU host (the
    jax-free CLI paths pass an explicit platform instead)."""
    import jax
    return jax.devices()[0].platform


def on_tpu(platform: Optional[str] = None) -> bool:
    """Is ``platform`` (default: the active backend) a Mosaic target?"""
    return (platform or current_platform()) in TPU_PLATFORMS


def eligible_entries(shape: DatasetShape, platform: str
                     ) -> List[EngineEntry]:
    """Entries that can serve ``shape`` on ``platform`` (the Mosaic
    entries are exactly the ``requires_tpu`` ones: they need the backend
    and a step GSPMD does not partition)."""
    mosaic_ok = on_tpu(platform) and not shape.gspmd
    return [e for e in ENTRIES
            if shape.num_bins <= e.max_bins
            and (mosaic_ok or not e.requires_tpu)]


def sweep_candidates(shape: DatasetShape, platform: str
                     ) -> List[Candidate]:
    """The autotune sweep grid: sweepable eligible entries x mbatch."""
    out: List[Candidate] = []
    for entry in eligible_entries(shape, platform):
        if not entry.sweepable:
            continue
        for k in MBATCH_CANDIDATES:
            out.append(Candidate(entry, k))
    return out


# ---------------------------------------------------------------------------
# cfg access (Config objects AND plain dicts — the gbdt delegates and
# their tests pass both)
# ---------------------------------------------------------------------------
def _get(cfg, name: str, default: Any = None) -> Any:
    if hasattr(cfg, "get"):
        v = cfg.get(name, default)
        return default if v is None else v
    return default


def _explicit(cfg, name: str) -> bool:
    """Did the USER set this knob (resolve-order rung 1)?"""
    if hasattr(cfg, "is_explicit"):
        return bool(cfg.is_explicit(name))
    try:
        return name in cfg
    except TypeError:  # pragma: no cover - exotic cfg objects
        return False


# ---------------------------------------------------------------------------
# per-knob resolvers (validation/warning behavior of the former gbdt
# _pick_* helpers, now registry-owned; gbdt keeps thin delegates)
# ---------------------------------------------------------------------------
def validated_mbatch_env(value: str) -> int:
    """Round and re-guard an ``LGBM_TPU_HIST_MBATCH`` override (1-16)."""
    k = int(value)
    if not 1 <= k <= 16:
        clamped = max(1, min(k, 16))
        log.warning(f"LGBM_TPU_HIST_MBATCH={value} outside [1, 16] "
                    f"(8K must fit the 128 MXU rows); clamped to {clamped}")
        k = clamped
    return k


def validated_fused_block_env(value: str, num_cols: int,
                              vmem_cap_bs: int) -> int:
    """Round and re-guard an ``LGBM_TPU_FUSED_BS`` override.

    The override exists for perf experiments, but it must not be able
    to recreate the hazards the automatic derivation prevents: the
    kernel requires a 32-multiple block size (Mosaic DMA alignment,
    ops/fused_split.py), and its scoped-VMEM buffers scale with
    ``block_size * num_cols`` — so the value is rounded down to a
    32-multiple and clamped to the same scoped-VMEM-derived cap the
    automatic path uses (``vmem_cap_bs``)."""
    bs = max(32, (int(value) // 32) * 32)
    if bs != int(value):
        log.warning(f"LGBM_TPU_FUSED_BS={value} is not a 32-multiple; "
                    f"rounded to {bs}")
    if bs > vmem_cap_bs:
        log.warning(
            f"LGBM_TPU_FUSED_BS={value} exceeds the scoped-VMEM cap for "
            f"{num_cols}-byte row records (max {vmem_cap_bs}); clamped — "
            "an unchecked override would recreate the VMEM blowup the "
            "guard prevents")
        bs = vmem_cap_bs
    return bs


def resolve_mbatch(cfg, decision: Optional[Dict[str, Any]] = None,
                   sources: Optional[Dict[str, str]] = None,
                   fused: bool = False) -> int:
    """``tpu_hist_mbatch``: K row blocks per one-hot contraction,
    M = 8K MXU rows; always clamped to [1, 16].

    The standalone engines (``fused=False``): user > env
    (LGBM_TPU_HIST_MBATCH) > autotune > default 8 — the depth their
    sweep measures, and where the chip has it ahead (k8 0.340 ms against
    k1 0.445 ms on 16k rows). The fused kernel (``fused=True``): user >
    env > :data:`FUSED_MBATCH` (source ``fused``). Neither the sweep's
    winner nor the standalone default reaches it: inside the fused walk
    no depth is faster than 1, 2 costs 0.7% and 8 a factor of ten
    (PERF.md section 6, PR 29), and the sweep never times the fused
    kernel."""
    src = "default"
    k = int(_get(cfg, "tpu_hist_mbatch", 8) or 8)
    if _explicit(cfg, "tpu_hist_mbatch"):
        src = "user"
    elif os.environ.get("LGBM_TPU_HIST_MBATCH", ""):
        k = validated_mbatch_env(os.environ["LGBM_TPU_HIST_MBATCH"])
        src = "env"
    elif fused:
        k, src = FUSED_MBATCH, "fused"
    elif decision and decision.get("hist_mbatch"):
        k = int(decision["hist_mbatch"])
        src = "autotune"
    if sources is not None:
        sources["hist_mbatch"] = src
    return max(1, min(k, 16))


def standalone_mbatch(cfg, res: Optional[Resolution]) -> int:
    """The depth of a run whose fused kernel was taken off AFTER
    :func:`resolve` answered for it (:func:`clamp_fused_block` found no
    block that fits, or caller-supplied gradients sent the run to the
    masked grower): its histograms come from a standalone engine after
    all, so it runs what :func:`resolve_mbatch` gives those, from the
    run's in-memory decision."""
    return resolve_mbatch(cfg, res.decision if res is not None else None)


def resolve_layout(cfg, num_bins: int,
                   decision: Optional[Dict[str, Any]] = None,
                   platform: Optional[str] = None,
                   sources: Optional[Dict[str, str]] = None) -> str:
    """``tpu_hist_layout``: the Mosaic one-hot register layout.

    "sublane" lays bins along sublanes (B <= 64 only — wider bin counts
    leave no room to group features into the 128 MXU rows). ``auto``
    is honest where a measurement exists: an autotune-cache winner for
    this shape-class selects the layout it measured fastest (the PR 6
    sweep showed sublane competitive at B <= 64); without a cache the
    conservative lane default holds."""
    mode = str(_get(cfg, "tpu_hist_layout", "auto") or "auto").lower()
    src = "user" if mode not in ("", "auto") else "default"
    if mode in ("", "auto"):
        mode = "lane"
        if decision and decision.get("hist_layout"):
            cand = str(decision["hist_layout"])
            if cand == "sublane" and (num_bins <= 0 or num_bins > 64):
                pass      # stale cache vs a wider re-bin: keep lane
            elif cand == "sublane" and not on_tpu(platform):
                pass      # Mosaic layout needs a TPU backend
            elif cand in ("lane", "sublane"):
                mode, src = cand, "autotune"
    elif mode not in ("lane", "sublane"):
        log.warning(f"tpu_hist_layout={mode!r} is not one of "
                    "auto|lane|sublane; using the lane layout (auto "
                    "stays on the conservative lane default until an "
                    "autotune cache records a sublane win for this "
                    "shape-class — tpu_autotune=first_run)")
        if sources is not None:
            sources["hist_layout"] = "default"
        return "lane"
    if mode == "sublane" and num_bins > 64:
        # num_bins <= 0 means "width unknown" (no train-set context,
        # e.g. reset_parameter on a loaded booster) — the bound is
        # enforced where a real width exists, not against a guess
        log.warning(
            f"tpu_hist_layout=sublane needs num_bins <= 64 (got "
            f"{num_bins}): bins lie along sublanes and wider counts "
            "cannot group features into the 128 MXU rows; using lane")
        if sources is not None:
            sources["hist_layout"] = "default"
        return "lane"
    if sources is not None:
        sources["hist_layout"] = src
    return mode


def resolve_impl(cfg, decision: Optional[Dict[str, Any]] = None,
                 sources: Optional[Dict[str, str]] = None,
                 gspmd: bool = False) -> str:
    """``tpu_hist_impl``: the standalone histogram engine. user >
    autotune > "auto" (the trace-time per-width heuristic in
    ops/histogram.py _resolve_impl — the ``tpu_autotune=off`` escape
    hatch). A GSPMD-partitioned step (:class:`DatasetShape`) takes the
    XLA einsum — structurally, since "auto" would pick the Mosaic kernel
    on a TPU; asking for ``pallas`` there outright is an error."""
    src = "default"
    impl = str(_get(cfg, "tpu_hist_impl", "auto") or "auto").lower()
    if gspmd:
        if impl == "pallas" and _explicit(cfg, "tpu_hist_impl"):
            raise ValueError(
                "tpu_hist_impl=pallas cannot serve this configuration: its "
                "train step is partitioned by GSPMD (masked grower under "
                "a mesh), and a Mosaic kernel cannot be partitioned "
                "automatically; use tpu_hist_impl=auto or xla")
        if sources is not None:
            sources["hist_impl"] = "gspmd"
        return "xla"
    if _explicit(cfg, "tpu_hist_impl") and impl != "auto":
        if impl not in ("xla", "pallas"):
            log.warning(f"tpu_hist_impl={impl!r} is not one of "
                        "auto|xla|pallas; using auto")
            impl = "auto"
        else:
            src = "user"
    elif decision and decision.get("hist_impl") in ("xla", "pallas"):
        impl, src = str(decision["hist_impl"]), "autotune"
    else:
        impl = "auto"
    if sources is not None:
        sources["hist_impl"] = src
    return impl


def resolve_fused_block(cfg, platform: Optional[str] = None,
                        sources: Optional[Dict[str, str]] = None) -> int:
    """``tpu_fused``: the fused per-split Mosaic kernel block size
    (0 = off). auto = on whenever a real TPU backend is present; the
    fused kernel is selected structurally, not by the microbench (see
    EngineEntry.sweepable); its hist_flush inherits the autotuned
    layout and runs its own depth (:func:`resolve_mbatch`). The
    record-width scoped-VMEM clamp re-runs at :func:`clamp_fused_block`
    once the row layout is known."""
    mode = str(_get(cfg, "tpu_fused", "auto") or "auto").lower()
    src = "user" if _explicit(cfg, "tpu_fused") else "default"
    if sources is not None:
        sources["fused_block"] = src
    if mode in ("off", "0", "false"):
        return 0
    if bool(_get(cfg, "tpu_fused_interpret", False)):
        # CI-only: run the Mosaic kernel in Pallas interpret mode on CPU
        bs = int(_get(cfg, "tpu_fused_block", 512) or 512)
        return max(32, (bs // 32) * 32)
    available = on_tpu(platform)
    if mode == "on" and not available:
        raise ValueError(
            "tpu_fused=on requires a TPU backend (the fused kernel is "
            f"Mosaic-only; platform is {platform or current_platform()!r}). "
            "Use tpu_fused=auto to take the XLA compact walk off-TPU, or "
            "tpu_fused_interpret=true to run the kernel in Pallas "
            "interpret mode for tests")
    if mode == "on" or (mode == "auto" and available):
        bs = int(_get(cfg, "tpu_fused_block", 512) or 512)
        return max(32, (bs // 32) * 32)
    return 0


def resolve_step_buckets(cfg,
                         sources: Optional[Dict[str, str]] = None) -> bool:
    """``tpu_step_buckets``: the bucketed grower-step ladder.

    On (the default), the step program's jit key carries the
    power-of-two leaf RUNG and the {unlimited, bounded} depth bucket
    instead of the exact (num_leaves, max_depth) pair — the actual
    budgets ride as traced scalars, so every configuration in a rung
    shares one compiled program. ``off`` is the exact-keyed escape
    hatch for parity benching."""
    mode = str(_get(cfg, "tpu_step_buckets", "auto") or "auto").lower()
    if sources is not None:
        sources["step_buckets"] = \
            "user" if _explicit(cfg, "tpu_step_buckets") else "default"
    if mode in ("off", "0", "false"):
        return False
    if mode not in ("", "auto", "on", "1", "true"):
        log.warning(f"tpu_step_buckets={mode!r} is not one of "
                    "auto|on|off; the ladder stays on")
    return True


def resolve_overlap(cfg,
                    sources: Optional[Dict[str, str]] = None) -> int:
    """``tpu_hist_overlap``: async histogram-collective overlap.

    ``on`` builds each leaf histogram in 2 feature groups with one
    psum_scatter/all-reduce per group, issued while the next group
    still accumulates — collective latency hides under the MXU
    contraction at unchanged byte totals. Only meaningful on the
    distributed learners. ``auto`` stays off until a real-TPU sweep
    says otherwise (the autotuner does not sweep it: overlap needs live
    collectives, which a single-chip microbench cannot time)."""
    mode = str(_get(cfg, "tpu_hist_overlap", "auto") or "auto").lower()
    if sources is not None:
        sources["hist_overlap"] = \
            "user" if _explicit(cfg, "tpu_hist_overlap") else "default"
    if mode in ("on", "1", "true"):
        return 2
    if mode not in ("", "auto", "off", "0", "false"):
        log.warning(f"tpu_hist_overlap={mode!r} is not one of "
                    "auto|on|off; overlap stays off")
    return 0


def clamp_fused_block(block: int, num_cols: int, mbatch: int,
                      hist_layout: str, num_bins: int, num_features: int,
                      env_override: str = "") -> int:
    """The record-width scoped-VMEM clamp (registry-owned since round
    12; previously inlined in gbdt._setup_compact_state).

    The kernel's streaming buffers scale with ``block_size * num_cols``
    and the batched-M pending ring with ``mbatch * block_size`` (bins +
    transposed channels + the flush's one-hot and block-diagonal
    transients — both register layouts charged, ops/fused_split.py
    fused_ring_bytes), and the flush's unrolled text and stack with
    feature groups x ``mbatch`` x ``block_size`` (``_FLUSH_ONEHOT_ROWS``:
    220 features of 256 bins run block 96 at depth 2 where their
    256-byte rows alone would allow 192); the histogram accumulator needs
    ``f_pad * stride * 32`` bytes regardless of block size, so a shape
    whose accumulator alone blows the ~16MB scoped limit falls back to
    the XLA walk (returns 0). ``env_override`` (LGBM_TPU_FUSED_BS) is
    rounded + re-guarded, never trusted raw."""
    if not block:
        return 0
    from ..ops.fused_split import _hist_packing, fused_block_cap
    vmem_cap_bs = fused_block_cap(num_cols, mbatch,
                                  hist_layout=hist_layout,
                                  num_features=num_features,
                                  num_bins=num_bins)
    bs = min(block, vmem_cap_bs)
    if env_override:
        # perf experiments; rounded + re-guarded, never trusted raw
        bs = validated_fused_block_env(env_override, num_cols, vmem_cap_bs)
    stride, f_pad, _ = _hist_packing(num_features, num_bins)
    f_hist_bytes = f_pad * stride * 32
    if f_hist_bytes > 6 << 20:
        log.warning("fused kernel disabled: histogram accumulator "
                    f"needs {f_hist_bytes >> 20}MB VMEM; using the "
                    "XLA compact walk")
        return 0
    return bs


def fit_fused_flush(res: "Resolution", num_cols: int, num_bins: int,
                    num_features: int, env_override: str = ""
                    ) -> Tuple[int, int]:
    """(fused_block, hist_mbatch) the fused kernel runs on this row
    record: :func:`clamp_fused_block` at the resolved depth and, where
    the depth is the fused default (source ``fused``: nobody named one)
    and the clamp's bound on the flush has cut the block, depth 1 at the
    block that holds as many rows a flush. One flush sums depth x block
    rows into the f32 accumulator, so the sums are the same bit for bit;
    the larger block streams faster and the shallower kernel compiles in
    half the time (220 features of 256 bins: depth 1 at block 192 ran 2.85
    s an iteration against 3.09 s at depth 2 and block 96, the
    comparison's gaps identical to the last digit; PERF.md section 6,
    PR 30). A block of 0 (the clamp took the kernel off) leaves the
    depth to :func:`standalone_mbatch`."""
    block = clamp_fused_block(res.fused_block, num_cols, res.hist_mbatch,
                              res.hist_layout, num_bins, num_features,
                              env_override)
    depth = res.hist_mbatch
    if (block and depth > 1 and not env_override
            and res.sources.get("hist_mbatch") == "fused"):
        shallow = clamp_fused_block(res.fused_block, num_cols, 1,
                                    res.hist_layout, num_bins, num_features)
        if shallow >= depth * block:
            return shallow, 1
    return block, depth


# ---------------------------------------------------------------------------
# THE resolve callsite
# ---------------------------------------------------------------------------
def resolve(cfg, shape: Optional[DatasetShape] = None,
            sample_provider=None, platform: Optional[str] = None,
            allow_sweep: bool = True,
            prior: Optional[Resolution] = None) -> Resolution:
    """Resolve every engine knob for one training run.

    ``shape`` keys the autotune cache (None = no shape context, e.g. a
    booster constructed without a train set: heuristic defaults only).
    ``sample_provider(n)`` returns up to ``n`` rows of the REAL binned
    matrix for the microbench; ``allow_sweep=False`` never runs a new
    sweep. ``prior`` (reset_parameter) is the run's previous
    Resolution: its in-memory decision is reused VERBATIM — no cache
    re-read, no file I/O in the training loop, and the engine a run
    measured at startup can neither vanish (unwritable cache) nor flip
    (cache rewritten underneath a live run) on a mid-run re-resolve.
    """
    platform = platform or current_platform()
    sources: Dict[str, str] = {}
    decision = None
    swept = False
    sclass = shape_class(shape) if shape is not None else None
    if prior is not None:
        decision = prior.decision
    elif shape is not None:
        from . import autotune
        decision, swept = autotune.decision_for(
            cfg, shape, platform, sample_provider=sample_provider,
            allow_sweep=allow_sweep)
    # 0 = bin width unknown (no train-set context): the sublane bound
    # cannot be checked, so it is not enforced against a made-up width
    num_bins = int(shape.num_bins) if shape is not None else 0
    layout = resolve_layout(cfg, num_bins, decision, platform, sources)
    gspmd = shape is not None and shape.gspmd
    impl = resolve_impl(cfg, decision, sources, gspmd=gspmd)
    if gspmd and prior is None and on_tpu(platform):
        log.info("engine registry: this step is partitioned by GSPMD "
                 "(masked grower under a mesh); histograms take the XLA "
                 "einsum — a Mosaic kernel cannot be partitioned "
                 "automatically")
    fused_block = resolve_fused_block(cfg, platform, sources)
    # only the compact grower fuses: the masked grower (a GSPMD step, or
    # one chip's small or caller-gradient runs) builds every histogram
    # with a standalone engine, at the standalone engines' depth
    fused = bool(fused_block) and not gspmd and (
        shape is None or shape.compact)
    mbatch = resolve_mbatch(cfg, decision, sources, fused=fused)
    step_buckets = resolve_step_buckets(cfg, sources)
    overlap = resolve_overlap(cfg, sources)
    if fused:
        entry_id = "fused_sublane" if layout == "sublane" else "fused_lane"
    elif decision and decision.get("entry"):
        entry_id = str(decision["entry"])
    elif impl == "pallas":
        entry_id = ("pallas_sublane" if layout == "sublane"
                    else "pallas_lane")
    else:
        entry_id = "xla_lane"
    res = Resolution(
        entry_id=entry_id, fused_block=fused_block, hist_impl=impl,
        hist_mbatch=mbatch, hist_layout=layout, hist_overlap=overlap,
        step_buckets=step_buckets, sources=sources, shape_class=sclass,
        autotuned=bool(decision), decision=decision)
    if decision and prior is None:
        log.info(
            f"engine registry: shape-class {sclass} -> {entry_id} "
            f"(layout={layout}, mbatch={mbatch}, impl={impl}; "
            f"{'measured now' if swept else 'autotune cache'})")
    return res


# ---------------------------------------------------------------------------
# serving-engine resolution (ROADMAP 4)
# ---------------------------------------------------------------------------
class ServingResolution(NamedTuple):
    """The registry's serving answer: which per-row router runs.

    ``engine`` is the resolved router (``walk`` | ``level``);
    ``entry_id`` the registry entry it maps to (``serve_qleaf`` when a
    quantized leaf slab rides the router); ``source`` the resolve-order
    rung that produced it (user / env / autotune / default).
    """
    engine: str
    entry_id: str
    source: str
    shape_class: Optional[str] = None
    decision: Optional[Dict[str, Any]] = None


def serving_shape_class(tree_bucket: int, depth: int, num_class: int,
                        quant: str = "off") -> str:
    """Autotune cache key for one serving shape: tree bucket + depth +
    class count (+ quant mode), the jit-key axes a frozen model's
    serving programs are compiled on. Distinct from the training shape
    classes by the ``serve-`` prefix."""
    tag = "" if quant in ("", "off", None) else f"-q{quant}"
    return f"serve-t{int(tree_bucket)}-d{int(depth)}-k{int(num_class)}{tag}"


def _serving_entry_id(engine: str, quant: str) -> str:
    if quant not in ("", "off", None):
        return "serve_qleaf"
    return f"serve_{engine}"


def resolve_serving_engine(cfg, depth: int, level_cap: int,
                           tree_bucket: int = 0, num_class: int = 1,
                           quant: str = "off",
                           platform: Optional[str] = None,
                           racer=None) -> ServingResolution:
    """Resolve ``tpu_predict_engine`` to a serving router.

    The same per-knob order as :func:`resolve`::

        user explicit > env LGBM_TPU_PREDICT_ENGINE > autotune cache
        > heuristic default

    ``level`` demotes to ``walk`` (with a warning) when the stack is
    deeper than ``level_cap`` — the per-level slab is O(2^depth) per
    tree, so deep/ragged buckets keep the walk. ``auto`` consults the
    autotune cache (shape class :func:`serving_shape_class`) and, when
    armed with a ``racer``, times the candidate engines on the real
    stacked trees (engines/autotune.serving_decision_for); unarmed it
    falls to the depth heuristic. ``scan`` never reaches here (callers
    branch to the reference path first).
    """
    platform = platform or current_platform()
    sclass = serving_shape_class(tree_bucket, depth, num_class, quant)

    def norm(value: str, source: str) -> Optional[ServingResolution]:
        if value in ("batched", "walk"):
            return ServingResolution("walk", _serving_entry_id(
                "walk", quant), source, sclass)
        if value == "level":
            if depth > level_cap:
                log.warning(
                    f"tpu_predict_engine=level: stacked depth {depth} "
                    f"exceeds tpu_level_depth_cap={level_cap}; the "
                    "bucket keeps the pointer walk")
                return ServingResolution("walk", _serving_entry_id(
                    "walk", quant), source, sclass)
            return ServingResolution("level", _serving_entry_id(
                "level", quant), source, sclass)
        if value not in ("", "auto"):
            log.warning(f"tpu_predict_engine={value!r} is not one of "
                        f"{'|'.join(SERVING_ENGINE_VALUES)}; using the "
                        "depth-batched walk")
            return ServingResolution("walk", _serving_entry_id(
                "walk", quant), source, sclass)
        return None

    raw = str(_get(cfg, "tpu_predict_engine", "batched")
              or "batched").lower()
    if _explicit(cfg, "tpu_predict_engine"):
        res = norm(raw, "user")
        if res is not None:
            return res
    env = os.environ.get("LGBM_TPU_PREDICT_ENGINE", "").strip().lower()
    if env:
        res = norm(env, "env")
        if res is not None:
            return res
    if raw != "auto":
        # unset knob keeps its heuristic default spelling ("batched")
        res = norm(raw, "default")
        if res is not None:
            return res
    # auto: measured decision when armed, depth heuristic otherwise
    from . import autotune
    decision, _swept = autotune.serving_decision_for(
        cfg, sclass, platform, runners_provider=racer)
    eng = (decision or {}).get("serve_engine")
    if eng in ("walk", "level"):
        if eng == "level" and depth > level_cap:
            eng = "walk"
        return ServingResolution(eng, _serving_entry_id(eng, quant),
                                 "autotune", sclass, decision)
    eng = "level" if depth <= level_cap else "walk"
    return ServingResolution(eng, _serving_entry_id(eng, quant),
                             "default", sclass)
