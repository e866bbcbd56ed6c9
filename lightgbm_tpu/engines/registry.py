"""Engine registry: the ONE owner of histogram-engine selection.

The engine knob space — {fused, pallas, xla-einsum} x batched-M depth x
block size x {lane, sublane} layout x learner mode — resolves behind one
table (:data:`ENTRIES`) and one callsite (:func:`resolve`), the way the
reference resolves col-wise vs row-wise histogram dispatch from ONE
decision point at ``InitTrain`` (``dataset.h:727``).

:func:`resolve` is a pure function of the config, the dataset's shape
and the platform: it reads no file, times nothing and carries nothing
from one call to the next. Resolve order, per knob (the contract
tests/test_registry.py pins)::

    user explicit > LGBM_TPU_* override > what platform and shape decide

so a run with nothing set resolves what the benchmark's cells resolve.

Registry entries carry their HLO-contract id: ``scripts/
verify_contracts.py`` enumerates contracts per entry (the entry id is
in the contract filename), so a new engine cannot land without either
a checked-in contract or a justified ``contract_exempt`` (TPU-only
Mosaic kernels, which the CPU contract harness cannot lower — their
parity is pinned by the cross-engine bit-identity tests instead).

tpulint R004 enforces the ownership: ``GrowerParams(hist_*=...)`` or a
direct engine-callable choice outside this package is a finding; the
one sanctioned escape hatch is ``ops/histogram.py::_resolve_impl``
(allowlist-anchored), the trace-time per-width dispatch that answers
when the registry hands ``"auto"`` through.

Module level is jax-free; functions that need a backend import jax
lazily.
"""
from __future__ import annotations

import os
from typing import Any, Dict, NamedTuple, Optional, Tuple

from ..utils import log

#: platforms with a Mosaic backend — the ONE copy; every "is this a
#: TPU" question in the package goes through :func:`on_tpu`
TPU_PLATFORMS = ("tpu",)

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
    #: Only the compact grower fuses
    compact: bool = True


class EngineEntry(NamedTuple):
    """One histogram engine the registry can select.

    ``contracts`` names the ``analysis/contracts/<mode>.json`` files
    that pin this entry's steady-state step program (at least one file
    name must contain the entry id); ``contract_exempt`` is the
    mandatory justification when no CPU contract can exist (TPU-only
    Mosaic kernels). The fused kernel is selected structurally (it
    replaces the partition+histogram streams and its binding constraint
    is the scoped-VMEM validator, :func:`clamp_fused_block`); it takes the
    resolved LAYOUT, which threads into its ``hist_flush``, and runs
    :data:`FUSED_MBATCH` unless the user or the environment names a depth
    (the standalone engines' 8 loses a factor of ten inside the fused
    walk).
    """
    id: str
    impl: str                     # hist_impl fed to ops/histogram dispatch
    layout: str                   # lane | sublane
    fused: bool
    description: str
    contracts: Tuple[str, ...] = ()
    contract_exempt: str = ""
    requires_tpu: bool = False
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
        requires_tpu=True),
    EngineEntry(
        "fused_lane", "auto", "lane", True,
        "fused partition+histogram Mosaic kernel (ops/fused_split.py), "
        "lane-layout hist_flush",
        contract_exempt="Mosaic kernels cannot lower on the CPU "
                        "contract harness; parity is pinned by "
                        "tests/test_fused.py leaf-count identity",
        requires_tpu=True),
    EngineEntry(
        "fused_sublane", "auto", "sublane", True,
        "fused Mosaic kernel with the bins-on-sublanes hist_flush "
        "(B <= 64)",
        contract_exempt="Mosaic kernels cannot lower on the CPU "
                        "contract harness; layout bit-identity is "
                        "pinned by tests/test_pack4_train.py",
        requires_tpu=True),
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
        contracts=("serve_walk",)),
    EngineEntry(
        "serve_level", "level", "lane", False,
        "level-order heap relayout (predict_raw_level): depth step d "
        "reads the contiguous [Tb, 2^d] per-level slab; buckets deeper "
        "than tpu_level_depth_cap keep the walk",
        contracts=("serve_level",)),
    EngineEntry(
        "serve_qleaf", "qleaf", "lane", False,
        "quantized leaf slab (tpu_leaf_quant=int8|f16) over the "
        "resolved walk/level router: narrow leaf gather + per-tree "
        "dequant scale, with a recorded max-score-error bound",
        contract_exempt="shares the serve_walk/serve_level step "
                        "program shape (only the leaf-slab dtype "
                        "narrows); score deviation is pinned by the "
                        "RECORDED bound and "
                        "tests/test_level_engine.py"),
)

#: tpu_predict_engine spellings the serving resolver accepts
SERVING_ENGINE_VALUES = ("batched", "walk", "level", "scan", "auto")


class Resolution(NamedTuple):
    """The registry's answer: every engine knob, with provenance.

    ``sources`` maps knob -> one of ``user`` / ``env`` / ``default`` so
    logs and tests can see WHICH rung of the resolve order produced each
    value (``hist_impl`` may also read ``gspmd`` and ``hist_mbatch``
    ``fused``: the structural answers). ``hist_mbatch``
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


def current_platform() -> str:
    """The active jax backend platform. A backend that fails to
    initialise raises here: it must never read as a CPU host (the
    jax-free CLI paths pass an explicit platform instead)."""
    import jax
    return jax.devices()[0].platform


def on_tpu(platform: Optional[str] = None) -> bool:
    """Is ``platform`` (default: the active backend) a Mosaic target?"""
    return (platform or current_platform()) in TPU_PLATFORMS


# ---------------------------------------------------------------------------
# cfg access (Config objects AND plain dicts: tests pass both)
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
# per-knob resolvers
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


def resolve_mbatch(cfg, sources: Optional[Dict[str, str]] = None,
                   fused: bool = False) -> int:
    """``tpu_hist_mbatch``: K row blocks per one-hot contraction,
    M = 8K MXU rows; always clamped to [1, 16].

    user > env (LGBM_TPU_HIST_MBATCH) > the engine's own depth: 8 for the
    standalone engines (``fused=False``; on the chip k8 0.340 ms against
    k1 0.445 ms on 16k rows), :data:`FUSED_MBATCH` for the fused kernel
    (``fused=True``, source ``fused``): inside the fused walk no depth is
    faster than 1, 2 costs 0.7% and 8 a factor of ten (PERF.md section 6,
    PR 29). ``resolve_mbatch(cfg)`` is also the depth of a run whose
    fused kernel was taken off AFTER :func:`resolve` answered for it
    (:func:`clamp_fused_block` found no block that fits, or
    caller-supplied gradients sent the run to the masked grower): its
    histograms come from a standalone engine after all."""
    src = "default"
    k = int(_get(cfg, "tpu_hist_mbatch", 8) or 8)
    if _explicit(cfg, "tpu_hist_mbatch"):
        src = "user"
    elif os.environ.get("LGBM_TPU_HIST_MBATCH", ""):
        k = validated_mbatch_env(os.environ["LGBM_TPU_HIST_MBATCH"])
        src = "env"
    elif fused:
        k, src = FUSED_MBATCH, "fused"
    if sources is not None:
        sources["hist_mbatch"] = src
    return max(1, min(k, 16))


def resolve_layout(cfg, num_bins: int,
                   sources: Optional[Dict[str, str]] = None) -> str:
    """``tpu_hist_layout``: the Mosaic one-hot register layout.

    "sublane" lays bins along sublanes (B <= 64 only — wider bin counts
    leave no room to group features into the 128 MXU rows) and is taken
    only when the user names it; ``auto`` is ``lane``."""
    mode = str(_get(cfg, "tpu_hist_layout", "auto") or "auto").lower()
    src = "user"
    if mode in ("", "auto"):
        mode, src = "lane", "default"
    elif mode not in ("lane", "sublane"):
        log.warning(f"tpu_hist_layout={mode!r} is not one of "
                    "auto|lane|sublane; using the lane layout")
        mode, src = "lane", "default"
    elif mode == "sublane" and num_bins > 64:
        # num_bins <= 0 means "width unknown" (no train-set context,
        # e.g. reset_parameter on a loaded booster) — the bound is
        # enforced where a real width exists, not against a guess
        log.warning(
            f"tpu_hist_layout=sublane needs num_bins <= 64 (got "
            f"{num_bins}): bins lie along sublanes and wider counts "
            "cannot group features into the 128 MXU rows; using lane")
        mode, src = "lane", "default"
    if sources is not None:
        sources["hist_layout"] = src
    return mode


def resolve_impl(cfg, sources: Optional[Dict[str, str]] = None,
                 gspmd: bool = False) -> str:
    """``tpu_hist_impl``: the standalone histogram engine. user >
    "auto" (the trace-time per-width choice in ops/histogram.py
    _resolve_impl). A GSPMD-partitioned step (:class:`DatasetShape`)
    takes the XLA einsum — structurally, since "auto" would pick the
    Mosaic kernel on a TPU; asking for ``pallas`` there outright is an
    error."""
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
    else:
        impl = "auto"
    if sources is not None:
        sources["hist_impl"] = src
    return impl


def resolve_fused_block(cfg, platform: Optional[str] = None,
                        sources: Optional[Dict[str, str]] = None) -> int:
    """``tpu_fused``: the fused per-split Mosaic kernel block size
    (0 = off). auto = on whenever a real TPU backend is present; its
    hist_flush takes the resolved layout and runs its own depth
    (:func:`resolve_mbatch`). The record-width scoped-VMEM clamp re-runs
    at :func:`clamp_fused_block` once the row layout is known."""
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
    distributed learners. ``auto`` stays off until a four-chip cell
    says otherwise (ROADMAP R8)."""
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
    and the pending ring with ``mbatch * block_size`` (the transposed
    blocks, their channel operands and the flush's one-hot,
    ops/fused_split.py fused_ring_bytes), and the flush's unrolled text
    with feature groups x ``mbatch`` x ``block_size``
    (``_FLUSH_ONEHOT_ROWS``: 220 features of 256 bins run block 128 at
    depth 2 where their 256-byte rows alone would allow 256); from 128
    rows up the block is whole lane tiles, because the histogram holds a
    block's rows along lanes; the histogram accumulator needs
    ``f_pad * stride * 32`` bytes regardless of block size, so a shape
    whose accumulator alone blows the ~16MB scoped limit falls back to
    the XLA walk (returns 0). ``env_override`` (LGBM_TPU_FUSED_BS) is
    rounded + re-guarded, never trusted raw."""
    if not block:
        return 0
    from ..ops.fused_split import _hist_flush_shape, fused_block_cap
    vmem_cap_bs = fused_block_cap(num_cols, mbatch,
                                  hist_layout=hist_layout,
                                  num_features=num_features,
                                  num_bins=num_bins)
    bs = min(block, vmem_cap_bs)
    if env_override:
        # perf experiments; rounded + re-guarded, never trusted raw
        bs = validated_fused_block_env(env_override, num_cols, vmem_cap_bs)
    # [8 levels, f_pad * width] words (two-level flush: features pad to
    # whole pairs); levels x width is the bin stride
    levels, width, f_pad, _ = _hist_flush_shape(num_features, num_bins,
                                                hist_layout)
    f_hist_bytes = f_pad * levels * width * 32
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
    half the time (220 features of 256 bins, PR 30's kernel: depth 1 at
    block 192 ran 2.85 s an iteration against 3.09 s at depth 2 and block
    96, the comparison's gaps identical to the last digit; PERF.md
    section 6, PR 30; since PR 33 the pair is 256 and 128). A block of
    0 (the clamp took the kernel off) leaves the depth to
    ``resolve_mbatch(cfg)``."""
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
            platform: Optional[str] = None) -> Resolution:
    """Resolve every engine knob for one training run: a pure function
    of ``cfg``, ``shape`` and ``platform`` (plus the ``LGBM_TPU_*``
    overrides), so ``_setup_train`` and ``reset_parameter`` make the same
    call and get the same answer. ``shape`` None = no shape context (a
    booster constructed without a train set): the bin width is unknown
    and the run is taken to fuse where the platform allows."""
    platform = platform or current_platform()
    sources: Dict[str, str] = {}
    # 0 = bin width unknown (no train-set context): the sublane bound
    # cannot be checked, so it is not enforced against a made-up width
    num_bins = int(shape.num_bins) if shape is not None else 0
    layout = resolve_layout(cfg, num_bins, sources)
    gspmd = shape is not None and shape.gspmd
    impl = resolve_impl(cfg, sources, gspmd=gspmd)
    fused_block = resolve_fused_block(cfg, platform, sources)
    # only the compact grower fuses: the masked grower (a GSPMD step, or
    # one chip's small or caller-gradient runs) builds every histogram
    # with a standalone engine, at the standalone engines' depth
    fused = bool(fused_block) and not gspmd and (
        shape is None or shape.compact)
    mbatch = resolve_mbatch(cfg, sources, fused=fused)
    step_buckets = resolve_step_buckets(cfg, sources)
    overlap = resolve_overlap(cfg, sources)
    if fused:
        entry_id = "fused_sublane" if layout == "sublane" else "fused_lane"
    elif impl == "pallas":
        entry_id = ("pallas_sublane" if layout == "sublane"
                    else "pallas_lane")
    else:
        entry_id = "xla_lane"
    return Resolution(
        entry_id=entry_id, fused_block=fused_block, hist_impl=impl,
        hist_mbatch=mbatch, hist_layout=layout, hist_overlap=overlap,
        step_buckets=step_buckets, sources=sources)


# ---------------------------------------------------------------------------
# serving-engine resolution (ROADMAP 4)
# ---------------------------------------------------------------------------
class ServingResolution(NamedTuple):
    """The registry's serving answer: which per-row router runs.

    ``engine`` is the resolved router (``walk`` | ``level``);
    ``entry_id`` the registry entry it maps to (``serve_qleaf`` when a
    quantized leaf slab rides the router); ``source`` the resolve-order
    rung that produced it (user / env / default).
    """
    engine: str
    entry_id: str
    source: str


def resolve_serving_engine(cfg, depth: int, level_cap: int,
                           quant: str = "off") -> ServingResolution:
    """Resolve ``tpu_predict_engine`` to a serving router.

    The same per-knob order as :func:`resolve`::

        user explicit > env LGBM_TPU_PREDICT_ENGINE > depth heuristic

    ``level`` demotes to ``walk`` (with a warning) when the stack is
    deeper than ``level_cap`` — the per-level slab is O(2^depth) per
    tree, so deep/ragged buckets keep the walk. ``auto`` is the depth
    heuristic: ``level`` up to the cap, ``walk`` past it. ``scan`` never
    reaches here (callers branch to the reference path first).
    """
    def answer(engine: str, source: str) -> ServingResolution:
        entry = ("serve_qleaf" if quant not in ("", "off", None)
                 else f"serve_{engine}")
        return ServingResolution(engine, entry, source)

    def norm(value: str, source: str) -> Optional[ServingResolution]:
        if value in ("batched", "walk"):
            return answer("walk", source)
        if value == "level":
            if depth > level_cap:
                log.warning(
                    f"tpu_predict_engine=level: stacked depth {depth} "
                    f"exceeds tpu_level_depth_cap={level_cap}; the "
                    "bucket keeps the pointer walk")
                return answer("walk", source)
            return answer("level", source)
        if value not in ("", "auto"):
            log.warning(f"tpu_predict_engine={value!r} is not one of "
                        f"{'|'.join(SERVING_ENGINE_VALUES)}; using the "
                        "depth-batched walk")
            return answer("walk", source)
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
    return answer("level" if depth <= level_cap else "walk", "default")
