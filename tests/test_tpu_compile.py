"""The Mosaic kernels and the train-step programs compile for a TPU v5e.

No chip is attached here: the TPU's compiler is installed and compiles for a
chip that is DESCRIBED (``topologies.get_topology_desc``). That shows what
Pallas interpret mode — all the rest of the CPU suite ever runs — cannot: a
slice not aligned to the tiling, a kernel over the scoped-VMEM limit, a
program that does not fit 16 GB, a kernel that cannot be partitioned.
Nothing runs, so these tests say nothing about results or times.

Rules this file keeps (the TPU library loads in ONE process at a time and is
held until exit, and pytest-xdist imports every test file in every worker):
the topology and everything built from it live in module-scoped, non-autouse
fixtures of THIS file and skip from there; nothing here touches the topology
at import, in a ``skipif`` or in ``parametrize`` arguments; every compile
runs in the test's own process; and all such tests stay in this one file. A
described-device executable cannot be read back from the persistent
compilation cache, so the cache is switched off around the compiles.

Tier-1 keeps the standalone histogram kernel (split and int8 at higgs
width, a few seconds each) and the five cells' fused kernels at the block
and depth the registry fits (higgs ~5 s and int8 ~6 s, 63 bins ~4 s, 67
features ~11 s, 220 features ~15 s; 10, 7, 4, 18 and 19 s before PR 38's
two-level flush), each one's text within 5% of its size before the ring
carries were packed;
the other fused variants and the whole step programs are ``slow``
(run them before spending chip time on a change to a kernel, its clamp, or
the step: ``pytest tests/test_tpu_compile.py -m 'slow or not slow'``).
"""
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, SingleDeviceSharding

import lightgbm_tpu as lgb
from lightgbm_tpu.engines import registry
from lightgbm_tpu.ops.compact import RowLayout
from lightgbm_tpu.ops.fused_split import fused_block_cap, fused_split
from lightgbm_tpu.ops.pallas_histogram import pallas_histogram
from lightgbm_tpu.ops.record_write import record_write

HBM_BYTES = 16 << 30            # one v5e chip
HIGGS_ROWS = 10_500_000
# binary objective, no weights: score + label + row id ride as extras
HIGGS_EXTRAS = 3


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chip_mesh(topo):
    from lightgbm_tpu.parallel.mesh import make_mesh
    return make_mesh(devices=topo.devices)


@pytest.fixture
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compiled_with_kernel(lowered):
    compiled = lowered.compile()   # raises what the chip's compiler raises
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# ------------------------------------------------ standalone histogram kernel
HIST_CASES = {
    # name: (rows, features, bins, mode, mbatch, layout)
    "higgs-split-k1": (1 << 20, 28, 256, "split", 1, "lane"),
    "higgs-split-k8": (1 << 20, 28, 256, "split", 8, "lane"),
    "higgs-int8-k8": (1 << 20, 28, 256, "int8", 8, "lane"),
    "b64-sublane-k8": (1 << 20, 28, 64, "split", 8, "sublane"),
    # a 16k-row call at the deepest batched-M the knob allows
    "sweep-sample-k16": (1 << 14, 28, 256, "split", 16, "lane"),
}
HIST_CASES_SLOW = {
    # the auto dispatch's F*B <= 50,000 boundary (ops/histogram._resolve_impl)
    "boundary-f195": (1 << 20, 195, 256, "split", 8, "lane"),
    "msltr-f137": (1 << 20, 137, 256, "split", 8, "lane"),
    "pack4-width-sublane-int8": (1 << 20, 28, 16, "int8", 8, "sublane"),
}


def _hist_compile(case, one_chip):
    rows, f, b, mode, mbatch, layout = case
    ch_t = jnp.int8 if mode == "int8" else jnp.float32
    _compiled_with_kernel(pallas_histogram.lower(
        jax.ShapeDtypeStruct((rows, f), jnp.uint8, sharding=one_chip),
        jax.ShapeDtypeStruct((rows, 4), ch_t, sharding=one_chip),
        b, mode=mode, mbatch=mbatch, hist_layout=layout))


@pytest.mark.parametrize("name", sorted(HIST_CASES))
def test_histogram_kernel_compiles_for_v5e(name, one_chip,
                                           no_persistent_cache):
    _hist_compile(HIST_CASES[name], one_chip)


@pytest.mark.slow
@pytest.mark.parametrize("name", sorted(HIST_CASES_SLOW))
def test_histogram_kernel_compiles_for_v5e_slow(name, one_chip,
                                                no_persistent_cache):
    _hist_compile(HIST_CASES_SLOW[name], one_chip)


# -------------------------------------------------------- fused split kernel
FUSED_CASES_SLOW = {
    # name: dict(features, bins, + fused_split keyword overrides)
    # the depths a user or LGBM_TPU_HIST_MBATCH can still hand the kernel
    # (8 is what fused entries ran before PR 29, ~80 s to compile)
    "higgs-dual-k1": dict(f=28, b=256, mbatch=1),
    "higgs-dual-k8": dict(f=28, b=256, mbatch=8),
    "higgs-dual-k16": dict(f=28, b=256, mbatch=16),
    "higgs-copyback-k8": dict(f=28, b=256, dual=False),
    "higgs-quant-k8": dict(f=28, b=256, quant=True),
    "b64-sublane-k8": dict(f=28, b=64, hist_layout="sublane"),
    # 65-128 bins pad to a stride of 128: two levels of hi (no cell's)
    "b100-dual-k2": dict(f=28, b=100, mbatch=2),
    "b100-quant-k2": dict(f=28, b=100, mbatch=2, quant=True),
    # narrow bins: refused before PR 24 (23.3 / 16.2 MB of scoped VMEM
    # against 16 MB) until _hist_packing bounded the group's compare tiles
    "b16-lane-k8": dict(f=28, b=16),
    "b32-lane-k8": dict(f=28, b=32),
    "pack4-lane-k8": dict(f=28, b=16, packed4=True),
    "pack4-sublane-k8": dict(f=28, b=16, packed4=True,
                             hist_layout="sublane"),
    "msltr-f137-dual-k8": dict(f=137, b=256),
    # the on-chip shape of the deleted tests/test_tpu_shapes.py: Allstate's
    # 4228 one-hot features bundle to 529 columns, and bundled data runs
    # the copy-back variant (boosting/gbdt._setup_compact_state)
    "efb-529-copyback-k8": dict(f=529, b=256, dual=False),
}


def _fused_compile(one_chip, f, b, rows=1 << 20, packed4=False, **kw):
    layout = RowLayout(num_features=f, num_extra=HIGGS_EXTRAS,
                       packed4=packed4)
    c = layout.num_cols
    bs = kw.pop("block_size", None) or min(512, fused_block_cap(
        c, kw.get("mbatch", 8), kw.get("quant", False),
        kw.get("hist_layout", "lane")))
    n = rows + bs + 32

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    i32 = arr((), jnp.int32)
    return _compiled_with_kernel(fused_split.lower(
        arr((n, c), jnp.uint8), arr((n, c), jnp.uint8), i32, i32, i32, i32,
        i32, i32, i32, i32, i32, arr((8,), jnp.uint32), layout=layout,
        num_bins=b, block_size=bs, smaller_left=i32, side=i32,
        num_rows=rows, **kw))


# past 8 MB or so of kernel text every streamed row pays 60-180 ns on the
# chip; every shape at 7.5 MB and under has run clean (PERF.md section 6,
# PR 30)
CLEAN_TEXT_BYTES = 7_500_000
# generated_code_size_in_bytes of the cells' kernels before the ring
# carries were packed (some 25 KB more than with them), compiled here for a
# described v5e; the two-level flush had cut them from 1.97, 1.92, 3.69 and
# 4.16 MB. A rewrite of the kernel stays within 5% of these
TEXT_UNPACKED_CARRIES = {"higgs": 1_010_688, "higgs_int8": 1_114_624,
                     "higgs_b63": 795_648, "istella": 2_048_512,
                     "criteo": 1_775_616}


def _fitted(features, bins, rows, quant=False):
    """(layout, block, depth) the registry fits a fused entry on a TPU
    with nothing set (``quant``: but ``use_quantized_grad``)."""
    layout = RowLayout(num_features=features, num_extra=HIGGS_EXTRAS)
    res = registry.resolve(
        {"use_quantized_grad": True} if quant else {}, platform="tpu",
        shape=registry.DatasetShape(rows, features, bins - 1, "serial",
                                    quant=quant))
    assert res.entry_id == "fused_lane"
    assert res.sources["hist_mbatch"] == "fused"
    return (layout,) + registry.fit_fused_flush(res, layout.num_cols, bins,
                                                features)


# cell: (features, bins, rows, quantized, record bytes, block, depth)
FUSED_CELLS = {
    # the headline kernel as higgs trains it: 128-byte records, 256 bins,
    # dual residency, block clamped to 384, at the depth the registry
    # resolves for a fused entry on a TPU with nothing set
    "higgs": (28, 256, HIGGS_ROWS, False, 128, 384, 2),
    # `higgs_quant_train`: higgs's records with the quantized gradients'
    # int8 channels and int32 accumulator, at the same block and depth
    "higgs_int8": (28, 256, HIGGS_ROWS, True, 128, 384, 2),
    # `higgs_b63_train`: eight features of 64 bins a matmul group,
    # concatenated along sublanes at a stride of 64: one level
    "higgs_b63": (28, 64, HIGGS_ROWS, False, 128, 384, 2),
    # `istella_train`: 220 features in 256-byte records at 256 bins. At
    # the fused default depth 2 and the record's own block of 192 its 110
    # unrolled feature groups wanted 23 MB of scoped VMEM where the
    # compiler gives 16 MB (the kernel was refused at its first step);
    # the registry fits depth 1 at a block of whole lane tiles (256: at
    # 192 the half-empty tile loses the masked weight load)
    "istella": (220, 256, 7_325_625, False, 256, 256, 1),
    # a shard's kernel in `criteo_dp4_train`: 67 features of 256 bins in
    # 128-byte records at higgs's block and depth; an odd feature count,
    # so the two-level flush's last pair is half empty
    "criteo": (67, 256, 10_000_000, False, 128, 384, 2),
}


@pytest.mark.parametrize("cell", list(FUSED_CELLS))
def test_fused_kernel_compiles_for_v5e(cell, one_chip, no_persistent_cache):
    """Each cell's kernel at the block and depth the registry fits it (the
    packed carries free VMEM and spend none of it), compiled for a v5e,
    its text within 5% of its size with unpacked carries and on the clean
    side of the cliff."""
    f, b, rows, quant, cols, want_bs, want_depth = FUSED_CELLS[cell]
    layout, bs, depth = _fitted(f, b, rows, quant=quant)
    assert (layout.num_cols, bs, depth) == (cols, want_bs, want_depth)
    compiled = _fused_compile(one_chip, f=f, b=b, mbatch=depth,
                              block_size=bs, quant=quant)
    text = compiled.memory_analysis().generated_code_size_in_bytes
    assert text <= 1.05 * TEXT_UNPACKED_CARRIES[cell] < CLEAN_TEXT_BYTES, text


@pytest.mark.slow
@pytest.mark.parametrize("name", sorted(FUSED_CASES_SLOW))
def test_fused_kernel_compiles_for_v5e_slow(name, one_chip,
                                            no_persistent_cache):
    _fused_compile(one_chip, **FUSED_CASES_SLOW[name])


# ------------------------------------------------------ record_write kernel
@pytest.mark.parametrize("rows,num_cols,grad_off", [
    (HIGGS_ROWS + 16_384, 128, 28),       # higgs's records: the whole tile
    (7_325_625 + 16_384, 256, 220),       # istella's: the second tile alone
], ids=["higgs", "istella"])
def test_record_write_compiles_for_v5e(rows, num_cols, grad_off, one_chip,
                                       no_persistent_cache):
    """The step's per-row columns (g·w, h·w, w, the score) written into
    the records at a cell's real size: streamed row blocks, the record
    array aliased to the output (nothing copied, no temporary)."""
    compiled = _compiled_with_kernel(jax.jit(
        lambda w, c: record_write(w, c, grad_off), donate_argnums=0).lower(
        jax.ShapeDtypeStruct((rows, num_cols), jnp.uint8, sharding=one_chip),
        jax.ShapeDtypeStruct((4, rows), jnp.float32, sharding=one_chip)))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= rows * num_cols, mem   # tiled rows
    assert mem.temp_size_in_bytes == 0, mem


def _record_write_in_place(text, rows, num_cols):
    """What the compiled step does with the ``u8[rows, num_cols]`` record
    array where it writes the per-row columns: the ``record_write`` call,
    and no update of the whole array (the parent's ``dynamic-update-slice``,
    ``scatter`` under ``shard_map``) and no row-major copy of a u8 operand
    over the rows (the parent's ``u8[rows, 16]``) or of the array."""
    assert "record_write" in text
    whole = re.findall(rf"= u8\[{rows},{num_cols}\]\S* "
                       r"(dynamic-update-slice|scatter)\(", text)
    assert not whole, whole
    copies = re.findall(rf"= (u8\[{rows},\d+\]\S*) copy\(", text)
    assert not copies, copies


# --------------------------------------------------- whole train-step programs
class _Captured(Exception):
    pass


def _abstract_step(monkeypatch, params, rows, features=28):
    """(booster, step args, step kwargs) of the FIRST train-step call a
    booster would make on a TPU, captured before anything runs.

    The engine registry asks the live backend which platform it is on; here
    that is the CPU, so the test steers ``current_platform`` (in the test,
    not through an option of the program) to get the engines a TPU run
    resolves: compact grower, fused kernel, Mosaic histograms. The step
    callable is then swapped for a recorder that keeps its arguments and
    aborts, because the kernel cannot execute on this backend."""
    monkeypatch.setattr(registry, "current_platform", lambda: "tpu")
    rng = np.random.RandomState(0)
    x = rng.randn(rows, features).astype(np.float32)
    y = (x[:, 0] + x[:, 1] > 0).astype(np.float64)
    bst = lgb.Booster(params, lgb.Dataset(x, label=y, params=params))
    g = bst._gbdt
    got = {}

    def recorder(*args, **kwargs):
        got["args"], got["kwargs"] = args, kwargs
        raise _Captured

    if g._use_compact:
        g._setup_compact_state()
        g._compact["step"] = recorder
    else:
        g._step_fn = recorder
    with pytest.raises(_Captured):
        g.train_one_iter()
    return bst, got["args"], got["kwargs"]


def _retarget(args, dim_map, sharding_of):
    """Abstract the captured arguments at the real row count, placed on the
    described devices (``jax.device_put`` to them is impossible)."""
    def leaf(v):
        if not isinstance(v, jax.Array):
            return v
        shape = tuple(dim_map.get(d, d) for d in v.shape)
        return jax.ShapeDtypeStruct(shape, v.dtype, sharding=sharding_of(v))
    return jax.tree_util.tree_map(leaf, args)


STEP_PARAMS = {
    "objective": "binary", "num_leaves": 255, "max_bin": 255,
    "min_data_in_leaf": 100, "verbosity": -1,
}


@pytest.mark.slow
def test_compact_step_compiles_for_v5e_at_higgs_shape(
        monkeypatch, one_chip, no_persistent_cache):
    """One whole serial train step at 10.5M x 28, 255 leaves, 255 bins.
    The per-row columns go into the records through ``record_write``:
    no update of the whole record array, no row-major copy (PR 40)."""
    bst, args, kwargs = _abstract_step(monkeypatch, STEP_PARAMS, 1 << 16)
    g = bst._gbdt
    assert g._use_compact and g.grower_params.fused_block > 0
    assert not g.grower_params.fused_interpret
    abstract = _retarget(args, g.flight_row_dims(HIGGS_ROWS),
                         lambda v: one_chip)
    compiled = _compiled_with_kernel(
        g._build_compact_step_fn().lower(*abstract, **kwargs))
    _record_write_in_place(compiled.as_text(), *abstract[0].shape)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, mem


@pytest.mark.slow
def test_data_parallel_step_compiles_for_four_v5e_chips(
        monkeypatch, four_chip_mesh, no_persistent_cache):
    """The same step under ``shard_map`` over a 4-chip mesh at 4M rows:
    the kernel partitions with the shards and the histogram collective is
    in the program; a shard's records take ``record_write`` as on one chip
    (the parent's ``scatter.454`` and its copy are gone)."""
    params = dict(STEP_PARAMS, tree_learner="data", tpu_mesh_shape="4")
    bst, args, kwargs = _abstract_step(monkeypatch, params, 1 << 18)
    g = bst._gbdt
    assert g._use_compact and g.mesh is not None
    dims = g.flight_row_dims(1 << 22)
    g.mesh = four_chip_mesh       # the step's shard_map closes over it
    abstract = _retarget(
        args, dims, lambda v: NamedSharding(four_chip_mesh, v.sharding.spec)
        if isinstance(v.sharding, NamedSharding) else None)
    k = kwargs.pop("k")
    step = g._build_compact_step_fn()
    # donating the records and the scratch as the step's own jit does:
    # without it every kernel that writes them in place needs a copy
    lowered = jax.jit(lambda *a: step(*a, k=k),
                      donate_argnums=(0, 1)).lower(*abstract)
    # what the program asks for (tpu_hist_scatter=auto: reduce-scatter) ...
    assert "reduce_scatter" in lowered.as_text()
    compiled = _compiled_with_kernel(lowered)
    # ... and what the v5e compiler makes of it: at this payload it
    # decomposes the reduce-scatter into all-reduce + slice
    text = compiled.as_text()
    assert "all-reduce" in text or "reduce-scatter" in text
    # a shard's records: the per-device shape of the row-sharded array
    rows, num_cols = abstract[0].shape
    _record_write_in_place(text, rows // len(four_chip_mesh.devices.flat),
                           num_cols)
    # per-chip bytes
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES, mem


@pytest.mark.slow
@pytest.mark.parametrize("top_k", [20, 8], ids=["full-election", "live-vote"])
def test_voting_step_compiles_for_four_v5e_chips(
        top_k, monkeypatch, four_chip_mesh, no_persistent_cache):
    """The voting learner's masked step at chip_smoke's four-chip size.
    GSPMD partitions it, and nothing partitions a Mosaic call (lowering
    one there is refused outright), so the registry hands this step the
    XLA einsum. At 28 features the default ``top_k=20`` elects every
    feature (the exact data-parallel histogram); ``top_k=8`` really votes."""
    params = dict(STEP_PARAMS, tree_learner="voting", tpu_mesh_shape="4",
                  num_leaves=31, top_k=top_k)
    bst, args, kwargs = _abstract_step(monkeypatch, params, 1 << 18)
    g = bst._gbdt
    assert not g._use_compact and g.mesh is not None
    assert g.grower_params.hist_impl == "xla"
    assert g._engine_resolution.sources["hist_impl"] == "gspmd"
    abstract = _retarget(
        args, {}, lambda v: NamedSharding(four_chip_mesh, v.sharding.spec)
        if isinstance(v.sharding, NamedSharding) else None)
    text = g._build_step_fn().lower(*abstract, **kwargs).compile().as_text()
    assert "all-reduce" in text or "all-gather" in text
