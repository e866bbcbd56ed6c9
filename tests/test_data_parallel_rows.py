"""What ``tree_learner=data`` past 2^24 rows stands on (the benchmark's
cell ``criteo_dp4_train``: 40,000,000 rows over four chips): row ids that
are int32 bytes from ``pack_rows`` to every reader, counts that cross
shards as integers, and the cell's own data at a toy size on four of the
suite's CPU devices, held to the plain reference and to the serial run."""
import copy
import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import lightgbm_tpu as lgb
from lightgbm_tpu.boosting.gbdt import GBDT
from lightgbm_tpu.ops.compact import (RowLayout, _u8_to_i32, pack_rows,
                                      partition_segment)
from lightgbm_tpu.ops.grower_compact import reduce_over_shards
from lightgbm_tpu.parallel.mesh import DATA_AXIS

from benchmarks import correct, run as bench_run
from benchmarks.generators import criteo_like
from benchmarks.traffic.train_window import score_in_dataset_order

TOY_ROWS, FEATURES, SHARDS = 40_000, 67, 4
INT32_MAX = 2**31 - 1


# ----------------------------------------------------- (a) the row id
@pytest.mark.parametrize("first", [0, 2**24 - 1, 2**24 + 1, INT32_MAX])
def test_row_id_is_int32_bytes_through_pack_partition_and_read(first, rng):
    """Ids from ``first`` down or up, through ``pack_rows``, one stable
    partition and the reader: every id comes back to the bit, at the place
    the partition put its row."""
    n, f, bs = 300, 5, 64
    ids = (first - np.arange(n) if first == INT32_MAX
           else first + np.arange(n)).astype(np.int32)
    binned = rng.randint(0, 16, size=(n, f)).astype(np.uint8)
    layout = RowLayout(num_features=f, num_extra=2)
    zeros = jnp.zeros((n,), jnp.float32)
    work = pack_rows(jnp.asarray(binned), zeros, zeros, zeros + 1,
                     jnp.arange(n, dtype=jnp.float32)[None, :], layout,
                     pad_rows=2 * bs, row_id=jnp.asarray(ids))
    off = layout.extra_off + 4
    np.testing.assert_array_equal(_u8_to_i32(work[:n, off:off + 4]), ids)

    left = binned[:, 2] <= 7
    work2, _ = partition_segment(
        work, jnp.zeros_like(work), jnp.int32(0), jnp.int32(n),
        jnp.int32(left.sum()), jnp.int32(2), jnp.int32(7), jnp.asarray(False),
        jnp.int32(0), jnp.asarray(False), jnp.zeros((1,), jnp.uint32), bs)
    want = np.concatenate([ids[left], ids[~left]])
    np.testing.assert_array_equal(_u8_to_i32(work2[:n, off:off + 4]), want)


def _sub_jaxprs(jaxpr):
    yield jaxpr
    for eqn in jaxpr.eqns:
        for value in eqn.params.values():
            for item in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(item, "jaxpr", item)
                if hasattr(inner, "eqns"):
                    yield from _sub_jaxprs(inner)


def f32_reads_of_record_columns(jaxpr):
    """The column ranges [lo, hi) of every slice of a u8 record array
    whose bytes a ``bitcast_convert_type`` then reads as float32, over the
    jaxpr and all it nests; None for a read whose source is no slice."""
    found = []
    for sub in _sub_jaxprs(jaxpr):
        made_by = {v: e for e in sub.eqns for v in e.outvars}
        for eqn in sub.eqns:
            if (eqn.primitive.name != "bitcast_convert_type"
                    or eqn.params["new_dtype"] != jnp.float32
                    or eqn.invars[0].aval.dtype != jnp.uint8):
                continue
            src = made_by.get(eqn.invars[0])
            while src is not None and src.primitive.name in (
                    "reshape", "transpose", "squeeze"):
                src = made_by.get(src.invars[0])
            if src is not None and src.primitive.name == "slice":
                found.append((src.params["start_indices"][-1],
                              src.params["limit_indices"][-1]))
            else:
                found.append(None)
    return found


@pytest.mark.parametrize("shards", [1, SHARDS])
def test_step_never_reads_the_row_id_as_f32(shards, rng):
    """In the step's jaxpr every float32 read of record bytes is a slice
    of named columns, and none of them touches the row id's four: a small
    integer's bits are an f32 denormal, which the TPU flushes."""
    X = np.abs(rng.normal(size=(1200, 6))).astype(np.float32)
    y = (X[:, 0] > 0.7).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
              "tpu_grower": "compact", "min_data_in_leaf": 5}
    if shards > 1:
        params.update(tree_learner="data", tpu_mesh_shape=str(shards))
    bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=1,
                    keep_training_booster=True)
    g = bst._gbdt
    step, seen = g._compact["step"], []

    def recording(*args, **kw):
        seen.append(jax.make_jaxpr(functools.partial(step, **kw))(*args))
        return step(*args, **kw)

    g._compact["step"] = recording
    bst.update()
    reads = f32_reads_of_record_columns(seen[0].jaxpr)
    layout = g._compact["layout"]
    rid = layout.extra_off + 4 * g._cx_rowid
    assert len(reads) >= 3 and None not in reads, reads
    assert all(hi <= rid or lo >= rid + 4 for lo, hi in reads), (reads, rid)
    # and the ids are the rows' own after two trees' partitions
    perm = g._compact_perm()
    assert sorted(perm) == list(range(g.num_data))


# ------------------------------------------- (b) counts across shards
@pytest.mark.parametrize("total", [2**24 + 1, 2**25 + 3])
@pytest.mark.parametrize("scatter", [False, True])
def test_counts_cross_shards_as_integers(total, scatter):
    """Four shards' histograms whose counts, each exact in f32, sum past
    2^24: the reduced counts are that sum to the unit, where the f32 sum
    of the same channels is not."""
    f, b = 4, 8
    share = np.full(SHARDS, total // SHARDS, np.int64)
    share[: total % SHARDS] += 1
    assert share.max() < 2**24 and share.sum() == total
    local = np.zeros((SHARDS, f, b, 4), np.float32)
    local[:, :, 0, 2] = share[:, None]          # in-bag count, bin 0
    local[:, :, 1, 3] = share[:, None] - 1      # raw count, bin 1
    local[:, :, :, 0] = 0.25
    mesh = Mesh(np.array(jax.devices()[:SHARDS]), (DATA_AXIS,))
    out = jax.jit(jax.shard_map(
        lambda x: reduce_over_shards(x[0], DATA_AXIS, scatter),
        mesh=mesh, in_specs=P(DATA_AXIS),
        out_specs=P(DATA_AXIS) if scatter else P(), check_vma=False))(
        jnp.asarray(local))
    sums, counts = (np.asarray(a) for a in out)
    assert counts.dtype == np.int32 and sums.dtype == np.float32
    assert sums.shape == counts.shape == (f, b, 2)
    assert (counts[:, 0, 0] == total).all()
    assert (counts[:, 1, 1] == total - SHARDS).all()
    assert (sums[:, :, 0] == 0.25 * SHARDS).all()
    assert float(np.float32(local[:, 0, 0, 2].sum(dtype=np.float32))) != total


# ------------------------------------- (c), (d) the cell at a toy size
@pytest.fixture(scope="module")
def toy():
    """``criteo_dp4_train``'s own files with the rows and the leaves cut,
    four shards named: four trees on four CPU devices (the first holds
    the average the boosting starts from, the window is the three after
    it), and the serial run of the same data."""
    cell, config = bench_run.load_cell("criteo_dp4_train")
    config = copy.deepcopy(config)
    config["sizes"]["rows"] = TOY_ROWS
    # under 65,536 rows the program would choose the masked grower
    config["params"].update(num_leaves=31, verbosity=-1, tpu_grower="compact",
                            tpu_mesh_shape=str(SHARDS))
    data = criteo_like.make(2**31 + 11, **config["sizes"])

    def train(params):
        ds = lgb.Dataset(data["XT"].T, label=data["label"], params=params)
        return lgb.train(params, ds, num_boost_round=4,
                         keep_training_booster=True)

    serial = dict(config["params"], tree_learner="serial")
    serial.pop("tpu_mesh_shape")
    return cell, config, data, train(config["params"]), train(serial)


def test_toy_cell_is_inside_its_limits_on_four_shards(toy):
    cell, config, data, bst, _ = toy
    g = bst._gbdt
    assert g._use_compact and g._compact["S"] == SHARDS
    assert g._compact["nl"] == TOY_ROWS // SHARDS
    assert getattr(bst._gbdt.train_set, "bundle_info", None) is None
    produced = {"model_text": bst.model_to_string(),
                "train_score": score_in_dataset_order(bst),
                "first_window_tree": 1, "window_iterations": 3, "seed": 1}
    readings = correct.reference_readings(produced, data, config)
    ok, rows = correct.judge(readings, cell["limits"])
    assert ok, rows
    assert readings["trees_missing"] == readings["leaf_count_wrong"] == 0


def test_toy_first_tree_is_the_serial_runs(toy):
    *_, mesh_bst, serial_bst = toy
    a, b = (correct.parse_trees(x.model_to_string())[0]
            for x in (mesh_bst, serial_bst))
    for key in ("split_feature", "threshold", "left_child", "right_child",
                "leaf_count", "internal_count"):
        np.testing.assert_array_equal(a[key], b[key], err_msg=key)
    # the same rows' gradients, summed in four shards' groups
    np.testing.assert_allclose(a["leaf_value"], b["leaf_value"], rtol=5e-4)


def test_score_in_dataset_order_under_a_mesh_is_predict(toy):
    _, _, data, bst, _ = toy
    got = score_in_dataset_order(bst)
    want = bst.predict(data["XT"].T, raw_score=True)
    assert got.shape == (TOY_ROWS,)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # the iteration events carry the mesh's counters
    from lightgbm_tpu.obs import flight
    ticks = [e for e in flight.recorder().events()
             if e["event"] == "iteration" and e.get("shards") == SHARDS]
    assert ticks and ticks[-1]["rows_per_shard"] == TOY_ROWS // SHARDS
    assert ticks[-1]["collectives"] > 0 and ticks[-1]["collective_bytes"] > 0


# --------------------------------------------------- (e) what bounds a job
@pytest.mark.parametrize("shards", [1, SHARDS])
def test_compact_setup_refuses_by_rows_a_shard(shards):
    mesh = (Mesh(np.array(jax.devices()[:shards]), (DATA_AXIS,))
            if shards > 1 else None)
    stub = types.SimpleNamespace(objective=None, mesh=mesh,
                                 num_data=shards * 2**24)
    with pytest.raises(RuntimeError, match=r"2\^24 - 1 rows a shard"):
        GBDT._setup_compact_state(stub)
    # one row a shard fewer passes the check (and then misses the stub's
    # other fields)
    stub.num_data = shards * (2**24 - 1)
    with pytest.raises(AttributeError):
        GBDT._setup_compact_state(stub)
