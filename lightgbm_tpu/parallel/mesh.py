"""Device mesh + sharding helpers for distributed training.

TPU-native replacement for the reference's distributed tree learners and
network layer (reference: src/treelearner/data_parallel_tree_learner.cpp —
rows partitioned across machines, histograms ReduceScattered over the
socket/MPI Network, src/network/network.cpp; topology maps linker_topo.cpp).

Here rows are sharded over a ``jax.sharding.Mesh`` axis and the jitted tree
grower runs under GSPMD: XLA partitions the histogram contraction over the row
axis and inserts the AllReduce over ICI automatically — the explicit
Bruck/recursive-halving machinery of the reference's network layer is subsumed
by the XLA collective implementation (SURVEY §2.7). Multi-host extends the same
mesh over DCN via ``jax.distributed.initialize`` (reference equivalent:
machines/machine_list_file config + TCP mesh construction,
linkers_socket.cpp:29-118).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
FEAT_AXIS = "feat"


def parse_mesh_shape(spec: str) -> Optional[Tuple[int, ...]]:
    """``tpu_mesh_shape`` strings: ``""``/``"auto"`` (all devices, 1-D),
    ``"8"`` (first 8 devices, 1-D), ``"4x2"`` (2-D: 4-way rows x 2-way
    features). Returns None for the all-devices default."""
    s = str(spec or "").strip().lower()
    if s in ("", "auto", "0"):
        return None
    parts = [p for p in s.replace("*", "x").split("x") if p]
    try:
        dims = tuple(int(p) for p in parts)
    except ValueError:
        raise ValueError(
            f"tpu_mesh_shape={spec!r}: expected 'N' (1-D row mesh) or "
            "'RxC' (2-D rows x features), e.g. '8' or '4x2'")
    if not dims or len(dims) > 2 or any(d < 1 for d in dims):
        raise ValueError(
            f"tpu_mesh_shape={spec!r}: need 1 or 2 positive factors "
            "(rows[ x features])")
    return dims


def make_mesh(num_devices: Optional[int] = None,
              devices: Optional[Sequence] = None,
              mesh_shape: Optional[Tuple[int, ...]] = None) -> Mesh:
    """Device mesh over the row (data) axis, optionally 2-D rows x features.

    The reference's world is ``num_machines`` ranks in a flat TCP/MPI mesh
    (network.h Init); ours is whatever devices JAX exposes (single host: all
    local chips; multi-host: the global device set). ``mesh_shape``
    (see :func:`parse_mesh_shape`) restricts the device count and, with
    two factors, folds the mesh to ``(data, feat)`` — the 2-D sharding
    for the wide one-hot shapes where the feature axis is worth
    partitioning too (ROADMAP 2; reference analogue: the row-wise vs
    col-wise histogram dispatch, dataset.h:727).
    """
    if devices is None:
        devices = jax.devices()
        if mesh_shape is not None:
            need = 1
            for d in mesh_shape:
                need *= d
            if need > len(devices):
                raise ValueError(
                    f"tpu_mesh_shape={'x'.join(map(str, mesh_shape))} "
                    f"needs {need} devices, have {len(devices)}")
            devices = devices[:need]
        elif num_devices is not None:
            devices = devices[:num_devices]
    devices = np.asarray(devices)
    if mesh_shape is not None and len(mesh_shape) == 2:
        return Mesh(devices.reshape(mesh_shape), (DATA_AXIS, FEAT_AXIS))
    return Mesh(devices, (DATA_AXIS,))


def mesh_axis_sizes(mesh: Mesh) -> Tuple[int, int]:
    """(row shards, feature shards) of a training mesh (1-D: feat=1)."""
    ax = dict(zip(mesh.axis_names, mesh.devices.shape))
    return ax.get(DATA_AXIS, 1), ax.get(FEAT_AXIS, 1)


def row_sharding(mesh: Mesh) -> NamedSharding:
    """[N, ...] arrays sharded along rows."""
    return NamedSharding(mesh, P(DATA_AXIS))


def row_sharding_2d(mesh: Mesh) -> NamedSharding:
    """[N, F] arrays sharded along rows, features replicated."""
    return NamedSharding(mesh, P(DATA_AXIS, None))


def row_feature_sharding(mesh: Mesh) -> NamedSharding:
    """[N, F] arrays sharded along BOTH axes of a 2-D ``(data, feat)``
    mesh (the wide one-hot shape: 4228 one-hot columns are worth
    partitioning too); on a 1-D mesh this is plain row sharding."""
    if FEAT_AXIS in mesh.axis_names:
        return NamedSharding(mesh, P(DATA_AXIS, FEAT_AXIS))
    return NamedSharding(mesh, P(DATA_AXIS, None))


def feature_sharding_2d(mesh: Mesh) -> NamedSharding:
    """[N, F] arrays sharded along features, rows replicated
    (feature-parallel learner: reference feature_parallel_tree_learner.cpp)."""
    return NamedSharding(mesh, P(None, DATA_AXIS))


def class_row_sharding(mesh: Mesh) -> NamedSharding:
    """[K, N] score arrays: classes replicated, rows sharded."""
    return NamedSharding(mesh, P(None, DATA_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def pad_rows(n: int, num_shards: int) -> int:
    """Rows must split evenly across shards; callers mask the tail
    (reference analogue: pre_partition / CheckOrPartition, dataset.h:110)."""
    return (-n) % num_shards


_barrier_seq = 0


def sync_barrier(tag: str, deadline_s: float = 0.0) -> None:
    """Named cross-process barrier with an optional watchdog deadline.

    Multi-process runs block until every rank arrives — the reference's
    ``Network::``AllReduce-as-barrier between training phases. A rank
    that never arrives (preempted worker, wedged runtime) used to hang
    the whole pod silently; under a positive ``deadline_s`` the wait
    surfaces as a structured ``TrainingInterrupted`` instead
    (parallel/multihost.py watchdog), and the training engine snapshots
    before exiting. Single-process runs only fire the fault-injection
    hook (so dryrun chaos tests exercise the same code path tier-1 runs
    on CPU).

    The wait goes through the coordination-service KV barrier
    (``wait_at_barrier``), which works on every backend — the XLA
    collective inside ``multihost_utils.sync_global_devices`` is not
    implemented for multiprocess CPU, which the 2-process dryrun tests
    rely on. Barrier ids carry a per-process sequence number; ranks call
    barriers in program order, so the ids line up across the pod.
    """
    from ..analysis.faultinject import active_plan
    from .multihost import kv_client, run_with_deadline

    global _barrier_seq
    _barrier_seq += 1
    seq = _barrier_seq

    def _sync():
        active_plan().fire("barrier", tag=tag)
        if jax.process_count() <= 1:
            return
        client = kv_client()
        if client is not None:
            # the KV timeout backstops the watchdog: keep it LARGER than
            # deadline_s so a hang surfaces as TrainingInterrupted first
            timeout_s = deadline_s * 2 if deadline_s > 0 else 600.0
            client.wait_at_barrier(f"lgbm_tpu_{tag}_{seq}",
                                   int(timeout_s * 1000))
        else:
            from jax.experimental import multihost_utils as mu
            mu.sync_global_devices(f"{tag}_{seq}")

    run_with_deadline(_sync, deadline_s, f"barrier {tag!r}")


def predict_shard_pad(n: int, num_shards: int, ladder) -> Optional[int]:
    """Padded row count for row-sharded bucketed predict, or None.

    Requests above the serving ladder's largest rung can run as ONE
    GSPMD-sharded program over this mesh instead of a host loop of
    max-rung slices: each shard gets ``bucket_rows(ceil(n/S))`` rows, so
    the compiled program is still keyed on a ladder rung (per shard) and
    steady-state stays zero-recompile. None = the per-shard share
    overflows the ladder too; the caller falls back to slicing.
    """
    from ..ops.predict import bucket_rows
    per_shard = -(-n // num_shards)
    rung = bucket_rows(per_shard, ladder)
    return None if rung is None else rung * num_shards
