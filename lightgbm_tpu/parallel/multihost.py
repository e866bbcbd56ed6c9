"""Multi-host (multi-process) training entry.

TPU-native replacement for the reference's cluster bootstrap
(reference: src/network/linkers_socket.cpp:29-118 — parse ``machines`` /
``machine_list_file``, bind ``local_listen_port``, build the full TCP mesh;
Dask analogue python-package/lightgbm/dask.py:374-412 builds the machines
string and runs one training process per worker).

On TPU pods the socket mesh is replaced by ``jax.distributed.initialize``:
every host runs the same training script, JAX wires the hosts over DCN, and
``jax.devices()`` then exposes the GLOBAL device set — the existing
data-parallel/voting/feature learners shard over all chips of all hosts with
no further changes (GSPMD inserts ICI collectives within a host and DCN
collectives across hosts).

Launch recipe (the reference's ``machines=ip1:port1,ip2:port2`` maps 1:1):

    # on every host, with the same machines list:
    params = {"tree_learner": "data",
              "machines": "10.0.0.1:12400,10.0.0.2:12400",
              "num_machines": 2}
    lgb.train(params, dataset, ...)

The first machines entry is the coordinator. Each host's process index is
inferred by matching a local interface address against the machines list, or
set explicitly via the LIGHTGBM_TPU_PROCESS_ID environment variable (the
reference resolves ranks the same way — by finding the local ip/port in the
list, linkers_socket.cpp:78-101).

Data feeding: each process passes only its local shard of rows (like the
reference's ``pre_partition=true``) and JAX's global sharding treats the
per-process arrays as one global dataset.
"""
from __future__ import annotations

import os
import socket
import threading
import time
from typing import Callable, List, Optional, Tuple

from ..utils import log

_initialized = False


class TrainingInterrupted(RuntimeError):
    """A collective/step blew its deadline (or a preemption surfaced).

    The structured replacement for a silent pod hang: carries what was
    running and the deadline that fired, and the training engine writes a
    best-effort final snapshot before re-raising it (engine.py), so a
    preemptible run loses at most the iterations since the last
    ``tpu_checkpoint_freq`` tick."""

    def __init__(self, what: str, deadline_s: float = 0.0):
        super().__init__(
            f"{what} exceeded its {deadline_s:.1f}s deadline"
            if deadline_s else what)
        self.what = what
        self.deadline_s = deadline_s


#: transient bootstrap/collective failure signatures (the TPU runtime
#: mid-restart family; matches the fault injector's TRANSIENT_MESSAGE).
#: This is the ONE canonical list — bench.py imports it (with a
#: standalone fallback) for its backend-init/resume retry classifiers.
TRANSIENT_ERRORS = (
    "Unable to initialize backend",
    "UNAVAILABLE", "Unavailable",
    "DEADLINE_EXCEEDED", "Deadline Exceeded",
    "failed to connect", "Failed to connect",
    "Connection reset", "Socket closed",
    "already in use",
    "No visible TPU", "device enumeration",
)


def run_with_deadline(fn: Callable, deadline_s: float, what: str, *,
                      retries: int = 0, backoff_s: float = 1.0):
    """Run ``fn()`` under a wall-clock watchdog.

    ``fn`` executes in a daemon worker thread; if it has not finished
    within ``deadline_s`` a structured :class:`TrainingInterrupted` is
    raised in the caller (the reference's socket linkers fail their
    connects after ``time_out`` minutes the same way,
    src/network/linkers_socket.cpp connect retry loop). ``deadline_s <= 0``
    runs ``fn`` inline with no watchdog (retries still apply).

    Transient failures (:data:`TRANSIENT_ERRORS` substrings) retry up to
    ``retries`` times with exponential backoff — the bootstrap analogue of
    the reference's per-linker connect retries.

    Caveat: a worker that blows its deadline is abandoned, not killed
    (Python cannot safely interrupt a thread blocked in native code). The
    caller is expected to snapshot and exit — the leaked thread dies with
    the process, which is the point of the final snapshot.
    """
    attempt = 0
    while True:
        try:
            if deadline_s and deadline_s > 0:
                box: dict = {}
                done = threading.Event()

                def _runner():
                    try:
                        box["value"] = fn()
                    except BaseException as err:  # noqa: BLE001 - re-raised
                        box["error"] = err
                    finally:
                        done.set()

                worker = threading.Thread(
                    target=_runner, daemon=True,
                    name=f"lgbm-tpu-watchdog[{what}]")
                worker.start()
                if not done.wait(deadline_s):
                    from ..obs import flight
                    flight.note("deadline", what=what,
                                deadline_s=deadline_s)
                    raise TrainingInterrupted(what, deadline_s)
                if "error" in box:
                    raise box["error"]
                return box.get("value")
            return fn()
        except TrainingInterrupted:
            raise
        except Exception as err:  # noqa: BLE001 - classified below
            msg = str(err)
            transient = any(t in msg for t in TRANSIENT_ERRORS)
            if not transient or attempt >= retries:
                raise
            delay = backoff_s * (2 ** attempt)
            attempt += 1
            from ..obs import flight
            flight.note("retry", what=what, attempt=attempt,
                        error=msg.splitlines()[0][:200])
            log.warning(
                f"{what}: transient failure (attempt {attempt}/"
                f"{retries}): {msg.splitlines()[0][:200]}; retrying in "
                f"{delay:.1f}s")
            time.sleep(delay)


def _parse_machines(machines: str, machine_list_file: str) -> List[str]:
    if machines:
        return [m.strip() for m in machines.split(",") if m.strip()]
    if machine_list_file:
        with open(machine_list_file) as f:
            out = []
            for line in f:
                line = line.strip().replace(" ", ":")
                if line:
                    out.append(line)
            return out
    return []


def _local_addresses() -> List[str]:
    addrs = {"127.0.0.1", "localhost"}
    try:
        hostname = socket.gethostname()
        addrs.add(hostname)
        for info in socket.getaddrinfo(hostname, None):
            addrs.add(info[4][0])
    except OSError:  # pragma: no cover
        pass
    return addrs


def infer_process_id(machines: List[str]) -> Optional[int]:
    """Rank = index of the local address in the machines list (reference:
    linkers_socket.cpp:78-101 finds the local ip/port the same way)."""
    env = os.environ.get("LIGHTGBM_TPU_PROCESS_ID")
    if env is not None:
        return int(env)
    hosts = [m.rsplit(":", 1)[0] for m in machines]
    if len(set(hosts)) != len(hosts):
        # several processes on one host are indistinguishable by address
        # (the reference disambiguates by binding the port,
        # linkers_socket.cpp:78-101; we cannot bind the coordinator's port)
        raise ValueError(
            "machines lists the same host more than once; set "
            "LIGHTGBM_TPU_PROCESS_ID per process to assign ranks")
    local = _local_addresses()
    for i, host in enumerate(hosts):
        if host in local:
            return i
    return None


_kv_seq = 0


def kv_client():
    """The coordination-service KV client, or None before
    ``jax.distributed.initialize`` (single-process runs). The KV plane
    works on every backend, including multiprocess CPU.

    The package's ONE private reach for it: jax 0.9.0 has no public
    accessor for the client, and ``global_state.client`` is where the
    installed version keeps it. No catch — if it moves, multi-process
    runs must fail here, not fall to a slower or wrong path."""
    from jax._src import distributed
    return distributed.global_state.client


def kv_allgather(arr, tag: str, timeout_s: float = 600.0):
    """Allgather a host numpy array across processes over the
    coordination-service KV store — no XLA collective involved.

    Each rank publishes its (npy-serialized) array under a sequenced,
    rank-suffixed key, then blocking-reads every peer's key; the
    sequence number keeps repeated gathers from colliding, and callers
    must invoke KV gathers in the same program order on every rank
    (the sync_barrier discipline). Returns the per-rank arrays in rank
    order — ragged first dimensions are fine, which the padded XLA
    allgather path cannot say.
    """
    import io
    import jax
    import numpy as np
    global _kv_seq
    _kv_seq += 1
    client = kv_client()
    if client is None:  # pragma: no cover - no coordination service
        raise RuntimeError(
            "kv_allgather needs the jax.distributed coordination service "
            "(call init_distributed first)")
    buf = io.BytesIO()
    np.save(buf, np.ascontiguousarray(arr), allow_pickle=False)
    prefix = f"lgbm_tpu_kvag_{tag}_{_kv_seq}"
    client.key_value_set_bytes(
        f"{prefix}/{jax.process_index()}", buf.getvalue())
    out = []
    for p in range(jax.process_count()):
        raw = client.blocking_key_value_get_bytes(
            f"{prefix}/{p}", int(timeout_s * 1000))
        out.append(np.load(io.BytesIO(raw), allow_pickle=False))
    # clean up so repeated gathers (one per Dataset construct) do not
    # grow coordinator memory forever: a delete is only safe once EVERY
    # rank has read every key, so fence first, then each rank removes
    # its own key (no contention; the barrier id rides the same seq)
    client.wait_at_barrier(f"{prefix}_read", int(timeout_s * 1000))
    client.key_value_delete(f"{prefix}/{jax.process_index()}")
    return out


def pool_bin_sample(sample):
    """Pool bin-construction samples across processes so every rank builds
    IDENTICAL bin mappers from the global distribution (reference:
    ConstructBinMappersFromTextData gathers per-rank samples and syncs the
    resulting mappers, src/io/dataset_loader.cpp:1070; without this two
    hosts would bin their local shards differently and train a silently
    wrong model).

    On multiprocess CPU the gather rides :func:`kv_allgather` — jax's CPU
    backend has no XLA cross-process collectives unless gloo is compiled
    in, but the coordination-service KV plane always works there (the
    sync_barrier pattern), and the one-shot construct-time sample is tiny.
    """
    import jax
    import numpy as np
    if jax.process_count() <= 1:
        return sample
    if jax.default_backend() == "cpu":
        return np.concatenate(kv_allgather(sample, "binsample"), axis=0)
    from jax.experimental import multihost_utils as mu
    counts = mu.process_allgather(
        np.asarray([sample.shape[0]], np.int64)).reshape(-1)
    m = int(counts.max())
    padded = np.zeros((m, sample.shape[1]), sample.dtype)
    padded[:sample.shape[0]] = sample
    gathered = np.asarray(mu.process_allgather(padded))   # [P, m, F]
    return np.concatenate(
        [gathered[p, :int(c)] for p, c in enumerate(counts)], axis=0)


def gather_metadata(md, n_local: int):
    """Concatenate per-process Metadata into the global Metadata, in process
    order (the same order jax.make_array_from_process_local_data lays out
    the feature rows). Requires equal per-process row counts."""
    import jax
    import numpy as np
    from jax.experimental import multihost_utils as mu
    from ..io.dataset import Metadata

    counts = mu.process_allgather(
        np.asarray([n_local], np.int64)).reshape(-1)
    if int(counts.min()) != int(counts.max()):
        raise ValueError(
            "multi-host training needs the same row count on every process "
            f"(got {counts.tolist()}); pre-partition the data evenly "
            "(reference: pre_partition / CheckOrPartition, dataset.h:110)")
    n_global = int(counts.sum())
    out = Metadata(n_global)
    for field in ("label", "weight", "init_score", "position"):
        v = getattr(md, field)
        flags = mu.process_allgather(
            np.asarray([0 if v is None else 1], np.int64)).reshape(-1)
        if int(flags.max()) == 0:
            continue
        if v is None:
            raise ValueError(
                f"metadata field {field} set on some processes but not here")
        v = np.asarray(v)
        # agree on the class-major layout BEFORE branching: every process
        # must run the same collective sequence, so shape validation is
        # itself a collective (kk = -1 marks an indivisible local size)
        if v.ndim == 2:
            kk = -(10 + v.shape[1])  # [n_local, K] row-major layout
        elif n_local > 0 and v.size % n_local == 0:
            kk = v.size // n_local
        else:
            kk = -1
        kks = mu.process_allgather(np.asarray([kk], np.int64)).reshape(-1)
        if int(kks.min()) != int(kks.max()) or kk == -1:
            raise ValueError(
                f"metadata field {field}: inconsistent per-process shapes "
                f"(local size {v.size} for {n_local} rows; gathered layout "
                f"codes {sorted(set(int(x) for x in kks))}; expected "
                "n_local or an exact class-major multiple on every process)")
        if v.ndim == 2:
            # [n_local, K] init scores: concatenate along rows
            g = np.asarray(mu.process_allgather(v))      # [P, n_local, K]
            setattr(out, field, g.reshape(-1, v.shape[1]))
        elif kk != 1:
            # flat class-major [K*n_local] (the reference Metadata layout,
            # src/io/metadata.cpp init_score_): gather per class so the
            # global vector stays class-major
            g = np.asarray(mu.process_allgather(
                v.reshape(kk, n_local)))                 # [P, K, n_local]
            setattr(out, field,
                    np.concatenate(list(g), axis=1).reshape(-1))
        else:
            setattr(out, field,
                    np.asarray(mu.process_allgather(v)).reshape(-1))
    # ranking groups: queries must never straddle processes — each rank
    # holds whole queries and the global boundary vector concatenates with
    # running row offsets (the reference's partition contract:
    # Metadata::CheckOrPartition keeps query blocks intact,
    # src/io/metadata.cpp; dataset.h:110). Validation is COLLECTIVE: every
    # process runs the same allgather sequence and raises together, never
    # leaving a peer blocked inside a collective.
    if md.query_boundaries is None:
        qstat, sizes = 0, np.zeros((0,), np.int64)   # no groups here
    else:
        qb = np.asarray(md.query_boundaries, np.int64)
        ok = qb[-1] == n_local
        qstat = 1 if ok else 2                       # 2 = straddling rows
        sizes = np.diff(qb) if ok else np.zeros((0,), np.int64)
    qstats = mu.process_allgather(
        np.asarray([qstat], np.int64)).reshape(-1)
    if int(qstats.max()) > 0:
        if int(qstats.min()) == 0 or int(qstats.max()) == 2:
            raise ValueError(
                "ranking groups are inconsistent across processes "
                f"(per-rank states {qstats.tolist()}: 0=missing, 1=ok, "
                "2=group sizes do not cover the local rows); every process "
                "needs `group` sizes summing to its local row count — "
                "queries must not straddle processes")
        nq = mu.process_allgather(
            np.asarray([sizes.size], np.int64)).reshape(-1)
        m = int(nq.max())
        padded = np.zeros((m,), np.int64)
        padded[:sizes.size] = sizes
        g = np.asarray(mu.process_allgather(padded))       # [P, m]
        all_sizes = np.concatenate(
            [g[p, :int(c)] for p, c in enumerate(nq)])
        out.group = all_sizes
        out.query_boundaries = np.concatenate(
            [[0], np.cumsum(all_sizes)]).astype(np.int64)
    return out


def to_host(arr):
    """Fetch a (possibly non-addressable) jax.Array as host numpy.

    Multi-process: sharded global arrays are not fully addressable from one
    process; allgather them (metrics and model pulls are host-side)."""
    import jax
    import numpy as np
    if isinstance(arr, jax.Array) and not arr.is_fully_addressable:
        if arr.is_fully_replicated:
            return np.asarray(arr.addressable_data(0))
        from jax.experimental import multihost_utils as mu
        return np.asarray(mu.process_allgather(arr, tiled=True))
    return np.asarray(arr)


def maybe_init_distributed(params) -> bool:
    """Bootstrap multi-process training when num_machines > 1 (alias-aware).

    Must run before dataset construction (bin-mapper sync) and before any
    backend-initializing JAX call."""
    from ..config import Config
    cfg = Config(params) if isinstance(params, dict) else params
    if int(cfg.get("num_machines", 1) or 1) > 1:
        return init_distributed(cfg)
    return False


def init_distributed(config) -> bool:
    """Initialize JAX multi-process training when num_machines > 1.

    Returns True when running (or already running) in multi-process mode.
    Safe to call on every host; a no-op for single-machine configs.
    """
    global _initialized
    num_machines = int(config.get("num_machines", 1) or 1)
    if num_machines <= 1:
        return False
    if _initialized:
        return True
    import jax
    machines = _parse_machines(
        str(config.get("machines", "")),
        str(config.get("machine_list_filename", "")))
    if machines and len(machines) != num_machines:
        raise ValueError(
            f"num_machines={num_machines} but machines lists "
            f"{len(machines)} entries")
    coordinator = machines[0] if machines else None
    process_id = infer_process_id(machines) if machines else None
    if coordinator is None or process_id is None:
        raise ValueError(
            "multi-machine training needs machines='ip:port,...' (or "
            "machine_list_filename) naming every host, with this host's "
            "address in the list or LIGHTGBM_TPU_PROCESS_ID set "
            "(reference: config.h machines / linkers_socket.cpp)")
    log.info(f"Initializing multi-host training: rank {process_id}/"
             f"{num_machines}, coordinator {coordinator}")
    # the bootstrap barrier is the first place a preempted/half-up pod
    # hangs: run it under the collective watchdog (deadline + exponential
    # backoff on transient failures) so a dead coordinator surfaces as a
    # structured TrainingInterrupted, not a silent stall (reference:
    # linkers_socket.cpp retries each connect and fails after time_out)
    deadline = float(config.get("tpu_collective_deadline_s", 0.0) or 0.0)
    retries = int(config.get("tpu_collective_retries", 3) or 0)
    from ..analysis.faultinject import active_plan

    def _bootstrap():
        active_plan(config).fire("backend_init")
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_machines,
            process_id=process_id)

    run_with_deadline(_bootstrap, deadline,
                      f"multi-host bootstrap (rank {process_id}, "
                      f"coordinator {coordinator})", retries=retries)
    _initialized = True
    # post-bootstrap barrier under the same watchdog: proves every rank
    # actually came up before dataset construction starts (a half-up pod
    # otherwise hangs later, inside the first bin-mapper sync)
    from .mesh import sync_barrier
    sync_barrier("lgbm-tpu-bootstrap", deadline_s=deadline)
    return True
