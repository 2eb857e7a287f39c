"""Multi-process mesh runtime bootstrap: ``jax.distributed`` for SODDA runs.

Every backend in this repo runs unchanged on a *multi-process* device mesh
— the paper's actual deployment model (Table 1's 250k x 18k problem on a
Spark cluster), where the (data=P, model=Q) grid spans hosts and the psum
collectives cross a real interconnect instead of being single-host
memcpys. This module is the bootstrap seam that turns N coordinated CPU
(or accelerator) processes into one global mesh runtime:

* :func:`initialize` — idempotent ``jax.distributed.initialize`` wrapper,
  driven by explicit arguments or the ``REPRO_COORDINATOR`` /
  ``REPRO_NUM_PROCESSES`` / ``REPRO_PROCESS_ID`` environment variables
  (what the test harness ``repro.testing.launch_coordinated`` exports).
  On CPU it selects the gloo collectives implementation so cross-process
  psums actually run. With one process and no coordinator it is a no-op:
  the single-host runtime IS the num_processes=1 degenerate case.
* :func:`process_count` / :func:`process_index` / :func:`is_coordinator`
  — topology queries (valid before initialize: 1 process, index 0).
* :func:`local_device_slice` — the contiguous global-index rectangle this
  process's addressable devices cover under a sharding; the placement
  contract ``repro.data.plane`` uses to generate ONLY the local ``(p, q)``
  tiles and hand them to ``jax.make_array_from_process_local_data``.
* :func:`put_sharded` / :func:`fetch_local` — process-count-agnostic
  host→device and device→host transfer: ``device_put`` / ``np.asarray``
  degenerate single-process paths, ``jax.make_array_from_callback`` (each
  process materializes only its addressable shards) and a jitted
  replicate-then-read collective for the multi-process ones. The driver's
  checkpoint restore/save and history fetch go through these, which is
  what makes ``run_resumable`` process-count agnostic (coordinator-only
  writes, fully-replicated carry/history fetch — see ``docs/multihost.md``).

The contract with the rest of the stack: call :func:`initialize` before
the first jax device query; build meshes from the *global* device set
(``repro.core.engine.make_mesh_for`` does); keep every process executing
the same sequence of compiled dispatches (collectives are the sync
points); gate host-side I/O on :func:`is_coordinator`.
"""
from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import numpy as np

__all__ = [
    "COORDINATOR_ENV",
    "NUM_PROCESSES_ENV",
    "PROCESS_ID_ENV",
    "initialize",
    "is_initialized",
    "is_coordinator",
    "process_count",
    "process_index",
    "local_device_slice",
    "put_sharded",
    "fetch_local",
    "barrier",
    "connect_mesh_collectives",
]

COORDINATOR_ENV = "REPRO_COORDINATOR"
NUM_PROCESSES_ENV = "REPRO_NUM_PROCESSES"
PROCESS_ID_ENV = "REPRO_PROCESS_ID"

# (coordinator_address, num_processes, process_id) of the successful
# initialize, or None — the idempotence/conflict guard.
_INITIALIZED: Optional[Tuple[Optional[str], int, int]] = None


def _resolve(explicit, env_name, cast):
    if explicit is not None:
        return cast(explicit)
    raw = os.environ.get(env_name)
    return cast(raw) if raw not in (None, "") else None


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> bool:
    """Bring up the ``jax.distributed`` runtime for this process (idempotent).

    Arguments omitted here fall back to the ``REPRO_COORDINATOR`` /
    ``REPRO_NUM_PROCESSES`` / ``REPRO_PROCESS_ID`` environment variables —
    the launch-harness path. Resolution rules:

    * nothing resolved, or ``num_processes == 1`` with no coordinator
      address: **no-op** — plain single-process jax, the degenerate case
      every test already runs. Returns False.
    * a coordinator address (any process count, including 1): start the
      distributed runtime. Process 0 hosts the coordination service; on
      CPU the gloo collectives implementation is selected first so
      cross-process psums lower. Returns True.
    * ``num_processes > 1`` without a coordinator address: error — there
      is nothing to rendezvous on.

    Must run before the first jax device query (jax backends initialize
    lazily; a started backend cannot join a distributed runtime). Once the
    runtime is up, further calls return True: arguments omitted (or no
    longer resolvable from the environment) inherit the live runtime's
    values, and any resolved argument that conflicts with them raises —
    one process belongs to one runtime.
    """
    global _INITIALIZED
    coord = _resolve(coordinator_address, COORDINATOR_ENV, str)
    nproc = _resolve(num_processes, NUM_PROCESSES_ENV, int)
    pid = _resolve(process_id, PROCESS_ID_ENV, int)

    if _INITIALIZED is not None:
        # the runtime is up; arguments omitted here inherit its values, any
        # resolved argument that conflicts with them is an error
        want = (coord if coord is not None else _INITIALIZED[0],
                nproc if nproc is not None else _INITIALIZED[1],
                pid if pid is not None else _INITIALIZED[2])
        if _INITIALIZED != want:
            raise RuntimeError(
                f"multihost.initialize already ran with {_INITIALIZED}; "
                f"cannot re-initialize with {want} — one process joins "
                "one runtime")
        return True

    if coord is None:
        if nproc is not None and nproc > 1:
            raise ValueError(
                f"num_processes={nproc} needs a coordinator_address "
                f"(or {COORDINATOR_ENV}) to rendezvous on")
        return False  # single-process degenerate case: nothing to do

    nproc = 1 if nproc is None else int(nproc)
    pid = 0 if pid is None else int(pid)
    if not 0 <= pid < nproc:
        raise ValueError(
            f"process_id={pid} outside [0, num_processes={nproc})")

    import jax
    # select gloo BEFORE the backend starts; harmless on non-CPU platforms
    # (the option only affects the CPU client). Checking the platform via
    # jax.default_backend() would itself start the backend, so set it
    # unconditionally.
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=nproc, process_id=pid)
    _INITIALIZED = (coord, nproc, pid)
    return True


def is_initialized() -> bool:
    """True once :func:`initialize` started the distributed runtime (the
    single-process no-op path leaves this False — there is no runtime)."""
    return _INITIALIZED is not None


def process_count() -> int:
    """Global process count (1 before/without distributed initialize)."""
    import jax
    return jax.process_count()


def process_index() -> int:
    """This process's index in [0, process_count())."""
    import jax
    return jax.process_index()


def is_coordinator() -> bool:
    """True on the process that owns host-side I/O (checkpoint writes,
    bench emission): process 0, or everywhere in single-process mode."""
    return process_index() == 0


def local_device_slice(sharding, global_shape) -> Tuple[slice, ...]:
    """The contiguous per-dimension slices of ``global_shape`` covered by
    this process's addressable devices under ``sharding``.

    This is the *host-local tile placement* contract: with the mesh built
    from ``jax.devices()`` (global, process-major order), each process's
    devices tile a contiguous hyperrectangle of the array — whole
    observation-row blocks when its device count is a multiple of the
    model axis. Raises ``ValueError`` when the addressable shards do not
    tile a rectangle exactly (an exotic device permutation): callers fall
    back to per-device placement, which needs no contiguity.
    """
    index_map = sharding.addressable_devices_indices_map(tuple(global_shape))
    if not index_map:
        raise ValueError("sharding has no addressable devices here")
    ndim = len(global_shape)
    starts = [None] * ndim
    stops = [None] * ndim
    cells = set()
    for idx in index_map.values():
        norm = []
        for d, sl in enumerate(idx):
            lo = sl.start if sl.start is not None else 0
            hi = sl.stop if sl.stop is not None else global_shape[d]
            norm.append((lo, hi))
            starts[d] = lo if starts[d] is None else min(starts[d], lo)
            stops[d] = hi if stops[d] is None else max(stops[d], hi)
        cells.add(tuple(norm))
    # the distinct shard rectangles must tile the bounding box exactly
    box = np.prod([stops[d] - starts[d] for d in range(ndim)])
    covered = sum(np.prod([hi - lo for lo, hi in cell]) for cell in cells)
    if covered != box:
        raise ValueError(
            f"addressable shards cover {covered} of the {box}-element "
            f"bounding box [{starts}, {stops}): not a contiguous rectangle")
    return tuple(slice(int(starts[d]), int(stops[d])) for d in range(ndim))


@functools.lru_cache(maxsize=8)
def _replicator(mesh):
    """Jitted identity that reshards its input fully-replicated over
    `mesh` — the collective that makes a cross-process array readable on
    every host (each process then holds a complete addressable copy)."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    return jax.jit(lambda x: x, out_shardings=NamedSharding(mesh, P()))


def fetch_local(x) -> np.ndarray:
    """``np.asarray(x)`` that also works on cross-process jax Arrays.

    Fully-addressable arrays (everything in single-process mode) take the
    plain ``np.asarray`` path — bitwise the pre-multihost behavior. A
    cross-process array is first resharded fully-replicated (a collective:
    **every** process of its mesh must call this in the same order), then
    read from the first local shard.
    """
    import jax
    if not isinstance(x, jax.Array) or x.is_fully_addressable:
        return np.asarray(x)
    if x.is_fully_replicated:
        # every process already holds a complete copy; no collective needed
        return np.asarray(x.addressable_data(0))
    mesh = getattr(x.sharding, "mesh", None)
    if mesh is None:  # pragma: no cover - non-NamedSharding cross-process
        raise ValueError(
            f"cannot fetch non-addressable array with {x.sharding!r}")
    return np.asarray(_replicator(mesh)(x).addressable_data(0))


def barrier(tag: str, *, timeout_s: float = 3600.0) -> None:
    """Block until every process reaches the barrier named ``tag``.

    A coordination-service rendezvous (gRPC through the process-0 service
    — no device collectives, no gloo), so it is safe at any point of the
    program and waits patiently for ``timeout_s``. Use it to re-sync the
    processes after a phase whose duration varies per rank (data
    generation, per-rank I/O): ranks that drift minutes apart and then
    hit a *collective* can wedge the runtime — the gloo rendezvous for a
    fresh communicator gives up on stragglers long before a plain recv
    would (see :func:`connect_mesh_collectives`). No-op without a
    distributed runtime; each ``tag`` names one barrier, so reuse across
    distinct sync points needs distinct tags.
    """
    if not is_initialized():
        return
    from jax._src import distributed
    client = getattr(distributed.global_state, "client", None)
    if client is None:  # pragma: no cover - runtime without a client
        return
    client.wait_at_barrier(tag, timeout_in_ms=int(timeout_s * 1000))


def connect_mesh_collectives(mesh) -> None:
    """Establish every cross-process collective channel `mesh` will use.

    Dispatches one tiny shard-mapped program that psums over each mesh
    axis separately and over all axes together — the communicator set the
    SODDA step programs use. The point is *when* this runs: right after
    :func:`initialize`, while the processes are still within milliseconds
    of each other, the gloo full-mesh connect behind each fresh
    communicator succeeds trivially. Deferred to the first real dispatch
    — minutes of per-rank data generation later — that same connect is
    entered by ranks minutes apart and can wedge or abort the runtime
    (observed on the 250k x 18k bench cell: every rank asleep in its
    first psum forever). Once connected, channels persist, and later
    collectives are plain sends/recvs that tolerate arbitrary stagger.
    No-op without a distributed runtime.
    """
    if not is_initialized():
        return
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    names = tuple(mesh.axis_names)
    spec = P(*names)
    ones = np.ones(mesh.devices.shape, dtype=np.float32)

    def body(t):
        acc = t
        for ax in names:
            acc = acc + jax.lax.psum(t, ax)
        return acc + jax.lax.psum(t, names)

    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=spec, out_specs=spec))
    jax.block_until_ready(f(put_sharded(ones, NamedSharding(mesh, spec))))


def put_sharded(value, sharding):
    """``jax.device_put(value, sharding)`` that also works when `sharding`
    spans processes.

    Single-process: exactly ``device_put`` (bitwise the pre-multihost
    restore path). Multi-process: ``jax.make_array_from_callback`` — the
    host value is sliced per *addressable* shard only, so each process
    materializes its own part of the global array and no cross-process
    transfer happens (the checkpoint layer reads the same files on every
    host; see ``docs/multihost.md``).
    """
    import jax
    if jax.process_count() == 1:
        return jax.device_put(value, sharding)
    arr = np.asarray(value)
    return jax.make_array_from_callback(arr.shape, sharding,
                                        lambda idx: arr[idx])
