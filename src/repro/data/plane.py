"""DataPlane: the block-partitioned data layer of the doubly-distributed run.

The paper's data model is a (P, Q) grid of tiles — observations split P
ways, features split Q ways, tile (p, q) resident on worker (p, q) and
never moving. Until this module existed, that structure was imposed *after
the fact*: a host-global ``(N, M)`` array was built first and every backend
re-derived its blocks from it, capping the runnable problem size at what
one host could materialize. A :class:`DataPlane` makes the block structure
the primitive instead:

* **shape/grid metadata** — ``N, M`` (global), ``P, Q`` (tile grid),
  ``n = N//P``, ``m = M//Q`` (tile shape) — the same grid the engine's
  ``(data, model)`` mesh uses, so tile (p, q) is exactly the shard
  ``shard_map`` places on device (p, q) (in_spec ``P('data','model')``);
* **per-tile access** — :meth:`DataPlane.x_tile` / :meth:`DataPlane.y_block`
  return one block without touching the others;
* **placement** — :meth:`DataPlane.materialize_for` produces the ``(X, y)``
  the backend's step consumes, *placed*: sharded over the mesh for the mesh
  backends (each tile device_put straight onto its worker), assembled on
  the default device for the single-host ones. Which node holds which block
  is decided here, once — not re-derived by every backend.

Three implementations:

``dense``      (:class:`DenseDataPlane`) — current behavior: wraps
               host-global arrays (or builds them from the canonical tile
               generator via :meth:`DenseDataPlane.from_key`). Peak host
               memory: the full ``(N, M)`` footprint.
``tiled``      (:class:`TiledDataPlane`) — sharded-on-creation: every tile
               is generated on demand from its ``fold_in``-derived key
               (``repro.data.synthetic.svm_tile_x``) and placed directly
               into its device's shard; no global array ever exists on the
               host. Generation is bitwise-identical to the corresponding
               slice of a ``dense`` plane built from the same key, for any
               mesh shape — so swapping planes cannot change the math, only
               the memory model (property-tested in
               ``tests/test_property.py``, held BITWISE across every
               backend in ``tests/test_conformance.py``).
``streaming``  (:class:`StreamingDataPlane`) — the first plane whose
               contents change over time: an unbounded sequence of
               epoch-reshuffled ``(N, M)`` windows, window ``e`` generated
               from the epoch key ``stream_epoch_key(key, e)`` (epoch 0 is
               BITWISE the ``tiled`` plane — the anchor proving the time
               dimension changed no math). Out-of-core by construction:
               only the window under the cursor (plus a prefetched next
               window, see :class:`StreamPrefetcher`) is ever resident, and
               a configurable ``resident_tile_budget`` bounds the host-side
               tile cache with regenerate-on-miss, so streams exceeding
               any single memory run at all.

The contract, key-derivation scheme, and memory model are documented in
``docs/data.md``; the registry below is statically scanned by
``tools/check_docs.py`` so an implementation cannot land undocumented.
"""
from __future__ import annotations

import abc
import copy
import threading
import time
from collections import OrderedDict
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Optional, Tuple, Type

import jax
import jax.numpy as jnp
import numpy as np

from repro.data import synthetic

__all__ = [
    "DataPlane",
    "DenseDataPlane",
    "StreamingDataPlane",
    "StreamPrefetcher",
    "TiledDataPlane",
    "as_data_plane",
    "available_planes",
    "make_plane",
    "register_plane",
]

_REGISTRY: Dict[str, Type["DataPlane"]] = {}


def register_plane(name: str):
    """Register a DataPlane implementation under `name`.

    The decoration is scanned statically by ``tools/check_docs.py`` (like
    the engine's ``register_backend``), which fails CI when a registered
    plane has no ``docs/data.md`` entry.
    """

    def deco(cls):
        if name in _REGISTRY:
            raise ValueError(f"data plane {name!r} already registered")
        _REGISTRY[name] = cls
        cls.plane_name = name
        return cls

    return deco


def available_planes() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def make_plane(kind: str, key, N: int, M: int, P: int, Q: int, **kwargs):
    """Build a registered plane from the canonical SVM tile generator."""
    try:
        cls = _REGISTRY[kind]
    except KeyError:
        raise ValueError(
            f"unknown data plane {kind!r}; available: {available_planes()}"
        ) from None
    return cls.from_key(key, N, M, P, Q, **kwargs)


class DataPlane(abc.ABC):
    """Block-partitioned (X, y) with a placement method per backend kind.

    Subclasses fix the tile grid at construction and provide per-tile
    access; the base class owns the placement logic (single-host assembly
    vs per-tile mesh placement), so a new implementation only describes
    where its blocks *come from*, never where they *go*.
    """

    N: int
    M: int
    P: int
    Q: int
    dtype = jnp.float32
    # True for planes whose contents advance over epochs (the driver's
    # resumable segment loop checks this to thread an epoch cursor through
    # placement and the checkpoint stamp)
    is_streaming = False

    def _init_grid(self, N: int, M: int, P: int, Q: int):
        if P < 1 or Q < 1 or N % P or M % Q:
            raise ValueError(
                f"tile grid ({P}, {Q}) must divide the data shape "
                f"({N}, {M})")
        self.N, self.M, self.P, self.Q = N, M, P, Q

    @property
    def n(self) -> int:
        """Rows per tile (observations per partition)."""
        return self.N // self.P

    @property
    def m(self) -> int:
        """Columns per tile (features per partition)."""
        return self.M // self.Q

    @property
    def dense_nbytes(self) -> int:
        """The host footprint a dense (N, M) + (N,) materialization costs.

        Derived from the plane's ``dtype`` (not a hard-coded 4) so the
        memory-model claims in the bench output stay honest for non-f32
        planes.
        """
        return jnp.dtype(self.dtype).itemsize * (self.N * self.M + self.N)

    @property
    def tile_nbytes(self) -> int:
        """The footprint of one (n, m) feature tile."""
        return jnp.dtype(self.dtype).itemsize * self.n * self.m

    @property
    def generation_key(self):
        """The base PRNG key this plane's tiles regenerate from, or None
        for planes wrapping concrete arrays (``dense``). The elastic grow
        path (``repro.distributed.fault_tolerance.regrow_plane``) reads
        this to extend the grid with tiles bitwise-equal to a fresh plane's
        — possible exactly because tile keys fold in only ``(p, q)``, never
        the grid shape."""
        return getattr(self, "_key", None)

    @property
    def flip_prob(self):
        """The label-noise probability of key-derived planes (None for
        planes wrapping concrete arrays) — regeneration must replay it."""
        return getattr(self, "_flip_prob", None)

    @abc.abstractmethod
    def x_tile(self, p: int, q: int):
        """The (n, m) feature tile of worker (p, q)."""

    @abc.abstractmethod
    def y_block(self, p: int):
        """The (n,) label block of observation partition p."""

    # -- the time dimension -------------------------------------------------
    def at_epoch(self, epoch: int) -> "DataPlane":
        """This plane's window at stream epoch `epoch`.

        A static plane has exactly one window — epoch 0 returns the plane
        itself, anything else is a loud error (a driver advancing a cursor
        through a plane that cannot move must not silently re-run the same
        data). Streaming planes override this with a cheap epoch view.
        """
        if epoch != 0:
            raise ValueError(
                f"{type(self).__name__} is static: it has no epoch "
                f"{epoch}, only the single window at epoch 0")
        return self

    # -- placement ----------------------------------------------------------
    def materialize(self):
        """Assembled global ``(X, y)`` on the default device (row-major
        concatenation of the tiles — the single canonical assembly order)."""
        X = jnp.concatenate(
            [jnp.concatenate([self.x_tile(p, q) for q in range(self.Q)],
                             axis=1) for p in range(self.P)], axis=0)
        y = jnp.concatenate([self.y_block(p) for p in range(self.P)])
        return X, y

    def materialize_for(self, backend: str, mesh=None, epoch=None):
        """``(X, y)`` placed the way `backend`'s step consumes them.

        With a mesh: global-shaped arrays sharded ``P('data','model')`` /
        ``P('data')`` over it — the exact in_specs of the distributed step,
        so dispatch moves no bytes. Without one: the assembled arrays on
        the default device. Placement is layout only; the values are
        bitwise-independent of it. ``epoch`` selects a stream window
        (:meth:`at_epoch`); ``None`` means the plane's current cursor —
        epoch 0 for static planes.
        """
        plane = self if epoch is None else self.at_epoch(epoch)
        if mesh is None:
            return plane.materialize()
        return plane._materialize_mesh(mesh)

    def _materialize_mesh(self, mesh):
        from repro.core.distributed import data_shardings
        x_sharding, y_sharding = data_shardings(mesh)
        Pm, Qm = mesh.shape["data"], mesh.shape["model"]
        if (Pm, Qm) != (self.P, self.Q):
            # shard grid != tile grid: assemble, let device_put re-split.
            # For a tiled plane this voids its whole memory model (the
            # assembled (N, M) array is exactly what it exists to avoid),
            # so the fallback is loud, not silent.
            import warnings
            warnings.warn(
                f"{type(self).__name__} tile grid ({self.P}, {self.Q}) != "
                f"mesh shape ({Pm}, {Qm}): falling back to assembling the "
                f"full ({self.N}, {self.M}) array before re-splitting — "
                "match the grids to keep per-tile placement",
                stacklevel=3)
            X, y = self.materialize()
            from repro.distributed.multihost import put_sharded
            return (put_sharded(X, x_sharding),
                    put_sharded(y, y_sharding))
        if jax.process_count() > 1:
            return self._materialize_mesh_process_local(
                x_sharding, y_sharding)
        return self._materialize_per_device(x_sharding, y_sharding)

    def _materialize_per_device(self, x_sharding, y_sharding):
        """Per-device placement: generate each addressable device's tile
        and assemble with ``make_array_from_single_device_arrays``. Needs
        no contiguity across the addressable shard set — the single-process
        path, and the multi-process fallback when this process's devices do
        not cover a contiguous rectangle (an exotic device permutation)."""
        x_parts, y_parts = [], []
        y_cache = {}  # one y_block(p) per row, shared by the row's Q devices
        index_map = x_sharding.addressable_devices_indices_map((self.N,
                                                                self.M))
        for device, (rows, cols) in index_map.items():
            p = (rows.start or 0) // self.n
            q = (cols.start or 0) // self.m
            # generate on the tile's own device: staging every tile on the
            # default device first would hold two tiles plus a temporary
            # there, which at 4.5 GB tiles no longer fits a 16 GB chip
            with jax.default_device(device):
                if p not in y_cache:
                    y_cache[p] = self.y_block(p)
                x_parts.append(jax.device_put(self.x_tile(p, q), device))
            y_parts.append(jax.device_put(y_cache[p], device))
        X = jax.make_array_from_single_device_arrays(
            (self.N, self.M), x_sharding, x_parts)
        y = jax.make_array_from_single_device_arrays(
            (self.N,), y_sharding, y_parts)
        return X, y

    def _materialize_mesh_process_local(self, x_sharding, y_sharding):
        """Multi-process placement: this process generates ONLY the tiles
        its addressable devices hold and hands the assembled host-local
        block to ``jax.make_array_from_process_local_data`` — no host ever
        materializes the global ``(N, M)`` array (the multihost half of
        the tiled plane's memory model; see ``docs/multihost.md``).

        Relies on host-local tile placement: the mesh is built from the
        process-major global device order, so each process's devices cover
        a contiguous rectangle of tiles
        (``repro.distributed.multihost.local_device_slice``). When they do
        not (an exotic device permutation), falls back to per-device
        placement, which needs no contiguity.
        """
        from repro.distributed.multihost import local_device_slice
        try:
            rows, cols = local_device_slice(x_sharding, (self.N, self.M))
        except ValueError:
            return self._materialize_per_device(x_sharding, y_sharding)
        if rows.start % self.n or rows.stop % self.n \
                or cols.start % self.m or cols.stop % self.m:
            raise ValueError(
                f"process-local slice rows={rows} cols={cols} is not "
                f"tile-aligned to the ({self.n}, {self.m}) tile shape — "
                "the mesh grid must match the plane's (P, Q) tile grid")
        p0, p1 = rows.start // self.n, rows.stop // self.n
        q0, q1 = cols.start // self.m, cols.stop // self.m
        x_local = np.concatenate(
            [np.concatenate([np.asarray(self.x_tile(p, q))
                             for q in range(q0, q1)], axis=1)
             for p in range(p0, p1)], axis=0)
        y_local = np.concatenate(
            [np.asarray(self.y_block(p)) for p in range(p0, p1)])
        X = jax.make_array_from_process_local_data(x_sharding, x_local,
                                                   (self.N, self.M))
        y = jax.make_array_from_process_local_data(y_sharding, y_local,
                                                   (self.N,))
        return X, y


@register_plane("dense")
class DenseDataPlane(DataPlane):
    """Host-global arrays behind the DataPlane interface (current behavior).

    Wraps existing ``(X, y)`` (any tile grid that divides them, default
    (1, 1)) or builds the arrays on the host from the canonical tile
    generator (:meth:`from_key` — numpy assembly, so the full ``(N, M)``
    footprint is genuinely paid, which is the point of this baseline).
    """

    def __init__(self, X, y, grid: Tuple[int, int] = (1, 1)):
        X = jnp.asarray(X)
        y = jnp.asarray(y)
        if X.ndim != 2 or y.shape != (X.shape[0],):
            raise ValueError(
                f"need X (N, M) and y (N,), got {X.shape} / {y.shape}")
        self._init_grid(X.shape[0], X.shape[1], grid[0], grid[1])
        self._X, self._y = X, y
        # the footprint metadata (dense_nbytes/tile_nbytes) must describe
        # the arrays actually wrapped, not the class default
        self.dtype = X.dtype

    @classmethod
    def from_key(cls, key, N: int, M: int, P: int, Q: int,
                 flip_prob: float = 0.01) -> "DenseDataPlane":
        n, m = N // P, M // Q
        if N % P or M % Q:
            raise ValueError(f"grid ({P}, {Q}) must divide ({N}, {M})")
        X = np.concatenate(
            [np.concatenate(
                [np.asarray(synthetic.svm_tile_x(key, p, q, n, m))
                 for q in range(Q)], axis=1) for p in range(P)], axis=0)
        y = np.concatenate(
            [np.asarray(synthetic.svm_label_block(key, p, n, Q, m,
                                                  flip_prob=flip_prob))
             for p in range(P)])
        return cls(X, y, grid=(P, Q))

    def x_tile(self, p: int, q: int):
        n, m = self.n, self.m
        return self._X[p * n:(p + 1) * n, q * m:(q + 1) * m]

    def y_block(self, p: int):
        n = self.n
        return self._y[p * n:(p + 1) * n]

    def materialize(self):
        return self._X, self._y

    def _materialize_mesh(self, mesh):
        from repro.core.distributed import data_shardings
        x_sharding, y_sharding = data_shardings(mesh)
        if jax.process_count() > 1:
            # every process holds the full host array (this plane's whole
            # point); each just places its own addressable shards
            from repro.distributed.multihost import put_sharded
            return (put_sharded(self._X, x_sharding),
                    put_sharded(self._y, y_sharding))
        return (jax.device_put(self._X, x_sharding),
                jax.device_put(self._y, y_sharding))


@register_plane("tiled")
class TiledDataPlane(DataPlane):
    """Sharded-on-creation plane: tiles generated straight into their shard.

    No global array is ever materialized on the host; each ``(p, q)`` tile
    is generated from its ``fold_in``-derived key on demand
    (``repro.data.synthetic.svm_tile_x``) and, on a mesh, device_put
    directly onto worker (p, q). Generation is bitwise-equal to the
    corresponding slice of :meth:`DenseDataPlane.from_key` with the same
    key, so the plane choice changes the memory model, never the math.
    Tiles are not cached — regeneration is a PRNG replay, which is cheaper
    than keeping ``(N, M)`` alive.
    """

    def __init__(self, key, N: int, M: int, P: int, Q: int,
                 flip_prob: float = 0.01):
        self._init_grid(N, M, P, Q)
        self._key = key
        self._flip_prob = flip_prob

    @classmethod
    def from_key(cls, key, N: int, M: int, P: int, Q: int,
                 flip_prob: float = 0.01) -> "TiledDataPlane":
        return cls(key, N, M, P, Q, flip_prob=flip_prob)

    def x_tile(self, p: int, q: int):
        if not (0 <= p < self.P and 0 <= q < self.Q):
            raise IndexError(f"tile ({p}, {q}) outside grid "
                             f"({self.P}, {self.Q})")
        return synthetic.svm_tile_x(self._key, p, q, self.n, self.m)

    def y_block(self, p: int):
        if not 0 <= p < self.P:
            raise IndexError(f"row block {p} outside grid P={self.P}")
        return synthetic.svm_label_block(self._key, p, self.n, self.Q,
                                         self.m, flip_prob=self._flip_prob)


@register_plane("streaming")
class StreamingDataPlane(DataPlane):
    """Epoch-reshuffled out-of-core plane: the window under the cursor.

    The stream is an unbounded sequence of ``(N, M)`` windows; window
    (epoch) ``e`` regenerates every tile from the epoch key
    ``repro.data.synthetic.stream_epoch_key(key, e)`` — fresh observations
    of the same planted separator every epoch, production traffic that
    never fits and never stops. Three properties carry the whole design:

    * **epoch 0 is the ``tiled`` plane, bitwise** — the anchor proving the
      time dimension changed no math (held per backend in
      ``tests/test_conformance.py``);
    * **a tile is a pure function of (key, epoch, p, q, n, m)** — never of
      how the stream was consumed — so a killed-and-resumed streaming run
      replays the exact bytes once the driver restores the stream cursor
      from the checkpoint stamp (``driver.run_resumable``);
    * **bounded residency** — tiles materialize through a host-side LRU
      cache capped at ``resident_tile_budget`` blocks (X tiles and y
      blocks alike; default two windows' worth — the consumed one plus the
      prefetched one) and are *regenerated on miss* (a PRNG replay), so
      peak host memory is a knob, not a function of stream length.

    :meth:`at_epoch` returns a cheap cursor view (shared cache, shared
    budget) — the handle :class:`StreamPrefetcher` places the *next*
    window through while the compiled segment consumes the current one.
    """

    is_streaming = True

    def __init__(self, key, N: int, M: int, P: int, Q: int,
                 flip_prob: float = 0.01,
                 resident_tile_budget: Optional[int] = None, epoch: int = 0):
        self._init_grid(N, M, P, Q)
        if resident_tile_budget is None:
            # current + prefetched window: P*Q X tiles + P y blocks each
            resident_tile_budget = 2 * (P * Q + P)
        if resident_tile_budget < 0:
            raise ValueError(
                f"resident_tile_budget must be >= 0 (0 disables caching), "
                f"got {resident_tile_budget}")
        if epoch < 0:
            raise ValueError(f"stream epoch must be >= 0, got {epoch}")
        self._key = key
        self._flip_prob = flip_prob
        self._epoch = int(epoch)
        self._budget = int(resident_tile_budget)
        # shared (not copied) by at_epoch views: the cache IS the resident
        # set, whichever cursor touched it last
        self._cache: OrderedDict = OrderedDict()
        self._cache_lock = threading.Lock()
        self._stats = {"hits": 0, "misses": 0}

    @classmethod
    def from_key(cls, key, N: int, M: int, P: int, Q: int,
                 flip_prob: float = 0.01,
                 **kwargs) -> "StreamingDataPlane":
        return cls(key, N, M, P, Q, flip_prob=flip_prob, **kwargs)

    @property
    def epoch(self) -> int:
        """The stream cursor this view reads at."""
        return self._epoch

    @property
    def resident_tile_budget(self) -> int:
        return self._budget

    @property
    def cache_stats(self) -> Dict[str, int]:
        """``{'hits', 'misses', 'resident'}`` of the shared tile cache —
        misses are regenerations (the out-of-core price of the budget)."""
        with self._cache_lock:
            return dict(self._stats, resident=len(self._cache))

    def at_epoch(self, epoch: int) -> "StreamingDataPlane":
        """A view of the same stream with the cursor at `epoch` (shared
        cache and stats; O(1), nothing is generated until a tile is read)."""
        if epoch < 0:
            raise ValueError(f"stream epoch must be >= 0, got {epoch}")
        if epoch == self._epoch:
            return self
        view = copy.copy(self)  # shares _cache/_cache_lock/_stats
        view._epoch = int(epoch)
        return view

    def _block(self, make, cache_key):
        """Budget-bounded LRU materialization with regenerate-on-miss."""
        with self._cache_lock:
            if cache_key in self._cache:
                self._cache.move_to_end(cache_key)
                self._stats["hits"] += 1
                return self._cache[cache_key]
            self._stats["misses"] += 1
        val = make()  # generate outside the lock: a PRNG replay, not I/O
        if self._budget:
            with self._cache_lock:
                self._cache[cache_key] = val
                self._cache.move_to_end(cache_key)
                while len(self._cache) > self._budget:
                    self._cache.popitem(last=False)
        return val

    def x_tile_at(self, epoch: int, p: int, q: int):
        """The (n, m) feature tile of worker (p, q) at stream `epoch`."""
        if not (0 <= p < self.P and 0 <= q < self.Q):
            raise IndexError(f"tile ({p}, {q}) outside grid "
                             f"({self.P}, {self.Q})")
        if epoch < 0:
            raise ValueError(f"stream epoch must be >= 0, got {epoch}")
        return self._block(
            lambda: synthetic.svm_stream_tile_x(self._key, epoch, p, q,
                                                self.n, self.m),
            (epoch, "x", p, q))

    def y_block_at(self, epoch: int, p: int):
        """The (n,) label block of partition p at stream `epoch`."""
        if not 0 <= p < self.P:
            raise IndexError(f"row block {p} outside grid P={self.P}")
        if epoch < 0:
            raise ValueError(f"stream epoch must be >= 0, got {epoch}")
        return self._block(
            lambda: synthetic.svm_stream_label_block(
                self._key, epoch, p, self.n, self.Q, self.m,
                flip_prob=self._flip_prob),
            (epoch, "y", p))

    def x_tile(self, p: int, q: int):
        return self.x_tile_at(self._epoch, p, q)

    def y_block(self, p: int):
        return self.y_block_at(self._epoch, p)


class StreamPrefetcher:
    """Double-buffered issue/consume feed over a streaming plane's epochs.

    The same idiom the async backends use for their exchange collective,
    lifted to the data plane: :meth:`issue` schedules epoch ``e``'s window
    — tile generation plus host→device placement — on a single worker
    thread, so it overlaps the compiled segment the consumer is currently
    running; :meth:`consume` blocks until the window is ready, retires
    every strictly older window (bounding residency to current +
    prefetched — the double buffer), and keeps the consumed one so
    repeated consumes of the same epoch are free.

    ``place`` is the placement half — typically the engine bundle's
    ``place_data`` closed over the plane: ``lambda e:
    bundle.place_data(plane, epoch=e)``.

    The prefetch-overlap ratio the streaming bench cell records is
    ``1 - wait_s / place_s``: the fraction of placement wall-time hidden
    behind compute (1.0 = every consume found its window already resident,
    0.0 = fully synchronous cold loads).

    ``depth`` bounds the *issue queue*: at most ``depth`` windows beyond
    the newest consumed epoch may be scheduled at once — :meth:`issue`
    beyond the bound is a silent no-op (the caller just re-issues after
    the next consume). ``depth=1`` is the classic double buffer and is
    bitwise the historical behavior; deeper queues absorb placement-time
    jitter across segments at the cost of one extra resident window each.
    The observed maximum lookahead is reported as ``queue_high_water``.
    """

    def __init__(self, place, depth: int = 1):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        self._place = place
        self.depth = int(depth)
        self._pool = ThreadPoolExecutor(max_workers=1,
                                        thread_name_prefix="stream-prefetch")
        self._pending: Dict[int, object] = {}  # epoch -> Future
        self._last_consumed = -1  # newest consumed epoch; -1 = none yet
        self._closed = False
        self._lock = threading.Lock()
        self.place_s = 0.0   # worker wall-time spent generating + placing
        self.wait_s = 0.0    # consumer wall-time blocked on a window
        self.consumed = 0
        self.cold_misses = 0  # consume() of a never-issued epoch
        self.queue_high_water = 0  # max lookahead windows ever in flight

    def issue(self, epoch: int):
        """Schedule epoch's window on the worker thread (idempotent; a
        no-op when ``depth`` windows are already queued past the newest
        consumed epoch — the bounded issue queue)."""
        with self._lock:
            if epoch in self._pending:
                return
            ahead = sum(1 for e in self._pending if e > self._last_consumed)
            if ahead >= self.depth:
                return
            self._pending[epoch] = self._pool.submit(self._job, epoch)
            self.queue_high_water = max(self.queue_high_water, ahead + 1)

    def _job(self, epoch: int):
        t0 = time.perf_counter()
        out = self._place(epoch)
        self.place_s += time.perf_counter() - t0  # single worker: no race
        return out

    def consume(self, epoch: int):
        """The placed ``(X, y)`` of `epoch`; blocks if still in flight."""
        with self._lock:
            fut = self._pending.get(epoch)
            if fut is None:
                # cold miss: schedule directly, bypassing the depth bound
                # (the consumer needs this window no matter what's queued)
                self.cold_misses += 1
                fut = self._pending[epoch] = self._pool.submit(
                    self._job, epoch)
        t0 = time.perf_counter()
        out = fut.result()
        self.wait_s += time.perf_counter() - t0
        self.consumed += 1
        with self._lock:  # retire strictly older windows (double buffer)
            self._last_consumed = max(self._last_consumed, epoch)
            for e in [e for e in self._pending if e < epoch]:
                del self._pending[e]
        return out

    @property
    def overlap_ratio(self) -> float:
        """Fraction of placement time hidden behind compute, in [0, 1]."""
        if self.place_s <= 0.0:
            return 1.0
        return max(0.0, min(1.0, 1.0 - self.wait_s / self.place_s))

    def stats(self) -> Dict[str, float]:
        return {"place_s": self.place_s, "wait_s": self.wait_s,
                "consumed": self.consumed, "cold_misses": self.cold_misses,
                "overlap_ratio": self.overlap_ratio, "depth": self.depth,
                "queue_high_water": self.queue_high_water}

    @property
    def closed(self) -> bool:
        """True once :meth:`close` has joined the worker thread — what the
        fault-injection suite asserts to prove a supervised retry leaked no
        prefetch thread."""
        return self._closed

    def close(self):
        self._pool.shutdown(wait=True)
        self._closed = True

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def as_data_plane(data) -> DataPlane:
    """Coerce `data` to a DataPlane.

    Accepts a plane (returned as-is) or a raw ``(X, y)`` pair (wrapped in a
    trivial-grid :class:`DenseDataPlane`) — the compatibility shim that
    lets every run entry point take either.
    """
    if isinstance(data, DataPlane):
        return data
    if isinstance(data, (tuple, list)) and len(data) == 2:
        return DenseDataPlane(data[0], data[1])
    raise TypeError(
        f"expected a DataPlane or an (X, y) pair, got {type(data).__name__}")
