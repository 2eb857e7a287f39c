"""Backend-agnostic SODDA engine.

The paper's claim is that one algorithm — the doubly-distributed SODDA
outer iteration — is the same object whether it runs vectorized on one
host, sharded over a (data=P, model=Q) device mesh, or with its inner loop
lowered to a Pallas kernel. This module encodes that claim as an API: every
implementation is a *backend* behind :func:`make_step`, and the conformance
suite (``tests/test_conformance.py``) holds all backends to the reference
trajectory under an explicit tolerance policy (``repro.testing.tolerances``).

Backends
--------
``reference``          single-host vmap implementation (``core.sodda``)
``pallas``             reference driver + Pallas inner kernel (``kernels``)
``shard_map``          doubly-distributed step on a mesh (``core.distributed``)
``shard_map+pallas``   distributed step with the Pallas inner kernel
``async``              stale-by-one delta exchange: the snapshot-gradient
                       exchange is double-buffered in an extended scan
                       carry, so iteration t consumes the buffer issued at
                       t-1 (``core.sodda.sodda_step_async``)
``async-mesh``         the stale-by-one schedule lifted onto the device
                       mesh: one shard_map body issues iteration t's psum
                       exchange and consumes the t-1 buffer from the
                       mesh-sharded carry, so the collective overlaps the
                       inner loop on real device topology
                       (``core.distributed.make_distributed_async_step``)

Options orthogonal to the backend (``EngineOptions``): delta exchange
strategy (``gather_deltas``), int8 wire compression of the two dominant
collectives (``compress_z``, ``compress_mu``) — meaningful only for the
distributed backends — and ``staleness`` (0 or 1), meaningful only for the
stale-by-one backends (``async``/``async-mesh``; the synchronous mesh
backends still reject it) — and ``block_l``, the Pallas inner kernel's
L-tiling schedule (``repro.kernels.tuning``), meaningful only for the
kernel backends (``pallas``/``shard_map+pallas``). All are rejected with
``ValueError`` on backends they cannot affect, so a silent no-op can never
masquerade as a measured ablation.

Every step function returned by :func:`make_step` has the uniform signature
``step(carry, X, y) -> carry``. For most backends the carry IS the plain
``SoddaState``; a backend may instead extend the scan carry (the async
backend threads its exchange buffer through it), in which case the carry
still exposes ``.w``/``.t``/``.key`` and :func:`make_bundle` provides the
``init_carry`` (warm-up) and ``finalize`` halves the driver composes around
the scan. See ``docs/architecture.md`` for the full carry contract.

The ``(X, y)`` a step consumes come from a data plane
(``repro.data.plane``): :func:`make_bundle` binds the backend's resolved
mesh into the bundle's ``place_data`` half, which materializes a
``DataPlane`` (or raw pair) with the placement this backend expects —
tiles sharded ``P('data','model')`` over the mesh for the mesh backends.
See ``docs/data.md``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, NamedTuple, Optional

import jax

from repro.configs.sodda_svm import SoddaConfig
from repro.core import losses, sodda
from repro.core.sodda import SoddaState, init_state, iteration_flops  # noqa: F401 (re-export)

__all__ = [
    "BACKENDS",
    "BASELINE_BACKENDS",
    "ASYNC_BACKENDS",
    "MESH_BACKENDS",
    "EngineOptions",
    "StepBundle",
    "available_backends",
    "register_backend",
    "make_step",
    "make_bundle",
    "make_objective",
    "make_mesh_for",
    "rescale_bundle",
    "run",
    "init_state",
    "iteration_flops",
]


@dataclasses.dataclass(frozen=True)
class EngineOptions:
    """Backend-orthogonal knobs for one SODDA step construction.

    mesh          jax Mesh with ('data', 'model') axes; required by the
                  distributed backends (auto-built from the local devices
                  when omitted and enough devices exist).
    gather_deltas True: all_gather of m_tilde sub-blocks (paper-faithful
                  concatenate, half the wires); False: zero-padded m-sized
                  delta psum.
    compress_mu   int8 wires for the snapshot-gradient psum over 'data'.
    compress_z    int8 wires for the partial-inner-product psum over 'model'.
    """

    mesh: Optional[object] = None
    gather_deltas: bool = True
    compress_mu: bool = False
    compress_z: bool = False
    staleness: Optional[int] = None  # async/async-mesh only; None = default
    # L-tiling schedule of the Pallas inner kernel (tuning.BlockConfig.block_l).
    # Meaningful only for the kernel backends ('pallas', 'shard_map+pallas');
    # None = the single-tile default. Pick with repro.kernels.tuning.autotune.
    block_l: Optional[int] = None

    @property
    def distributed_kwargs(self):
        return dict(gather_deltas=self.gather_deltas,
                    compress_mu=self.compress_mu, compress_z=self.compress_z)

    def require_no_wires(self, backend: str):
        if self.compress_mu or self.compress_z:
            raise ValueError(
                f"backend {backend!r} has no collectives to compress; "
                "compress_mu/compress_z require a distributed backend")
        if not self.gather_deltas:
            raise ValueError(
                f"backend {backend!r} has no delta exchange; gather_deltas "
                "only selects a strategy for distributed backends")
        if self.mesh is not None:
            raise ValueError(
                f"backend {backend!r} runs on one host and takes no mesh; "
                "pass mesh only to distributed backends")

    def require_synchronous(self, backend: str):
        if self.staleness is not None:
            raise ValueError(
                f"backend {backend!r} exchanges synchronously; staleness is "
                "only meaningful for the stale-by-one backends "
                "('async', 'async-mesh')")

    def require_no_kernel(self, backend: str):
        if self.block_l is not None:
            raise ValueError(
                f"backend {backend!r} does not run the Pallas inner kernel; "
                "block_l only tunes the kernel backends "
                "('pallas', 'shard_map+pallas')")

    def resolve_staleness(self) -> int:
        """The effective staleness of a stale-by-one backend (default 1)."""
        staleness = 1 if self.staleness is None else int(self.staleness)
        if staleness not in (0, 1):
            raise ValueError(
                f"staleness must be 0 (synchronous parity) or 1 "
                f"(stale-by-one), got {self.staleness!r}")
        return staleness


StepFn = Callable[..., SoddaState]


class StepBundle(NamedTuple):
    """A backend's step plus its scan-carry protocol.

    Most backends carry the plain ``SoddaState`` through the scan; a backend
    may extend the carry with extra buffers (the async backend double-buffers
    its exchange vector there). The driver composes the three halves into
    one compiled program::

        carry = init_carry(state, X, y)   # warm-up: build/validate buffers
        carry = step(carry, X, y)         # repeated inside the scan
        state = finalize(carry)           # strip buffers back to SoddaState

    ``init_carry`` runs *inside* the driver's single compiled dispatch (it
    is traced, not eagerly executed), so a warm-up exchange costs no extra
    host round-trip. Every carry must expose ``.w`` so the driver can record
    the objective mid-scan. Plain step functions are wrapped into trivial
    bundles by :func:`make_bundle` (identity init/finalize).

    ``place_data`` is the bundle's data-plane half: it maps a
    ``repro.data.plane.DataPlane`` (or a raw ``(X, y)`` pair) to the placed
    arrays this backend's step consumes — sharded over the backend's mesh
    for the mesh backends, assembled on the default device otherwise.
    Factories normally leave it ``None`` and :func:`make_bundle` fills in
    the placement matched to the backend's resolved mesh, so "which worker
    holds which block" is decided by the data plane, not re-derived per
    backend. For streaming planes ``place_data(data, epoch=e)`` places
    stream window ``e`` (``epoch=None`` places the plane's current cursor);
    the resumable driver's prefetcher calls this half on its worker thread
    — one placed window per epoch, the streaming half of the seam.
    """

    step: StepFn  # (carry, X, y) -> carry
    init_carry: Callable  # (SoddaState, X, y) -> carry
    finalize: Callable  # carry -> SoddaState
    place_data: Optional[Callable] = None  # DataPlane | (X, y)[, epoch] -> (X, y)


def _as_bundle(obj) -> StepBundle:
    if isinstance(obj, StepBundle):
        return obj
    return StepBundle(step=obj,
                      init_carry=lambda state, X, y: state,
                      finalize=lambda carry: carry)


def _place_data(backend: str, mesh, data, epoch=None):
    from repro.data.plane import as_data_plane
    return as_data_plane(data).materialize_for(backend, mesh=mesh,
                                               epoch=epoch)


BackendFactory = Callable[[SoddaConfig, EngineOptions], StepFn]

_REGISTRY: Dict[str, BackendFactory] = {}


def register_backend(name: str):
    """Register a backend factory ``f(cfg, opts) -> step | StepBundle``.

    A factory may return a plain step (carried state is ``SoddaState``) or a
    :class:`StepBundle` when the backend extends the scan carry. Future
    scaling work (multi-host, new exchange schemes) plugs in here and is
    immediately covered by the conformance matrix.
    """

    def deco(factory: BackendFactory) -> BackendFactory:
        if name in _REGISTRY:
            raise ValueError(f"backend {name!r} already registered")
        _REGISTRY[name] = factory
        return factory

    return deco


def available_backends():
    return tuple(sorted(_REGISTRY))


def make_mesh_for(cfg: SoddaConfig):
    """A (data=P, model=Q) mesh over the *global* device set for `cfg`'s grid.

    In a multi-process runtime (``repro.distributed.multihost``) the mesh
    spans every process's devices — ``jax.devices()``, process-major order,
    so each process's addressable devices tile contiguous mesh positions
    (the host-local placement contract of ``DataPlane``). Single-process,
    global == local and this is the mesh the seed tests always built.
    """
    need = cfg.P * cfg.Q
    have = jax.device_count()
    if have < need:
        raise ValueError(
            f"cfg grid {cfg.P}x{cfg.Q} needs {need} devices, have {have} "
            f"across {jax.process_count()} process(es) "
            "(force more with --xla_force_host_platform_device_count)")
    return jax.make_mesh((cfg.P, cfg.Q), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def _resolve_mesh(cfg: SoddaConfig, opts: EngineOptions):
    return opts.mesh if opts.mesh is not None else make_mesh_for(cfg)


# ---------------------------------------------------------------------------
# Built-in backends
# ---------------------------------------------------------------------------
@register_backend("reference")
def _reference(cfg: SoddaConfig, opts: EngineOptions) -> StepFn:
    opts.require_no_wires("reference")
    opts.require_synchronous("reference")
    opts.require_no_kernel("reference")

    def step(state, X, y):
        return sodda.sodda_step(state, X, y, cfg, use_kernel=False)

    return step


@register_backend("pallas")
def _pallas(cfg: SoddaConfig, opts: EngineOptions) -> StepFn:
    opts.require_no_wires("pallas")
    opts.require_synchronous("pallas")
    block_l = opts.block_l

    def step(state, X, y):
        return sodda.sodda_step(state, X, y, cfg, use_kernel=True,
                                block_l=block_l)

    return step


@register_backend("shard_map")
def _shard_map(cfg: SoddaConfig, opts: EngineOptions) -> StepFn:
    from repro.core.distributed import make_distributed_step
    opts.require_synchronous("shard_map")
    opts.require_no_kernel("shard_map")
    return make_distributed_step(_resolve_mesh(cfg, opts), cfg,
                                 **opts.distributed_kwargs)


@register_backend("shard_map+pallas")
def _shard_map_pallas(cfg: SoddaConfig, opts: EngineOptions) -> StepFn:
    from repro.core.distributed import make_distributed_step
    opts.require_synchronous("shard_map+pallas")
    return make_distributed_step(_resolve_mesh(cfg, opts), cfg,
                                 use_kernel=True, block_l=opts.block_l,
                                 **opts.distributed_kwargs)


@register_backend("radisa-avg")
def _radisa_avg(cfg: SoddaConfig, opts: EngineOptions) -> StepFn:
    """RADiSA-avg baseline (Nathan & Klabjan) behind the same registry, so
    every driver/benchmark runs baselines and SODDA through one code path."""
    opts.require_no_wires("radisa-avg")
    opts.require_synchronous("radisa-avg")
    opts.require_no_kernel("radisa-avg")
    from repro.core import radisa

    def step(state, X, y):
        return radisa.radisa_avg_step(state, X, y, cfg)

    return step


@register_backend("async")
def _async(cfg: SoddaConfig, opts: EngineOptions) -> StepBundle:
    """Stale-by-one delta exchange on the extended scan carry.

    The snapshot-gradient exchange is double-buffered in the carry
    (``AsyncSoddaState.mu``): iteration t's inner loop consumes the buffer
    issued at t-1 while issuing its own, so the exchange overlaps the
    compute it has no data dependence on instead of blocking it. The carry
    is initialized by a one-iteration warm-up exchange (``init_carry``, run
    inside the driver's compiled program) and stripped back to a plain
    ``SoddaState`` by ``finalize``. ``staleness=0`` degenerates to the
    synchronous schedule — the exact-parity anchor of the conformance suite.
    """
    opts.require_no_wires("async")
    opts.require_no_kernel("async")
    staleness = opts.resolve_staleness()

    def step(carry, X, y):
        return sodda.sodda_step_async(carry, X, y, cfg, staleness=staleness)

    def init_carry(state, X, y):
        return sodda.init_async_state(state, X, y, cfg)

    def finalize(carry):
        return carry.sync_state()

    return StepBundle(step=step, init_carry=init_carry, finalize=finalize)


@register_backend("async-mesh")
def _async_mesh(cfg: SoddaConfig, opts: EngineOptions) -> StepBundle:
    """Stale-by-one delta exchange as one shard_map body on the mesh.

    The scan carry is ``AsyncSoddaState`` with the exchange buffer sharded
    ``P('model')`` alongside the iterate; iteration t's shard_map body
    consumes the psum issued at t-1 while issuing its own, so the collective
    overlaps the fully-local inner loop on real device topology instead of
    blocking it (see ``core.distributed.make_distributed_async_step``).
    ``staleness=0`` degenerates to the synchronous ``shard_map`` schedule —
    the BITWISE conformance anchor against that backend.
    """
    from repro.core.distributed import make_distributed_async_step
    opts.require_no_kernel("async-mesh")
    return make_distributed_async_step(
        _resolve_mesh(cfg, opts), cfg, staleness=opts.resolve_staleness(),
        **opts.distributed_kwargs)


BACKENDS = ("reference", "pallas", "shard_map", "shard_map+pallas")
BASELINE_BACKENDS = ("radisa-avg",)
ASYNC_BACKENDS = ("async", "async-mesh")
# backends that execute on a ('data', 'model') device mesh and accept/require
# the mesh option (auto-built from local devices when omitted)
MESH_BACKENDS = ("shard_map", "shard_map+pallas", "async-mesh")


# ---------------------------------------------------------------------------
# Public constructors
# ---------------------------------------------------------------------------
def make_bundle(cfg: SoddaConfig, backend: str = "reference", *, mesh=None,
                gather_deltas: bool = True, compress_mu: bool = False,
                compress_z: bool = False, staleness: Optional[int] = None,
                block_l: Optional[int] = None) -> StepBundle:
    """Build the full :class:`StepBundle` (step + carry protocol) for `backend`.

    This is what the scan driver composes: ``place_data`` (DataPlane ->
    placed arrays) outside the compiled program, ``init_carry`` (warm-up)
    before the scan, ``step`` inside it, ``finalize`` after. For plain
    backends the init/finalize halves are identities and the carry is the
    ``SoddaState`` itself.
    """
    try:
        factory = _REGISTRY[backend]
    except KeyError:
        raise ValueError(
            f"unknown backend {backend!r}; available: {available_backends()}"
        ) from None
    if backend in MESH_BACKENDS and mesh is None:
        # resolved here (not in the factory) so the bundle's place_data half
        # shards onto the same mesh the step executes on
        mesh = make_mesh_for(cfg)
    opts = EngineOptions(mesh=mesh, gather_deltas=gather_deltas,
                         compress_mu=compress_mu, compress_z=compress_z,
                         staleness=staleness, block_l=block_l)
    bundle = _as_bundle(factory(cfg, opts))
    if bundle.place_data is None:
        data_mesh = opts.mesh if backend in MESH_BACKENDS else None
        bundle = bundle._replace(
            place_data=functools.partial(_place_data, backend, data_mesh))
    return bundle


def rescale_bundle(cfg: SoddaConfig, backend: str, new_P: int, **options):
    """Rebuild the engine bundle for a rescaled observation grid — the
    elastic-rescale seam of ``repro.distributed.fault_tolerance``.

    Returns ``(new_cfg, new_mesh, bundle)``: ``new_cfg`` is `cfg` with
    ``P=new_P`` and the same per-partition ``n``. SODDA's Theorems 1-4 hold
    for any P, so both directions are the same algorithm on a different
    observation set: a *shrink* drops the lost partitions' rows from the
    problem, a *grow* (``new_P > cfg.P`` — capacity returned) adds the new
    partitions' rows (regenerated bitwise by the data plane's fold_in tile
    keys, or re-ingested in production). ``m_tilde`` re-splits to
    ``M // (Q * new_P)`` and ``pi_q`` is redrawn next iteration either way.
    Mesh backends get a fresh ``(new_P, Q)`` mesh — the old mesh contains
    the dead worker's devices (shrink) or lacks the returned ones (grow);
    single-host backends get ``mesh=None``. `options` are the run's engine
    options, revalidated against the rebuilt backend.
    """
    if new_P < 1:
        raise ValueError(
            f"rescale_bundle needs new_P >= 1, got {new_P}")
    if cfg.M % (cfg.Q * new_P):
        raise ValueError(
            f"cannot rescale to P={new_P}: M={cfg.M} must split into "
            f"Q*P={cfg.Q * new_P} equal sub-blocks (m_tilde would not be "
            "integral)")
    new_cfg = dataclasses.replace(cfg, name=f"{cfg.name}-P{new_P}", P=new_P)
    new_mesh = make_mesh_for(new_cfg) if backend in MESH_BACKENDS else None
    return new_cfg, new_mesh, make_bundle(new_cfg, backend, mesh=new_mesh,
                                          **options)


def make_step(cfg: SoddaConfig, backend: str = "reference", *, mesh=None,
              gather_deltas: bool = True, compress_mu: bool = False,
              compress_z: bool = False, staleness: Optional[int] = None,
              block_l: Optional[int] = None) -> StepFn:
    """Build a SODDA step ``(carry, X, y) -> carry`` for `backend`.

    For plain backends the carry is the ``SoddaState``; for extended-carry
    backends (``async``) the step maps the backend's own carry type — use
    :func:`make_bundle` to obtain its ``init_carry``/``finalize`` halves.
    """
    return make_bundle(cfg, backend, mesh=mesh, gather_deltas=gather_deltas,
                       compress_mu=compress_mu, compress_z=compress_z,
                       staleness=staleness, block_l=block_l).step


def make_objective(cfg: SoddaConfig, backend: str = "reference", *, mesh=None,
                   data=None):
    """Objective ``F(X, y, w)`` evaluated the way `backend` would see it.

    Backends without a sharded objective (including externally registered
    ones) get the exact single-host objective — same math, one device.

    With ``data`` (a ``repro.data.plane.DataPlane`` or an ``(X, y)`` pair),
    the returned callable is instead the closed objective ``F(w)``: the
    plane is materialized once with the placement `backend` consumes
    (sharded over the mesh for mesh backends) and bound in.
    """
    if backend not in _REGISTRY:
        raise ValueError(
            f"unknown backend {backend!r}; available: {available_backends()}")
    obj_mesh = None
    if backend in MESH_BACKENDS:
        from repro.core.distributed import distributed_objective
        obj_mesh = _resolve_mesh(cfg, EngineOptions(mesh=mesh))
        obj = distributed_objective(obj_mesh, cfg)
    else:
        if mesh is not None:
            raise ValueError(
                f"backend {backend!r} runs on one host and takes no mesh")
        obj = jax.jit(functools.partial(losses.objective, cfg.loss))
    if data is None:
        return obj
    X, y = _place_data(backend, obj_mesh, data)
    return functools.partial(obj, X, y)


def run(key, data, cfg: SoddaConfig, iters: int, backend: str = "reference",
        *, record_every: int = 1, mesh=None, **options):
    """Engine-level run for any backend — now the scan-compiled driver.

    ``data`` is a ``repro.data.plane.DataPlane`` or a raw ``(X, y)`` pair;
    it is placed for `backend` by the bundle's ``place_data`` half before
    the single dispatch. Returns (final state, [(t, F(w^t)) history]); the
    objective is always the exact single-host one so histories are
    comparable across backends. All ``iters`` iterations fuse into one
    device program (see ``repro.core.driver``); the legacy per-iteration
    loop survives as ``driver.run_python_loop`` for benchmarking and parity
    testing.
    """
    from repro.core import driver
    return driver.run(key, data, cfg, iters, backend,
                      record_every=record_every, mesh=mesh, **options)
