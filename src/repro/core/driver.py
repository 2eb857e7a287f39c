"""Scan-compiled run subsystem: the one outer-loop driver for every backend.

The paper's headline claim is *per-cost* early-iteration superiority, but a
per-iteration Python loop measures dispatch overhead, not the algorithm:
every outer iteration pays a fresh jit dispatch and a host sync for the
objective (the pitfall Dünner et al. document for the original Spark
experiments). This module fuses the whole run on device:

  * all ``iters`` outer iterations of any registered engine backend compile
    into a single :func:`jax.lax.scan`, chunked by ``record_every``;
  * the objective is recorded **on device** into the scan's preallocated
    history buffer (the stacked ys) — never synced to host mid-run;
  * the state buffers are donated to the compiled run, so the iterate is
    updated in place across the whole trajectory;
  * the host sees exactly one dispatch and one device->host transfer, at
    the very end.

record_every chunking semantics
-------------------------------
The scan's xs is the sequence of *chunk lengths*: ``iters // record_every``
full chunks of ``record_every`` steps each, plus one shorter tail chunk of
``iters % record_every`` steps when it does not divide evenly. Each scan
step evaluates the objective at the chunk's *entry* iterate, then advances
the carry through its chunk with an inner ``fori_loop``; one final
objective evaluation after the scan covers the last iterate. The recorded
ticks are therefore ``record_ticks(iters, record_every)`` — every multiple
of ``record_every`` strictly below ``iters``, plus ``iters`` itself (e.g.
``(0, 2, 4, 5)`` for ``iters=5, record_every=2``). ``record_every`` changes
only *observation* cadence, never the trajectory: the same ``iters`` steps
run regardless.

Carry contract
--------------
The scan carry is whatever the backend's :class:`repro.core.engine
.StepBundle` defines. The compiled program is ``finalize(scan(step, ...,
init_carry(state, X, y)))``: ``init_carry`` is the warm-up half (the async
backend issues its first exchange there, so the first consumed buffer is
valid — traced into the same single dispatch, not a separate call), and
``finalize`` strips any extra buffers back to a plain ``SoddaState``.
Every carry exposes ``.w``, which is how the objective is recorded
mid-scan. The ``state`` argument of the compiled run is donated — its
buffers are consumed by the first use inside the program and must not be
reused by the caller (regression-tested in ``tests/test_conformance.py``).
On the mesh backends (``engine.MESH_BACKENDS``) donation only aliases when
the initial state already carries the program's output sharding;
:func:`run` places it there via :func:`place_initial_state`, and callers
driving a :func:`make_run` executable by hand should do the same.

Data-plane contract
-------------------
Every run entry point takes ``data`` — a ``repro.data.plane.DataPlane`` or
a raw ``(X, y)`` pair (coerced by ``as_data_plane``). The driver never
places data itself: it hands the plane to the backend bundle's
``place_data`` half, which materializes the tiles with the placement the
backend consumes (sharded ``P('data','model')`` over the mesh for mesh
backends — each tile resident on its worker before dispatch — assembled on
the default device otherwise). Placement is layout only; swapping planes
with the same key cannot change the math (held BITWISE per backend in
``tests/test_conformance.py``). See ``docs/data.md``.

Streaming planes (``plane.is_streaming``) add a time dimension to the
contract: :func:`run` and :func:`run_python_loop` place the plane's current
cursor window (epoch 0 by default — which is BITWISE the ``tiled`` plane's
data, the conformance anchor), while :func:`run_resumable` advances the
stream one epoch per segment: segment ``i`` consumes window ``i``
(``epoch = done // segment_iters`` — a pure function of trajectory
position, never of how the stream was consumed), placed ahead of time by a
:class:`repro.data.plane.StreamPrefetcher` so window ``i+1`` generates and
lands on device while segment ``i``'s compiled dispatch runs. The cursor is
stamped into every checkpoint (``stream_epoch``) and cross-checked on
restore, so a killed-and-resumed streaming run replays the exact window
sequence — bitwise — of the uninterrupted one.

:func:`run` keeps the exact ``(final_state, [(t, F(w^t))])`` contract of the
legacy drivers (``engine.run`` / ``sodda.run`` / ``radisa.run_radisa_avg``
are now thin wrappers over it). :func:`run_python_loop` preserves the old
per-iteration dispatch loop as the benchmark baseline and the parity oracle
for ``tests/test_conformance.py``. :func:`run_resumable` splits ``iters``
into checkpointed segments (one compiled dispatch each) so a preempted run
resumes mid-trajectory, bitwise. Note that backends may be
bitwise-nondeterministic *relative to the reference trajectory* while still
correct — the async backend legitimately diverges iterate-by-iterate and is
held to the relaxed ``STALENESS`` policy of ``repro.testing.tolerances``
instead; scan-vs-loop parity for the *same* backend still holds for every
backend, async included.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.sodda_svm import SoddaConfig
from repro.core import losses
from repro.core.sodda import OBJECTIVE_SCOPE

__all__ = ["record_ticks", "make_run", "place_initial_state", "run",
           "run_resumable", "migrate_resumable", "replay_segment",
           "restore_resumable_state", "run_python_loop"]


def record_ticks(iters: int, record_every: int) -> Tuple[int, ...]:
    """The iteration indices a run records the objective at.

    Matches the legacy loop: every multiple of ``record_every`` strictly
    below ``iters``, plus the final iterate — e.g. (0, 2, 4, 5) for
    ``iters=5, record_every=2``.
    """
    if iters < 0:
        raise ValueError(f"iters must be >= 0, got {iters}")
    if record_every < 1:
        raise ValueError(f"record_every must be >= 1, got {record_every}")
    return tuple(range(0, iters, record_every)) + (iters,)


def _recorded_objective(loss: str, X, y, w):
    """F(w) as the scan programs record it, under the objective's scope."""
    with jax.named_scope(OBJECTIVE_SCOPE):
        return losses.objective(loss, X, y, w)


def _chunk_lengths(iters: int, record_every: int) -> Tuple[int, ...]:
    """Per-chunk step counts: full ``record_every`` chunks + the remainder."""
    n_full, rem = divmod(iters, record_every)
    return (record_every,) * n_full + ((rem,) if rem else ())


@functools.lru_cache(maxsize=64)
def _cached_run(cfg: SoddaConfig, iters: int, backend: str, record_every: int,
                record_objective: bool, mesh,
                options: Tuple[Tuple[str, object], ...]):
    """Build + cache the compiled scan driver for one run shape.

    Keyed on everything that changes the computation (config, backend,
    iteration/record structure, mesh, engine options) so repeated runs —
    the conformance matrix, the goldens, the benchmark reps — reuse one
    executable instead of re-tracing per call.
    """
    from repro.core import engine  # local: engine imports core.sodda

    bundle = engine.make_bundle(cfg, backend, mesh=mesh, **dict(options))
    obj = functools.partial(_recorded_objective, cfg.loss)
    lens = jnp.asarray(_chunk_lengths(iters, record_every), jnp.int32)

    def _run(state, X, y):
        # warm-up half: build the backend's scan carry (for the async
        # backend this issues the first exchange) — traced into this same
        # program, so it costs no extra dispatch
        carry = bundle.init_carry(state, X, y)

        def chunk(c, length):
            f = obj(X, y, c.w) if record_objective else None  # on device
            c = jax.lax.fori_loop(0, length,
                                  lambda _, cc: bundle.step(cc, X, y), c)
            return c, f

        carry, fs = jax.lax.scan(chunk, carry, lens)
        final = bundle.finalize(carry)
        if not record_objective:
            return final, jnp.zeros((0,), jnp.float32)
        return final, jnp.concatenate([fs, obj(X, y, final.w)[None]])

    # donate the state buffers: the iterate is rewritten in place over the
    # whole trajectory rather than round-tripping per iteration
    return jax.jit(_run, donate_argnums=(0,))


def make_run(cfg: SoddaConfig, iters: int, backend: str = "reference", *,
             record_every: int = 1, record_objective: bool = True,
             mesh=None, **options):
    """Compiled run ``(state, X, y) -> (final_state, history_buffer)``.

    ``history_buffer`` is the on-device ``(len(record_ticks),)`` f32 array of
    objective values at :func:`record_ticks` — nothing is synced to host.
    The state argument is donated; do not reuse it after the call.

    ``record_objective=False`` compiles the pure iteration program — no
    objective evaluations at all, empty history buffer. Used by perf
    analysis (the objective's collectives would otherwise drown the step's
    own communication profile) and by production runs that monitor
    elsewhere.
    """
    record_ticks(iters, record_every)  # validate arguments eagerly
    return _cached_run(cfg, iters, backend, record_every, record_objective,
                       mesh, tuple(sorted(options.items())))


def place_initial_state(state, cfg: SoddaConfig, backend: str, mesh=None):
    """Lay the initial state out the way `backend`'s compiled run shards it.

    The mesh backends produce their outputs sharded over the ('data',
    'model') mesh (the iterate — and the async-mesh exchange buffer —
    along 'model', the scalars replicated). Donation can only alias an
    input buffer whose sharding matches the output it is rewritten into, so
    a single-device initial state silently defeats ``donate_argnums`` on
    those backends: XLA drops the alias and the iterate round-trips per
    run. This helper device_puts the state into the matching layout;
    single-host backends pass through untouched. :func:`run` applies it
    automatically — call it yourself only when driving a
    :func:`make_run` executable by hand (as the donation regression test
    does).
    """
    from repro.core import engine

    if backend not in engine.MESH_BACKENDS:
        return state
    mesh = mesh if mesh is not None else engine.make_mesh_for(cfg)
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.distributed.multihost import put_sharded
    return type(state)(
        w=put_sharded(state.w, NamedSharding(mesh, P("model"))),
        t=put_sharded(state.t, NamedSharding(mesh, P())),
        key=put_sharded(state.key, NamedSharding(mesh, P())))


def _checked_bundle(data, cfg: SoddaConfig, backend: str, mesh, options):
    """Coerce `data` to a plane, validate it against `cfg`, and resolve the
    backend bundle — the shared front half of every placement path."""
    from repro.data.plane import as_data_plane

    plane = as_data_plane(data)
    if (plane.N, plane.M) != (cfg.N, cfg.M):
        raise ValueError(
            f"data plane shape ({plane.N}, {plane.M}) does not match cfg "
            f"{cfg.name!r} ({cfg.N}, {cfg.M})")
    return plane, _cached_bundle(cfg, backend, mesh, options)


def _placed_data(data, cfg: SoddaConfig, backend: str, mesh, options):
    """:func:`_checked_bundle` plus placement through the bundle's
    ``place_data`` half (the plane's current window — epoch 0 unless the
    caller advanced a streaming plane's cursor)."""
    plane, bundle = _checked_bundle(data, cfg, backend, mesh, options)
    return bundle, bundle.place_data(plane)


def run(key, data, cfg: SoddaConfig, iters: int, backend: str = "reference",
        *, record_every: int = 1, mesh=None, **options):
    """Run `iters` outer iterations of `backend` as one fused device program.

    ``data`` is a ``repro.data.plane.DataPlane`` or a raw ``(X, y)`` pair,
    placed for `backend` before the dispatch (see the data-plane contract
    in the module docstring). Returns ``(final_state, [(t, F(w^t))
    history])`` — the exact contract of the legacy per-iteration drivers,
    produced with a single dispatch and a single end-of-run host sync. The
    objective is always the exact single-host one so histories are
    comparable across backends.
    """
    from repro.core.sodda import init_state

    _, (X, y) = _placed_data(data, cfg, backend, mesh,
                             tuple(sorted(options.items())))
    compiled = make_run(cfg, iters, backend, record_every=record_every,
                        mesh=mesh, **options)
    # copy the key: the state is donated, and donating an alias of the
    # caller's key buffer would delete it out from under them. The mesh
    # placement makes that donation real on the mesh backends (see
    # place_initial_state).
    state = place_initial_state(init_state(jnp.array(key, copy=True), cfg.M),
                                cfg, backend, mesh)
    state, fs = compiled(state, X, y)
    from repro.distributed.multihost import fetch_local
    hist = [(t, float(f))
            for t, f in zip(record_ticks(iters, record_every),
                            fetch_local(fs))]
    return state, hist


@functools.lru_cache(maxsize=64)
def _cached_bundle(cfg: SoddaConfig, backend: str, mesh,
                   options: Tuple[Tuple[str, object], ...]):
    from repro.core import engine
    return engine.make_bundle(cfg, backend, mesh=mesh, **dict(options))


@functools.lru_cache(maxsize=8)
def _cached_objective(loss: str):
    return jax.jit(functools.partial(losses.objective, loss))


def run_python_loop(key, data, cfg: SoddaConfig, iters: int,
                    backend: str = "reference", *, record_every: int = 1,
                    mesh=None, **options):
    """The legacy per-iteration dispatch loop (one jit call + one host sync
    per recorded objective). Kept as the benchmark baseline the scan driver
    is measured against and as the parity oracle for the conformance suite.
    ``data`` is a plane or an ``(X, y)`` pair, like :func:`run`.

    The step and objective executables are cached across calls (a fresh
    ``jax.jit`` wrapper per call would be a jit-cache miss), so a short
    warmup invocation genuinely warms a subsequent timed one and the
    measured loop overhead is dispatch + host sync, not compilation.
    """
    from repro.core.sodda import init_state

    record_ticks(iters, record_every)  # same argument validation as run()
    bundle, (X, y) = _placed_data(data, cfg, backend, mesh,
                                  tuple(sorted(options.items())))
    obj = _cached_objective(cfg.loss)
    carry = bundle.init_carry(init_state(key, cfg.M), X, y)
    hist = []
    for it in range(iters):
        if it % record_every == 0:
            hist.append((it, float(obj(X, y, carry.w))))
        carry = bundle.step(carry, X, y)
    state = bundle.finalize(carry)
    hist.append((iters, float(obj(X, y, state.w))))
    return state, hist


# ---------------------------------------------------------------------------
# Resumable runs: segment the trajectory at checkpoint boundaries.
# ---------------------------------------------------------------------------
# The active in-scan commit sink (one slot: resumable dispatches are
# host-serial). The compiled segment program calls the module-level
# _dispatch_in_scan_commit below — never a per-run closure, which would
# defeat the lru_cache — and the driver installs/clears the actual sink
# around each dispatch. io_callback runs the sink on a runtime thread, so
# neither a thread-local nor a contextvar would reach it.
#
# Sink exceptions must NOT escape the callback: an error propagating out
# of an *unordered* io_callback (the only kind mesh programs may use)
# leaves the dispatch permanently un-done and `block_until_ready` hangs
# forever. The dispatcher traps the first exception in _COMMIT_ERROR,
# suppresses every later commit of the dispatch (a killed worker commits
# nothing further), and the driver re-raises it host-side after the sync.
_ACTIVE_COMMIT = [None]
_COMMIT_ERROR = [None]


def _dispatch_in_scan_commit(base, step, fbuf, carry):
    sink = _ACTIVE_COMMIT[0]
    if sink is not None and _COMMIT_ERROR[0] is None:
        try:
            sink(int(base), int(step), np.asarray(fbuf), carry)
        except BaseException as exc:  # noqa: BLE001 - re-raised by the driver
            _COMMIT_ERROR[0] = exc


def _commit_groups(seg_iters: int, record_every: int, commit_every: int):
    """The segment's chunk lengths grouped so each *full* group ends on an
    in-scan commit point (a multiple of ``commit_every`` iterations past the
    segment entry); a shorter tail group ends the segment without one — its
    boundary belongs to the host-side save path. Returns
    ``((chunk_lens, commits), ...)``."""
    groups, cur, acc = [], [], 0
    for length in _chunk_lengths(seg_iters, record_every):
        cur.append(length)
        acc += length
        if acc % commit_every == 0:
            groups.append((tuple(cur), True))
            cur = []
    if cur:
        groups.append((tuple(cur), False))
    return tuple(groups)


@functools.lru_cache(maxsize=64)
def _cached_segment_run(cfg: SoddaConfig, seg_iters: int, backend: str,
                        record_every: int, mesh,
                        options: Tuple[Tuple[str, object], ...],
                        commit_every: int = 0):
    """Compiled carry-level segment ``(carry, X, y) -> (carry, fs)``.

    Unlike :func:`_cached_run` this neither builds nor strips the carry
    (``init_carry``/``finalize`` run once per *run*, not per segment — the
    async exchange buffer must survive segment boundaries or resuming would
    silently restart the staleness schedule) and records the objective at
    chunk *entries* only: a segment's exit iterate is the next segment's
    entry, so the per-segment histories concatenate into exactly the
    uninterrupted run's ticks, with the final objective appended once by
    :func:`run_resumable`.

    With ``commit_every > 0`` the signature grows a trailing ``base``
    argument (the global iteration count at segment entry) and the program
    interleaves :func:`jax.experimental.io_callback` commit points between
    chunk groups: after every ``commit_every`` iterations the carry, the
    objectives recorded so far and the global step are handed to the host
    sink (:data:`_ACTIVE_COMMIT`), which writes a crash-atomic checkpoint
    *while the dispatch is still running*. The callbacks return nothing and
    touch no values, so the commit-enabled program computes the bitwise-same
    trajectory as the plain one. Ordered callbacks are used on single-device
    programs; mesh programs use unordered ones (XLA rejects ordered effects
    in multi-device computations) — safe because each commit is an
    independent atomic step directory and resume takes the max committed.

    Deliberately NOT donated, unlike :func:`_cached_run`: the segment carry
    is rebound in a host-side chain (``carry, fs = compiled(carry, ...)``),
    and on this jax/CPU combination a donated input whose last reference
    dies while the aliased output lives on is corrupted nondeterministically
    when the executable is deserialized from the persistent compilation
    cache (reproducible via ``tests/test_resumable.py`` on a warm
    ``.pytest_cache/jax_compilation_cache``). A segment copies one carry —
    a few KB per *segment*, noise next to the checkpoint write it
    accompanies.
    """
    from jax.experimental import io_callback

    from repro.core import engine

    bundle = engine.make_bundle(cfg, backend, mesh=mesh, **dict(options))
    obj = functools.partial(_recorded_objective, cfg.loss)

    def chunk(c, length, X, y):
        f = obj(X, y, c.w)
        c = jax.lax.fori_loop(0, length,
                              lambda _, cc: bundle.step(cc, X, y), c)
        return c, f

    if not commit_every:
        lens = jnp.asarray(_chunk_lengths(seg_iters, record_every), jnp.int32)

        def _run(carry, X, y):
            return jax.lax.scan(
                lambda c, length: chunk(c, length, X, y), carry, lens)

        return jax.jit(_run)

    groups = _commit_groups(seg_iters, record_every, commit_every)
    ordered = mesh is None

    def _run_commit(carry, X, y, base):
        fs_parts, off = [], 0
        for group_lens, commits in groups:
            lens = jnp.asarray(group_lens, jnp.int32)
            carry, fs = jax.lax.scan(
                lambda c, length: chunk(c, length, X, y), carry, lens)
            fs_parts.append(fs)
            off += sum(group_lens)
            if commits:
                io_callback(_dispatch_in_scan_commit, None, base,
                            base + jnp.int32(off),
                            jnp.concatenate(fs_parts), carry,
                            ordered=ordered)
        return carry, jnp.concatenate(fs_parts)

    return jax.jit(_run_commit)


@functools.lru_cache(maxsize=64)
def _cached_init_carry(cfg: SoddaConfig, backend: str, mesh,
                       options: Tuple[Tuple[str, object], ...]):
    """Jitted warm-up half for the segmented driver.

    Eager execution would dispatch the async backends' warm-up exchange
    op-by-op (orders of magnitude slower through shard_map) and round
    differently from the fused program, costing the resumable driver its
    bitwise parity with :func:`run` on those backends.
    """
    bundle = _cached_bundle(cfg, backend, mesh, options)
    return jax.jit(bundle.init_carry)


def _key_stamp(key):
    """The run's base PRNG key as JSON-able ints (for the resume guard)."""
    return [int(x) for x in np.asarray(key).ravel().tolist()]


def _data_fingerprint(plane) -> str:
    """A cheap content fingerprint of a data plane for the resume guard.

    Hashes the grid metadata plus the corner tile and first label block —
    one tile's regeneration, not a pass over the full dataset — which
    distinguishes different keys/datasets with overwhelming probability
    (the guard is against silent mistakes, not adversaries). Content only,
    no plane kind: dense and tiled planes from the same key are the same
    data (placement is layout, never math), so either resumes the other.
    Streaming planes are fingerprinted at their **epoch-0 window** so the
    fingerprint is cursor-independent — where the stream currently points
    is trajectory state (stamped separately as ``stream_epoch``), not data
    identity.
    """
    import hashlib

    plane = plane.at_epoch(0)  # no-op for static planes
    h = hashlib.sha256()
    h.update(repr((plane.N, plane.M, plane.P, plane.Q)).encode())
    h.update(np.asarray(plane.x_tile(0, 0)).tobytes())
    h.update(np.asarray(plane.y_block(0)).tobytes())
    return h.hexdigest()


def _validate_segmenting(iters: int, segment_iters: int, record_every: int,
                         commit_every: int = 0):
    record_ticks(iters, record_every)  # validate iters/record_every
    if segment_iters < 1:
        raise ValueError(f"segment_iters must be >= 1, got {segment_iters}")
    if segment_iters % record_every:
        raise ValueError(
            f"segment_iters ({segment_iters}) must be a multiple of "
            f"record_every ({record_every}) so segment boundaries land on "
            "recording ticks")
    if commit_every < 0:
        raise ValueError(f"commit_every must be >= 0, got {commit_every}")
    if commit_every:
        if commit_every % record_every:
            raise ValueError(
                f"commit_every ({commit_every}) must be a multiple of "
                f"record_every ({record_every}) so every in-scan commit "
                "carries a complete history prefix")
        if segment_iters % commit_every:
            raise ValueError(
                f"segment_iters ({segment_iters}) must be a multiple of "
                f"commit_every ({commit_every}) so commit points tile the "
                "segment and every resume lands on a commit-cadence step")


def run_resumable(key, data, cfg: SoddaConfig, iters: int,
                  backend: str = "reference", *, checkpoint_dir: str,
                  segment_iters: int, record_every: int = 1, mesh=None,
                  keep: int = 3, commit_every: int = 0, on_commit=None,
                  on_segment=None, on_segment_start=None,
                  stream_stats=None, prefetch_depth: int = 1, **options):
    """:func:`run` split into checkpointed segments (ROADMAP "Driver-level
    checkpointing", the host-side version: chunk boundary = preemption
    point).

    The trajectory runs as ``ceil(iters / segment_iters)`` compiled
    dispatches; after each one the backend's scan *carry* (not just the
    ``SoddaState`` — the async exchange buffer rides along) and the history
    so far are written through ``repro.checkpoint`` into `checkpoint_dir`.
    A rerun with the same arguments restores the latest committed segment
    boundary and continues; because the carry round-trips losslessly
    (float32/uint32 → npy → device) and every segment replays the same
    compiled program, the resumed trajectory is **bitwise** the
    uninterrupted one (regression-tested in ``tests/test_resumable.py``).

    ``segment_iters`` must be a multiple of ``record_every`` so segment
    boundaries land on recording ticks. ``on_segment(iters_done)`` is an
    optional host callback after each segment's save, and
    ``on_segment_start(iters_done)`` fires before each segment's dispatch —
    the two fault-injection seams: a kill in ``on_segment`` lands *after*
    its boundary committed (a restart resumes past it), a kill in
    ``on_segment_start`` lands *before* any new commit (a restart replays
    the same segment — the no-progress path a restart budget must bound).
    The segment supervisor (``repro.distributed.fault_tolerance``) also
    times segments between the two seams. Returns the exact
    ``(final_state, [(t, F(w^t)) history])`` contract of :func:`run`.

    With a **streaming** plane the run is an epoch-reshuffled pass over the
    stream: segment ``i`` trains on window ``i`` (one epoch per segment, so
    checkpoint boundary = epoch boundary and the cursor is always
    ``done // segment_iters``), with window ``i+1`` prefetched — generated
    and placed on device by a background thread — while segment ``i``'s
    compiled dispatch runs. The cursor rides every checkpoint as the
    ``stream_epoch`` stamp and is cross-checked on restore. Pass a dict as
    ``stream_stats`` to receive the prefetcher's overlap accounting
    (``overlap_ratio``, ``place_s``, ``wait_s``, ...) and the plane's tile
    cache counters after the run; ignored for static planes.
    ``prefetch_depth`` widens the prefetch window: up to that many future
    epochs are queued on the placement thread at once (default 1 — the
    classic double buffer, bitwise the historical behavior; the trained
    trajectory never depends on depth, only residency/overlap do).

    ``commit_every > 0`` makes the *segment itself* preemptible: the
    compiled program additionally commits the carry every ``commit_every``
    iterations from inside the scan, through an
    :func:`jax.experimental.io_callback` whose host sink reuses the same
    crash-atomic ``CheckpointManager`` write path (tmp + rename + commit
    marker) and stamps the same resume guard, with the history prefix
    reconstructed from the on-device objective buffer. A kill mid-dispatch
    then loses at most ``commit_every`` iterations instead of the whole
    segment, and a rerun resumes — bitwise — from the newest in-scan commit
    (``done`` mid-segment: the first dispatch just finishes that segment).
    ``commit_every`` must be a multiple of ``record_every`` and divide
    ``segment_iters``. ``on_commit(iters_done)`` fires after each in-scan
    commit lands — the mid-segment fault-injection seam; it runs inside the
    dispatch, where an escaping exception would hang an unordered
    io_callback's dispatch forever, so the dispatcher traps it, suppresses
    the dispatch's remaining commits (a killed worker commits nothing
    further) and re-raises it here once the dispatch drains — the original
    exception, unwrapped, after ``commit_every``-granular progress landed.
    """
    from repro.checkpoint import CheckpointManager, latest_step, \
        read_extra, restore_checkpoint
    from repro.core.sodda import init_state
    from repro.data.plane import StreamPrefetcher
    from repro.distributed import multihost

    _validate_segmenting(iters, segment_iters, record_every, commit_every)
    if commit_every and jax.process_count() > 1:
        # the io_callback commit sink runs on each process's runtime
        # callback thread with no cross-process ordering; a mid-scan commit
        # could interleave with another host's and tear the checkpoint.
        # Segment boundaries (host-side, collectively fetched,
        # coordinator-written) are the multi-process preemption points.
        raise ValueError(
            "commit_every > 0 (in-scan commits) is not supported under a "
            "multi-process runtime; use commit_every=0 — segment "
            "boundaries are the preemption points")

    opt_key = tuple(sorted(options.items()))
    plane, bundle = _checked_bundle(data, cfg, backend, mesh, opt_key)
    fingerprint = _data_fingerprint(plane)
    manager = CheckpointManager(checkpoint_dir, every=segment_iters,
                                keep=keep)
    prefetch = None
    if plane.is_streaming:
        prefetch = StreamPrefetcher(
            lambda e: bundle.place_data(plane, epoch=e),
            depth=prefetch_depth)

    def stamp(done_now, hist_now):
        extra = {"history": [[t, f] for t, f in hist_now],
                 "backend": backend,
                 "record_every": record_every,
                 "segment_iters": segment_iters,
                 "options": [list(kv) for kv in opt_key],
                 "data": fingerprint,
                 "streaming": plane.is_streaming,
                 "key": _key_stamp(key)}
        if plane.is_streaming:
            # the cursor of the next segment to run from this boundary
            # (mid-segment: still inside its own window's epoch)
            extra["stream_epoch"] = done_now // segment_iters
        return extra

    def _in_scan_sink(base, step, fbuf, carry_np):
        """Host half of the io_callback commit: write the step-atomic
        checkpoint with the history prefix the dispatch has produced so
        far. Runs on the runtime callback thread while the host thread
        blocks on this dispatch's results, so `hist` is stable."""
        if step % segment_iters == 0:
            return  # boundary: the host-side save below owns it
        commit_hist = hist + [(base + k * record_every, float(f))
                              for k, f in enumerate(fbuf)]
        manager.save(step, carry_np, extra=stamp(step, commit_hist))
        if on_commit is not None:
            on_commit(step)

    try:
        # epoch 0 is both segment 0's window and the warm-up/template
        # window; for static planes it is the only window there is
        if prefetch is not None:
            X, y = prefetch.consume(0)
        else:
            X, y = bundle.place_data(plane)

        # the t=0 carry doubles as the restore template (same pytree
        # structure and shardings as every later carry)
        state0 = place_initial_state(
            init_state(jnp.array(key, copy=True), cfg.M), cfg, backend, mesh)
        carry = _cached_init_carry(cfg, backend, mesh, opt_key)(state0, X, y)
        done, hist = 0, []
        latest = latest_step(checkpoint_dir)
        if latest is not None:
            if latest > iters:
                raise ValueError(
                    f"checkpoint at iteration {latest} in {checkpoint_dir!r} "
                    f"is beyond the requested iters={iters}")
            # a checkpoint resumed under different run parameters would
            # splice a mixed-cadence (or different-algorithm) history
            # together without any numerical error to catch it: a changed
            # staleness continues a different algorithm, a changed
            # segment_iters strands `done` off the save cadence (maybe_save
            # never fires again). Refuse BEFORE the template-shaped restore
            # (a backend mismatch would otherwise surface as an opaque
            # missing-leaf error).
            _, extra = read_extra(checkpoint_dir, latest)
            want = {"backend": backend, "record_every": record_every,
                    "segment_iters": segment_iters,
                    # JSON round-trips tuples as lists; normalize
                    "options": [list(kv) for kv in opt_key],
                    # same-shaped but different data would splice two
                    # problems into one trajectory just as silently...
                    "data": fingerprint,
                    # ...a static run resumed as a streaming one (or vice
                    # versa) would change every window after the cursor...
                    "streaming": plane.is_streaming,
                    # ...and a different seed would return the old seed's
                    # trajectory relabeled (the restored carry holds the
                    # RNG state; the key argument only builds the template)
                    "key": _key_stamp(key)}
            # every guard key must be present: a stampless or partial stamp
            # (hand-seeded dirs, pre-guard writers) proves nothing, and
            # resuming with zero validation is exactly the silent-splice
            # failure the guard exists to refuse
            missing = sorted(set(want) - set(extra))
            if missing:
                raise ValueError(
                    f"checkpoint in {checkpoint_dir!r} has no resume-guard "
                    f"stamp for {missing}: cannot validate that the run "
                    "parameters match, refusing to resume — use a fresh "
                    "checkpoint_dir, or re-stamp the state via "
                    "migrate_resumable")
            for k, v in want.items():
                if extra[k] != v:
                    raise ValueError(
                        f"checkpoint in {checkpoint_dir!r} was written with "
                        f"{k}={extra[k]!r}; resuming with {k}={v!r} would "
                        "corrupt the trajectory/history — use a fresh "
                        "checkpoint_dir or the original parameters")
            if plane.is_streaming:
                if "stream_epoch" not in extra:
                    raise ValueError(
                        f"checkpoint in {checkpoint_dir!r} carries no "
                        "stream_epoch cursor stamp: cannot restore the "
                        "stream position, refusing to resume")
                if int(extra["stream_epoch"]) != latest // segment_iters:
                    raise ValueError(
                        f"checkpoint in {checkpoint_dir!r} stamps "
                        f"stream_epoch={extra['stream_epoch']!r} but its "
                        f"boundary at iteration {latest} implies epoch "
                        f"{latest // segment_iters} — the stamp was "
                        "tampered with or written by a different cadence")
            if latest % record_every:
                raise ValueError(
                    f"checkpoint at iteration {latest} in {checkpoint_dir!r} "
                    f"is not on the record_every={record_every} cadence — "
                    "not a boundary or in-scan commit this run could have "
                    "written; refusing to resume")
            done, restored, extra = restore_checkpoint(checkpoint_dir, carry)
            carry = jax.tree.map(
                lambda leaf, proto: multihost.put_sharded(
                    leaf, proto.sharding),
                restored, carry)
            hist = [(int(t), float(f)) for t, f in extra.get("history", [])]

        while done < iters:
            if on_segment_start is not None:
                on_segment_start(done)
            # a mid-segment resume (done off the boundary cadence — an
            # in-scan commit) first runs the remainder of its segment, so
            # the save cadence realigns at the next boundary
            seg = min(segment_iters - done % segment_iters, iters - done)
            if prefetch is not None:
                # consume this segment's window (already resident unless
                # this is the first segment after a cold start/resume),
                # then issue the next prefetch_depth windows so they
                # generate and land on device underneath this segment's
                # compiled dispatch (the prefetcher bounds the queue)
                epoch = done // segment_iters
                X, y = prefetch.consume(epoch)
                last_epoch = (iters - 1) // segment_iters
                for ahead in range(1, prefetch.depth + 1):
                    if epoch + ahead <= last_epoch:
                        prefetch.issue(epoch + ahead)
            compiled = _cached_segment_run(cfg, seg, backend, record_every,
                                           mesh, opt_key, commit_every)
            if commit_every:
                _ACTIVE_COMMIT[0] = _in_scan_sink
                _COMMIT_ERROR[0] = None
                try:
                    carry, fs = compiled(carry, X, y, jnp.int32(done))
                    # finish all commits while the sink is installed and
                    # before hist advances
                    jax.block_until_ready((carry, fs))
                finally:
                    _ACTIVE_COMMIT[0] = None
                if _COMMIT_ERROR[0] is not None:
                    # surface the trapped in-dispatch fault; commits after
                    # it were suppressed, so resume restarts from it
                    exc, _COMMIT_ERROR[0] = _COMMIT_ERROR[0], None
                    raise exc
            else:
                carry, fs = compiled(carry, X, y)
            hist += [(done + t, float(f))
                     for t, f in zip(range(0, seg, record_every),
                                     multihost.fetch_local(fs))]
            done += seg
            if jax.process_count() > 1:
                # the host fetch is a collective (every process replicates
                # the carry in the same order); only the coordinator then
                # touches the filesystem — one writer, N readers on resume
                host_carry = jax.tree.map(multihost.fetch_local, carry)
                if multihost.is_coordinator():
                    manager.maybe_save(done, host_carry,
                                       extra=stamp(done, hist))
            else:
                manager.maybe_save(done, carry, extra=stamp(done, hist))
            if on_segment is not None:
                on_segment(done)

        if prefetch is not None:
            # the final objective must see the last segment's window — on
            # the normal path it is the one just consumed (free), on a
            # resume-from-complete the loop never ran and it is regenerated
            X, y = prefetch.consume((iters - 1) // segment_iters
                                    if iters > 0 else 0)
            if stream_stats is not None:
                stream_stats.update(prefetch.stats())
                stream_stats["cache"] = plane.cache_stats
        final = bundle.finalize(carry)
        hist.append((iters, float(multihost.fetch_local(
            _cached_objective(cfg.loss)(X, y, final.w)))))
        return final, hist
    finally:
        if prefetch is not None:
            prefetch.close()


def migrate_resumable(key, data, cfg: SoddaConfig, done: int, state,
                      backend: str = "reference", *, checkpoint_dir: str,
                      segment_iters: int, record_every: int = 1, mesh=None,
                      history=(), keep: int = 3, **options):
    """Seed `checkpoint_dir` with a committed checkpoint at iteration `done`
    carrying `state`, so :func:`run_resumable` continues it there as if the
    run had always been its own — the elastic-rescale migration seam.

    ``state`` is a plain ``SoddaState`` — P-independent by construction (the
    ``(M,)`` iterate, the 1-based step counter, the base PRNG key), which is
    exactly why a carry survives a topology change: the caller finalizes the
    old grid's carry, rebuilds ``cfg``/``data``/``mesh`` for the new grid
    (``repro.core.engine.rescale_bundle``), and this function re-runs the
    backend's warm-up half on the *new* problem (an extended-carry backend
    gets a fresh exchange buffer — the old one aggregated data that no
    longer exists) and stamps the checkpoint with the new run's resume
    guard. ``done`` must be a segment boundary so the shrunk run's save
    cadence continues unbroken; ``history`` is the trajectory recorded so
    far, spliced into the new run's checkpoint extra.
    """
    from repro.checkpoint import save_checkpoint
    from repro.core.sodda import SoddaState
    from repro.data.plane import as_data_plane

    _validate_segmenting(max(done, 0), segment_iters, record_every)
    if done < 0 or done % segment_iters:
        raise ValueError(
            f"migration point ({done}) must be a segment boundary "
            f"(non-negative multiple of segment_iters={segment_iters})")
    opt_key = tuple(sorted(options.items()))
    plane = as_data_plane(data)
    _, (X, y) = _placed_data(plane, cfg, backend, mesh, opt_key)
    placed = place_initial_state(
        SoddaState(w=state.w, t=state.t, key=state.key), cfg, backend, mesh)
    carry = _cached_init_carry(cfg, backend, mesh, opt_key)(placed, X, y)
    extra = {"history": [[int(t), float(f)] for t, f in history],
             "backend": backend, "record_every": record_every,
             "segment_iters": segment_iters,
             "options": [list(kv) for kv in opt_key],
             "data": _data_fingerprint(plane),
             "streaming": plane.is_streaming,
             "key": _key_stamp(key)}
    if plane.is_streaming:
        extra["stream_epoch"] = done // segment_iters
    if jax.process_count() > 1:
        from repro.distributed import multihost
        host_carry = jax.tree.map(multihost.fetch_local, carry)
        if multihost.is_coordinator():
            save_checkpoint(checkpoint_dir, done, host_carry, extra=extra,
                            keep=keep)
    else:
        save_checkpoint(checkpoint_dir, done, carry, extra=extra, keep=keep)
    return carry


def restore_resumable_state(key, data, cfg: SoddaConfig,
                            backend: str = "reference", *,
                            checkpoint_dir: str, mesh=None, step=None,
                            **options):
    """``(done, SoddaState, history)`` of a committed checkpoint written by
    :func:`run_resumable` (the latest one unless ``step`` picks another).

    Builds the restore template through the same warm-up machinery as the
    driver — so extended carries (the async exchange buffer) restore with
    the right structure — and finalizes the carry down to the P-independent
    ``SoddaState``. This is the handle the elastic layer uses to lift a
    committed iterate off a run it aborted (e.g. the straggler-triggered
    rescale in ``repro.distributed.fault_tolerance.run_elastic_auto``):
    the state feeds :func:`migrate_resumable` on the new grid.
    """
    from repro.checkpoint import restore_checkpoint
    from repro.core.sodda import init_state

    opt_key = tuple(sorted(options.items()))
    plane, bundle = _checked_bundle(data, cfg, backend, mesh, opt_key)
    # any window yields the template (shapes/shardings, never values)
    X, y = bundle.place_data(plane)
    state0 = place_initial_state(
        init_state(jnp.array(key, copy=True), cfg.M), cfg, backend, mesh)
    template = _cached_init_carry(cfg, backend, mesh, opt_key)(state0, X, y)
    done, restored, extra = restore_checkpoint(checkpoint_dir, template,
                                               step=step)
    from repro.distributed import multihost
    carry = jax.tree.map(
        lambda leaf, proto: multihost.put_sharded(leaf, proto.sharding),
        restored, template)
    hist = [(int(t), float(f)) for t, f in extra.get("history", [])]
    return done, bundle.finalize(carry), hist


def replay_segment(key, data, cfg: SoddaConfig, backend: str = "reference",
                   *, checkpoint_dir: str, segment_iters: int,
                   record_every: int = 1, mesh=None, step=None, **options):
    """Speculatively re-execute the span between two committed checkpoints
    and cross-check the result against the committed carry — the
    verification half of a straggler response.

    A flagged-slow worker's output is exactly the output you should trust
    least; because every span is a pure function of its entry carry and its
    data window, a backup execution can replay it and compare **bitwise**.
    ``step`` selects the replay target (default: the latest committed step);
    the replay restores the committed step *before* it and re-dispatches the
    span through the same compiled segment program.

    Read-only: touches no checkpoint, advances nothing. Returns a report
    dict — ``replayed`` False (with a ``reason``) when there is no
    predecessor to replay from or the span is not replayable (crosses a
    stream window, off the record cadence), else ``start``/``end`` and
    ``match`` (True iff every carry leaf reproduced bitwise).
    """
    from repro.checkpoint import committed_steps, restore_checkpoint
    from repro.core.sodda import init_state

    _validate_segmenting(segment_iters, segment_iters, record_every)
    opt_key = tuple(sorted(options.items()))
    plane, bundle = _checked_bundle(data, cfg, backend, mesh, opt_key)
    steps = committed_steps(checkpoint_dir)
    end = step if step is not None else (steps[-1] if steps else None)
    report = {"replayed": False, "start": None, "end": end, "match": None}
    if end is None or end not in steps:
        report["reason"] = "no committed checkpoint to replay to"
        return report
    prior = [s for s in steps if s < end]
    if not prior:
        report["reason"] = "no committed predecessor to replay from"
        return report
    start = prior[-1]
    report["start"] = start
    if (end - start) % record_every:
        report["reason"] = "span is off the record_every cadence"
        return report
    if plane.is_streaming and start // segment_iters != \
            (end - 1) // segment_iters:
        report["reason"] = "span crosses a stream window boundary"
        return report

    epoch = start // segment_iters if plane.is_streaming else None
    X, y = (bundle.place_data(plane) if epoch is None
            else bundle.place_data(plane, epoch=epoch))
    state0 = place_initial_state(
        init_state(jnp.array(key, copy=True), cfg.M), cfg, backend, mesh)
    template = _cached_init_carry(cfg, backend, mesh, opt_key)(state0, X, y)
    from repro.distributed import multihost
    _, restored, _ = restore_checkpoint(checkpoint_dir, template, step=start)
    carry = jax.tree.map(
        lambda leaf, proto: multihost.put_sharded(leaf, proto.sharding),
        restored, template)
    compiled = _cached_segment_run(cfg, end - start, backend, record_every,
                                   mesh, opt_key)
    carry, _ = compiled(carry, X, y)
    _, committed, _ = restore_checkpoint(checkpoint_dir, template, step=end)
    match = all(
        np.array_equal(multihost.fetch_local(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(carry), jax.tree.leaves(committed)))
    report.update(replayed=True, match=bool(match))
    return report
