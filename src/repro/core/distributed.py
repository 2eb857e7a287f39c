"""Doubly-distributed SODDA via shard_map on a (data=P, model=Q) mesh.

Worker (p, q) == device (p, q). The data tile x^{p,q} is resident and never
moves (in_spec P('data','model')); the parameter vector is sharded along
'model' (each feature partition's m-block lives on its column, replicated
across rows). Collectives per outer iteration:

  * psum over 'model' of the sampled partial inner products  (d_local f32 / dev)
  * psum over 'data'  of the C-masked snapshot gradient      (m f32 / dev)
  * psum over 'data'  of the updated sub-block delta         (m f32 / dev)

versus O(M) per *inner* step for data-parallel SGD — this is the paper's
communication saving realized with JAX collectives. The randomness is
reconstructed per-device with the exact fold_in scheme of
``partition.sample_iteration`` so this implementation is bit-comparable to
``repro.core.sodda.sodda_step`` (up to f32 reduction order).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.sodda_svm import SoddaConfig
from repro.core import losses
from repro.core.partition import _exact_count_mask
from repro.core.sodda import (CONSUME_SCOPE, EXCHANGE_SCOPE, ISSUE_SCOPE,
                              AsyncSoddaState, SoddaState, _counts, _gamma,
                              inner_loop)

__all__ = ["data_shardings", "make_distributed_step",
           "make_distributed_async_step", "make_local_halves",
           "distributed_objective", "iteration_collective_bytes"]


def data_shardings(mesh):
    """The (X, y) placement of the doubly-distributed step, as shardings.

    X is tiled ``P('data', 'model')`` — worker (p, q)'s resident block
    x^{p,q} — and y is split ``P('data')`` (each observation partition's
    labels replicated across its mesh row). These are exactly the in_specs
    of every shard_map body in this module; ``DataPlane.materialize_for``
    places data with them *before* dispatch, so the compiled step finds its
    tiles already resident instead of scattering a host-global array.
    """
    from jax.sharding import NamedSharding
    return (NamedSharding(mesh, P("data", "model")),
            NamedSharding(mesh, P("data")))


def make_local_halves(cfg: SoddaConfig, gather_deltas: bool = True,
                      compress_mu: bool = False, compress_z: bool = False,
                      use_kernel: bool = False, block_l=None):
    """The per-device *issue*/*consume* halves of one outer iteration.

    ``issue_local`` performs paper steps 5-8: sample B/C/D, reduce the
    partial inner products over 'model', and psum the C-masked snapshot
    gradient over 'data' — everything the iteration puts on the wire for the
    exchange. ``consume_local`` performs steps 10-19 against a *given*
    ``mu_q``: block assignment, the fully-local inner loop, and the
    sub-block assembly collective.

    The synchronous :func:`make_distributed_step` composes them back to back
    (consume blocks on issue); the stale-by-one
    :func:`make_distributed_async_step` instead feeds ``consume_local`` the
    previous iteration's ``mu_q`` from the extended ``AsyncSoddaState``
    carry, exactly as the single-host ``async`` backend does with
    ``repro.core.sodda.sodda_step_async``. Both halves re-derive their
    randomness from ``fold_in(key, t)``, so they need no shared state beyond
    ``(t, key)`` — which is what allows them to be split across iterations
    at all.
    """
    n, m, mt, L, M = cfg.n, cfg.m, cfg.m_tilde, cfg.L, cfg.M
    b_count, c_count, d_local = _counts(cfg)
    deriv = functools.partial(losses.loss_deriv, cfg.loss)

    def issue_local(X_loc, y_loc, w_loc, t, key):
        with jax.named_scope(ISSUE_SCOPE):
            p = jax.lax.axis_index("data")
            q = jax.lax.axis_index("model")
            kt = jax.random.fold_in(key, t)
            kb, kd, _, _ = jax.random.split(kt, 4)

            # --- steps 5-7: B^t / C^t / D^t (B, C identical on all devices)
            u = jax.random.uniform(kb, (M,))
            mask_b = _exact_count_mask(u, b_count)
            mask_c = _exact_count_mask(u, c_count)
            mb_loc = jax.lax.dynamic_slice(mask_b, (q * m,), (m,))
            mc_loc = jax.lax.dynamic_slice(mask_c, (q * m,), (m,))
            ud = jax.random.uniform(jax.random.fold_in(kd, p), (n,))
            md_loc = _exact_count_mask(ud, d_local)

            # --- step 8: stochastic snapshot gradient ---
            z_part = X_loc @ (w_loc * mb_loc)  # (n,)
            with jax.named_scope(EXCHANGE_SCOPE):
                if compress_z:
                    # §Perf iteration 2: the z = x_j^B w_B partial-sum
                    # reduction over 'model' is the DOMINANT collective of a
                    # SODDA iteration (d*n scalars/device vs m for mu) —
                    # int8 wires cut it 4x; the margin error feeds an
                    # already-stochastic snapshot estimator.
                    from repro.optim.grad_compression import compressed_psum
                    z = compressed_psum(z_part, "model")
                else:
                    z = jax.lax.psum(z_part, "model")
            s = deriv(z, y_loc) * md_loc / (cfg.P * d_local)
            mu_part = mc_loc * (X_loc.T @ s)
            with jax.named_scope(EXCHANGE_SCOPE):
                if compress_mu:
                    from repro.optim.grad_compression import compressed_psum
                    mu_q = compressed_psum(mu_part, "data")  # int8, f32 out
                else:
                    mu_q = jax.lax.psum(mu_part, "data")  # (m,)
            return mu_q

    def consume_local(X_loc, y_loc, w_loc, mu_q, t, key):
        with jax.named_scope(CONSUME_SCOPE):
            p = jax.lax.axis_index("data")
            q = jax.lax.axis_index("model")
            gamma = _gamma(cfg, t)
            kt = jax.random.fold_in(key, t)
            _, _, kp, kj = jax.random.split(kt, 4)

            # --- step 10: pi_q block assignment (one sub-block per worker)
            pi_q = jax.random.permutation(jax.random.fold_in(kp, q), cfg.P)
            k = pi_q[p]

            # --- steps 13-17: fully local inner loop ---
            J = jax.random.randint(jax.random.fold_in(kj, p * cfg.Q + q),
                                   (L,), 0, n)
            # the L sampled rows first, then the sub-block's columns: the
            # chip's n x mt sub-block is never copied
            Xl = jax.lax.dynamic_slice(X_loc[J], (0, k * mt), (L, mt))
            yl = y_loc[J]
            w0 = jax.lax.dynamic_slice(w_loc, (k * mt,), (mt,))
            mu_blk = jax.lax.dynamic_slice(mu_q, (k * mt,), (mt,))
            if use_kernel:
                from repro.kernels import ops as kops  # local: optional dep
                wL = kops.sodda_inner(w0[None], Xl[None], yl[None],
                                      mu_blk[None], gamma, cfg.loss,
                                      block_l=block_l)[0]
            else:
                wL = inner_loop(cfg.loss, w0, Xl, yl, mu_blk, gamma)

            # --- step 19: assemble. Each (q, k) block was updated by exactly
            # one row; share the new blocks across the column.
            if gather_deltas:
                # all_gather the (owner_row, block) pairs then scatter
                # locally: volume (P-1)/P * m per device, half of the psum
                # variant.
                with jax.named_scope(EXCHANGE_SCOPE):
                    blocks = jax.lax.all_gather(wL, "data")  # (P, mt)
                    ks = jax.lax.all_gather(k, "data")  # (P,)
                # row r's block is blocks[r], and it updated block ks[r]
                w_new = w_loc.reshape(cfg.P, mt).at[ks].set(blocks) \
                    .reshape(m)
            else:
                delta = jnp.zeros((m,), w_loc.dtype)
                delta = jax.lax.dynamic_update_slice(delta, wL - w0,
                                                     (k * mt,))
                with jax.named_scope(EXCHANGE_SCOPE):
                    delta = jax.lax.psum(delta, "data")
                w_new = w_loc + delta
            return w_new

    return issue_local, consume_local


def make_distributed_step(mesh, cfg: SoddaConfig, gather_deltas: bool = True,
                          compress_mu: bool = False, compress_z: bool = False,
                          use_kernel: bool = False, block_l=None):
    """Build the jitted shard_map SODDA step for `mesh` (data=P, model=Q).

    The step composes the :func:`make_local_halves` pair synchronously:
    consume blocks on the exchange it just issued.

    gather_deltas=True uses an all_gather of the m_tilde-sized updated
    sub-blocks along 'data' ((P-1)/P * m bytes/device); False uses a psum of
    an m-sized zero-padded delta (2(P-1)/P * m) — kept for the perf ablation
    in EXPERIMENTS.md §Perf.

    compress_mu=True runs the snapshot-gradient psum over 'data' through the
    int8 quantized all-reduce (grad_compression) — composing the paper's own
    C^t coordinate masking with 4x narrower wires. The inner loop tolerates
    a slightly perturbed mu (it is already a stochastic estimate; Theorem 1
    only needs bounded second moments).

    use_kernel=True runs the fully-local inner loop through the Pallas
    kernel wrapper (``repro.kernels.ops.sodda_inner`` with a per-device
    batch of one block) — the 'shard_map+pallas' engine backend.
    """
    Pn, Qn = mesh.shape["data"], mesh.shape["model"]
    assert (Pn, Qn) == (cfg.P, cfg.Q), (mesh.shape, cfg)
    issue_local, consume_local = make_local_halves(
        cfg, gather_deltas=gather_deltas, compress_mu=compress_mu,
        compress_z=compress_z, use_kernel=use_kernel, block_l=block_l)

    def step_local(X_loc, y_loc, w_loc, t, key):
        mu_q = issue_local(X_loc, y_loc, w_loc, t, key)
        return consume_local(X_loc, y_loc, w_loc, mu_q, t, key)

    smapped = jax.shard_map(
        step_local,
        mesh=mesh,
        in_specs=(P("data", "model"), P("data"), P("model"), P(), P()),
        out_specs=P("model"),
        # the all_gather + scatter assembly IS replicated across 'data' but
        # the static checker cannot infer it; psum path is inferable.
        check_vma=False,
    )

    @jax.jit
    def step(state: SoddaState, X, y):
        w_new = smapped(X, y, state.w, state.t, state.key)
        return SoddaState(w=w_new, t=state.t + 1, key=state.key)

    return step


def make_distributed_async_step(mesh, cfg: SoddaConfig, staleness: int = 1,
                                gather_deltas: bool = True,
                                compress_mu: bool = False,
                                compress_z: bool = False,
                                use_kernel: bool = False):
    """The ``async-mesh`` engine backend: a stale-by-one shard_map step.

    Returns the ``(step, init_carry, finalize)`` triple of the engine's
    ``StepBundle`` protocol. The scan carry is ``AsyncSoddaState`` with the
    exchange buffer ``mu`` laid out exactly like the iterate — global shape
    ``(M,)``, sharded ``P('model')`` (each feature partition's m-block
    resident on its mesh column, replicated across 'data' rows, which is the
    replication the issuing psum produces).

    Inside one shard_map body, iteration t *issues* its own exchange (the
    psum over 'data' of the C-masked snapshot gradient) into the next carry
    and *consumes* the buffer issued at t-1 from the current carry. The
    issued collective therefore has no consumer in its own iteration: XLA is
    free to overlap it with the fully-local inner loop it has no data
    dependence on, instead of stalling every device on the wire — the
    overlap the single-host ``async`` backend can only simulate in carry
    dataflow is here expressed on the real device topology.

    ``staleness=0`` consumes the just-issued buffer: the body is then
    operation-for-operation the synchronous composition of
    :func:`make_local_halves`, so it is held BITWISE to
    :func:`make_distributed_step` (the conformance anchor). ``staleness=1``
    runs the genuinely stale schedule and is held to the relaxed STALENESS
    policy, like the single-host ``async`` backend.

    The warm-up half maps only ``issue_local`` (its outputs are pure psums,
    so its replication is statically inferable and the VMA check stays on —
    unless the int8-compressed collectives, whose replication the checker
    cannot see through, are selected); the composed step inherits the
    all_gather + scatter assembly that already defeats the static checker in
    :func:`make_distributed_step`, hence ``check_vma=False`` there.
    """
    if staleness not in (0, 1):
        raise ValueError(
            f"staleness must be 0 (synchronous parity) or 1 (stale-by-one), "
            f"got {staleness!r}")
    Pn, Qn = mesh.shape["data"], mesh.shape["model"]
    assert (Pn, Qn) == (cfg.P, cfg.Q), (mesh.shape, cfg)
    issue_local, consume_local = make_local_halves(
        cfg, gather_deltas=gather_deltas, compress_mu=compress_mu,
        compress_z=compress_z, use_kernel=use_kernel)

    def step_local(X_loc, y_loc, w_loc, mu_loc, t, key):
        mu_issued = issue_local(X_loc, y_loc, w_loc, t, key)
        mu_consumed = mu_loc if staleness else mu_issued
        w_new = consume_local(X_loc, y_loc, w_loc, mu_consumed, t, key)
        return w_new, mu_issued

    smapped = jax.shard_map(
        step_local,
        mesh=mesh,
        in_specs=(P("data", "model"), P("data"), P("model"), P("model"),
                  P(), P()),
        out_specs=(P("model"), P("model")),
        # same assembly as make_distributed_step: replicated across 'data'
        # in a way the static checker cannot infer
        check_vma=False,
    )

    # jitted: the python-loop driver calls init_carry eagerly once per run,
    # and an un-jitted shard_map dispatch executes op-by-op (three orders of
    # magnitude slower on a fake multi-device host); inside the scan
    # driver's compiled program the jit wrapper simply inlines
    issue_smapped = jax.jit(jax.shard_map(
        issue_local,
        mesh=mesh,
        in_specs=(P("data", "model"), P("data"), P("model"), P(), P()),
        out_specs=P("model"),
        check_vma=not (compress_mu or compress_z),
    ))

    @jax.jit
    def step(carry: AsyncSoddaState, X, y):
        w_new, mu_new = smapped(X, y, carry.w, carry.mu, carry.t, carry.key)
        return AsyncSoddaState(w=w_new, t=carry.t + 1, key=carry.key,
                               mu=mu_new)

    def init_carry(state: SoddaState, X, y) -> AsyncSoddaState:
        # warm-up: issue the exchange for iteration state.t so the first
        # consume sees a valid buffer. Traced into the driver's single
        # compiled dispatch; the iterate has not moved, so the first
        # iteration is effectively synchronous (staleness starts at t+1).
        mu = issue_smapped(X, y, state.w, state.t, state.key)
        return AsyncSoddaState(w=state.w, t=state.t, key=state.key, mu=mu)

    def finalize(carry: AsyncSoddaState) -> SoddaState:
        return carry.sync_state()

    from repro.core.engine import StepBundle  # local: engine lazy-imports us
    return StepBundle(step=step, init_carry=init_carry, finalize=finalize)


def iteration_collective_bytes(cfg: SoddaConfig, gather_deltas: bool = True,
                               compress_mu: bool = False,
                               compress_z: bool = False) -> dict:
    """Analytic per-device wire bytes of one outer iteration's collectives.

    Ring-collective costs on the (data=P, model=Q) mesh (send volume per
    device; f32 wires are 4 bytes, int8-compressed wires 1 byte + a scale
    scalar per shard, which is dropped as negligible):

      * ``z``     psum of the (n,)-sized partial inner products over 'model'
                  — 2(Q-1)/Q · n per device
      * ``mu``    psum of the (m,)-sized masked snapshot gradient over
                  'data' — 2(P-1)/P · m per device
      * ``delta`` sub-block assembly over 'data': all_gather of the m̃-sized
                  updated blocks ((P-1)/P · m) or the zero-padded m-sized
                  delta psum (2(P-1)/P · m)

    The ``async-mesh`` backend moves exactly the same bytes as the sync
    ``shard_map`` step — the point of stale-by-one is *when* the mu psum's
    consumer runs (next iteration), not how much it ships.
    """
    P_, Q_, n, m = cfg.P, cfg.Q, cfg.n, cfg.m
    z = 2.0 * (Q_ - 1) / Q_ * n * (1 if compress_z else 4)
    mu = 2.0 * (P_ - 1) / P_ * m * (1 if compress_mu else 4)
    delta = (1.0 if gather_deltas else 2.0) * (P_ - 1) / P_ * m * 4
    return {"z": z, "mu": mu, "delta": delta, "total": z + mu + delta}


def distributed_objective(mesh, cfg: SoddaConfig):
    """Sharded objective F(w) for monitoring (psum over both axes)."""

    def obj_local(X_loc, y_loc, w_loc):
        z = jax.lax.psum(X_loc @ w_loc, "model")
        v = jnp.sum(losses.loss_value(cfg.loss, z, y_loc))
        v = jax.lax.psum(v, "data") / cfg.N
        # replicated scalar out
        return v

    smapped = jax.shard_map(
        obj_local, mesh=mesh,
        in_specs=(P("data", "model"), P("data"), P("model")),
        out_specs=P(),
    )
    return jax.jit(smapped)
