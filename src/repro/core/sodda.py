"""SODDA — StOchastic Doubly Distributed Algorithm (paper Algorithm 1).

Single-host reference implementation, fully vectorized over the (P, Q)
worker grid with vmap; the shard_map implementation in
``repro.core.distributed`` is bit-comparable (same `sample_iteration`
randomness), and ``repro.kernels.sodda_inner`` is the Pallas TPU kernel for
the inner loop validated against `inner_loop` here.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.sodda_svm import SoddaConfig
from repro.core import losses
from repro.core.partition import IterationSample, sample_iteration

__all__ = ["SoddaState", "AsyncSoddaState", "init_state", "init_async_state",
           "sodda_step", "sodda_step_async", "working_sets", "consume_update",
           "run", "snapshot_gradient", "inner_loop", "iteration_flops",
           "ISSUE_SCOPE", "EXCHANGE_SCOPE", "CONSUME_SCOPE",
           "OBJECTIVE_SCOPE"]

# The stages of an outer iteration, as ``jax.named_scope`` names. A scope
# writes only the ``op_name`` metadata of the HLO instructions traced under
# it (``.../sodda.issue/dot_general``), so a profile of any compiled run
# splits device time by stage at no run-time cost. An instruction's stage is
# its innermost scope: the exchange's collectives sit inside the issue and
# consume halves.
ISSUE_SCOPE = "sodda.issue"  # sample draw, the snapshot gradient's passes
EXCHANGE_SCOPE = "sodda.exchange"  # the mesh step's collectives
CONSUME_SCOPE = "sodda.consume"  # row gather, inner chains, assembly
OBJECTIVE_SCOPE = "sodda.objective"  # the driver's recorded F(w)


class SoddaState(NamedTuple):
    w: jnp.ndarray  # (M,) current iterate
    t: jnp.ndarray  # int32, 1-based outer iteration (for gamma_t)
    key: jnp.ndarray  # base PRNG key (folded with t each iteration)


class AsyncSoddaState(NamedTuple):
    """Extended scan carry for the stale-by-one engine backends.

    The plain :class:`SoddaState` fields plus the double-buffered exchange
    vector: ``mu`` holds the snapshot-gradient exchange *issued* during
    outer iteration t-1 (at w^{t-1} under the t-1 sample). Iteration t's
    inner loop consumes it while issuing the iteration-t exchange into the
    next carry, so the exchange has no data dependence on the compute it
    overlaps with.

    Two backends thread this carry through the scan: the single-host
    ``async`` backend (:func:`sodda_step_async`, ``mu`` a plain ``(M,)``
    array) and the mesh ``async-mesh`` backend
    (``repro.core.distributed.make_distributed_async_step``, same global
    ``(M,)`` shape but sharded ``P('model')`` alongside the iterate — the
    replication its issuing psum produces, so carrying it across iterations
    moves no bytes). Both strip back to :class:`SoddaState` via
    :meth:`sync_state` in the driver's finalize half.
    """

    w: jnp.ndarray  # (M,) current iterate
    t: jnp.ndarray  # int32, 1-based outer iteration
    key: jnp.ndarray  # base PRNG key
    mu: jnp.ndarray  # (M,) exchange buffer issued one iteration earlier

    def sync_state(self) -> "SoddaState":
        """Drop the exchange buffer (the driver's finalize half)."""
        return SoddaState(w=self.w, t=self.t, key=self.key)


def init_state(key, M: int) -> SoddaState:
    return SoddaState(w=jnp.zeros((M,), jnp.float32), t=jnp.array(1, jnp.int32), key=key)


# ---------------------------------------------------------------------------
# Step 8: stochastic snapshot gradient — the *issue* half of the exchange
#   mu^t = (1/d^t) sum_{j in D^t} bar_grad_{w_{C^t}} f_j(x_j^{B^t} w_{B^t})
# On a mesh this is the psum over 'data' a synchronous step blocks on; the
# async backend issues it one iteration ahead (see sodda_step_async).
# ---------------------------------------------------------------------------
def snapshot_gradient(loss: str, X, y, w, sample: IterationSample, d_count: int):
    zb = X @ (w * sample.mask_b)  # inner products restricted to B^t
    s = losses.loss_deriv(loss, zb, y) * sample.mask_d / d_count
    return sample.mask_c * (X.T @ s)  # coordinates restricted to C^t


# ---------------------------------------------------------------------------
# Steps 13-17: the L-step inner loop on one sub-block (paper step 16):
#   wbar <- wbar - gamma * [ l'(x.wbar) x - l'(x.w0) x + mu_blk ]
# (gradients evaluated at the block-restricted inner product — fully local)
# ---------------------------------------------------------------------------
def inner_loop(loss: str, w0, Xl, yl, mu_blk, gamma):
    """w0 (mt,), Xl (L, mt), yl (L,), mu_blk (mt,) -> (mt,)."""
    deriv = functools.partial(losses.loss_deriv, loss)

    def step(wbar, inp):
        x, yy = inp
        z1 = x @ wbar
        z0 = x @ w0
        g = (deriv(z1, yy) - deriv(z0, yy)) * x + mu_blk
        return wbar - gamma * g, None

    wL, _ = jax.lax.scan(step, w0, (Xl, yl))
    return wL


# ---------------------------------------------------------------------------
# One full outer iteration (paper steps 5-19)
# ---------------------------------------------------------------------------
def _counts(cfg: SoddaConfig):
    b = max(1, int(round(cfg.b_frac * cfg.M)))
    c = max(1, min(b, int(round(cfg.c_frac * cfg.M))))
    d_local = max(1, int(round(cfg.d_frac * cfg.n)))
    return b, c, d_local


def _gamma(cfg: SoddaConfig, t):
    return cfg.lr0 / (1.0 + jnp.sqrt(jnp.maximum(t - 1, 0).astype(jnp.float32))) \
        if cfg.constant_lr <= 0 else jnp.float32(cfg.constant_lr)


def _issue(cfg: SoddaConfig, X, y, w, t, key):
    """The issue half of iteration t: draw the sample, compute the exchange.

    One definition shared by the synchronous step, the async step, and the
    async warm-up — the 'first async iteration is effectively synchronous'
    invariant depends on all three issuing identically.
    """
    b_count, c_count, d_local = _counts(cfg)
    with jax.named_scope(ISSUE_SCOPE):
        smp = sample_iteration(key, t, cfg.P, cfg.Q, cfg.n, cfg.M, cfg.L,
                               b_count, c_count, d_local)
        mu = snapshot_gradient(cfg.loss, X, y, w, smp, cfg.P * d_local)
    return smp, mu


def working_sets(X, y, w, mu, smp: IterationSample, cfg: SoddaConfig):
    """Steps 10-13: every worker's inner-loop inputs, read where they lie.

    Worker (p, q) updates sub-block q*P + pi_q(p), columns
    [(q*P + pi_q(p)) * mt, +mt) of X, from rows p*n + J[p, q]. One gather
    of (1, mt) slices takes those P*Q*L rows from X as laid out: X is never
    reshaped or copied, and no worker's n x mt sub-block is materialised.
    Returns Xl (P, Q, L, mt), yl (P, Q, L), w0 and mu_blk (P, Q, mt).
    """
    P, Q, n, L, mt = cfg.P, cfg.Q, cfg.n, cfg.L, cfg.m_tilde
    blk = jnp.arange(Q) * P + smp.pi.T  # (P, Q)
    rows = jnp.arange(P)[:, None, None] * n + smp.J  # (P, Q, L)
    cols = jnp.broadcast_to((blk * mt)[:, :, None], rows.shape)
    starts = jnp.stack([rows, cols], axis=-1).reshape(P * Q * L, 2)
    Xl = jax.lax.gather(
        X, starts,
        jax.lax.GatherDimensionNumbers(offset_dims=(1,),
                                       collapsed_slice_dims=(0,),
                                       start_index_map=(0, 1)),
        slice_sizes=(1, mt),
        mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS,
    ).reshape(P, Q, L, mt)
    return (Xl, y[rows], w.reshape(Q * P, mt)[blk],
            mu.reshape(Q * P, mt)[blk])


def consume_update(X, y, w, mu, smp: IterationSample, gamma,
                   cfg: SoddaConfig, use_kernel: bool = False,
                   block_l=None):
    """Steps 10-19 — the *consume* half of an outer iteration.

    Gathers the per-(p, q) working sets for the iteration's sample, runs the
    L-step inner loops against the given exchange vector ``mu`` (fresh in
    the synchronous step, one iteration stale in the async backend), and
    concatenates the updated sub-blocks into the new iterate. Fully local:
    on a mesh nothing here needs a collective except the final concatenate.
    """
    P, Q, M, L = cfg.P, cfg.Q, cfg.M, cfg.L
    mt = cfg.m_tilde
    with jax.named_scope(CONSUME_SCOPE):
        Xl, yl, w0, mu_blk = working_sets(X, y, w, mu, smp, cfg)

        if use_kernel:
            from repro.kernels import ops as kops  # local: optional dep
            wL = kops.sodda_inner(
                w0.reshape(P * Q, mt), Xl.reshape(P * Q, L, mt),
                yl.reshape(P * Q, L), mu_blk.reshape(P * Q, mt),
                gamma, cfg.loss, block_l=block_l).reshape(P, Q, mt)
        else:
            wL = jax.vmap(jax.vmap(
                lambda w_, X_, y_, m_: inner_loop(cfg.loss, w_, X_, y_, m_,
                                                  gamma)
            ))(w0, Xl, yl, mu_blk)

        # step 19: conflict-free concatenation, each (q, pi_q(p)) once
        q_idx = jnp.repeat(jnp.arange(Q), P)
        k_idx = smp.pi.reshape(-1)
        new_wb = w.reshape(Q, P, mt).at[q_idx, k_idx].set(
            wL.transpose(1, 0, 2).reshape(Q * P, mt))
        return new_wb.reshape(M)


@functools.partial(jax.jit, static_argnames=("cfg", "use_kernel", "block_l"))
def sodda_step(state: SoddaState, X, y, cfg: SoddaConfig,
               use_kernel: bool = False, block_l=None):
    gamma = _gamma(cfg, state.t)
    smp, mu = _issue(cfg, X, y, state.w, state.t, state.key)
    w_new = consume_update(X, y, state.w, mu, smp, gamma, cfg, use_kernel,
                           block_l=block_l)
    return SoddaState(w=w_new, t=state.t + 1, key=state.key)


# ---------------------------------------------------------------------------
# Stale-by-one outer iteration: the 'async' engine backend. The exchange is
# double-buffered in the scan carry — iteration t consumes the buffer issued
# at t-1 and issues its own for t+1, so the issue half (on a mesh: the
# snapshot-gradient psum) has no consumer in its own iteration and overlaps
# the inner-loop compute instead of blocking it.
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("cfg", "staleness"))
def sodda_step_async(carry: AsyncSoddaState, X, y, cfg: SoddaConfig,
                     staleness: int = 1):
    """One stale-by-one outer iteration on the extended carry.

    Issue half: compute this iteration's snapshot-gradient exchange from the
    current iterate. Consume half: run the inner loops against ``carry.mu``,
    the buffer issued one iteration earlier. ``staleness=0`` consumes the
    just-issued buffer instead — arithmetically the synchronous
    :func:`sodda_step`, the exact-parity anchor in the conformance suite.
    """
    gamma = _gamma(cfg, carry.t)
    smp, mu_issued = _issue(cfg, X, y, carry.w, carry.t, carry.key)
    mu_consumed = carry.mu if staleness else mu_issued
    w_new = consume_update(X, y, carry.w, mu_consumed, smp, gamma, cfg)
    return AsyncSoddaState(w=w_new, t=carry.t + 1, key=carry.key, mu=mu_issued)


def init_async_state(state: SoddaState, X, y, cfg: SoddaConfig) -> AsyncSoddaState:
    """Warm-up (the driver's carry-init half): issue the exchange for
    iteration ``state.t`` so the first consume sees a valid buffer.

    Because the iterate has not moved yet, the first async iteration is
    effectively synchronous (it consumes exactly the buffer it would have
    computed itself); staleness begins at the second iteration.
    """
    _, mu = _issue(cfg, X, y, state.w, state.t, state.key)
    return AsyncSoddaState(w=state.w, t=state.t, key=state.key, mu=mu)


def run(key, X, y, cfg: SoddaConfig, iters: int, record_every: int = 1,
        use_kernel: bool = False):
    """Run SODDA, returning (final state, [(t, F(w^t)) history]).

    Thin wrapper over the scan-compiled driver (``repro.core.driver``): the
    whole trajectory is one fused device program, not a per-iteration loop.
    """
    from repro.core import driver  # local import: driver builds on engine
    return driver.run(key, (X, y), cfg, iters,
                      "pallas" if use_kernel else "reference",
                      record_every=record_every)


# ---------------------------------------------------------------------------
# Analytic per-iteration cost (gradient-coordinate evaluations), used by the
# benchmark to reproduce the paper's "better in early iterations" claim on a
# machine-independent x-axis.
# ---------------------------------------------------------------------------
def iteration_flops(cfg: SoddaConfig, exact_snapshot: bool = False) -> float:
    b = 1.0 if exact_snapshot else cfg.b_frac
    c = 1.0 if exact_snapshot else cfg.c_frac
    d = 1.0 if exact_snapshot else cfg.d_frac
    snapshot = 2.0 * d * cfg.N * (b * cfg.M) + 2.0 * d * cfg.N * (c * cfg.M)
    inner = cfg.P * cfg.Q * cfg.L * 6.0 * cfg.m_tilde
    return snapshot + inner
