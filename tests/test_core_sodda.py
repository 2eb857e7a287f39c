"""Behavioural tests of the paper's algorithm (Algorithm 1 + claims)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.sodda_svm import SoddaConfig
from repro.core import losses, radisa, sodda
from repro.core.partition import blocks_view, pi_permutations, sample_iteration
from repro.data.synthetic import make_svm_data

CFG = SoddaConfig(P=4, Q=3, n=300, m=48, L=16, lr0=0.05)


@pytest.fixture(scope="module")
def data():
    X, y, z = make_svm_data(jax.random.PRNGKey(0), CFG.N, CFG.M)
    return X, y


def test_sodda_decreases_loss(data):
    X, y = data
    _, hist = sodda.run(jax.random.PRNGKey(1), X, y, CFG, 20, record_every=20)
    assert hist[-1][1] < hist[0][1] * 0.6, hist


def test_sodda_full_fractions_equals_radisa(data):
    """b=c=d=1 reduces SODDA's snapshot to the exact full gradient
    (paper Corollary 1: RADiSA is a special case)."""
    X, y = data
    cfg_full = dataclasses.replace(CFG, b_frac=1.0, c_frac=1.0, d_frac=1.0)
    s0 = sodda.init_state(jax.random.PRNGKey(2), CFG.M)
    out1 = sodda.sodda_step(s0, X, y, cfg_full)
    out2 = radisa.radisa_step(s0, X, y, CFG)
    np.testing.assert_allclose(out1.w, out2.w, rtol=1e-6, atol=1e-7)


def test_snapshot_gradient_unbiased_scaling(data):
    """E[mu] = (c/M) grad F (paper Claim 2, eq. 17): check the masked
    estimator against the exact gradient on the sampled coordinates."""
    X, y = data
    w = jax.random.normal(jax.random.PRNGKey(3), (CFG.M,)) * 0.1
    b_count, c_count, d_local = sodda._counts(
        dataclasses.replace(CFG, b_frac=1.0, d_frac=1.0))
    smp = sample_iteration(jax.random.PRNGKey(4), 0, CFG.P, CFG.Q, CFG.n,
                           CFG.M, CFG.L, b_count, c_count, d_local)
    mu = sodda.snapshot_gradient("hinge", X, y, w, smp, CFG.P * d_local)
    exact = losses.full_gradient("hinge", X, y, w)
    # with b=d=1, mu must equal the exact gradient on C and 0 elsewhere
    np.testing.assert_allclose(mu, exact * smp.mask_c, rtol=1e-5, atol=1e-6)


def test_pi_is_permutation():
    pi = pi_permutations(jax.random.PRNGKey(5), 7, 13)
    assert pi.shape == (7, 13)
    for q in range(7):
        assert sorted(np.asarray(pi[q]).tolist()) == list(range(13))


def test_sample_iteration_invariants_fallback():
    """Hypothesis-free fallback for the sample_iteration property suite in
    tests/test_property.py — same shared checker
    (repro.testing.check_iteration_sample), fixed seed/shape sweep."""
    from repro.testing import assert_samples_equal, check_iteration_sample
    cases = [
        # (seed, t, P, Q, n, mt, L, b_frac, c_frac, d_frac)
        (0, 0, 2, 2, 8, 4, 4, 0.85, 0.80, 0.85),
        (1, 7, 4, 3, 10, 2, 3, 1.0, 1.0, 1.0),
        (2, 1, 1, 1, 2, 1, 1, 0.01, 0.01, 0.01),
        (3, 999, 3, 2, 6, 3, 5, 0.5, 0.9, 0.33),
    ]
    for seed, t, P, Q, n, mt, L, bf, cf, df in cases:
        M = Q * P * mt
        b = max(1, int(round(bf * M)))
        c = max(1, min(b, int(round(cf * M))))
        d = max(1, int(round(df * n)))
        key = jax.random.PRNGKey(seed)
        s = sample_iteration(key, t, P, Q, n, M, L, b, c, d)
        check_iteration_sample(s, P, Q, n, M, L, b, c, d)
        # fold_in determinism: pure function of (key, t)
        assert_samples_equal(
            s, sample_iteration(key, t, P, Q, n, M, L, b, c, d))


def test_step19_concatenation_conflict_free(data):
    """Each omega sub-block must be written by exactly one worker: running
    one step twice with the same key gives identical iterates (pure fn)."""
    X, y = data
    s0 = sodda.init_state(jax.random.PRNGKey(6), CFG.M)
    w1 = sodda.sodda_step(s0, X, y, CFG).w
    w2 = sodda.sodda_step(s0, X, y, CFG).w
    np.testing.assert_array_equal(w1, w2)


def test_blocks_view_roundtrip():
    X = jnp.arange(4 * 6 * 2 * 12, dtype=jnp.float32).reshape(8, 72) * 0  # shape probe
    X = jax.random.normal(jax.random.PRNGKey(7), (8, 72))
    P, Q = 2, 3
    Xb = blocks_view(X, P, Q)  # (P, QP, n, mt)
    n, mt = 4, 12
    for p in range(P):
        for q in range(Q):
            for k in range(P):
                block = Xb[p, q * P + k]
                want = X[p * n:(p + 1) * n, q * 24 + k * mt: q * 24 + (k + 1) * mt]
                np.testing.assert_array_equal(block, want)


def test_radisa_avg_decreases_loss(data):
    X, y = data
    _, hist = radisa.run_radisa_avg(jax.random.PRNGKey(8), X, y, CFG, 15,
                                    record_every=15)
    assert hist[-1][1] < hist[0][1] * 0.7


def test_paper_claim_sodda_beats_radisa_avg_early_per_flop(data):
    """Paper §5: SODDA reaches good-quality solutions faster (on a
    machine-independent gradient-coordinate cost axis) in early iterations."""
    X, y = data
    budget = 12 * sodda.iteration_flops(CFG)  # small early-phase budget
    it_s = int(budget / sodda.iteration_flops(CFG))
    it_r = max(1, int(budget / radisa.radisa_avg_iteration_flops(CFG)))
    _, hs = sodda.run(jax.random.PRNGKey(9), X, y, CFG, it_s, record_every=it_s)
    _, hr = radisa.run_radisa_avg(jax.random.PRNGKey(9), X, y, CFG, it_r,
                                  record_every=it_r)
    assert hs[-1][1] < hr[-1][1] * 1.05, (hs[-1], hr[-1])


def test_constant_lr_converges_to_neighborhood(data):
    """Theorem 3 trade-off: larger constant gamma converges faster but to a
    larger gamma-proportional neighborhood; smaller gamma, run to its own
    horizon, reaches a lower plateau."""
    X, y = data
    cfg_big = dataclasses.replace(CFG, constant_lr=0.02)
    _, h_big = sodda.run(jax.random.PRNGKey(11), X, y, cfg_big, 60,
                         record_every=10)
    cfg_small = dataclasses.replace(CFG, constant_lr=0.005)
    _, h_small = sodda.run(jax.random.PRNGKey(11), X, y, cfg_small, 240,
                           record_every=10)
    # faster early progress at large gamma (compared at iteration 10)
    assert h_big[1][1] < h_small[1][1] * 0.8, (h_big[1], h_small[1])
    # smaller gamma ends in a smaller neighborhood
    plateau_big = min(v for _, v in h_big[3:])
    plateau_small = min(v for _, v in h_small[3:])
    assert plateau_small < plateau_big, (plateau_small, plateau_big)


def test_elastic_rescale_continues_converging(data):
    """SODDA is natively elastic: after dropping observation partitions
    (P=4 -> P=2), the iterate carries over (same M) and keeps improving on
    the surviving data — no state surgery beyond the rescale plan."""
    from repro.distributed.fault_tolerance import rescale_plan
    X, y = data
    state = sodda.init_state(jax.random.PRNGKey(12), CFG.M)
    for _ in range(6):
        state = sodda.sodda_step(state, X, y, CFG)
    plan, moved = rescale_plan(CFG.P, 2, CFG.n)
    assert set(plan) == {0, 1} and moved > 0
    cfg2 = dataclasses.replace(CFG, P=2)  # m_tilde doubles; pi redrawn
    keep = 2 * CFG.n
    X2, y2 = X[:keep], y[:keep]
    f_before = float(losses.objective(CFG.loss, X2, y2, state.w))
    state2 = sodda.SoddaState(w=state.w, t=state.t, key=state.key)
    for _ in range(10):
        state2 = sodda.sodda_step(state2, X2, y2, cfg2)
    f_after = float(losses.objective(CFG.loss, X2, y2, state2.w))
    assert f_after < f_before, (f_before, f_after)


def test_inner_loop_zero_iterations_is_identity():
    """L=0: the scan body never runs, so inner_loop must return w0."""
    key = jax.random.PRNGKey(0)
    w0 = jax.random.normal(key, (16,))
    Xl = jnp.zeros((0, 16))
    yl = jnp.zeros((0,))
    mu = jax.random.normal(jax.random.fold_in(key, 1), (16,))
    for loss in losses.LOSSES:
        out = sodda.inner_loop(loss, w0, Xl, yl, mu, 0.05)
        np.testing.assert_array_equal(out, w0)


def test_inner_loop_zero_gamma_is_identity():
    """gamma=0: every update is a no-op regardless of the data."""
    key = jax.random.PRNGKey(1)
    w0 = jax.random.normal(key, (16,))
    Xl = jax.random.normal(jax.random.fold_in(key, 1), (5, 16))
    yl = jnp.sign(jax.random.normal(jax.random.fold_in(key, 2), (5,)))
    mu = jax.random.normal(jax.random.fold_in(key, 3), (16,))
    for loss in losses.LOSSES:
        out = sodda.inner_loop(loss, w0, Xl, yl, mu, 0.0)
        np.testing.assert_array_equal(out, w0)


def test_counts_edge_cases():
    """c is clamped to <= b, and every count bottoms out at 1 for tiny
    fractions (the samples can never be empty)."""
    cfg = dataclasses.replace(CFG, b_frac=0.5, c_frac=0.9)
    b, c, d = sodda._counts(cfg)
    assert c <= b  # C^t subset of B^t even when c_frac > b_frac
    tiny = dataclasses.replace(CFG, b_frac=1e-9, c_frac=1e-9, d_frac=1e-9)
    b, c, d = sodda._counts(tiny)
    assert (b, c, d) == (1, 1, 1)
    full = dataclasses.replace(CFG, b_frac=1.0, c_frac=1.0, d_frac=1.0)
    b, c, d = sodda._counts(full)
    assert (b, c, d) == (CFG.M, CFG.M, CFG.n)


def test_iteration_flops_snapshot_ordering():
    """The benchmark x-axis: exact snapshot (b=c=d=1) must cost strictly
    more than the sampled snapshot whenever any fraction < 1."""
    sampled = sodda.iteration_flops(CFG, exact_snapshot=False)
    exact = sodda.iteration_flops(CFG, exact_snapshot=True)
    assert 0.0 < sampled < exact
    full = dataclasses.replace(CFG, b_frac=1.0, c_frac=1.0, d_frac=1.0)
    np.testing.assert_allclose(sodda.iteration_flops(full, False),
                               sodda.iteration_flops(full, True))


def test_kernel_path_matches_reference(data):
    """use_kernel=True (Pallas sodda_inner, interpret mode) is numerically
    the reference implementation."""
    X, y = data
    s0 = sodda.init_state(jax.random.PRNGKey(10), CFG.M)
    w_ref = sodda.sodda_step(s0, X, y, CFG, use_kernel=False).w
    w_ker = sodda.sodda_step(s0, X, y, CFG, use_kernel=True).w
    np.testing.assert_allclose(w_ref, w_ker, rtol=2e-5, atol=1e-6)


def _working_sets_oracle(X, y, w, mu, smp, cfg):
    """The consume half's gather as it was first written: X re-laid out to
    (P, QP, n, mt), then a double vmap over the worker grid."""
    P, Q, n, mt = cfg.P, cfg.Q, cfg.n, cfg.m_tilde
    Xb = X.reshape(P, n, Q * P, mt).transpose(0, 2, 1, 3)
    yb = y.reshape(P, n)
    wb = w.reshape(Q, P, mt)
    mub = mu.reshape(Q, P, mt)
    pq_p, pq_q = jnp.meshgrid(jnp.arange(P), jnp.arange(Q), indexing="ij")

    def gather_one(p, q):
        k = smp.pi[q, p]
        rows = smp.J[p, q]
        return Xb[p, q * P + k][rows], yb[p][rows], wb[q, k], mub[q, k]

    return jax.vmap(jax.vmap(gather_one))(pq_p, pq_q)


def _consume_oracle(X, y, w, mu, smp, gamma, cfg):
    P, Q, M, mt = cfg.P, cfg.Q, cfg.M, cfg.m_tilde
    Xl, yl, w0, mu_blk = _working_sets_oracle(X, y, w, mu, smp, cfg)
    wL = jax.vmap(jax.vmap(
        lambda w_, X_, y_, m_: sodda.inner_loop(cfg.loss, w_, X_, y_, m_,
                                                gamma)))(w0, Xl, yl, mu_blk)
    q_idx = jnp.repeat(jnp.arange(Q), P)
    new_wb = w.reshape(Q, P, mt).at[q_idx, smp.pi.reshape(-1)].set(
        wL.transpose(1, 0, 2).reshape(Q * P, mt))
    return new_wb.reshape(M)


# (P, Q, n, m, L): one worker; Table 1's 5x3 grid with mt = 130, not a
# multiple of 128; L > n, so rows are drawn with repeats
GATHER_CASES = [(1, 1, 8, 4, 5), (5, 3, 12, 650, 7), (2, 3, 3, 10, 16)]


def _gather_problem(P, Q, n, m, L, seed):
    cfg = SoddaConfig(P=P, Q=Q, n=n, m=m, L=L, lr0=0.05)
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    X = jax.random.normal(ks[0], (cfg.N, cfg.M))
    y = jnp.sign(jax.random.normal(ks[1], (cfg.N,)))
    w = jax.random.normal(ks[2], (cfg.M,))
    mu = jax.random.normal(ks[3], (cfg.M,))
    return cfg, X, y, w, mu


def _sample(cfg, seed, t):
    b, c, d = sodda._counts(cfg)
    return sample_iteration(jax.random.PRNGKey(seed), t, cfg.P, cfg.Q, cfg.n,
                            cfg.M, cfg.L, b, c, d)


@pytest.mark.parametrize("P,Q,n,m,L", GATHER_CASES)
def test_working_sets_gather_in_place_is_bitwise(P, Q, n, m, L):
    """The working sets taken where they lie in X are the re-laid-out
    copy's, float for float, over several random samples."""
    cfg, X, y, w, mu = _gather_problem(P, Q, n, m, L, seed=20 + P)
    new = jax.jit(sodda.working_sets, static_argnames="cfg")
    old = jax.jit(_working_sets_oracle, static_argnames="cfg")
    for t in range(1, 5):
        smp = _sample(cfg, 30 + P, t)
        if L > n:
            assert len(np.unique(np.asarray(smp.J[0, 0]))) < L
        got, want = new(X, y, w, mu, smp, cfg), old(X, y, w, mu, smp, cfg)
        for g, e in zip(got, want):
            assert g.shape == e.shape
            np.testing.assert_array_equal(g, e)


def test_consume_update_matches_relayout_oracle_bitwise():
    """One reference-path consume half gives the iterate the re-laid-out
    gather gave, bit for bit."""
    cfg, X, y, w, mu = _gather_problem(*GATHER_CASES[1], seed=40)
    smp = _sample(cfg, 41, 3)
    gamma = sodda._gamma(cfg, jnp.int32(3))
    got = jax.jit(sodda.consume_update, static_argnames="cfg")(
        X, y, w, mu, smp, gamma, cfg)
    want = jax.jit(_consume_oracle, static_argnames="cfg")(
        X, y, w, mu, smp, gamma, cfg)
    np.testing.assert_array_equal(got, want)
