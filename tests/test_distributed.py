"""Distributed-correctness tests.

The shard_map SODDA equivalence needs a (P=4 x Q=3)=12-device mesh; the
session runs on a forced 12-device host platform (see conftest), so all of
these run IN-PROCESS — no subprocess respawns, one jit warm-up per step
variant for the whole session.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.sodda_svm import SoddaConfig
from repro.core import engine, sodda
from repro.core.distributed import (distributed_objective,
                                    iteration_collective_bytes,
                                    make_distributed_async_step,
                                    make_distributed_step)
from repro.data.synthetic import make_svm_data
from repro.testing import medium_fixture_config, sodda_test_mesh


@pytest.fixture(scope="module")
def equiv_result():
    cfg = SoddaConfig(P=4, Q=3, n=120, m=24, L=8, lr0=0.05)
    X, y, _ = make_svm_data(jax.random.PRNGKey(0), cfg.N, cfg.M)
    mesh = sodda_test_mesh(cfg)

    state = sodda.init_state(jax.random.PRNGKey(1), cfg.M)
    step_d = make_distributed_step(mesh, cfg)
    obj_d = distributed_objective(mesh, cfg)

    s_ref, s_dist = state, state
    errs = []
    for t in range(5):
        s_ref = sodda.sodda_step(s_ref, X, y, cfg)
        s_dist = step_d(s_dist, X, y)
        errs.append(float(jnp.max(jnp.abs(s_ref.w - s_dist.w))))
    import repro.core.losses as losses
    return {
        "errs": errs,
        "scale": float(jnp.max(jnp.abs(s_ref.w))),
        "obj_dist": float(obj_d(X, y, s_dist.w)),
        "obj_ref": float(losses.objective(cfg.loss, X, y, s_dist.w)),
    }


def test_shard_map_sodda_matches_reference(equiv_result):
    """5 outer iterations on a 4x3 device grid: the doubly-distributed
    shard_map implementation must track the single-host reference to f32
    reduction-order tolerance."""
    r = equiv_result
    assert max(r["errs"]) < 1e-4 * max(r["scale"], 1.0), r


def test_distributed_objective_matches(equiv_result):
    r = equiv_result
    np.testing.assert_allclose(r["obj_dist"], r["obj_ref"], rtol=1e-5)


def test_async_mesh_first_step_after_warmup_is_synchronous():
    """The warm-up issues the exchange for the first iteration before the
    iterate has moved, so the first stale-by-one step consumes exactly the
    buffer the synchronous step would have computed inline — the mesh analog
    of the single-host 'first async iteration is effectively synchronous'
    invariant. Staleness only begins at the second step, where the mesh
    trajectory must leave the synchronous one."""
    cfg = SoddaConfig(P=4, Q=3, n=120, m=24, L=8, lr0=0.05)
    X, y, _ = make_svm_data(jax.random.PRNGKey(0), cfg.N, cfg.M)
    mesh = sodda_test_mesh(cfg)
    sync_step = make_distributed_step(mesh, cfg)
    bundle = make_distributed_async_step(mesh, cfg, staleness=1)

    state = sodda.init_state(jax.random.PRNGKey(1), cfg.M)
    carry = bundle.init_carry(state, X, y)
    s_sync = sync_step(state, X, y)
    carry = bundle.step(carry, X, y)
    np.testing.assert_allclose(np.asarray(carry.w), np.asarray(s_sync.w),
                               rtol=0, atol=1e-6)
    # second step: the consumed buffer is now genuinely stale — the
    # stale-by-one trajectory must diverge from the synchronous one
    s_sync2 = sync_step(s_sync, X, y)
    carry2 = bundle.step(carry, X, y)
    assert float(jnp.max(jnp.abs(carry2.w - s_sync2.w))) > 0.0


def test_issue_consume_staleness_zero_fallback():
    """Hypothesis-free fallback for the issue∘consume property test in
    tests/test_property.py: at staleness=0 the composed halves are bitwise
    the synchronous make_distributed_step for arbitrary (w, key, t), and the
    NaN-poisoned stale buffer is provably unconsumed. Fixed seed/t sweep."""
    from repro.testing import make_problem, small_fixture_config
    cfg = small_fixture_config()
    mesh = sodda_test_mesh(cfg)
    X, y = make_problem(cfg)
    sync_step = make_distributed_step(mesh, cfg)
    bundle = make_distributed_async_step(mesh, cfg, staleness=0)
    for seed, t in ((0, 1), (7, 2), (42, 999), (3, 10_000)):
        key = jax.random.PRNGKey(seed)
        w = jax.random.normal(jax.random.fold_in(key, 1), (cfg.M,)) * 0.1
        t_arr = jnp.array(t, jnp.int32)
        out_sync = sync_step(sodda.SoddaState(w=w, t=t_arr, key=key), X, y)
        out_async = bundle.step(
            sodda.AsyncSoddaState(w=w, t=t_arr, key=key,
                                  mu=jnp.full((cfg.M,), jnp.nan)), X, y)
        np.testing.assert_array_equal(np.asarray(out_sync.w),
                                      np.asarray(out_async.w), err_msg=f"seed={seed} t={t}")
        assert bool(jnp.isfinite(out_async.mu).all())


def test_iteration_collective_bytes_accounting():
    """The analytic wire model the bench reports: compression narrows only
    the compressed collective 4x, the delta-psum exchange doubles the
    assembly bytes, and async-mesh ships exactly the sync step's volume."""
    cfg = SoddaConfig(P=4, Q=3, n=120, m=24, L=8, lr0=0.05)
    base = iteration_collective_bytes(cfg)
    assert base["total"] == base["z"] + base["mu"] + base["delta"]
    assert base["z"] == 2.0 * (cfg.Q - 1) / cfg.Q * cfg.n * 4
    q8 = iteration_collective_bytes(cfg, compress_z=True, compress_mu=True)
    assert q8["z"] == base["z"] / 4 and q8["mu"] == base["mu"] / 4
    assert q8["delta"] == base["delta"]
    psum = iteration_collective_bytes(cfg, gather_deltas=False)
    assert psum["delta"] == 2 * base["delta"]


def test_compressed_psum_roundtrip():
    """int8-quantized psum vs exact psum on a 1-device mesh (semantics) —
    and error feedback drives the average bias to ~0 over steps."""
    from repro.optim.grad_compression import (ErrorFeedback, compressed_psum,
                                              compressed_psum_ef)
    mesh = jax.make_mesh((1,), ("d",))
    x = jax.random.normal(jax.random.PRNGKey(0), (256,))

    def f(x):
        return compressed_psum(x, "d")

    out = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=jax.sharding.PartitionSpec(),
                            out_specs=jax.sharding.PartitionSpec(),
                            check_vma=False))(x)
    # two quantizations, each with error <= scale/2 = absmax/254
    assert float(jnp.max(jnp.abs(out - x))) <= float(jnp.max(jnp.abs(x))) / 100

    def g(x, res):
        ef = ErrorFeedback(residual=res)
        out, ef2 = compressed_psum_ef(x, ef, "d")
        return out, ef2.residual

    gj = jax.jit(jax.shard_map(
        g, mesh=mesh,
        in_specs=(jax.sharding.PartitionSpec(), jax.sharding.PartitionSpec()),
        out_specs=(jax.sharding.PartitionSpec(), jax.sharding.PartitionSpec()),
        check_vma=False))
    res = jnp.zeros((256,))
    acc = jnp.zeros((256,))
    for _ in range(64):
        out, res = gj(x, res)
        acc = acc + out
    # with error feedback the time-average converges to the true value
    np.testing.assert_allclose(acc / 64, x, atol=5e-3 * float(jnp.max(jnp.abs(x))))


def test_compressed_psum_multi_axis():
    """tuple-axis handling: psum over ('a', 'b') == nested single-axis
    reductions; on a 1x1 mesh it must round-trip the input."""
    from repro.optim.grad_compression import compressed_psum
    mesh = jax.make_mesh((1, 1), ("a", "b"))
    x = jax.random.normal(jax.random.PRNGKey(2), (64,))
    out = jax.jit(jax.shard_map(
        lambda v: compressed_psum(v, ("a", "b")), mesh=mesh,
        in_specs=jax.sharding.PartitionSpec(),
        out_specs=jax.sharding.PartitionSpec(), check_vma=False))(x)
    assert out.shape == x.shape
    assert float(jnp.max(jnp.abs(out - x))) <= float(jnp.max(jnp.abs(x))) / 50


@pytest.mark.slow
def test_compressed_collectives_preserve_convergence():
    """int8 z/mu wires (§Perf cell A it3) must not degrade SODDA."""
    cfg = medium_fixture_config()  # 4x3 grid, 2000 x 360
    X, y, _ = make_svm_data(jax.random.PRNGKey(0), cfg.N, cfg.M)
    mesh = sodda_test_mesh(cfg)
    obj = distributed_objective(mesh, cfg)
    out = {}
    for name, kw in {"exact": {}, "q8": dict(compress_mu=True,
                                             compress_z=True)}.items():
        step = engine.make_step(cfg, "shard_map", mesh=mesh, **kw)
        s = sodda.init_state(jax.random.PRNGKey(1), cfg.M)
        for _ in range(15):
            s = step(s, X, y)
        out[name] = float(obj(X, y, s.w))
    assert out["exact"] < 0.6  # converged meaningfully
    assert abs(out["q8"] - out["exact"]) < 0.05 * max(out["exact"], 0.1), out


def test_sharding_rules_cover_all_archs():
    from repro.configs import get_config, list_archs
    from repro.distributed.sharding_rules import batch_axes, decode_mode, rules_for
    from repro.configs import SHAPES
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    for name in list_archs():
        cfg = get_config(name)
        rules = rules_for(cfg, mesh)
        assert "vocab" in rules and "batch" in rules
        for shape in SHAPES.values():
            axes = batch_axes(cfg, shape, mesh)
            assert isinstance(axes, tuple)
        assert decode_mode(cfg, mesh) in ("heads", "seq", "none")
