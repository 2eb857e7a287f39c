"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.sodda_inner import sodda_inner_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas

KEY = jax.random.PRNGKey(0)


def k(i):
    return jax.random.fold_in(KEY, i)


# ---------------------------------------------------------------------------
# sodda_inner
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,L,mt", [(1, 4, 128), (6, 16, 128), (3, 32, 256),
                                    (2, 8, 384)])
@pytest.mark.parametrize("loss", ["hinge", "logistic", "squared"])
def test_sodda_inner_shapes(B, L, mt, loss):
    w0 = jax.random.normal(k(1), (B, mt)) * 0.1
    Xl = jax.random.normal(k(2), (B, L, mt))
    yl = jnp.sign(jax.random.normal(k(3), (B, L)))
    mu = jax.random.normal(k(4), (B, mt)) * 0.01
    out = sodda_inner_pallas(w0, Xl, yl, mu, 0.03, loss)
    want = ref.sodda_inner_ref(w0, Xl, yl, mu, 0.03, loss)
    # the kernel hoists z0 = Xl @ w0 into one matvec (different fp
    # accumulation order than the per-step dots of the reference)
    np.testing.assert_allclose(out, want, rtol=3e-4, atol=2e-5)


def test_sodda_inner_ops_padding():
    """ops wrapper pads mt to 128; padding must be exact."""
    B, L, mt = 2, 8, 100  # deliberately unaligned
    w0 = jax.random.normal(k(5), (B, mt)) * 0.1
    Xl = jax.random.normal(k(6), (B, L, mt))
    yl = jnp.sign(jax.random.normal(k(7), (B, L)))
    mu = jax.random.normal(k(8), (B, mt)) * 0.01
    out = ops.sodda_inner(w0, Xl, yl, mu, 0.05, "hinge")
    want = ref.sodda_inner_ref(w0, Xl, yl, mu, 0.05, "hinge")
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# sodda_inner: the blocked-schedule conformance battery.
#
# tuning.BlockConfig tiles the L dimension; the kernel's hoisted snapshot
# matvec is per-row independent, so every legal block_l must be BITWISE
# against the single-tile default — and all of them track the jnp oracle
# within the usual hoisted-matvec accumulation tolerance.
# ---------------------------------------------------------------------------
from repro.core.losses import LOSSES  # noqa: E402
from repro.kernels import tuning  # noqa: E402

_DERIV_TOL = dict(rtol=3e-4, atol=2e-5)  # hoisted-matvec accumulation order


def _sodda_case(B, L, mt, seed):
    w0 = jax.random.normal(k(seed), (B, mt)) * 0.1
    Xl = jax.random.normal(k(seed + 1), (B, L, mt))
    yl = jnp.sign(jax.random.normal(k(seed + 2), (B, L)))
    mu = jax.random.normal(k(seed + 3), (B, mt)) * 0.01
    return w0, Xl, yl, mu


@pytest.mark.parametrize("loss", sorted(LOSSES))
@pytest.mark.parametrize("block_l", [1, 2, 4, 8])
def test_sodda_inner_blocked_vs_ref(loss, block_l):
    """Every schedule x every registered loss against the oracle, at a
    deliberately non-128-aligned mt (the ops padding path)."""
    B, L, mt = 2, 8, 130
    w0, Xl, yl, mu = _sodda_case(B, L, mt, 50)
    out = ops.sodda_inner(w0, Xl, yl, mu, 0.04, loss, block_l=block_l)
    want = ref.sodda_inner_ref(w0, Xl, yl, mu, 0.04, loss)
    np.testing.assert_allclose(out, want, **_DERIV_TOL)


@pytest.mark.parametrize("loss", sorted(LOSSES))
def test_sodda_inner_every_legal_block_bitwise(loss):
    """The BITWISE anchor: each legal BlockConfig vs the default schedule,
    raw kernel level. Tiling may only change the schedule, never a bit."""
    B, L, mt = 3, 12, 256
    w0, Xl, yl, mu = _sodda_case(B, L, mt, 60)
    base = sodda_inner_pallas(w0, Xl, yl, mu, 0.03, loss)
    legal = tuning.legal_configs(L, mt)
    assert [c.block_l for c in legal][0] == L  # default is the first cand.
    assert len(legal) >= 4
    for cfg in legal:
        got = sodda_inner_pallas(w0, Xl, yl, mu, 0.03, loss,
                                 block_l=cfg.block_l)
        np.testing.assert_array_equal(np.asarray(base), np.asarray(got),
                                      err_msg=f"{loss} {cfg}")


def test_sodda_inner_rejects_illegal_block():
    """The kernel validates through tuning — illegal schedules get the
    named refusal, not a wrong-answer launch."""
    B, L, mt = 1, 8, 128
    w0, Xl, yl, mu = _sodda_case(B, L, mt, 70)
    with pytest.raises(tuning.AlignmentError):
        sodda_inner_pallas(w0, Xl, yl, mu, 0.03, "hinge", block_l=3)


# Property sweep: hypothesis when available, an example-based sweep of the
# same draw space otherwise (this container has no hypothesis wheel).
try:
    import hypothesis
    import hypothesis.strategies as st
    HAS_HYPOTHESIS = True
except ImportError:
    HAS_HYPOTHESIS = False

_PROP_CASES = [  # (L, block_l, mt, loss) — mirrors the strategy's domain
    (4, 2, 64, "hinge"), (6, 3, 100, "logistic"), (8, 4, 128, "squared"),
    (12, 6, 200, "hinge"), (12, 4, 130, "logistic"), (6, 1, 64, "squared"),
]


def _check_blocked_matches_default(L, block_l, mt, loss, seed):
    B = 2
    w0, Xl, yl, mu = _sodda_case(B, L, tuning.padded_mt(mt), seed)
    base = sodda_inner_pallas(w0, Xl, yl, mu, 0.05, loss)
    got = sodda_inner_pallas(w0, Xl, yl, mu, 0.05, loss, block_l=block_l)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(got))


if HAS_HYPOTHESIS:
    @hypothesis.given(data=st.data(), seed=st.integers(0, 2 ** 16),
                      loss=st.sampled_from(sorted(LOSSES)))
    @hypothesis.settings(max_examples=12, deadline=None)
    def test_sodda_inner_blocked_property(data, seed, loss):
        L = data.draw(st.sampled_from([4, 6, 8, 12]))
        block_l = data.draw(st.sampled_from(
            [b for b in range(1, L + 1) if L % b == 0]))
        mt = data.draw(st.integers(1, 256))
        _check_blocked_matches_default(L, block_l, mt, loss, seed % 97)
else:
    @pytest.mark.parametrize("L,block_l,mt,loss", _PROP_CASES)
    def test_sodda_inner_blocked_property_fallback(L, block_l, mt, loss):
        _check_blocked_matches_default(L, block_l, mt, loss, 80)


def test_interpret_flag_threaded_not_pinned(monkeypatch):
    """The seed pinned interpret=True inside ops — which would silently run
    the emulator on TPU forever. Regression: the flag must be THREADED from
    the caller (or repro.platform's default), never hard-coded."""
    captured = []
    real = ops.sodda_inner_pallas

    def spy(*args, **kw):
        captured.append(kw.get("interpret"))
        return real(*args, **kw)

    monkeypatch.setattr(ops, "sodda_inner_pallas", spy)
    # unique mt per call: jit only re-traces (and so only re-hits the spy)
    # on a fresh (shape, statics) cache key
    w0, Xl, yl, mu = _sodda_case(1, 4, 137, 90)
    ops.sodda_inner(w0, Xl, yl, mu, 0.03, "hinge", interpret=True)
    w0, Xl, yl, mu = _sodda_case(1, 4, 139, 91)
    ops.sodda_inner(w0, Xl, yl, mu, 0.03, "hinge")
    assert captured == [True, None]  # explicit passes through; None defers


def test_interpret_default_derives_from_platform(monkeypatch):
    """interpret=None resolves via repro.platform.interpret_default — the
    one switch that knows whether a compiled path exists."""
    from repro.kernels import sodda_inner as si
    calls = []
    monkeypatch.setattr(si.repro_platform, "interpret_default",
                        lambda: calls.append(1) or True)
    w0, Xl, yl, mu = _sodda_case(1, 4, 128, 95)
    si.sodda_inner_pallas(w0, Xl, yl, mu, 0.03, "hinge")  # None -> derived
    assert calls == [1]
    si.sodda_inner_pallas(w0, Xl, yl, mu, 0.03, "hinge", interpret=True)
    assert calls == [1]  # explicit flag: platform not consulted


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,H,KV,S,D", [(1, 4, 4, 128, 64), (2, 4, 2, 256, 64),
                                        (1, 8, 2, 128, 128)])
@pytest.mark.parametrize("opts", [dict(causal=True),
                                  dict(causal=True, window=64),
                                  dict(causal=True, softcap=30.0),
                                  dict(causal=False)])
def test_flash_attention_shapes(B, H, KV, S, D, opts):
    q = jax.random.normal(k(10), (B, H, S, D), jnp.float32) * 0.5
    kk = jax.random.normal(k(11), (B, KV, S, D), jnp.float32) * 0.5
    v = jax.random.normal(k(12), (B, KV, S, D), jnp.float32)
    out = flash_attention_pallas(q, kk, v, bq=64, bk=64, **opts)
    want = ref.attention_naive(q.transpose(0, 2, 1, 3), kk.transpose(0, 2, 1, 3),
                               v.transpose(0, 2, 1, 3), **opts).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)


def test_flash_attention_bf16():
    B, H, KV, S, D = 1, 2, 2, 128, 64
    q = (jax.random.normal(k(13), (B, H, S, D)) * 0.5).astype(jnp.bfloat16)
    kk = (jax.random.normal(k(14), (B, KV, S, D)) * 0.5).astype(jnp.bfloat16)
    v = jax.random.normal(k(15), (B, KV, S, D)).astype(jnp.bfloat16)
    out = flash_attention_pallas(q, kk, v, bq=64, bk=64, causal=True)
    want = ref.attention_naive(
        q.transpose(0, 2, 1, 3), kk.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=True).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(out.astype(jnp.float32), want.astype(jnp.float32),
                               rtol=0.05, atol=0.05)


def test_attention_ref_matches_naive():
    """the chunked online-softmax reference itself vs textbook attention."""
    B, S, H, KV, D = 2, 200, 4, 2, 32  # non-chunk-aligned S
    q = jax.random.normal(k(16), (B, S, H, D)) * 0.3
    kk = jax.random.normal(k(17), (B, S, KV, D)) * 0.3
    v = jax.random.normal(k(18), (B, S, KV, D))
    got = ref.attention_ref(q, kk, v, causal=True, chunk=64)
    want = ref.attention_naive(q, kk, v, causal=True)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_attention_decode_offset():
    """q_offset reproduces the decode position semantics."""
    B, S, H, D = 1, 96, 2, 32
    q = jax.random.normal(k(19), (B, S, H, D)) * 0.3
    kk = jax.random.normal(k(20), (B, S, H, D)) * 0.3
    v = jax.random.normal(k(21), (B, S, H, D))
    full = ref.attention_naive(q, kk, v, causal=True)
    last = ref.attention_naive(q[:, -1:], kk, v, causal=True, q_offset=S - 1)
    np.testing.assert_allclose(last[:, 0], full[:, -1], rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# ssd scan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,S,H,P,G,N,chunk", [
    (1, 64, 2, 16, 1, 16, 16), (2, 128, 4, 16, 2, 32, 32),
    (1, 96, 2, 32, 1, 64, 32),
])
def test_ssd_scan_shapes(B, S, H, P, G, N, chunk):
    x = jax.random.normal(k(30), (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(k(31), (B, S, H)))
    A = -jnp.exp(jax.random.normal(k(32), (H,)) * 0.3)
    Bm = jax.random.normal(k(33), (B, S, G, N)) * 0.3
    Cm = jax.random.normal(k(34), (B, S, G, N)) * 0.3
    want = ref.ssd_ref(x, dt, A, Bm, Cm)
    got = ssd_scan_pallas(x.transpose(0, 2, 1, 3), dt.transpose(0, 2, 1), A,
                          Bm.transpose(0, 2, 1, 3), Cm.transpose(0, 2, 1, 3),
                          chunk=chunk).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_ssd_chunked_jnp_matches_ref():
    from repro.models.ssm import ssd_chunked
    B, S, H, P, G, N = 2, 128, 4, 16, 1, 32
    x = jax.random.normal(k(35), (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(k(36), (B, S, H)))
    A = -jnp.exp(jax.random.normal(k(37), (H,)) * 0.3)
    Bm = jax.random.normal(k(38), (B, S, G, N)) * 0.3
    Cm = jax.random.normal(k(39), (B, S, G, N)) * 0.3
    D = jnp.ones((H,))
    want = ref.ssd_ref(x, dt, A, Bm, Cm, D)
    got = ssd_chunked(x, dt, A, Bm, Cm, D, chunk=32)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_ssd_ops_unaligned_seq():
    B, S, H, P, G, N = 1, 100, 2, 16, 1, 16  # S not chunk-aligned -> pad path
    x = jax.random.normal(k(40), (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(k(41), (B, S, H)))
    A = -jnp.exp(jax.random.normal(k(42), (H,)) * 0.3)
    Bm = jax.random.normal(k(43), (B, S, G, N)) * 0.3
    Cm = jax.random.normal(k(44), (B, S, G, N)) * 0.3
    got = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=32, force="pallas")
    want = ref.ssd_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
