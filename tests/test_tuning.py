"""The kernel autotuner (repro.kernels.tuning): legality refusals by named
error, deterministic selection, the on-disk cache round-trip, measured
refinement, and the driver-level guarantee that a tuned schedule changes
nothing but time — trajectories stay bitwise."""
import json
import os

import jax
import numpy as np
import pytest

from repro.core import driver
from repro.kernels import tuning
from repro.kernels.tuning import (AlignmentError, BlockConfig,
                                  KernelTuningError, VmemBudgetError)
from repro.testing import CONFORMANCE_ITERS, make_problem, small_fixture_config


@pytest.fixture(autouse=True)
def _fresh_cache():
    tuning.clear_cache()
    yield
    tuning.clear_cache()


# ---------------------------------------------------------------------------
# Legality: named errors, never silent clamps.
# ---------------------------------------------------------------------------
def test_non_dividing_block_raises_alignment_error():
    with pytest.raises(AlignmentError, match="does not divide"):
        tuning.validate_config(BlockConfig(block_l=5), L=16, mt=128)


def test_unaligned_mt_raises_alignment_error():
    with pytest.raises(AlignmentError, match="lane"):
        tuning.validate_config(BlockConfig(block_l=4), L=16, mt=100)


def test_non_positive_block_raises_alignment_error():
    with pytest.raises(AlignmentError):
        tuning.validate_config(BlockConfig(block_l=0), L=16, mt=128)


def test_oversized_block_raises_vmem_budget_error():
    cfg = BlockConfig(block_l=64)
    need = tuning.vmem_bytes(cfg, 64, 256)
    with pytest.raises(VmemBudgetError, match="VMEM"):
        tuning.validate_config(cfg, 64, 256, vmem_limit=need - 1)
    # both named errors are KernelTuningError (and ValueError for callers
    # that do not import the taxonomy)
    assert issubclass(VmemBudgetError, KernelTuningError)
    assert issubclass(AlignmentError, ValueError)


def test_vmem_bytes_accounts_double_buffering():
    cfg = BlockConfig(block_l=8)
    got = tuning.vmem_bytes(cfg, 64, 256)
    # X and label streams, w0/mu/out blocks (each a (8, 256) tile), d0
    want = (2 * 8 * 256 * 4) + (2 * 8 * 128 * 4) + (3 * 2 * 8 * 256 * 4) \
        + (8 * 128 * 4)
    assert got == want
    # rows round up to the 8-sublane tile: block_l=3 plans like block_l=8
    assert tuning.vmem_bytes(BlockConfig(block_l=3), 12, 256) == got


def test_padded_mt_rounds_to_lane():
    assert tuning.padded_mt(1) == 128
    assert tuning.padded_mt(128) == 128
    assert tuning.padded_mt(129) == 256


# ---------------------------------------------------------------------------
# Enumeration + model selection.
# ---------------------------------------------------------------------------
def test_legal_configs_descending_divisors():
    got = [c.block_l for c in tuning.legal_configs(12, 128)]
    assert got == [12, 6, 4, 3, 2, 1]


def test_legal_configs_filters_vmem():
    limit = tuning.vmem_bytes(BlockConfig(block_l=6), 12, 128)
    got = [c.block_l for c in tuning.legal_configs(12, 128, vmem_limit=limit)]
    assert got == [6, 4, 3, 2, 1]  # the full-L tile no longer fits


def test_autotune_refuses_impossible_shape():
    # even block_l=1 busts the budget: ~5 * mtp * 4 bytes resident
    huge_mt = 128 * 8000
    with pytest.raises(VmemBudgetError, match="no legal"):
        tuning.autotune("hinge", 2, huge_mt, platform="tpu")


def test_autotune_cpu_prefers_single_tile():
    """The model's honest cpu/interpret conclusion: per-grid-step overhead
    dwarfs any overlap win, so the default single tile is selected — which
    is what makes the bench cell's tuned/default ratio exactly 1.0 there."""
    cfg = tuning.autotune("hinge", 64, 512, platform="cpu")
    assert cfg == tuning.default_config(64, 512)


def test_autotune_deterministic_in_process():
    a = tuning.autotune("hinge", 64, 512, platform="tpu")
    b = tuning.autotune("hinge", 64, 512, platform="tpu")
    tuning.clear_cache()  # force a re-derivation, not a cache hit
    c = tuning.autotune("hinge", 64, 512, platform="tpu")
    assert a == b == c
    assert isinstance(a, BlockConfig)


# ---------------------------------------------------------------------------
# The on-disk cache: round-trips through the serialized form.
# ---------------------------------------------------------------------------
def test_disk_cache_round_trip(tmp_path):
    cache_dir = str(tmp_path)
    first = tuning.autotune("hinge", 64, 512, platform="tpu",
                            cache_dir=cache_dir)
    path = os.path.join(cache_dir, "sodda_tuning_cache.json")
    assert os.path.exists(path)
    with open(path) as fh:
        payload = json.load(fh)
    key = "loss=hinge|L=64|mt=512|platform=tpu"
    assert payload[key] == first.as_dict()
    assert BlockConfig.from_dict(payload[key]) == first
    # a fresh in-memory cache (a new process, in effect) must reload the
    # identical config from disk
    tuning.clear_cache()
    assert tuning.autotune("hinge", 64, 512, platform="tpu",
                           cache_dir=cache_dir) == first


def test_disk_cache_is_authoritative(tmp_path):
    """The stored choice wins over re-derivation — proving the selection
    actually flows through the on-disk form, not past it."""
    cache_dir = str(tmp_path)
    tuning.autotune("hinge", 64, 512, platform="tpu", cache_dir=cache_dir)
    path = os.path.join(cache_dir, "sodda_tuning_cache.json")
    key = "loss=hinge|L=64|mt=512|platform=tpu"
    with open(path) as fh:
        payload = json.load(fh)
    payload[key] = {"block_l": 16}  # a legal, non-default pin
    with open(path, "w") as fh:
        json.dump(payload, fh)
    tuning.clear_cache()
    got = tuning.autotune("hinge", 64, 512, platform="tpu",
                          cache_dir=cache_dir)
    assert got == BlockConfig(block_l=16)


def test_cache_key_distinguishes_shape_and_platform(tmp_path):
    cache_dir = str(tmp_path)
    tuning.autotune("hinge", 64, 512, platform="tpu", cache_dir=cache_dir)
    tuning.autotune("logistic", 32, 128, platform="cpu", cache_dir=cache_dir)
    with open(os.path.join(cache_dir, "sodda_tuning_cache.json")) as fh:
        payload = json.load(fh)
    assert set(payload) == {"loss=hinge|L=64|mt=512|platform=tpu",
                            "loss=logistic|L=32|mt=128|platform=cpu"}


# ---------------------------------------------------------------------------
# Measured refinement.
# ---------------------------------------------------------------------------
def test_measure_rerank_overrides_model():
    """When real timings disagree with the model, the timings win."""
    calls = []

    def measure(c):
        calls.append(c.block_l)
        return float(c.block_l)  # smaller blocks "measure" faster

    got = tuning.autotune("hinge", 64, 512, platform="tpu", measure=measure)
    assert got.block_l == min(calls)
    # the single-tile default is always in the measured pool — the
    # no-regression anchor (model top-k alone could exclude it)
    assert 64 in calls


def test_measure_not_called_on_cache_hit():
    calls = []
    tuning.autotune("hinge", 64, 512, platform="tpu",
                    measure=lambda c: (calls.append(c), 1.0)[1])
    n = len(calls)
    assert n > 0
    tuning.autotune("hinge", 64, 512, platform="tpu",
                    measure=lambda c: (calls.append(c), 1.0)[1])
    assert len(calls) == n


# ---------------------------------------------------------------------------
# Driver-level guarantee: tuning changes the schedule, never the numbers.
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def fixture_problem():
    cfg = small_fixture_config()
    return cfg, make_problem(cfg)


def _trajectory(cfg, X, y, **options):
    key = jax.random.PRNGKey(0)
    state, hist = driver.run(key, (X, y), cfg, CONFORMANCE_ITERS, "pallas",
                             **options)
    return np.asarray(state.w), hist


def test_tuned_pallas_trajectory_bitwise_vs_untuned(fixture_problem):
    """Autotuned block_l through the real driver: BITWISE against the
    default schedule — the exactness claim of docs/kernels.md, held at the
    level users consume it."""
    cfg, (X, y) = fixture_problem
    tuned = tuning.autotune(cfg.loss, cfg.L, cfg.m_tilde,
                            platform=jax.default_backend())
    w_def, h_def = _trajectory(cfg, X, y)
    w_tuned, h_tuned = _trajectory(cfg, X, y, block_l=tuned.block_l)
    np.testing.assert_array_equal(w_def, w_tuned)
    assert h_def == h_tuned


def test_every_legal_block_trajectory_bitwise(fixture_problem):
    """Not just the tuner's pick: EVERY legal block_l is trajectory-bitwise
    vs the default — the anchor that makes autotuning safe to apply blind."""
    cfg, (X, y) = fixture_problem
    w_def, h_def = _trajectory(cfg, X, y)
    legal = tuning.legal_configs(cfg.L, cfg.m_tilde)
    assert len(legal) >= 2  # the fixture L must actually tile
    for c in legal:
        w_c, h_c = _trajectory(cfg, X, y, block_l=c.block_l)
        np.testing.assert_array_equal(w_def, w_c, err_msg=str(c))
        assert h_def == h_c, c


def test_non_kernel_backend_rejects_block_l(fixture_problem):
    """block_l on a backend that never runs the kernel is a silent no-op
    waiting to happen — the engine refuses it like any other inapplicable
    option."""
    cfg, (X, y) = fixture_problem
    with pytest.raises(ValueError, match="block_l"):
        driver.run(jax.random.PRNGKey(0), (X, y), cfg, 2, "reference",
                   block_l=2)


def test_tuning_cli_reports_selection(capsys):
    assert tuning._main(["--loss", "hinge", "--L", "64", "--mt", "512",
                         "--platform", "cpu"]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["selected"] == {"block_l": 64}
    assert report["platform"] == "cpu"
    assert [c["block_l"] for c in report["candidates"]] == \
        [c.block_l for c in tuning.legal_configs(64, 512)]
    assert report["predicted_us"] > 0
