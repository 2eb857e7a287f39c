"""Compiles of the SODDA main path for a described TPU v5e; no chip runs.

The TPU compiler is installed with jax and compiles for a topology it is
only told about, so this file refuses, at no chip time, what the chip's
compiler would refuse: the inner kernel for every loss at Table 1's
one-chip block shape (B=15 chains, L=64, mt=1,200), every block_l the
autotuner may offer, the one-chip ``pallas`` run program at
``chip_smoke.py``'s size, and the 2x2 ``shard_map+pallas`` program at
Table 1 SMALL (250,000 x 18,000), each fitting in about twice its X and
with no consume-half array beyond its gathered rows. The topology is
described inside a module fixture, so only the worker that runs this file
loads libtpu.
"""
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import platform as repro_platform
from repro.core import driver
from repro.core.sodda import CONSUME_SCOPE, SoddaState
from repro.kernels import ops, tuning

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import chip_smoke  # noqa: E402

HBM_BYTES = 15.75e9  # what XLA lets one v5e program use
# the consume half's largest array: its gathered rows are 15 x 64 x 1,200
# floats (4.6 MB) on one chip; a re-layout or a copy of a sub-block is GBs
CONSUME_ARRAY_BYTES = 64e6
B, L, MT = 15, 64, 1200  # P*Q chains, inner length, m_tilde at 5x3 x 18k


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # libtpu logs nowhere
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no libtpu, or it is held elsewhere
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A TPU compile written to the persistent cache cannot be read back
    without a chip: keep the cache off for this file."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch):
    """This host's backend is the CPU, so the run path would pick interpret
    mode; the described chip compiles the kernel with Mosaic."""
    monkeypatch.setattr(repro_platform, "interpret_default",
                        lambda plat=None: False)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_kernel(topo, loss, block_l=None):
    from jax.sharding import SingleDeviceSharding
    one = SingleDeviceSharding(topo.devices[0])
    f32 = jnp.float32
    return ops.sodda_inner.lower(
        _sds((B, MT), f32, one), _sds((B, L, MT), f32, one),
        _sds((B, L), f32, one), _sds((B, MT), f32, one), _sds((), f32, one),
        loss=loss, block_l=block_l, interpret=False).compile()


@pytest.mark.parametrize("loss", ["hinge", "logistic", "squared"])
def test_inner_kernel_compiles_for_v5e(topo, loss):
    compiled = _compile_kernel(topo, loss)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "block_l", [c.block_l for c in tuning.legal_configs(L, MT)])
def test_every_legal_block_compiles_for_v5e(topo, block_l):
    """Whatever the autotuner may offer, Mosaic accepts."""
    compiled = _compile_kernel(topo, "hinge", block_l=block_l)
    assert "tpu_custom_call" in compiled.as_text()


def _run_program(cfg, backend, mesh=None, one=None):
    """Compile `driver.run`'s program for `cfg` on described devices."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    f32 = jnp.float32
    if mesh is None:
        w_sh = x_sh = y_sh = rep = one
    else:
        rep = NamedSharding(mesh, P())
        w_sh = NamedSharding(mesh, P("model"))
        x_sh = NamedSharding(mesh, P("data", "model"))
        y_sh = NamedSharding(mesh, P("data"))
    state = SoddaState(w=_sds((cfg.M,), f32, w_sh),
                       t=_sds((), jnp.int32, rep),
                       key=_sds((2,), jnp.uint32, rep))
    run = driver.make_run(cfg, chip_smoke.ITERS, backend,
                          record_every=chip_smoke.RECORD_EVERY, mesh=mesh)
    compiled = run.lower(state, _sds((cfg.N, cfg.M), f32, x_sh),
                         _sds((cfg.N,), f32, y_sh)).compile()
    kernels = [line for line in compiled.as_text().splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert kernels
    for line in kernels:  # the Mosaic kernel is timed as the consume half
        op_name = re.search(r'op_name="([^"]*)"', line).group(1)
        assert re.findall(r"sodda\.\w+", op_name)[-1] == CONSUME_SCOPE
    for name, nbytes in _consume_arrays(compiled.as_text()):
        assert nbytes <= CONSUME_ARRAY_BYTES, (name, nbytes)
    mem = compiled.memory_analysis()
    return mem.argument_size_in_bytes + mem.temp_size_in_bytes


_ITEMSIZE = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "pred": 1, "s8": 1,
             "u8": 1, "f64": 8, "s64": 8, "u64": 8}


def _consume_arrays(hlo):
    """(name, bytes) of each array an instruction scoped innermost in the
    consume half produces; parameters, tuples and bitcasts hold no new
    bytes."""
    out = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.-]+) = (\w+)\[([\d,]*)\]\S* "
                     r"([\w-]+)\(", line)
        op_name = re.search(r'op_name="([^"]*)"', line)
        if not m or not op_name or m.group(4) in ("parameter", "bitcast"):
            continue
        scopes = re.findall(r"sodda\.\w+", op_name.group(1))
        if not scopes or scopes[-1] != CONSUME_SCOPE:
            continue
        dims = [int(d) for d in m.group(3).split(",") if d]
        out.append((m.group(1),
                    _ITEMSIZE[m.group(2)] * int(np.prod(dims, dtype=np.int64))))
    assert out  # the consume half is in the program, and parsed
    return out


def test_one_chip_pallas_run_fits_v5e(topo, compiled_kernels):
    """chip_smoke.py's one-chip program: X (3.6 GB) plus temporaries fit."""
    from jax.sharding import SingleDeviceSharding
    cfg = chip_smoke.ONE_CHIP
    need = _run_program(cfg, "pallas",
                        one=SingleDeviceSharding(topo.devices[0]))
    x_bytes = cfg.N * cfg.M * 4
    assert x_bytes < need < min(2.1 * x_bytes, HBM_BYTES), need / x_bytes


def test_four_chip_shard_map_pallas_run_fits_v5e(topo, compiled_kernels):
    """Table 1 SMALL on a 2x2 mesh: each chip's X shard (4.5 GB) plus
    temporaries fit."""
    from jax.sharding import AxisType, Mesh
    cfg = chip_smoke.FOUR_CHIP
    mesh = Mesh(np.array(topo.devices).reshape(cfg.P, cfg.Q),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    need = _run_program(cfg, "shard_map+pallas", mesh=mesh)
    shard = cfg.n * cfg.m * 4
    assert shard < need < min(2.1 * shard, HBM_BYTES), need / shard
