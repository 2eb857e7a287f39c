"""The reference's reads of X: rows gathered where they lie, bit for bit as
NumPy indexes them, and, compiled for a described TPU v5e, with no copy of
the tile they are read from.

The compiles describe the topology inside a module fixture, so only the
worker that runs this file loads libtpu, and they skip where it cannot be
described.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, REPO)

from bench import reference  # noqa: E402

L = 8  # rows a block at test size


def _numpy_blocks(X, rows, col0, mt):
    return np.stack([X[r][:, c:c + mt] for r, c in zip(rows, col0)])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("nblocks", [1, 2, 3])
@pytest.mark.parametrize("shape", [(96, 48), (16, 200)], ids=["tall", "wide"])
def test_gather_reads_what_numpy_indexes(shape, nblocks, dtype):
    n, m = shape
    mt = m // 4
    rng = np.random.default_rng(nblocks)
    X = np.asarray(jnp.asarray(rng.standard_normal(shape), dtype))
    rows = rng.integers(0, n, (nblocks, L))
    col0 = rng.integers(0, m - mt + 1, nblocks)
    col0[-1] = m - mt  # a block that ends at the tile's last column
    got = reference._gather(jnp.asarray(X), jnp.asarray(rows, jnp.int32),
                            jnp.asarray(col0, jnp.int32), mt)
    assert got.dtype == X.dtype
    np.testing.assert_array_equal(np.asarray(got),
                                  _numpy_blocks(X, rows, col0, mt))


@pytest.mark.parametrize("per_tile", [1, 2, 3])
def test_blocks_read_each_tile_of_a_mesh_in_place(per_tile):
    """Blocks spread over a 2x2 mesh's tiles, `per_tile` to a tile, one of
    them ending at its tile's last column, come back in the asked order."""
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    n, m, mt = 40, 30, 10  # a tile's rows and columns, a block's columns
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
    rng = np.random.default_rng(per_tile)
    X = rng.standard_normal((2 * n, 2 * m)).astype(np.float32)
    y = np.sign(rng.standard_normal(2 * n)).astype(np.float32)
    tiles = reference.Tiles(
        jax.device_put(X, NamedSharding(mesh, P("data", "model"))),
        jax.device_put(y, NamedSharding(mesh, P("data"))))
    rows, col0 = [], []
    for p in range(2):
        for q in range(2):
            for k in range(per_tile):
                rows.append(p * n + rng.integers(0, n, L))
                col0.append(q * m + m - (k + 1) * mt)
    order = rng.permutation(len(col0))
    rows, col0 = np.stack(rows)[order], np.array(col0)[order]
    np.testing.assert_array_equal(np.asarray(tiles.blocks(rows, col0, mt)),
                                  _numpy_blocks(X, rows, col0, mt))


# a read of X may hold no temporary beyond what a gathered block of rows
# needs: a copy of a tile is GBs
TEMP_BYTES = 64e6
# (tile, blocks read from it, m_tilde): one chip's X at Table 1 SMALL's
# widths; a 2x2 chip's tile at Table 1 SMALL and at Table 1 LARGE; a wide
# tile, which the TPU lays out row-major
TILES = [((50_000, 18_000), 15, 1_200), ((125_000, 9_000), 1, 4_500),
         ((150_000, 13_500), 2, 6_750), ((10_000, 47_236), 3, 11_809)]


@pytest.fixture(scope="module")
def one_chip():
    """A described v5e chip, with the persistent compilation cache off: a
    TPU compile written there cannot be read back without a chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # libtpu logs nowhere
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no libtpu, or it is held elsewhere
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("read", ["gather", "matvec", "rmatvec"])
@pytest.mark.parametrize("tile,blocks,mt", TILES,
                         ids=[f"{n}x{m}" for (n, m), _, _ in TILES])
def test_reads_of_a_tile_copy_nothing_on_a_v5e(one_chip, tile, blocks, mt,
                                               read):
    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    n, m = tile
    if read == "gather":
        lowered = reference._gather.lower(
            sds(tile), sds((blocks, 64), jnp.int32), sds((blocks,), jnp.int32),
            mt=mt)
    elif read == "matvec":
        lowered = reference._matvec.lower(sds(tile), sds((m,)),
                                          dtype=jnp.float32)
    else:
        lowered = reference._rmatvec.lower(sds(tile), sds((n,)),
                                           dtype=jnp.float32)
    mem = lowered.compile().memory_analysis()
    assert mem.temp_size_in_bytes <= TEMP_BYTES, mem
