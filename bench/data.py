"""The benchmark's own inputs: the paper's synthetic SVM data, from the seed.

x ~ U[-1, 1] scaled by sqrt(3) to unit variance, a planted z ~ U[-1, 1]^M,
y = sign(x . z) on the unscaled x, and 1% of labels flipped (Fang & Klabjan
2018, section 5.1; the tile scheme of ``repro.data.synthetic``, copied here so
that no change to the program can change the data it is measured on).

Each device makes its own (rows, cols) tile, row slab by row slab into a
buffer updated in place, and keeps the slab's partial label products as it
goes; the partials of one row of tiles are summed over 'model'. No tile is
drawn twice and no device holds more than its tile plus one slab. Random
bits come from an ``rbg`` key, which the TPU draws far faster than
threefry.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

SCALE = np.float32(np.sqrt(3.0))  # U[-1, 1] has variance 1/3
FLIP_PROB = 0.01
SLAB_BYTES = 128 << 20  # upper bound on one slab of random rows


def seed_keys(seed: int):
    """(run key, data key) for ``--seed``: all 64 bits of the seed count.

    The run key is the program's kind of key (threefry, ``uint32[2]``); the
    data key is an ``rbg`` key.
    """
    seed %= 1 << 64
    base = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)
    run_key = jax.random.fold_in(base, 1)
    words = jnp.concatenate([jax.random.key_data(jax.random.fold_in(base, 2)),
                             jax.random.key_data(jax.random.fold_in(base, 3))])
    return run_key, jax.random.wrap_key_data(words, impl="rbg")


def slab_rows(rows: int, cols: int) -> int:
    """The largest divisor of `rows` whose slab fits `SLAB_BYTES`."""
    cap = max(1, SLAB_BYTES // (4 * cols))
    return max(d for d in range(1, min(rows, cap) + 1) if rows % d == 0)


def _fill(X, zdot, data_key, pd, qd, s):
    """Slab s of tile (pd, qd): X's rows, scaled, and their products with z.

    One slab per call into donated buffers, which XLA updates in place; a
    loop over the slabs inside one program would copy the whole tile.
    """
    rows, cols = X.shape
    slab = slab_rows(rows, cols)
    kx, kz = jax.random.fold_in(data_key, 0), jax.random.fold_in(data_key, 1)
    kt = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(kx, pd),
                                               qd), s)
    z = jax.random.uniform(jax.random.fold_in(kz, qd), (cols,), jnp.float32,
                           -1.0, 1.0)
    u = jax.random.uniform(kt, (slab, cols), jnp.float32, -1.0, 1.0)
    X = jax.lax.dynamic_update_slice(X, u * SCALE, (s * slab, 0))
    part = jnp.dot(u, z, precision=jax.lax.Precision.HIGHEST)
    return X, jax.lax.dynamic_update_slice(zdot, part, (s * slab,))


def _labels(data_key, pd, zdot):
    y = jnp.where(zdot >= 0, 1.0, -1.0).astype(jnp.float32)
    kf = jax.random.fold_in(data_key, 2)
    flips = jax.random.bernoulli(jax.random.fold_in(kf, pd), FLIP_PROB,
                                 zdot.shape)
    return jnp.where(flips, -y, y)


def generate(data_key, N: int, M: int, mesh=None, device=None):
    """(X (N, M) f32, y (N,) f32) on `device`, or tiled over `mesh`.

    On a mesh, X is laid out ``P('data', 'model')`` and y ``P('data')``, the
    layout of ``repro.core.distributed.data_shardings``.
    """
    from jax.sharding import NamedSharding, SingleDeviceSharding
    from jax.sharding import PartitionSpec as P

    if mesh is None:
        Pd = Qd = 1
        x_sh = z_sh = key_sh = SingleDeviceSharding(device)

        def fill(X, zdot, key, s):
            return _fill(X, zdot, key, 0, 0, s)

        def labels(key, zdot):
            return _labels(key, 0, zdot)
    else:
        Pd, Qd = mesh.shape["data"], mesh.shape["model"]
        x_sh = NamedSharding(mesh, P("data", "model"))
        z_sh = NamedSharding(mesh, P(("data", "model")))
        key_sh = NamedSharding(mesh, P())

        def fill_local(X, zdot, key, s):
            return _fill(X, zdot, key, jax.lax.axis_index("data"),
                         jax.lax.axis_index("model"), s)

        def labels_local(key, zdot):
            return _labels(key, jax.lax.axis_index("data"),
                           jax.lax.psum(zdot, "model"))

        fill = jax.shard_map(
            fill_local, mesh=mesh,
            in_specs=(P("data", "model"), P(("data", "model")), P(), P()),
            out_specs=(P("data", "model"), P(("data", "model"))),
            check_vma=False)
        labels = jax.shard_map(labels_local, mesh=mesh,
                               in_specs=(P(), P(("data", "model"))),
                               out_specs=P("data"), check_vma=False)
    rows, cols = N // Pd, M // Qd
    X = jax.jit(lambda: jnp.zeros((N, M), jnp.float32), out_shardings=x_sh)()
    zdot = jax.jit(lambda: jnp.zeros((N * Qd,), jnp.float32),
                   out_shardings=z_sh)()
    key = jax.device_put(data_key, key_sh)
    fill = jax.jit(fill, donate_argnums=(0, 1))
    for s in range(rows // slab_rows(rows, cols)):
        X, zdot = fill(X, zdot, key, s)
    return X, jax.jit(labels)(key, zdot)
