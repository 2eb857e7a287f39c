"""Pallas TPU kernel for SODDA's inner loop (paper Algorithm 1, steps 13-17).

The inner loop is a length-L sequential chain of rank-1 SVRG-corrected
updates on an m_tilde-sized parameter sub-block. It is latency-critical
(sequential dependence, two m_tilde-dot-products + one axpy per step) and the
natural TPU mapping is: pin wbar, w0, mu (3 * mt floats) in VMEM for the whole
chain, pre-compute the snapshot margins z0 = Xl @ w0 once per tile (the
reference recomputes x.w0 every step — the kernel hoists it, which is exact
because w0 is loop-invariant), then stream the L rows from VMEM.

Grid: ``(B, L // block_l)`` — one program chain per (p, q) block (all P*Q
blocks are independent), tiled over the L dimension by a tunable
``BlockConfig.block_l`` (see `repro.kernels.tuning`). The output block's
index map ignores the tile axis, so the running ``wbar`` stays resident in
VMEM across a block's whole tile chain (TPU grids run sequentially,
innermost axis fastest; the block is written back to HBM once per b) while
Pallas double-buffers the streamed ``(block_l, mt)`` X tiles underneath the
compute. The hoisted snapshot margins tile exactly: each row's margin is an
independent reduction, so computing z0 per tile is bitwise-identical to one
full-L pass, and the sequential chain itself is untouched — every legal
``block_l`` produces bitwise-identical results (the conformance anchor in
tests/test_kernels.py).

Mosaic layout: a block's last two dimensions must be multiples of (8, 128)
or span the whole array. The wrapper therefore hands the kernel 4-D
``(B, L // block_l, block_l, mt)`` rows and ``(B, L // block_l, block_l,
1)`` label columns, and ``(B, 1, mt)`` vectors, so every block's last two
dimensions are whole and every divisor of L is a legal ``block_l``. The
chain reads its row, label and ``d0`` through ``pl.ds`` windows on refs
(Mosaic cannot lower a dynamic index into a value), keeps every per-step
scalar as a ``(1, 1)`` vector, and reads gamma from SMEM.

VMEM budget per program (f32, padded to (8, 128) tiles) is accounted by
`tuning.vmem_bytes`; legality (budget + lane alignment + divisibility) is
checked by `tuning.validate_config`. `block_l=None` means one tile
(`block_l = L`), the seed kernel's shape.

Alignment: mt must be a multiple of 128 (lane width) — `ops.sodda_inner`
zero-pads; zero columns are exact no-ops for every supported loss because
g = (l'(z1,y) - l'(z0,y)) * x + mu vanishes coordinate-wise where x = mu = 0.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import losses
from repro import platform as repro_platform


def _kernel(gamma_ref, w0_ref, x_ref, y_ref, mu_ref, out_ref, d0_ref, *,
            block_l: int, loss: str):
    deriv = functools.partial(losses.loss_deriv, loss)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():  # first tile of this block's chain: seed wbar with w0
        out_ref[0] = w0_ref[0]

    w0 = w0_ref[0]  # (1, mt) — loop-invariant snapshot
    mu = mu_ref[0]  # (1, mt)
    gamma = gamma_ref[0]
    # hoisted snapshot margins, one row reduction each; per-tile hoisting is
    # bitwise-equal to the full-L pass because each row's sum is independent
    z0 = jnp.sum(x_ref[0, 0] * w0, axis=1, keepdims=True)  # (block_l, 1)
    d0_ref[...] = deriv(z0, y_ref[0, 0])  # loop-invariant within the tile

    def step(i, wbar):
        x = x_ref[0, 0, pl.ds(i, 1), :]  # (1, mt)
        yi = y_ref[0, 0, pl.ds(i, 1), :]  # (1, 1)
        z1 = jnp.sum(x * wbar, axis=1, keepdims=True)  # (1, 1)
        g = (deriv(z1, yi) - d0_ref[pl.ds(i, 1), :]) * x + mu
        return wbar - gamma * g

    out_ref[0] = jax.lax.fori_loop(0, block_l, step, out_ref[0])


def sodda_inner_pallas(w0, Xl, yl, mu, gamma, loss: str = "hinge",
                       interpret: Optional[bool] = None,
                       block_l: Optional[int] = None):
    """w0 (B, mt), Xl (B, L, mt), yl (B, L), mu (B, mt), gamma scalar -> (B, mt).

    `interpret=None` derives from `repro.platform.interpret_default()`
    (compiled on TPU, interpreted elsewhere) — never pinned. `block_l=None`
    means the single-tile default; anything else must be a legal
    `BlockConfig.block_l` for (L, mt) per `tuning.validate_config`.
    """
    from repro.kernels import tuning  # deferred: tuning imports no kernels

    B, L, mt = Xl.shape
    if interpret is None:
        interpret = repro_platform.interpret_default()
    if block_l is None:
        block_l = L
    tuning.validate_config(tuning.BlockConfig(block_l=block_l), L, mt)
    n_tiles = L // block_l
    dtype = w0.dtype
    vec = pl.BlockSpec((1, 1, mt), lambda b, j: (b, 0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, block_l=block_l, loss=loss),
        grid=(B, n_tiles),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            vec,
            pl.BlockSpec((1, 1, block_l, mt), lambda b, j: (b, j, 0, 0)),
            pl.BlockSpec((1, 1, block_l, 1), lambda b, j: (b, j, 0, 0)),
            vec,
        ],
        out_specs=vec,
        out_shape=jax.ShapeDtypeStruct((B, 1, mt), dtype),
        scratch_shapes=[pltpu.VMEM((block_l, 1), dtype)],
        interpret=interpret,
        name="sodda_inner",
    )(jnp.reshape(jnp.asarray(gamma, dtype), (1,)),
      w0.reshape(B, 1, mt),
      Xl.reshape(B, n_tiles, block_l, mt),
      yl.reshape(B, n_tiles, block_l, 1),
      mu.reshape(B, 1, mt))
    return out.reshape(B, mt)
