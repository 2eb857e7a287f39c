"""Jit'd public wrappers for the Pallas kernels.

Padding/alignment and backend dispatch live here. The SODDA inner kernel
always runs: compiled by Mosaic on TPU, in interpret mode elsewhere
(`repro.platform.interpret_default`), never swapped for its jnp oracle. The
language-model kernels compile on TPU and otherwise fall back to the
pure-jnp references in ``ref.py`` unless ``force="pallas"``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.sodda_inner import sodda_inner_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_axis(x, axis: int, mult: int):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("loss", "block_l", "interpret"))
def sodda_inner(w0, Xl, yl, mu, gamma, loss: str = "hinge", block_l=None,
                interpret=None):
    """Batched SODDA inner loop. w0 (B,mt), Xl (B,L,mt), yl (B,L), mu (B,mt).

    `block_l` is the L-tiling schedule (`tuning.BlockConfig.block_l`;
    None = single tile). `interpret=None` derives from `repro.platform`
    inside `sodda_inner_pallas` — it is threaded, never pinned here.
    """
    mt = w0.shape[-1]
    w0p, pad = _pad_axis(w0, 1, 128)
    Xlp, _ = _pad_axis(Xl, 2, 128)
    mup, _ = _pad_axis(mu, 1, 128)
    out = sodda_inner_pallas(w0p, Xlp, yl, mup, gamma, loss,
                             interpret=interpret, block_l=block_l)
    return out[:, :mt]


# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "q_offset", "force"))
def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, q_offset: int = 0, force: str = "auto"):
    """q (B,Sq,H,D), k/v (B,Sk,KV,D) -> (B,Sq,H,D) (layout as models use it)."""
    use_kernel = force == "pallas" or (force == "auto" and _on_tpu())
    if not use_kernel:
        return ref.attention_ref(q, k, v, causal=causal, window=window,
                                 softcap=softcap, q_offset=q_offset)
    Sq, Sk = q.shape[1], k.shape[1]
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    bq = min(128, Sq) if Sq % 128 else 128
    qt, qpad = _pad_axis(qt, 2, bq)
    kt, _ = _pad_axis(kt, 2, 128)
    vt, _ = _pad_axis(vt, 2, 128)
    out = flash_attention_pallas(qt, kt, vt, causal=causal, window=window,
                                 softcap=softcap, q_offset=q_offset,
                                 bq=bq, bk=128, interpret=not _on_tpu())
    return out[:, :, :Sq].transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("chunk", "force"))
def ssd_scan(x, dt, A, Bm, Cm, D=None, chunk: int = 128, force: str = "auto"):
    """x (B,S,H,P), dt (B,S,H), A (H,), Bm/Cm (B,S,G,N) -> y (B,S,H,P)."""
    use_kernel = force == "pallas" or (force == "auto" and _on_tpu())
    if not use_kernel:
        return ref.ssd_ref(x, dt, A, Bm, Cm, D)
    S = x.shape[1]
    xt = x.transpose(0, 2, 1, 3)  # (B,H,S,P)
    dtt = dt.transpose(0, 2, 1)
    Bt = Bm.transpose(0, 2, 1, 3)  # (B,G,S,N)
    Ct = Cm.transpose(0, 2, 1, 3)
    xt, _ = _pad_axis(xt, 2, chunk)
    dtt, _ = _pad_axis(dtt, 2, chunk)
    Bt, _ = _pad_axis(Bt, 2, chunk)
    Ct, _ = _pad_axis(Ct, 2, chunk)
    y = ssd_scan_pallas(xt, dtt, A, Bt, Ct, chunk=chunk,
                        interpret=not _on_tpu())
    y = y[:, :, :S].transpose(0, 2, 1, 3)
    if D is not None:
        y = y + (D[None, None, :, None] * x.astype(y.dtype)).astype(y.dtype)
    return y
