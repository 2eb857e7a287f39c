"""GQA attention: templates, train/prefill forward, and two decode paths.

Head padding: if num_heads is not divisible by the TP width (arctic: 56 q
heads on a 16-wide model axis), q-heads are padded up to ``padded_heads`` and
the output-projection rows of exactly one padded head per GQA group are
zeroed at init. Zero wo rows receive zero gradients under any
multiplicative optimizer state, so this is *exactly* the 56-head
architecture, head-relabeled — see DESIGN.md §8.

Decode paths:
  * 'heads' — KV cache sharded over kv heads on 'model' (kv % 16 == 0).
  * 'seq'   — KV cache sharded over sequence on 'model'; attention runs as a
    shard_map flash-decode: each device reduces its own cache chunk to
    (m, l, o) partials which are combined with a pmax/psum softmax merge.
    This is how a 16-wide TP group serves GQA models whose kv-head count
    does not divide the mesh (chatglm3 kv=2, minitron/gemma2/kimi kv=8) —
    and it bounds per-device cache memory by S/16 regardless of kv count.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, round_up
from repro.kernels import ops as kops
from repro.models.layers import apply_rope
from repro.models.params import ParamSpec


def padded_heads(cfg: ArchConfig) -> int:
    # keep in sync with distributed.sharding_rules.padded_heads
    return round_up(cfg.num_heads, 16)


def head_mask(cfg: ArchConfig):
    """(Hp,) float mask — 0 for padded q heads (one per GQA group tail)."""
    Hp = padded_heads(cfg)
    if Hp == cfg.num_heads:
        return jnp.ones((Hp,), jnp.float32)
    group = Hp // cfg.num_kv_heads
    per_group_real = cfg.num_heads // cfg.num_kv_heads
    pos_in_group = jnp.arange(Hp) % group
    return (pos_in_group < per_group_real).astype(jnp.float32)


def attn_template(cfg: ArchConfig) -> dict:
    hd = cfg.resolved_head_dim
    Hp, KV, d = padded_heads(cfg), cfg.num_kv_heads, cfg.d_model
    return {
        "wq": ParamSpec((d, Hp, hd), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((d, KV, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((d, KV, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((Hp, hd, d), ("heads", "head_dim", "embed")),
    }


def zero_padded_wo(cfg: ArchConfig, attn_params: dict) -> dict:
    mask = head_mask(cfg).astype(attn_params["wo"].dtype)
    return dict(attn_params, wo=attn_params["wo"] * mask[:, None, None])


def qkv(p, h, cfg: ArchConfig, positions):
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
    k = jnp.einsum("bsd,dgk->bsgk", h, p["wk"])
    v = jnp.einsum("bsd,dgk->bsgk", h, p["wv"])
    frac = 0.5 if cfg.name.startswith("chatglm") else 1.0  # chatglm 2d-RoPE
    q = apply_rope(q, positions, cfg.rope_theta, frac)
    k = apply_rope(k, positions, cfg.rope_theta, frac)
    return q, k, v


def attn_forward(p, h, cfg: ArchConfig, positions, *, window: int = 0,
                 force: str = "auto"):
    """Full-sequence (train / prefill) attention. h (B,S,d) -> (B,S,d),
    plus the (k, v) tensors for cache construction."""
    q, k, v = qkv(p, h, cfg, positions)
    out = kops.flash_attention(q, k, v, causal=True, window=window,
                               softcap=cfg.attn_logit_softcap, force=force)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), (k, v)


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------
def decode_attn_heads(p, h, cfg: ArchConfig, cache_k, cache_v, pos, window: int = 0):
    """'heads' decode: h (B,1,d); cache (B,S,KV,hd) kv-head-sharded."""
    q, k_new, v_new = qkv(p, h, cfg, pos[:, None])
    cache_k = _write_cache(cache_k, k_new, pos)
    cache_v = _write_cache(cache_v, v_new, pos)
    group = q.shape[2] // cache_k.shape[2]
    kk = jnp.repeat(cache_k, group, axis=2)
    vv = jnp.repeat(cache_v, group, axis=2)
    s = jnp.einsum("bqhk,bshk->bhqs", q, kk).astype(jnp.float32)
    s = s / jnp.sqrt(q.shape[-1])
    if cfg.attn_logit_softcap:
        s = cfg.attn_logit_softcap * jnp.tanh(s / cfg.attn_logit_softcap)
    kpos = jnp.arange(cache_k.shape[1])
    mask = kpos[None, :] <= pos[:, None]  # (B,S)
    if window > 0:
        mask = mask & (pos[:, None] - kpos[None, :] < window)
    s = jnp.where(mask[:, None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqs,bshk->bqhk", w, vv.astype(jnp.float32)).astype(h.dtype)
    return jnp.einsum("bqhk,hkd->bqd", out, p["wo"]), (cache_k, cache_v)


def decode_attn_seq(p, h, cfg: ArchConfig, cache_k, cache_v, pos, mesh,
                    window: int = 0, axis: str = "model", batch_axes=("data",)):
    """'seq' decode: cache sequence-sharded over `axis`; flash-decode merge."""
    q, k_new, v_new = qkv(p, h, cfg, pos[:, None])
    scale = 1.0 / (q.shape[-1] ** 0.5)
    softcap = cfg.attn_logit_softcap

    def local(q_loc, kc, vc, kn, vn, pos_loc):
        i = jax.lax.axis_index(axis)
        S_loc = kc.shape[1]
        # write the new kv into whichever shard owns position `pos`
        off = pos_loc[0] - i * S_loc
        in_range = (off >= 0) & (off < S_loc)
        off_c = jnp.clip(off, 0, S_loc - 1)
        kn1 = jnp.where(in_range, kn[:, 0], kc[:, off_c].astype(kn.dtype))
        vn1 = jnp.where(in_range, vn[:, 0], vc[:, off_c].astype(vn.dtype))
        kc = jax.lax.dynamic_update_slice_in_dim(kc, kn1[:, None].astype(kc.dtype), off_c, 1)
        vc = jax.lax.dynamic_update_slice_in_dim(vc, vn1[:, None].astype(vc.dtype), off_c, 1)
        group = q_loc.shape[2] // kc.shape[2]
        kk = jnp.repeat(kc, group, axis=2)
        vv = jnp.repeat(vc, group, axis=2)
        s = jnp.einsum("bqhk,bshk->bhqs", q_loc, kk).astype(jnp.float32) * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        kpos = i * S_loc + jnp.arange(S_loc)
        mask = kpos[None, :] <= pos_loc[:, None]
        if window > 0:
            mask = mask & (pos_loc[:, None] - kpos[None, :] < window)
        s = jnp.where(mask[:, None, None], s, -1e30)
        m = jnp.max(s, axis=-1)  # (B,H,1)
        p_ = jnp.exp(s - m[..., None])
        l = jnp.sum(p_, axis=-1)
        o = jnp.einsum("bhqs,bshk->bhqk", p_, vv.astype(jnp.float32))
        m_g = jax.lax.pmax(m, axis)
        corr = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * corr, axis)
        o_g = jax.lax.psum(o * corr[..., None], axis)
        out = (o_g / jnp.maximum(l_g, 1e-37)[..., None])  # (B,H,1,hd)
        return out.transpose(0, 2, 1, 3), kc, vc

    b = batch_axes if len(batch_axes) > 1 else batch_axes[0]
    # batch may be unshardable (long_500k B=1): then replicate over batch axes
    n_b = 1
    for a in batch_axes:
        n_b *= mesh.shape[a]
    if q.shape[0] % n_b:
        b = None
    out, cache_k, cache_v = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(b), P(b, axis), P(b, axis), P(b), P(b), P(b)),
        out_specs=(P(b), P(b, axis), P(b, axis)),
    )(q, cache_k, cache_v, k_new, v_new, pos)
    out = out.astype(h.dtype)
    return jnp.einsum("bqhk,hkd->bqd", out, p["wo"]), (cache_k, cache_v)


def _write_cache(cache, new, pos):
    """cache (B,S,KV,hd); new (B,1,KV,hd); pos (B,) — all equal in batch.

    Writes at pos % S: a no-op for full-context caches (pos < S) and ring
    semantics for windowed caches (zamba2 long-context serving)."""
    return jax.lax.dynamic_update_slice_in_dim(
        cache, new.astype(cache.dtype), pos[0] % cache.shape[1], axis=1)
