"""Shared layer primitives: norms, RoPE / M-RoPE, SwiGLU MLP, blocked
(flash-style) attention in pure ``jax.lax`` — the portable path; the Pallas
kernel in ``repro.kernels.flash_attention`` is the TPU fast path with the
same semantics (validated against each other in tests)."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

# --------------------------------------------------------------------- init

def dense_init(key, shape, in_dim, dtype):
    return (jax.random.normal(key, shape) / jnp.sqrt(in_dim)).astype(dtype)


# --------------------------------------------------------------------- norms

def rmsnorm(x, w, eps=1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * w.astype(jnp.float32)).astype(dt)


def layernorm(x, w, b, eps=1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------- RoPE

def rope_cos_sin(positions, dim, theta):
    """positions: (..., S) int -> cos/sin (..., S, dim//2) float32."""
    half = dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def mrope_cos_sin(positions3, dim, theta, sections):
    """Qwen2-VL M-RoPE: positions3 (3, B, S) for (t, h, w); ``sections``
    partitions the dim//2 frequency slots among the three streams."""
    assert sum(sections) == dim // 2
    cos_t, sin_t = rope_cos_sin(positions3, dim, theta)   # (3, B, S, dim//2)
    parts_c, parts_s = [], []
    start = 0
    for i, sec in enumerate(sections):
        parts_c.append(cos_t[i, ..., start:start + sec])
        parts_s.append(sin_t[i, ..., start:start + sec])
        start += sec
    return jnp.concatenate(parts_c, -1), jnp.concatenate(parts_s, -1)


def apply_rope(x, cos, sin):
    """x: (B, S, H, D); cos/sin: (B, S, D//2) — half-rotation (NeoX)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[:, :, None, :].astype(x.dtype)
    s = sin[:, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


# ----------------------------------------------------------------------- MLP

def swiglu(x, wg, wu, wd):
    h = jax.nn.silu(x @ wg) * (x @ wu)
    return h @ wd


# ----------------------------------------------- blocked (flash-style) attn

def blocked_attention(q, k, v, *, causal: bool, window=None,
                      block: int = 1024, q_offset=0,
                      kv_len: Optional[jax.Array] = None,
                      scale: Optional[float] = None, recompute: bool = False):
    """Online-softmax attention over KV blocks (memory O(S·block)).

    q: (B, Sq, H, D); k, v: (B, Skv, KV, D) with H % KV == 0 (GQA).
    ``q_offset``: global position of q[0] (prefill continuation / decode).
    ``window`` > 0: sliding-window attention (key j visible to query i iff
    i - window < j <= i).  ``kv_len``: valid prefix length of k/v (padding).
    ``recompute``: the backward pass recomputes each block's scores instead
    of keeping all Sq x Skv probabilities (for long training sequences).
    Returns (B, Sq, H, D) in q.dtype.
    """
    B, Sq, H, D = q.shape
    _, Skv, KV, _ = k.shape
    Dv = v.shape[-1]
    G = H // KV
    nblk = -(-Skv // block)
    pad = nblk * block - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(B, nblk, block, KV, D).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nblk, block, KV, Dv).transpose(1, 0, 2, 3, 4)

    qg = q.reshape(B, Sq, KV, G, D).astype(jnp.float32)
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    iq = q_offset + jnp.arange(Sq)

    def step(carry, xs):
        m, l, acc = carry
        kblk, vblk, bi = xs
        jk = bi * block + jnp.arange(block)
        s = jnp.einsum("bqkgd,bjkd->bkgqj", qg, kblk.astype(jnp.float32)) * scale
        mask = jnp.ones((Sq, block), dtype=bool)
        if causal:
            mask &= jk[None, :] <= iq[:, None]
        if window is not None:          # static int or traced scalar; >0
            mask &= jk[None, :] > iq[:, None] - window
        if kv_len is not None:
            mask &= (jk < kv_len)[None, :]
        else:
            mask &= (jk < Skv)[None, :]
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(-1))
        # guard fully-masked rows (m_new == -inf)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(mask[None, None, None], p, 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * alpha + p.sum(-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bkgqj,bjkd->bkgqd", p, vblk.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KV, G, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, KV, G, Sq, Dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(jax.checkpoint(step) if recompute else step,
                                  (m0, l0, a0), (kb, vb, jnp.arange(nblk)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, Dv).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, *, pos, window=None,
                     scale: Optional[float] = None):
    """Single-step attention against a cache.

    q: (B, 1, H, D); caches: (B, Smax, KV, D); ``pos``: (B,) or scalar —
    number of valid cache entries (the new token's kv must already be
    written at pos-? caller convention: caches hold pos+1 valid entries,
    i.e. index ``pos`` is the current token).
    """
    B, _, H, D = q.shape
    _, Smax, KV, _ = k_cache.shape
    Dv = v_cache.shape[-1]
    G = H // KV
    qg = q.reshape(B, KV, G, D).astype(jnp.float32)
    s = jnp.einsum("bkgd,bjkd->bkgj", qg, k_cache.astype(jnp.float32))
    s *= scale if scale is not None else 1.0 / (D ** 0.5)
    j = jnp.arange(Smax)
    cur = jnp.broadcast_to(jnp.asarray(pos), (B,))
    mask = j[None, :] <= cur[:, None]
    if window is not None:
        mask &= j[None, :] > (cur[:, None] - window)
    s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgj,bjkd->bkgd", p, v_cache.astype(jnp.float32))
    return out.reshape(B, 1, H, Dv).astype(q.dtype)
