"""Flash attention (forward) as a Pallas TPU kernel.

TPU-native adaptation: the working set per grid cell is one q-tile
(blk_q x D) held in VMEM with running max / denominator / accumulator in
VMEM scratch; the kv-sequence is the innermost ("arbitrary") grid dim so
the accumulator carries across kv tiles without HBM round-trips.  Tiles
are MXU-aligned (128 lanes).  Causal / sliding-window tiles that are fully
masked are skipped with ``pl.when`` — on TPU that prunes ~half the MXU work
for causal prefill.

The jnp oracle is ``repro.kernels.ref.attention_ref``; CPU tests run this
kernel with ``interpret=True``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, causal, window, blk_q, blk_k, n_kv, seq_q, seq_kv):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * blk_q
    k_start = ki * blk_k
    relevant = True
    if causal:
        relevant = k_start <= q_start + blk_q - 1
    if window is not None:
        relevant = jnp.logical_and(relevant,
                                   k_start + blk_k - 1 > q_start - window)

    @pl.when(relevant)
    def _compute():
        q = q_ref[0].astype(jnp.float32)          # (blk_q, D)
        k = k_ref[0].astype(jnp.float32)          # (blk_k, D)
        v = v_ref[0].astype(jnp.float32)          # (blk_k, Dv)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        iq = q_start + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
        jk = k_start + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
        mask = jk < seq_kv
        if causal:
            mask = jnp.logical_and(mask, jk <= iq)
        if window is not None:
            mask = jnp.logical_and(mask, jk > iq - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(mask, jnp.exp(s - m_safe), 0.0)
        alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
            p.astype(v.dtype), v)
        m_scr[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _out():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal=True, window=None, scale=None,
                           blk_q=128, blk_k=128, interpret=False):
    """q: (B, Sq, H, D); k, v: (B, Skv, KV, D/Dv).  Returns (B, Sq, H, Dv)."""
    B, Sq, H, D = q.shape
    _, Skv, KV, Dv = v.shape
    G = H // KV
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    blk_q = min(blk_q, Sq)
    blk_k = min(blk_k, Skv)
    if window == 0:
        window = None

    pad_q = (-Sq) % blk_q
    pad_k = (-Skv) % blk_k
    qr = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kr = k.transpose(0, 2, 1, 3).reshape(B * KV, Skv, D)
    vr = v.transpose(0, 2, 1, 3).reshape(B * KV, Skv, Dv)
    if pad_q:
        qr = jnp.pad(qr, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kr = jnp.pad(kr, ((0, 0), (0, pad_k), (0, 0)))
        vr = jnp.pad(vr, ((0, 0), (0, pad_k), (0, 0)))
    n_q = qr.shape[1] // blk_q
    n_kv = kr.shape[1] // blk_k

    kern = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        blk_q=blk_q, blk_k=blk_k, n_kv=n_kv, seq_q=Sq, seq_kv=Skv)

    out = pl.pallas_call(
        kern,
        grid=(B * H, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, blk_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_k, D), lambda b, i, j, G=G: (b // G, j, 0)),
            pl.BlockSpec((1, blk_k, Dv), lambda b, i, j, G=G: (b // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk_q, Dv), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, n_q * blk_q, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qr, kr, vr)
    out = out[:, :Sq].reshape(B, H, Sq, Dv).transpose(0, 2, 1, 3)
    return out
