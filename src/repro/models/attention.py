"""Attention modules: GQA/MQA (with optional sliding window and M-RoPE)
and MLA (multi-head latent attention, MiniCPM3/DeepSeek-V2 style, with the
absorbed decode path serving directly from the compressed latent cache)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import ModelConfig
from repro.models.layers import (apply_rope, blocked_attention, decode_attention,
                                 dense_init)


# =============================================================== GQA / MQA

def gqa_init(cfg: ModelConfig, key):
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": dense_init(k1, (d, H * hd), d, cfg.pdt),
        "wk": dense_init(k2, (d, KV * hd), d, cfg.pdt),
        "wv": dense_init(k3, (d, KV * hd), d, cfg.pdt),
        "wo": dense_init(k4, (H * hd, d), H * hd, cfg.pdt),
    }


def _qkv(cfg, p, x):
    B, S, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ p["wq"].astype(x.dtype)).reshape(B, S, H, hd)
    k = (x @ p["wk"].astype(x.dtype)).reshape(B, S, KV, hd)
    v = (x @ p["wv"].astype(x.dtype)).reshape(B, S, KV, hd)
    return q, k, v


def gqa_forward(cfg: ModelConfig, p, x, rope=None, *, causal=True, window=None,
                return_kv=False):
    """Full-sequence path (train / prefill).  ``rope``: (cos, sin) or None."""
    q, k, v = _qkv(cfg, p, x)
    if rope is not None:
        cos, sin = rope
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    kv_out = (k, v)                      # caches keep the compact KV heads
    H, KV = cfg.n_heads, cfg.n_kv_heads
    ka, va, qa = k, v, q
    # TP-friendliness: with KV < model-axis the (KV, G) split replicates the
    # whole attention per shard; repeating KV to H restores head sharding
    # (transient, bf16 — see EXPERIMENTS.md §Perf hillclimb 1).
    if cfg.tp_repeat_kv and H > KV:
        ka = jnp.repeat(k, H // KV, axis=2)
        va = jnp.repeat(v, H // KV, axis=2)
    if cfg.pad_heads_to and ka.shape[2] == qa.shape[2] and H % cfg.pad_heads_to:
        Hp = -(-H // cfg.pad_heads_to) * cfg.pad_heads_to
        pad = ((0, 0), (0, 0), (0, Hp - H), (0, 0))
        qa, ka, va = jnp.pad(qa, pad), jnp.pad(ka, pad), jnp.pad(va, pad)
    # pin the head axis to the model mesh axis — without the constraint the
    # partitioner replicates the whole attention when it cannot propagate
    # sharding through the repeat/reshape (hillclimb 1, iteration 2)
    from repro.parallel import context as pctx
    qa = pctx.constrain(qa, ("__dp__", None, "model", None))
    ka = pctx.constrain(ka, ("__dp__", None, "model", None))
    va = pctx.constrain(va, ("__dp__", None, "model", None))
    o = blocked_attention(qa, ka, va, causal=causal, window=window,
                          block=cfg.attn_block,
                          scale=1.0 / (cfg.head_dim ** 0.5))
    o = o[:, :, :H, :]
    out = o.reshape(x.shape[0], x.shape[1], -1) @ p["wo"].astype(x.dtype)
    if return_kv:
        return out, kv_out
    return out


def gqa_decode(cfg: ModelConfig, p, x, kc, vc, pos, rope=None, *, window=None):
    """One-token step.  kc/vc: (B, Smax, KV, hd); pos: scalar index of the
    new token.  Returns (out, kc, vc) with the caches updated at ``pos``."""
    q, k, v = _qkv(cfg, p, x)
    if rope is not None:
        cos, sin = rope
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    kc = jax.lax.dynamic_update_slice_in_dim(kc, k.astype(kc.dtype), pos, axis=1)
    vc = jax.lax.dynamic_update_slice_in_dim(vc, v.astype(vc.dtype), pos, axis=1)
    o = decode_attention(q, kc, vc, pos=pos, window=window)
    out = o.reshape(x.shape[0], 1, -1) @ p["wo"].astype(x.dtype)
    return out, kc, vc


# ===================================================================== MLA

def mla_init(cfg: ModelConfig, key):
    d, H = cfg.d_model, cfg.n_heads
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    nd, rd, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = jax.random.split(key, 6)
    if qr:
        q = {"wq_a": dense_init(ks[0], (d, qr), d, cfg.pdt),
             "q_norm": jnp.ones((qr,), cfg.pdt),
             "wq_b": dense_init(ks[1], (qr, H * (nd + rd)), qr, cfg.pdt)}
    else:                                  # no query compression (DeepSeek-V3 lite)
        q = {"wq": dense_init(ks[1], (d, H * (nd + rd)), d, cfg.pdt)}
    return {
        **q,
        "wkv_a": dense_init(ks[2], (d, kvr + rd), d, cfg.pdt),
        "kv_norm": jnp.ones((kvr,), cfg.pdt),
        "wkv_b": dense_init(ks[3], (kvr, H * (nd + vd)), kvr, cfg.pdt),
        "wo": dense_init(ks[4], (H * vd, d), H * vd, cfg.pdt),
    }


def _mla_q(cfg, p, x, rope):
    from repro.models.layers import rmsnorm
    B, S, _ = x.shape
    H, nd, rd = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    if "wq" in p:
        q = (x @ p["wq"].astype(x.dtype)).reshape(B, S, H, nd + rd)
    else:
        ql = rmsnorm(x @ p["wq_a"].astype(x.dtype), p["q_norm"], cfg.norm_eps)
        q = (ql @ p["wq_b"].astype(x.dtype)).reshape(B, S, H, nd + rd)
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    if rope is not None:
        cos, sin = rope
        q_rope = apply_rope(q_rope, cos[..., :rd // 2], sin[..., :rd // 2])
    return q_nope, q_rope


def _mla_latent(cfg, p, x, rope):
    from repro.models.layers import rmsnorm
    rd = cfg.qk_rope_dim
    kv = x @ p["wkv_a"].astype(x.dtype)
    ckv = rmsnorm(kv[..., :cfg.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = kv[..., cfg.kv_lora_rank:][:, :, None, :]     # one shared head
    if rope is not None:
        cos, sin = rope
        k_rope = apply_rope(k_rope, cos[..., :rd // 2], sin[..., :rd // 2])
    return ckv, k_rope[:, :, 0, :]


def mla_forward(cfg: ModelConfig, p, x, rope=None, *, causal=True,
                return_kv=False):
    """Train/prefill: expand latent to per-head K/V (standard MLA math)."""
    B, S, _ = x.shape
    H, nd, rd, vd = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    q_nope, q_rope = _mla_q(cfg, p, x, rope)
    ckv, k_rope = _mla_latent(cfg, p, x, rope)
    kvb = (ckv @ p["wkv_b"].astype(x.dtype)).reshape(B, S, H, nd + vd)
    k_nope, v = kvb[..., :nd], kvb[..., nd:]
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                                  (B, S, H, rd))], -1)
    # TP-friendliness (same reasoning as gqa_forward): pad the head axis to
    # the model-axis multiple and pin it, else MLA attention replicates
    from repro.parallel import context as pctx
    if cfg.pad_heads_to and H % cfg.pad_heads_to:
        Hp = -(-H // cfg.pad_heads_to) * cfg.pad_heads_to
        pad = ((0, 0), (0, 0), (0, Hp - H), (0, 0))
        q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)
    q = pctx.constrain(q, ("__dp__", None, "model", None))
    k = pctx.constrain(k, ("__dp__", None, "model", None))
    v = pctx.constrain(v, ("__dp__", None, "model", None))
    o = blocked_attention(q, k, v, causal=causal, block=cfg.attn_block,
                          scale=1.0 / ((nd + rd) ** 0.5),
                          recompute=cfg.attn_recompute)[:, :, :H, :]
    out = o.reshape(B, S, -1) @ p["wo"].astype(x.dtype)
    if return_kv:
        return out, (ckv, k_rope)
    return out


def mla_decode(cfg: ModelConfig, p, x, ckv_c, krope_c, pos, rope=None):
    """Absorbed decode: attention runs in the compressed latent space so the
    cache is (kv_lora + rope) per token instead of 2·H·head_dim — the MLA
    serving advantage.  ckv_c: (B, Smax, kvr); krope_c: (B, Smax, rd)."""
    B = x.shape[0]
    H, nd, rd, vd = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    kvr = cfg.kv_lora_rank
    q_nope, q_rope = _mla_q(cfg, p, x, rope)               # (B,1,H,nd/rd)
    ckv, k_rope = _mla_latent(cfg, p, x, rope)             # (B,1,kvr), (B,1,rd)
    ckv_c = jax.lax.dynamic_update_slice_in_dim(ckv_c, ckv.astype(ckv_c.dtype), pos, 1)
    krope_c = jax.lax.dynamic_update_slice_in_dim(krope_c, k_rope.astype(krope_c.dtype), pos, 1)
    # absorb W^{kv_b} K-half into the query
    wkvb = p["wkv_b"].astype(x.dtype).reshape(kvr, H, nd + vd)
    wk = wkvb[..., :nd]                                    # (kvr, H, nd)
    q_abs = jnp.einsum("bqhn,rhn->bqhr", q_nope, wk)       # (B,1,H,kvr)
    q_eff = jnp.concatenate([q_abs, q_rope], -1)           # (B,1,H,kvr+rd)
    k_eff = jnp.concatenate([ckv_c, krope_c], -1)[:, :, None, :]  # 1 kv head
    o_lat = decode_attention(q_eff, k_eff, ckv_c[:, :, None, :], pos=pos,
                             scale=1.0 / ((nd + rd) ** 0.5))  # (B,1,H,kvr)
    wv = wkvb[..., nd:]                                    # (kvr, H, vd)
    o = jnp.einsum("bqhr,rhv->bqhv", o_lat, wv)
    out = o.reshape(B, 1, -1) @ p["wo"].astype(x.dtype)
    return out, ckv_c, krope_c
