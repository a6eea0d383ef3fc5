"""Whisper-style encoder-decoder backbone.

Per the assignment spec the audio frontend (mel + conv downsampling) is a
STUB: ``input_specs`` provides precomputed frame embeddings (B, S_enc, d).
Encoder: bidirectional attention + sinusoidal positions.  Decoder: causal
self-attention + cross-attention to encoder states + learned positions.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models.common import ModelConfig
from repro.models.layers import decode_attention, dense_init, layernorm


def sinusoid_pos(S, d):
    pos = jnp.arange(S, dtype=jnp.float32)[:, None]
    i = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / (10000.0 ** (2 * i / d))
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _ln_init(cfg):
    return {"w": jnp.ones((cfg.d_model,), cfg.pdt),
            "b": jnp.zeros((cfg.d_model,), cfg.pdt)}


def _mlp_init(cfg, key):
    d, f = cfg.d_model, cfg.d_ff
    k1, k2 = jax.random.split(key)
    return {"w1": dense_init(k1, (d, f), d, cfg.pdt),
            "b1": jnp.zeros((f,), cfg.pdt),
            "w2": dense_init(k2, (f, d), f, cfg.pdt),
            "b2": jnp.zeros((d,), cfg.pdt)}


def _enc_layer_init(cfg, key):
    k1, k2 = jax.random.split(key)
    return {"ln1": _ln_init(cfg), "attn": attn.gqa_init(cfg, k1),
            "ln2": _ln_init(cfg), "mlp": _mlp_init(cfg, k2)}


def _dec_layer_init(cfg, key):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"ln1": _ln_init(cfg), "self": attn.gqa_init(cfg, k1),
            "ln2": _ln_init(cfg), "cross": attn.gqa_init(cfg, k2),
            "ln3": _ln_init(cfg), "mlp": _mlp_init(cfg, k3)}


def init_encdec(cfg: ModelConfig, key):
    ks = jax.random.split(key, 4)
    return {
        "embed": dense_init(ks[0], (cfg.vocab, cfg.d_model), cfg.d_model, cfg.pdt),
        "dec_pos": (0.02 * jax.random.normal(ks[1], (cfg.max_positions, cfg.d_model))).astype(cfg.pdt),
        "enc_layers": jax.vmap(lambda k: _enc_layer_init(cfg, k))(
            jax.random.split(ks[2], cfg.enc_layers)),
        "dec_layers": jax.vmap(lambda k: _dec_layer_init(cfg, k))(
            jax.random.split(ks[3], cfg.dec_layers)),
        "enc_ln": _ln_init(cfg), "dec_ln": _ln_init(cfg),
    }


def _mlp(pl, x):
    h = jax.nn.gelu(x @ pl["w1"].astype(x.dtype) + pl["b1"].astype(x.dtype))
    return h @ pl["w2"].astype(x.dtype) + pl["b2"].astype(x.dtype)


def _ln(pl, x, eps):
    return layernorm(x, pl["w"], pl["b"], eps)


def encode(cfg: ModelConfig, params, frames):
    """frames: (B, S_enc, d) stub embeddings -> encoder states."""
    B, S, d = frames.shape
    x = frames.astype(cfg.cdt) + sinusoid_pos(S, d)[None].astype(cfg.cdt)

    def body(carry, pl):
        h = _ln(pl["ln1"], carry, cfg.norm_eps)
        a = attn.gqa_forward(cfg, pl["attn"], h, None, causal=False)
        x = carry + a
        x = x + _mlp(pl["mlp"], _ln(pl["ln2"], x, cfg.norm_eps))
        return x, None

    x, _ = jax.lax.scan(body, x, params["enc_layers"])
    return _ln(params["enc_ln"], x, cfg.norm_eps)


def _dec_block(cfg, pl, x, enc_kv, *, self_kv=None, return_kv=False):
    """Full-sequence decoder block.  ``enc_kv``: (k_e, v_e) precomputed."""
    h = _ln(pl["ln1"], x, cfg.norm_eps)
    a = attn.gqa_forward(cfg, pl["self"], h, None, causal=True,
                         return_kv=return_kv)
    kv = None
    if return_kv:
        a, kv = a
    x = x + a
    h = _ln(pl["ln2"], x, cfg.norm_eps)
    B, S, _ = h.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (h @ pl["cross"]["wq"].astype(h.dtype)).reshape(B, S, H, hd)
    o = attn.attention(q, enc_kv[0], enc_kv[1], causal=False, block=cfg.attn_block)
    x = x + o.reshape(B, S, -1) @ pl["cross"]["wo"].astype(h.dtype)
    x = x + _mlp(pl["mlp"], _ln(pl["ln3"], x, cfg.norm_eps))
    return x, kv


def cross_kv(cfg, pl_cross, enc):
    B, Se, _ = enc.shape
    KV, hd = cfg.n_kv_heads, cfg.head_dim
    k = (enc @ pl_cross["wk"].astype(enc.dtype)).reshape(B, Se, KV, hd)
    v = (enc @ pl_cross["wv"].astype(enc.dtype)).reshape(B, Se, KV, hd)
    return k, v


def forward(cfg: ModelConfig, params, frames, dec_tokens):
    """Train path.  Returns (logits over decoder positions, aux=0)."""
    enc = encode(cfg, params, frames)
    B, Sd = dec_tokens.shape
    x = jnp.take(params["embed"], dec_tokens, axis=0).astype(cfg.cdt)
    x = x + params["dec_pos"][:Sd][None].astype(x.dtype)

    def body(carry, pl):
        ekv = cross_kv(cfg, pl["cross"], enc)
        y, _ = _dec_block(cfg, pl, carry, ekv)
        return y, None

    x, _ = jax.lax.scan(body, x, params["dec_layers"])
    x = _ln(params["dec_ln"], x, cfg.norm_eps)
    logits = (x @ params["embed"].T.astype(x.dtype)).astype(jnp.float32)
    return logits, jnp.float32(0.0)


def loss_fn(cfg: ModelConfig, params, batch, **_kw):
    logits, _ = forward(cfg, params, batch["frames"], batch["dec_tokens"])
    tgt = batch["dec_tokens"][:, 1:]
    lg = logits[:, :-1]
    lse = jax.nn.logsumexp(lg, axis=-1)
    ll = jnp.take_along_axis(lg, tgt[..., None], axis=-1)[..., 0]
    loss = jnp.mean(lse - ll)
    return loss, {"ce": loss, "aux": jnp.float32(0.0)}


# -------------------------------------------------------------- serving

def init_cache(cfg: ModelConfig, batch: int, max_len: int, enc_len: int):
    L = cfg.dec_layers
    KV, hd = cfg.n_kv_heads, cfg.head_dim
    cdt = cfg.cdt
    return {
        "pos": jnp.zeros((), jnp.int32),
        "k": jnp.zeros((L, batch, max_len, KV, hd), cdt),
        "v": jnp.zeros((L, batch, max_len, KV, hd), cdt),
        "ek": jnp.zeros((L, batch, enc_len, KV, hd), cdt),
        "ev": jnp.zeros((L, batch, enc_len, KV, hd), cdt),
    }


def prefill(cfg: ModelConfig, params, frames, dec_tokens, max_len: int):
    """Encode + run the decoder prompt; returns (last_logits, cache)."""
    enc = encode(cfg, params, frames)
    B, Sd = dec_tokens.shape
    x = jnp.take(params["embed"], dec_tokens, axis=0).astype(cfg.cdt)
    x = x + params["dec_pos"][:Sd][None].astype(x.dtype)

    def body(carry, pl):
        ekv = cross_kv(cfg, pl["cross"], enc)
        y, kv = _dec_block(cfg, pl, carry, ekv, return_kv=True)
        return y, (kv[0], kv[1], ekv[0], ekv[1])

    x, (k, v, ek, ev) = jax.lax.scan(body, x, params["dec_layers"])
    cache = init_cache(cfg, B, max_len, enc.shape[1])
    cache["pos"] = jnp.int32(Sd)
    cache["k"] = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cfg.cdt), 0, axis=2)
    cache["v"] = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cfg.cdt), 0, axis=2)
    cache["ek"], cache["ev"] = ek.astype(cfg.cdt), ev.astype(cfg.cdt)
    x = _ln(params["dec_ln"], x, cfg.norm_eps)
    logits = (x[:, -1:] @ params["embed"].T.astype(x.dtype)).astype(jnp.float32)
    return logits, cache


def decode_step(cfg: ModelConfig, params, cache, tokens):
    """One decoder token against self+cross caches."""
    B = tokens.shape[0]
    pos = cache["pos"]
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.cdt)
    x = x + jax.lax.dynamic_slice_in_dim(params["dec_pos"], pos, 1)[None].astype(x.dtype)
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    def body(carry, xs):
        pl, kc, vc, ek, ev = xs
        h = _ln(pl["ln1"], carry, cfg.norm_eps)
        a, kc, vc = attn.gqa_decode(cfg, pl["self"], h, kc, vc, pos, None)
        x = carry + a
        h = _ln(pl["ln2"], x, cfg.norm_eps)
        q = (h @ pl["cross"]["wq"].astype(h.dtype)).reshape(B, 1, H, hd)
        o = decode_attention(q, ek, ev, pos=ek.shape[1] - 1)
        x = x + o.reshape(B, 1, -1) @ pl["cross"]["wo"].astype(h.dtype)
        x = x + _mlp(pl["mlp"], _ln(pl["ln3"], x, cfg.norm_eps))
        return x, (kc, vc)

    x, (k, v) = jax.lax.scan(body, x, (params["dec_layers"], cache["k"],
                                       cache["v"], cache["ek"], cache["ev"]))
    cache["k"], cache["v"] = k, v
    cache["pos"] = pos + 1
    x = _ln(params["dec_ln"], x, cfg.norm_eps)
    logits = (x @ params["embed"].T.astype(x.dtype)).astype(jnp.float32)
    return logits, cache
