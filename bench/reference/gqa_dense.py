"""Plain reference of a dense decoder with grouped-query attention (GQA),
its next-token loss, and AdamW, in float32.

Written from the equations alone and importing nothing of the program under
test.  The sizes are the configuration file's ``model`` group (Hugging Face
names).  Per layer, with G = heads / kv heads:

    h   = rmsnorm(x) * norm1
    q   = h Wq (H heads), k = h Wk, v = h Wv (KV heads each, of head_dim)
    rope on q and k (half rotation; pair i turns at theta^(-2i / head_dim))
    query head j reads kv head j // G
    x  += softmax_causal(q.k / sqrt(head_dim)) v  Wo
    x  += (silu(h2 Wg) * h2 Wu) Wd,   h2 = rmsnorm(x) * norm2

then a final rmsnorm and logits = x U, with U the output head (the
embedding's transpose where ``tie_word_embeddings``).  Weights come from the
seed by the same splitting of the key as the job's initialisation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.reference import common as C

leaves = C.leaves
program_leaves = C.program_leaves


def init_params(m: dict, key) -> dict:
    d, V, f = m["hidden_size"], m["vocab_size"], m["intermediate_size"]
    H, KV, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    k_emb, k_layers, k_un = jax.random.split(key, 3)
    layers = []
    for lk in jax.random.split(k_layers, m["num_hidden_layers"]):
        ks = jax.random.split(lk, 8)
        a = jax.random.split(ks[0], 4)
        w = jax.random.split(ks[2], 3)
        layers.append({
            "norm1": jnp.ones((d,)), "norm2": jnp.ones((d,)),
            "wq": C.normal(a[0], (d, H * hd), d), "wk": C.normal(a[1], (d, KV * hd), d),
            "wv": C.normal(a[2], (d, KV * hd), d), "wo": C.normal(a[3], (H * hd, d), H * hd),
            "wg": C.normal(w[0], (d, f), d), "wu": C.normal(w[1], (d, f), d),
            "wd": C.normal(w[2], (f, d), f),
        })
    out = {"embed": C.normal(k_emb, (V, d), d), "final_norm": jnp.ones((d,)),
           "layers": layers}
    if not m["tie_word_embeddings"]:
        out["unembed"] = C.normal(k_un, (d, V), d)
    return out


def _rope(x, pos, theta):
    """x: (S, heads, hd); half rotation with hd/2 pairs."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freq
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def layer(m, mm, p, x, pos):
    S = x.shape[0]
    H, KV, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    h = C.rmsnorm(x, p["norm1"], eps)
    q = _rope(mm("sd,dk->sk", h, p["wq"]).reshape(S, H, hd), pos, theta)
    k = _rope(mm("sd,dk->sk", h, p["wk"]).reshape(S, KV, hd), pos, theta)
    v = mm("sd,dk->sk", h, p["wv"]).reshape(S, KV, hd)
    o = C.causal_attention(mm, q, jnp.repeat(k, H // KV, axis=1),
                           jnp.repeat(v, H // KV, axis=1))
    x = x + mm("sk,kd->sd", o, p["wo"])
    return x + C.swiglu_mlp(mm, p, C.rmsnorm(x, p["norm2"], eps))


first_steps = functools.partial(C.first_steps, init_params, layer)


def step_flops(m: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one training step, from shapes alone: the q, k, v and
    output projections, the SwiGLU MLP, the output head and causal
    attention (half of the S x S products); forward once, backward twice."""
    d, V, f, L = (m["hidden_size"], m["vocab_size"], m["intermediate_size"],
                  m["num_hidden_layers"])
    H, KV, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    per_token_layer = 2 * (2 * d * H * hd + 2 * d * KV * hd + 3 * d * f)
    attn_per_seq_layer = 2 * H * seq * seq * 2 * hd / 2             # causal
    forward = batch * seq * (L * per_token_layer + 2 * d * V) \
        + batch * L * attn_per_seq_layer
    return 3.0 * forward
