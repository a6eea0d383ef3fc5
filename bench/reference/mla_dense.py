"""Plain reference of the training job: a dense decoder with multi-head
latent attention (MLA), its next-token loss, and AdamW, in float32.

Written from the equations alone and importing nothing of the program under
test.  The equations are those of the configuration file's ``model`` group;
``departures`` in that file lists where they differ from the published
model.  Per layer:

    h   = rmsnorm(x) * norm1
    q   = rmsnorm(h Wq_a) * q_norm  Wq_b            -> H heads of (nope, rope)
    kv  = h Wkv_a = [c | k_rope]; c = rmsnorm(c) * kv_norm
    [k_nope | v] = c Wkv_b                           (H heads)
    rope on q_rope and on the one shared k_rope head (half rotation; pair i
    turns at theta^(-i / rope_dim) for i < rope_dim / 2)
    x  += softmax_causal([q_nope|q_rope].[k_nope|k_rope] / sqrt(nope+rope)) v  Wo
    x  += (silu(h2 Wg) * h2 Wu) Wd,   h2 = rmsnorm(x) * norm2

then a final rmsnorm and logits = x E^T with the tied embedding E.  The loss
is the mean over all predicted positions of logsumexp(logits) - logit(next).

Weights come from the seed by the same splitting of the key as the job's
initialisation (normal / sqrt(fan_in), norms at one), so that both start
from the same point.  What the references share is in ``common.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.reference import common as C

leaves = C.leaves
program_leaves = C.program_leaves


def init_params(m: dict, key) -> dict:
    d, H, V = m["hidden_size"], m["num_attention_heads"], m["vocab_size"]
    qr, kvr = m["q_lora_rank"], m["kv_lora_rank"]
    nd, rd, vd = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    f = m["intermediate_size"]
    k_emb, k_layers, _ = jax.random.split(key, 3)
    layers = []
    for lk in jax.random.split(k_layers, m["num_hidden_layers"]):
        ks = jax.random.split(lk, 8)
        a = jax.random.split(ks[0], 6)
        w = jax.random.split(ks[2], 3)
        layers.append({
            "norm1": jnp.ones((d,)), "norm2": jnp.ones((d,)),
            "wq_a": C.normal(a[0], (d, qr), d), "q_norm": jnp.ones((qr,)),
            "wq_b": C.normal(a[1], (qr, H * (nd + rd)), qr),
            "wkv_a": C.normal(a[2], (d, kvr + rd), d), "kv_norm": jnp.ones((kvr,)),
            "wkv_b": C.normal(a[3], (kvr, H * (nd + vd)), kvr),
            "wo": C.normal(a[4], (H * vd, d), H * vd),
            "wg": C.normal(w[0], (d, f), d), "wu": C.normal(w[1], (d, f), d),
            "wd": C.normal(w[2], (f, d), f),
        })
    return {"embed": C.normal(k_emb, (V, d), d), "final_norm": jnp.ones((d,)),
            "layers": layers}


def _rope(x, pos, theta):
    """x: (S, heads, r); half rotation over r with r/2 pairs."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / x.shape[-1])
    ang = pos[:, None].astype(jnp.float32) * freq          # (S, half)
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def layer(m, mm, p, x, pos):
    S = x.shape[0]
    H, eps = m["num_attention_heads"], m["rms_norm_eps"]
    nd, rd, vd = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    kvr, theta = m["kv_lora_rank"], m["rope_theta"]
    h = C.rmsnorm(x, p["norm1"], eps)
    ql = C.rmsnorm(mm("sd,dr->sr", h, p["wq_a"]), p["q_norm"], eps)
    q = mm("sr,rk->sk", ql, p["wq_b"]).reshape(S, H, nd + rd)
    q = jnp.concatenate([q[..., :nd], _rope(q[..., nd:], pos, theta)], -1)
    kv = mm("sd,dk->sk", h, p["wkv_a"])
    c = C.rmsnorm(kv[:, :kvr], p["kv_norm"], eps)
    k_rope = _rope(kv[:, None, kvr:], pos, theta)           # (S, 1, rd)
    kvb = mm("sr,rk->sk", c, p["wkv_b"]).reshape(S, H, nd + vd)
    k = jnp.concatenate([kvb[..., :nd], jnp.broadcast_to(k_rope, (S, H, rd))], -1)
    o = C.causal_attention(mm, q, k, kvb[..., nd:])
    x = x + mm("sk,kd->sd", o, p["wo"])
    return x + C.swiglu_mlp(mm, p, C.rmsnorm(x, p["norm2"], eps))


first_steps = functools.partial(C.first_steps, init_params, layer)


def step_flops(m: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one training step, from shapes alone: every projection
    of the MLA block, the SwiGLU MLP, the tied output head, and causal
    attention (half of the S x S score and value products); forward once,
    backward twice the forward.  Recomputation, padded heads, masked-out
    attention blocks and elementwise work are not model FLOPs, so the count
    stays at or under XLA's count of the compiled step."""
    d, H, V = m["hidden_size"], m["num_attention_heads"], m["vocab_size"]
    qr, kvr = m["q_lora_rank"], m["kv_lora_rank"]
    nd, rd, vd = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    f, L = m["intermediate_size"], m["num_hidden_layers"]
    per_token_layer = 2 * (d * qr + qr * H * (nd + rd) + d * (kvr + rd)
                           + kvr * H * (nd + vd) + H * vd * d + 3 * d * f)
    attn_per_seq_layer = 2 * H * seq * seq * ((nd + rd) + vd) / 2   # causal
    forward = batch * seq * (L * per_token_layer + 2 * d * V) \
        + batch * L * attn_per_seq_layer
    return 3.0 * forward
