"""Decoder-only LM assembly for the dense / moe / ssm / hybrid / vlm
families: scan-over-stacked-layers (one-layer HLO regardless of depth),
configurable remat, and three entry points — ``forward`` (train),
``prefill`` (build caches), ``decode_step`` (one token).

An expert model may lead with ``first_dense`` dense layers
(``params["dense_layers"]``), scanned before the stack of expert layers
(``params["layers"]``).  A DeepSeek-V3 expert layer also reads a buffer
that the optimizer does not own: its router bias, ``buffers["router_bias"]``
(one row per expert layer), updated after each step from the step's
per-expert loads (``update_buffers``)."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as flash
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.common import ModelConfig
from repro.models.layers import dense_init, mrope_cos_sin, rmsnorm, rope_cos_sin, swiglu

NEG_WINDOW_OFF = 1 << 30   # "window" value that disables windowing


# ------------------------------------------------------------------- params

def _layer_init(cfg: ModelConfig, key, dense: bool = False):
    ks = jax.random.split(key, 8)
    p = {"norm1": jnp.ones((cfg.d_model,), cfg.pdt)}
    if cfg.family == "ssm":
        p["ssm"] = ssm_mod.ssm_init(cfg, ks[0])
        return p
    if cfg.attn_kind == "mla":
        p["attn"] = attn.mla_init(cfg, ks[0])
    else:
        p["attn"] = attn.gqa_init(cfg, ks[0])
    p["norm2"] = jnp.ones((cfg.d_model,), cfg.pdt)
    if cfg.family == "moe" and not dense:
        p["moe"] = moe_mod.moe_init(cfg, ks[1])
        if cfg.dense_residual:
            p["mlp"] = _mlp_init(cfg, ks[2])
    else:
        p["mlp"] = _mlp_init(cfg, ks[2])
    if cfg.family == "hybrid":
        p["ssm"] = ssm_mod.ssm_init(cfg, ks[3])
        p["fuse_a"] = jnp.full((cfg.d_model,), 0.5, cfg.pdt)
        p["fuse_s"] = jnp.full((cfg.d_model,), 0.5, cfg.pdt)
    return p


def _mlp_init(cfg, key):
    d, f = cfg.d_model, cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    return {"wg": dense_init(k1, (d, f), d, cfg.pdt),
            "wu": dense_init(k2, (d, f), d, cfg.pdt),
            "wd": dense_init(k3, (f, d), f, cfg.pdt)}


def init_lm(cfg: ModelConfig, key):
    k_emb, k_layers, k_un = jax.random.split(key, 3)
    keys, n = jax.random.split(k_layers, cfg.n_layers), cfg.first_dense
    params = {
        "embed": dense_init(k_emb, (cfg.vocab, cfg.d_model), cfg.d_model, cfg.pdt),
        "final_norm": jnp.ones((cfg.d_model,), cfg.pdt),
        "layers": jax.vmap(lambda k: _layer_init(cfg, k))(keys[n:]),
    }
    if n:
        params["dense_layers"] = jax.vmap(lambda k: _layer_init(cfg, k, dense=True))(keys[:n])
    if not cfg.tie_embeddings:
        params["unembed"] = dense_init(k_un, (cfg.d_model, cfg.vocab),
                                       cfg.d_model, cfg.pdt)
    if cfg.pos == "learned":
        params["pos_table"] = (0.02 * jax.random.normal(
            k_un, (cfg.max_positions, cfg.d_model))).astype(cfg.pdt)
    return params


def init_buffers(cfg: ModelConfig):
    """State that the step updates outside the optimizer: the router bias
    of each expert layer (DeepSeek-V3's auxiliary-loss-free balancing), or
    None for a model without it."""
    if cfg.router != "sigmoid":
        return None
    return {"router_bias": jnp.zeros((cfg.n_layers - cfg.first_dense, cfg.n_experts),
                                     jnp.float32)}


def update_buffers(cfg: ModelConfig, buffers, load):
    """b_i += bias_rate * sign(mean load - load_i), in each expert layer,
    over the loads this chip routed.  load: (expert layers, n_experts)."""
    step = cfg.bias_rate * jnp.sign(jnp.mean(load, -1, keepdims=True) - load)
    return {"router_bias": buffers["router_bias"] + step}


def moe_counters(cfg: ModelConfig, stats) -> dict:
    """The step's dispatch counters over all expert layers: rows the held
    experts' grouped matmuls were given, the rows of the busiest held
    expert in any layer, and the rows routed to a held expert whose output
    came back all zero (counted from the outputs, not from the routing)."""
    held = stats["load"][:, cfg.expert_first:cfg.expert_first + cfg.held]
    return {"moe_rows": jnp.sum(stats["rows"]),
            "moe_load_max": jnp.max(held).astype(jnp.int32),
            "moe_dropped": jnp.sum(stats["dropped"]).astype(jnp.int32)}


def layer_windows(cfg: ModelConfig):
    """The layers' attention window.  One static value where every layer
    has the same (an int, or None for full attention), so that attention
    may take the flash kernel; a per-layer array only for a hybrid stack
    that mixes windowed and global layers (NEG_WINDOW_OFF = full
    attention)."""
    if cfg.family == "hybrid" and cfg.swa_window and cfg.global_layers:
        win = jnp.full((cfg.n_layers,), cfg.swa_window, jnp.int32)
        return win.at[jnp.array(cfg.global_layers)].set(NEG_WINDOW_OFF)
    return cfg.swa_window or None


# -------------------------------------------------------------------- block

def _block(cfg: ModelConfig, pl, x, rope, window, bias=None, *, return_kv=False):
    """One transformer block, full-sequence path.  Returns (x, stats, kv):
    ``stats`` holds the balance loss ``aux`` and, for a dropless expert
    layer, its ``load`` and ``rows``."""
    stats = {"aux": jnp.float32(0.0)}
    kv = None
    if cfg.family == "ssm":
        out = ssm_mod.ssm_forward(cfg, pl["ssm"], rmsnorm(x, pl["norm1"], cfg.norm_eps),
                                  return_state=return_kv)
        if return_kv:
            out, kv = out
        return x + out, stats, kv

    with jax.named_scope("attention"):
        h = rmsnorm(x, pl["norm1"], cfg.norm_eps)
        if cfg.attn_kind == "mla":
            a = attn.mla_forward(cfg, pl["attn"], h, rope, return_kv=return_kv)
        else:
            a = attn.gqa_forward(cfg, pl["attn"], h, rope, window=window,
                                 return_kv=return_kv)
    if return_kv:
        a, kv = a
    if cfg.family == "hybrid":
        s_out = ssm_mod.ssm_forward(cfg, pl["ssm"], h, return_state=return_kv)
        if return_kv:
            s_out, sstate = s_out
            kv = (*kv, *sstate)
        x = x + pl["fuse_a"].astype(x.dtype) * a + pl["fuse_s"].astype(x.dtype) * s_out
    else:
        x = x + a

    with jax.named_scope("mlp"):
        h2 = rmsnorm(x, pl["norm2"], cfg.norm_eps)
        if "moe" in pl:
            m, stats = _moe(cfg, pl["moe"], h2, bias)
            if cfg.dense_residual:
                m = m + swiglu(h2, pl["mlp"]["wg"].astype(x.dtype),
                               pl["mlp"]["wu"].astype(x.dtype),
                               pl["mlp"]["wd"].astype(x.dtype))
        else:
            m = swiglu(h2, pl["mlp"]["wg"].astype(x.dtype),
                       pl["mlp"]["wu"].astype(x.dtype),
                       pl["mlp"]["wd"].astype(x.dtype))
    return x + m, stats, kv


def _moe(cfg: ModelConfig, p, h, bias):
    """The expert layer: (out, stats)."""
    if cfg.router == "sigmoid":
        return moe_mod.moe_dropless(cfg, p, h, bias)
    m, aux = moe_mod.moe_forward(cfg, p, h)
    return m, {"aux": aux}


def _remat(cfg, fn):
    """Remat "dots" saves the matmuls' outputs and the flash kernel's
    residuals (its output and log-sum-exp), so that the backward runs no
    forward of attention again."""
    if cfg.remat == "none":
        return fn
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    policies = jax.checkpoint_policies
    return jax.checkpoint(fn, policy=policies.save_from_both_policies(
        policies.dots_with_no_batch_dims_saveable,
        policies.save_only_these_names(*flash.RESIDUALS)))


# ----------------------------------------------------------------- forward

def _embed(cfg, params, tokens):
    with jax.named_scope("embed"):
        return jnp.take(params["embed"], tokens, axis=0).astype(cfg.cdt)


def _rope_for(cfg: ModelConfig, positions):
    """positions: (B,S) int32, or (3,B,S) for mrope; returns (cos, sin)."""
    if cfg.pos == "learned":
        return None
    dim = cfg.qk_rope_dim * 2 if cfg.attn_kind == "mla" else cfg.head_dim
    if cfg.pos == "mrope":
        return mrope_cos_sin(positions, dim, cfg.rope_theta, cfg.mrope_sections)
    return rope_cos_sin(positions, dim, cfg.rope_theta)


def _segments(cfg: ModelConfig, params, buffers):
    """Each scanned stack of layers in order, with its windows and router
    biases: the leading dense layers, if any, then the rest."""
    windows = layer_windows(cfg)
    bias = None if buffers is None else buffers["router_bias"]
    if "dense_layers" not in params:
        return [(params["layers"], windows, bias)]
    n = cfg.first_dense
    per_layer = isinstance(windows, jax.Array)
    return [(params["dense_layers"], windows[:n] if per_layer else windows, None),
            (params["layers"], windows[n:] if per_layer else windows, bias)]


def _scan_stack(cfg: ModelConfig, body, x, layers, windows, bias, *caches,
                remat=False):
    """``lax.scan`` of ``body(carry, pl, window, bias, *caches)`` over a stack
    of layers.  A per-layer window array is scanned with the layers; a
    static window reaches every layer as it is, and stays static."""
    per_layer = isinstance(windows, jax.Array)

    def step(carry, xs):
        pl, win, b, *c = xs
        return body(carry, pl, win if per_layer else windows, b, *c)

    return jax.lax.scan(_remat(cfg, step) if remat else step, x,
                        (layers, windows if per_layer else None, bias, *caches))


def forward(cfg: ModelConfig, params, tokens, positions=None, buffers=None):
    """Train-path logits.  tokens: (B,S) int32.  Returns (logits_f32, aux)."""
    logits, stats = _forward(cfg, params, tokens, positions, buffers)
    return logits, jnp.sum(stats["aux"])


def _forward(cfg: ModelConfig, params, tokens, positions, buffers):
    """(logits, per-layer stats of the last stack); the leading dense
    layers add no balance loss."""
    B, S = tokens.shape[-2:] if tokens.ndim >= 2 else (1, tokens.shape[0])
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        if cfg.pos == "mrope":
            positions = jnp.broadcast_to(positions, (3, B, S))
    x = _embed(cfg, params, tokens)
    if cfg.pos == "learned":
        x = x + params["pos_table"][:S][None].astype(x.dtype)
    rope = _rope_for(cfg, positions)

    def body(carry, pl, win, bias):
        y, stats, _ = _block(cfg, pl, carry, rope, win, bias)
        return y, stats

    for layers, windows, bias in _segments(cfg, params, buffers):
        x, stats = _scan_stack(cfg, body, x, layers, windows, bias, remat=True)
    with jax.named_scope("loss"):       # the head exists for the loss
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        un = (params["embed"].T if cfg.tie_embeddings else params["unembed"])
        logits = (x @ un.astype(x.dtype)).astype(jnp.float32)
    return logits, stats


def loss_fn(cfg: ModelConfig, params, batch, buffers=None):
    """Next-token cross-entropy plus ``aux_weight`` times the balance loss.
    batch: {tokens: (B,S)}.  A dropless expert model's metrics also carry
    the per-expert ``load`` of each expert layer and ``moe_counters``."""
    tokens = batch["tokens"]
    logits, stats = _forward(cfg, params, tokens, batch.get("positions"), buffers)
    aux = jnp.sum(stats["aux"])
    with jax.named_scope("loss"):
        tgt = tokens[:, 1:]
        lg = logits[:, :-1]
        lse = jax.nn.logsumexp(lg, axis=-1)
        ll = jnp.take_along_axis(lg, tgt[..., None], axis=-1)[..., 0]
        loss = jnp.mean(lse - ll)
        metrics = {"ce": loss, "aux": aux}
        if "load" in stats:
            metrics.update(moe_counters(cfg, stats), load=stats["load"])
        return loss + cfg.aux_weight * aux, metrics


# ------------------------------------------------------------------ serving

def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    """Decode cache pytree, stacked over layers."""
    L = cfg.n_layers
    c = {"pos": jnp.zeros((), jnp.int32)}
    cdt = cfg.cdt
    if cfg.family != "ssm":
        if cfg.attn_kind == "mla":
            c["ckv"] = jnp.zeros((L, batch, max_len, cfg.kv_lora_rank), cdt)
            c["krope"] = jnp.zeros((L, batch, max_len, cfg.qk_rope_dim), cdt)
        else:
            kvh, hd = cfg.n_kv_heads, cfg.head_dim
            c["k"] = jnp.zeros((L, batch, max_len, kvh, hd), cdt)
            c["v"] = jnp.zeros((L, batch, max_len, kvh, hd), cdt)
    if cfg.family in ("ssm", "hybrid"):
        st, cv = ssm_mod.ssm_init_cache(cfg, batch, cdt)
        c["ssm_state"] = jnp.broadcast_to(st[None], (L, *st.shape))
        c["conv_state"] = jnp.broadcast_to(cv[None], (L, *cv.shape))
    return c


def prefill(cfg: ModelConfig, params, tokens, max_len: int, positions=None,
            buffers=None):
    """Run the full prompt, return (last_logits, cache)."""
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        if cfg.pos == "mrope":
            positions = jnp.broadcast_to(positions, (3, B, S))
    x = _embed(cfg, params, tokens)
    if cfg.pos == "learned":
        x = x + params["pos_table"][:S][None].astype(x.dtype)
    rope = _rope_for(cfg, positions)

    def body(carry, pl, win, bias):
        y, _stats, kv = _block(cfg, pl, carry, rope, win, bias, return_kv=True)
        return y, kv

    parts = []
    for layers, windows, bias in _segments(cfg, params, buffers):
        x, kvs = _scan_stack(cfg, body, x, layers, windows, bias)
        parts.append(kvs)
    if len(parts) > 1:                  # caches stack all layers in order
        kvs = jax.tree.map(lambda *a: jnp.concatenate(a), *parts)
    cache = init_cache(cfg, B, max_len)
    cache["pos"] = jnp.int32(S)
    if cfg.family == "ssm":
        cache["ssm_state"] = kvs[0]
        cache["conv_state"] = kvs[1]
    else:
        if cfg.attn_kind == "mla":
            ckv, krope = kvs[0], kvs[1]
            cache["ckv"] = jax.lax.dynamic_update_slice_in_dim(
                cache["ckv"], ckv.astype(cache["ckv"].dtype), 0, axis=2)
            cache["krope"] = jax.lax.dynamic_update_slice_in_dim(
                cache["krope"], krope.astype(cache["krope"].dtype), 0, axis=2)
        else:
            k, v = kvs[0], kvs[1]
            cache["k"] = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), 0, axis=2)
            cache["v"] = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), 0, axis=2)
        if cfg.family == "hybrid":
            cache["ssm_state"] = kvs[2]
            cache["conv_state"] = kvs[3]
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    un = (params["embed"].T if cfg.tie_embeddings else params["unembed"])
    logits = (x[:, -1:] @ un.astype(x.dtype)).astype(jnp.float32)
    return logits, cache


def _block_decode(cfg: ModelConfig, pl, x, rope, window, caches, pos, bias=None):
    """One block, one token.  ``caches``: per-layer slice tuple."""
    new = []
    if cfg.family == "ssm":
        h = rmsnorm(x, pl["norm1"], cfg.norm_eps)
        out, st, cv = ssm_mod.ssm_decode(cfg, pl["ssm"], h, caches[0], caches[1])
        return x + out, (st, cv)

    h = rmsnorm(x, pl["norm1"], cfg.norm_eps)
    if cfg.attn_kind == "mla":
        a, ckv, krope = attn.mla_decode(cfg, pl["attn"], h, caches[0], caches[1],
                                        pos, rope)
        new += [ckv, krope]
    else:
        a, kc, vc = attn.gqa_decode(cfg, pl["attn"], h, caches[0], caches[1],
                                    pos, rope, window=window)
        new += [kc, vc]
    if cfg.family == "hybrid":
        s_out, st, cv = ssm_mod.ssm_decode(cfg, pl["ssm"], h, caches[2], caches[3])
        new += [st, cv]
        x = x + pl["fuse_a"].astype(x.dtype) * a + pl["fuse_s"].astype(x.dtype) * s_out
    else:
        x = x + a
    h2 = rmsnorm(x, pl["norm2"], cfg.norm_eps)
    if "moe" in pl:
        m, _ = _moe(cfg, pl["moe"], h2, bias)
        if cfg.dense_residual:
            m = m + swiglu(h2, pl["mlp"]["wg"].astype(x.dtype),
                           pl["mlp"]["wu"].astype(x.dtype),
                           pl["mlp"]["wd"].astype(x.dtype))
        x = x + m
    else:
        x = x + swiglu(h2, pl["mlp"]["wg"].astype(x.dtype),
                       pl["mlp"]["wu"].astype(x.dtype),
                       pl["mlp"]["wd"].astype(x.dtype))
    return x, tuple(new)


def _cache_keys(cfg: ModelConfig):
    if cfg.family == "ssm":
        return ("ssm_state", "conv_state")
    keys = ("ckv", "krope") if cfg.attn_kind == "mla" else ("k", "v")
    if cfg.family == "hybrid":
        keys = (*keys, "ssm_state", "conv_state")
    return keys


def decode_step(cfg: ModelConfig, params, cache, tokens, buffers=None):
    """One serving step.  tokens: (B, 1) int32; returns (logits, cache)."""
    B = tokens.shape[0]
    pos = cache["pos"]
    if cfg.pos == "mrope":
        positions = jnp.broadcast_to(pos.astype(jnp.int32), (3, B, 1))
    else:
        positions = jnp.broadcast_to(pos.astype(jnp.int32), (B, 1))
    x = _embed(cfg, params, tokens)
    if cfg.pos == "learned":
        x = x + jax.lax.dynamic_slice_in_dim(params["pos_table"], pos, 1)[None].astype(x.dtype)
    rope = _rope_for(cfg, positions)
    keys = _cache_keys(cfg)

    def body(carry, pl, win, bias, *caches):
        return _block_decode(cfg, pl, carry, rope, win, caches, pos, bias)

    segments = _segments(cfg, params, buffers)
    parts, start = [], 0
    for layers, windows, bias in segments:
        n = jax.tree.leaves(layers)[0].shape[0]
        caches = [cache[k] if len(segments) == 1 else cache[k][start:start + n]
                  for k in keys]
        x, new = _scan_stack(cfg, body, x, layers, windows, bias, *caches)
        parts.append(new)
        start += n
    for i, k in enumerate(keys):
        cache[k] = jnp.concatenate([p[i] for p in parts]) if len(parts) > 1 else parts[0][i]
    cache["pos"] = pos + 1
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    un = (params["embed"].T if cfg.tie_embeddings else params["unembed"])
    logits = (x @ un.astype(x.dtype)).astype(jnp.float32)
    return logits, cache
