"""Mixture-of-Experts layer with capacity-factor gather/scatter dispatch.

Design notes (expert parallelism on the ``model`` mesh axis):
  * tokens are reshaped to (groups, group_len, d) with groups sharded over
    the data axes — dispatch indices are computed per group;
  * dispatch/combine are pure data movement (scatter/gather), NOT the GShard
    dense one-hot einsum, whose mask matmul FLOPs would dwarf the expert
    FLOPs at 128 experts and poison the roofline's useful-FLOPs ratio;
  * expert weights (E, d, f) are sharded on E over ``model``; XLA SPMD
    inserts the all-to-alls between the token-sharded and expert-sharded
    views (inspected in the dry-run HLO);
  * over-capacity tokens are dropped (capacity_factor, GShard-style) — the
    standard trade for static shapes.

Returns the layer output plus the load-balancing auxiliary loss
(Switch-style: E · Σ_e f_e · p_e).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import ModelConfig
from repro.models.layers import dense_init


def moe_init(cfg: ModelConfig, key):
    d, E, f = cfg.d_model, cfg.n_experts, cfg.d_expert
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], (d, E), d, jnp.float32),
        "wg": dense_init(ks[1], (E, d, f), d, cfg.pdt),
        "wu": dense_init(ks[2], (E, d, f), d, cfg.pdt),
        "wd": dense_init(ks[3], (E, f, d), f, cfg.pdt),
    }


def moe_capacity(cfg: ModelConfig, group_len: int) -> int:
    c = int(group_len * cfg.top_k / cfg.n_experts * cfg.capacity_factor)
    return max(4, -(-c // 4) * 4)          # round up to a multiple of 4


def moe_forward(cfg: ModelConfig, p, x):
    """x: (B, S, d) -> (out: (B, S, d), aux_loss: scalar).

    Dispatches to the explicit shard_map EP implementation when a mesh with
    a compatible ``model`` axis is in scope (see EXPERIMENTS.md §Perf
    hillclimb 3); otherwise the pjit-auto gather implementation below."""
    from repro.parallel import context
    mesh = context.current_mesh()
    if mesh is not None and "model" in mesh.axis_names:
        M = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
        B, S, _ = x.shape
        if M > 1 and cfg.n_experts % M == 0 and S % M == 0 and \
                (B * S) // M >= cfg.top_k:
            return _moe_shard_map(cfg, p, x, mesh, M)
    return _moe_gather(cfg, p, x)


def _route(cfg: ModelConfig, router, xt):
    """Shared routing: top-k weights/ids + Switch aux loss.  xt: (T, d)."""
    E, K = cfg.n_experts, cfg.top_k
    logits = (xt @ router.astype(jnp.float32)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, K)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    f_e = jnp.mean(jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(1), axis=0)
    p_e = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(f_e / K * p_e)
    return w, idx, aux


def _slots(idx_f, E, C):
    """Slot of each (token,k) in its expert's capacity-C queue."""
    onehot = jax.nn.one_hot(idx_f, E, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - onehot
    slot = jnp.take_along_axis(pos, idx_f[:, None], axis=-1)[:, 0]
    return jnp.minimum(slot, C - 1), (slot < C)


def _moe_shard_map(cfg: ModelConfig, p, x, mesh, M):
    """Expert parallelism with explicit all-to-alls.

    Tokens enter sharded (batch over the DP axes, sequence over ``model``);
    each shard routes its own tokens, builds per-expert send buffers, and
    two ``all_to_all``s over the model axis move tokens to their experts
    and back.  Wire bytes per device ≈ 2·T_loc·k·cf·d — two orders of
    magnitude below what the auto-partitioned scatter/gather produced for
    arctic-480b (the baseline's dominant roofline term)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    E_loc = E // M
    dp = tuple(a for a in mesh.axis_names if a != "model")
    all_axes = tuple(mesh.axis_names)

    def local(xl, router, wg, wu, wd):
        # xl: (B_loc, S/M, d); wg/wu/wd: (E_loc, d, f)
        Bl, Sl, _ = xl.shape
        T = Bl * Sl
        xt = xl.reshape(T, d)
        C = moe_capacity(cfg, T)
        w, idx, aux = _route(cfg, router, xt)
        idx_f = idx.reshape(T * K)
        slot, keep = _slots(idx_f, E, C)
        keep = keep.astype(xl.dtype)
        token_of = jnp.arange(T * K) // K
        buf = jnp.zeros((E, C, d), xl.dtype).at[idx_f, slot].add(
            xt[token_of] * keep[:, None])                    # (E, C, d)
        # ship tokens to their expert's shard
        buf = buf.reshape(M, E_loc, C, d)
        buf = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=0,
                                 tiled=False)                # (M, E_loc, C, d)
        h = buf.transpose(1, 0, 2, 3).reshape(E_loc, M * C, d)
        a = jax.nn.silu(jnp.einsum("emd,edf->emf", h, wg.astype(xl.dtype)))
        a = a * jnp.einsum("emd,edf->emf", h, wu.astype(xl.dtype))
        o = jnp.einsum("emf,efd->emd", a, wd.astype(xl.dtype))
        o = o.reshape(E_loc, M, C, d).transpose(1, 0, 2, 3)
        o = jax.lax.all_to_all(o, "model", split_axis=0, concat_axis=0,
                               tiled=False)                  # back home
        o = o.reshape(E, C, d)
        y = o[idx_f, slot] * keep[:, None]                   # (T*K, d)
        y = (y.reshape(T, K, d) * w[..., None].astype(y.dtype)).sum(1)
        aux = jax.lax.pmean(aux, all_axes)
        return y.reshape(Bl, Sl, d), aux

    xspec = P(dp if B % max(1, _prod(mesh, dp)) == 0 else None, "model", None)
    f = shard_map(local, mesh=mesh,
                  in_specs=(xspec, P(), P("model", None, None),
                            P("model", None, None), P("model", None, None)),
                  out_specs=(xspec, P()), check_vma=False)
    out, aux = f(x, p["router"], p["wg"], p["wu"], p["wd"])
    return out, aux


def _prod(mesh, axes):
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    t = 1
    for a in axes:
        t *= sizes[a]
    return t


def _moe_gather(cfg: ModelConfig, p, x):
    """pjit-auto gather/scatter implementation (portable baseline)."""
    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    Tg = min(cfg.moe_group, B * S)
    T = B * S
    pad = (-T) % Tg
    xt = x.reshape(T, d)
    if pad:
        xt = jnp.pad(xt, ((0, pad), (0, 0)))
    G = xt.shape[0] // Tg
    xg = xt.reshape(G, Tg, d)
    C = moe_capacity(cfg, Tg)

    logits = (xg @ p["router"].astype(jnp.float32)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                  # (G,Tg,E)
    w, idx = jax.lax.top_k(probs, K)                         # (G,Tg,K)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)

    # aux load-balancing loss (Switch): fraction routed vs mean prob
    f_e = jnp.mean(jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(2), axis=(0, 1))
    p_e = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(f_e / K * p_e)

    # slot assignment: position of each (token,k) within its expert's queue
    idx_f = idx.reshape(G, Tg * K)                           # token-major order
    onehot = jax.nn.one_hot(idx_f, E, dtype=jnp.int32)       # (G,TK,E)
    pos = jnp.cumsum(onehot, axis=1) - onehot                # slots before this one
    slot = jnp.take_along_axis(pos, idx_f[..., None], axis=-1)[..., 0]  # (G,TK)
    keep = (slot < C).astype(xg.dtype)

    token_of = jnp.arange(Tg * K) // K                       # (TK,)
    slot_c = jnp.minimum(slot, C - 1)

    def dispatch(xg_g, e_g, slot_g, keep_g):
        vals = xg_g[token_of] * keep_g[:, None]              # (TK, d)
        return jnp.zeros((E, C, d), xg.dtype).at[e_g, slot_g].add(vals)

    buf = jax.vmap(dispatch)(xg, idx_f, slot_c, keep)        # (G,E,C,d)

    # expert FFN (SwiGLU), E sharded on the model axis
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", buf, p["wg"].astype(xg.dtype)))
    h = h * jnp.einsum("gecd,edf->gecf", buf, p["wu"].astype(xg.dtype))
    out_buf = jnp.einsum("gecf,efd->gecd", h, p["wd"].astype(xg.dtype))

    def combine(out_g, e_g, slot_g, keep_g):
        return out_g[e_g, slot_g] * keep_g[:, None]          # (TK, d)

    y = jax.vmap(combine)(out_buf, idx_f, slot_c, keep)      # (G,TK,d)
    y = (y.reshape(G, Tg, K, d) * w[..., None].astype(y.dtype)).sum(2)
    y = y.reshape(G * Tg, d)[:T].reshape(B, S, d)
    return y, aux
