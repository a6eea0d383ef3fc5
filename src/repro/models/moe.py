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

``moe_dropless`` is the DeepSeek-V3 expert layer (``router="sigmoid"``):
it routes over all ``n_experts``, computes the part of the result that the
experts held here give (``experts_held`` from ``expert_first``; the other
experts' part is computed on the chips that hold them), and drops nothing:
the (token, choice) rows of the held experts are sorted by expert and go
through one grouped matmul (``jax.lax.ragged_dot``) per projection.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import ModelConfig
from repro.models.layers import dense_init, swiglu


def moe_init(cfg: ModelConfig, key):
    if cfg.router == "sigmoid":
        return held_moe_init(cfg, key)
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


# ================================================ DeepSeek-V3, dropless

def held_moe_init(cfg: ModelConfig, key):
    """The router over all ``n_experts``; the weights of the held experts,
    each drawn from its own key, so that a share holds what the uncut layer
    holds for those experts; the shared experts as one SwiGLU."""
    d, E, f = cfg.d_model, cfg.n_experts, cfg.d_expert
    ks = jax.random.split(key, 5)
    mine = slice(cfg.expert_first, cfg.expert_first + cfg.held)

    def experts(k, shape, fan_in):
        return jax.vmap(lambda ke: dense_init(ke, shape, fan_in, cfg.pdt))(
            jax.random.split(k, E)[mine])

    p = {"router": dense_init(ks[0], (d, E), d, jnp.float32),
         "wg": experts(ks[1], (d, f), d), "wu": experts(ks[2], (d, f), d),
         "wd": experts(ks[3], (f, d), f)}
    if cfg.n_shared_experts:
        fs = cfg.n_shared_experts * f
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p["shared"] = {"wg": dense_init(k1, (d, fs), d, cfg.pdt),
                       "wu": dense_init(k2, (d, fs), d, cfg.pdt),
                       "wd": dense_init(k3, (fs, d), fs, cfg.pdt)}
    return p


def route_sigmoid(cfg: ModelConfig, router, bias, x):
    """DeepSeek-V3's router (``noaux_tc`` with one group) over x: (B, S, d).

    Float32 sigmoid scores; the top-k of score + bias are chosen (the bias
    steers selection only); their weights are the unbiased scores,
    normalised over the k and times ``routed_scale``.  Returns the weights
    and expert ids (B*S, k), each expert's load (tokens that chose it, (E,))
    and the sequence-wise balance loss, mean over the batch's sequences of
    sum_i f_i P_i (arXiv:2412.19437 eqs. 17-20)."""
    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    s = jax.nn.sigmoid(jnp.dot(x.reshape(B * S, d).astype(jnp.float32),
                               router.astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST))  # (T, E)
    sel = s if bias is None else s + jax.lax.stop_gradient(bias)
    _, idx = jax.lax.top_k(sel, K)
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * cfg.routed_scale
    chosen = jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(1)       # (T, E)
    f = chosen.reshape(B, S, E).sum(1) * (E / (K * S))
    P = (s / s.sum(-1, keepdims=True)).reshape(B, S, E).mean(1)
    aux = jnp.mean(jnp.sum(f * P, -1))
    return w, idx, chosen.sum(0), aux


def moe_dropless(cfg: ModelConfig, p, x, bias=None):
    """x: (B, S, d) -> (out, stats).  ``stats``: the balance loss ``aux``,
    the load of every expert ``load`` (E,), ``rows``, the rows the held
    experts' grouped matmuls were given, and ``dropped``, the (token,
    choice) pairs routed to a held expert whose output row reached the
    combine all zero."""
    B, S, d = x.shape
    T, K, Eh = B * S, cfg.top_k, cfg.held
    dt = x.dtype
    xt = x.reshape(T, d)
    with jax.named_scope("router"):
        w, idx, load, aux = route_sigmoid(cfg, p["router"], bias, x)
    with jax.named_scope("dispatch"):
        e = idx.reshape(T * K) - cfg.expert_first
        held = (e >= 0) & (e < Eh)
        e = jnp.where(held, e, Eh)               # other chips' experts sort last
        order = jnp.argsort(e, stable=True)
        sizes = jnp.zeros((Eh + 1,), jnp.int32).at[e].add(1)[:Eh]
        # a grouped matmul leaves the rows past its groups undefined (the
        # TPU's ragged_dot does not write them), on the way forward and
        # back: zero them at every product's input and output
        live = (jnp.arange(T * K) < sizes.sum())[:, None]
        rows = jnp.repeat(xt, K, axis=0).at[order].get(unique_indices=True)
        rows = jnp.where(live, rows, 0)
    with jax.named_scope("experts"):
        def gmm(a, wt):
            return jnp.where(live, jax.lax.ragged_dot(a, wt.astype(dt), sizes), 0)
        h = jax.nn.silu(gmm(rows, p["wg"])) * gmm(rows, p["wu"])
        out = gmm(h, p["wd"])
    with jax.named_scope("combine"):
        inv = jnp.zeros_like(order).at[order].set(jnp.arange(T * K, dtype=order.dtype))
        y = out.at[inv].get(unique_indices=True).reshape(T, K, d)
        # a held (token, choice) whose row came back all zero was lost on
        # the way: at dispatch, in the grouped matmul or in the unsort
        dropped = jnp.sum(held.reshape(T, K) & ~jnp.any(y != 0, axis=-1))
        y = (y * w[..., None].astype(dt)).sum(1)
    if "shared" in p:
        with jax.named_scope("shared"):
            sh = p["shared"]
            y = y + swiglu(xt, sh["wg"].astype(dt), sh["wu"].astype(dt), sh["wd"].astype(dt))
    return y.reshape(B, S, d), {"aux": aux, "load": load, "rows": sizes.sum(),
                                "dropped": dropped}
