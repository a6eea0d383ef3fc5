"""Plain reference of the training job for a DeepSeek-V3 block (Moonlight):
multi-head latent attention without query compression, leading dense
layers, then expert layers with a sigmoid router, a selection bias and
shared experts; the next-token loss plus the sequence-wise balance loss;
AdamW and the router-bias update; in float32.

Written from the equations alone (arXiv:2412.19437 §2.1, the public
``modeling_deepseek.py``) and importing nothing of the program under test.
The configuration file's ``model`` group gives the sizes; its
``departures`` lists where the equations differ from the published model.
Per layer:

    h   = rmsnorm(x) * norm1
    q   = h Wq                                      -> H heads of (nope, rope)
    kv  = h Wkv_a = [c | k_rope]; c = rmsnorm(c) * kv_norm
    [k_nope | v] = c Wkv_b                           (H heads)
    rope on q_rope and on the one shared k_rope head (half rotation; pair i
    turns at theta^(-i / rope_dim) for i < rope_dim / 2)
    x  += softmax_causal([q_nope|q_rope].[k_nope|k_rope] / sqrt(nope+rope)) v  Wo
    h2  = rmsnorm(x) * norm2

then, in a leading dense layer, x += (silu(h2 Wg) * h2 Wu) Wd; in an expert
layer, with s = sigmoid(h2 R) over all E experts (float32):

    the k experts of largest s + b are chosen (b: the router bias)
    g_e = s_e / sum of s over the chosen * routed_scaling_factor, for chosen e
    x  += sum over the held experts e of g_e (silu(h2 Wg_e) * h2 Wu_e) Wd_e
          + (silu(h2 Sg) * h2 Su) Sd                 (the shared experts)

The held experts are ``experts_held`` of the ``n_routed_experts`` from the
first (the chip's share); g_e is zero where e was not chosen, so the sum
runs over every token and every held expert, with no sort, no dispatch and
nothing dropped.  The loss is the mean over all predicted positions of
logsumexp(logits) - logit(next), logits = x U through an untied head, plus
alpha times the sum over expert layers of the balance loss, the mean over
sequences of sum_i f_i P_i, with f_i = E / (k S) times the count of the
sequence's tokens that chose i and P_i the sequence's mean of
s_i / sum_j s_j.  After each AdamW step the bias of each expert layer moves
by gamma * sign(mean load - load_i), over the step's loads of all experts.

Weights come from the seed by the same splitting of the key as the job's
initialisation (normal / sqrt(fan_in), norms at one; each expert from its
own key), so that both start from the same point.  Memory: the rows of a
batch are taken one at a time, attention one block of queries at a time,
and the optimizer's moments and the first parameters stay on the host, so
that the reference fits on one chip once the job's state is freed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import common as C

Q_BLOCK = 512             # queries per attention block


def _sizes(m: dict) -> dict:
    E = m["n_routed_experts"]
    return {"d": m["hidden_size"], "H": m["num_attention_heads"], "V": m["vocab_size"],
            "kvr": m["kv_lora_rank"], "nd": m["qk_nope_head_dim"],
            "rd": m["qk_rope_head_dim"], "vd": m["v_head_dim"],
            "f": m["intermediate_size"], "fe": m["moe_intermediate_size"],
            "fs": m["n_shared_experts"] * m["moe_intermediate_size"],
            "E": E, "K": m["num_experts_per_tok"], "held": m.get("experts_held", E),
            "first": m.get("expert_first", 0), "L": m["num_hidden_layers"],
            "dense": m["first_k_dense_replace"]}


def init_params(m: dict, key) -> dict:
    z = _sizes(m)
    d, H, V = z["d"], z["H"], z["V"]
    qk = H * (z["nd"] + z["rd"])
    mine = slice(z["first"], z["first"] + z["held"])
    k_emb, k_layers, k_un = jax.random.split(key, 3)
    dense, layers = [], []
    for i, lk in enumerate(jax.random.split(k_layers, z["L"])):
        ks = jax.random.split(lk, 8)
        a = jax.random.split(ks[0], 6)
        p = {"norm1": jnp.ones((d,)), "norm2": jnp.ones((d,)),
             "attn": {"wq": C.normal(a[1], (d, qk), d),
                      "wkv_a": C.normal(a[2], (d, z["kvr"] + z["rd"]), d),
                      "kv_norm": jnp.ones((z["kvr"],)),
                      "wkv_b": C.normal(a[3], (z["kvr"], H * (z["nd"] + z["vd"])), z["kvr"]),
                      "wo": C.normal(a[4], (H * z["vd"], d), H * z["vd"])}}
        if i < z["dense"]:
            w = jax.random.split(ks[2], 3)
            p["mlp"] = {"wg": C.normal(w[0], (d, z["f"]), d),
                        "wu": C.normal(w[1], (d, z["f"]), d),
                        "wd": C.normal(w[2], (z["f"], d), z["f"])}
            dense.append(p)
            continue
        r = jax.random.split(ks[1], 5)

        def experts(k, shape, fan_in):
            return jnp.stack([C.normal(ke, shape, fan_in)
                              for ke in jax.random.split(k, z["E"])[mine]])
        sh = jax.random.split(r[4], 3)
        p["moe"] = {"router": C.normal(r[0], (d, z["E"]), d),
                    "wg": experts(r[1], (d, z["fe"]), d),
                    "wu": experts(r[2], (d, z["fe"]), d),
                    "wd": experts(r[3], (z["fe"], d), z["fe"]),
                    "shared": {"wg": C.normal(sh[0], (d, z["fs"]), d),
                               "wu": C.normal(sh[1], (d, z["fs"]), d),
                               "wd": C.normal(sh[2], (z["fs"], d), z["fs"])}}
        layers.append(p)
    return {"embed": C.normal(k_emb, (V, d), d), "final_norm": jnp.ones((d,)),
            "unembed": C.normal(k_un, (d, V), d), "dense_layers": dense, "layers": layers}


# ------------------------------------------------------------ leaf names

def _flat(prefix: str, tree) -> dict:
    return {prefix + ".".join(str(p.key) for p in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def leaves(tree) -> dict:
    """A reference tree by leaf: ``embed``, ``layers.<i>.attn.wq``,
    ``layers.<i>.moe.shared.wg``, ``dense_layers.<i>.mlp.wd``, ..."""
    out = {k: v for k, v in tree.items() if k not in ("dense_layers", "layers")}
    for group in ("dense_layers", "layers"):
        for i, layer in enumerate(tree[group]):
            out.update(_flat(f"{group}.{i}.", layer))
    return out


def program_leaves(tree) -> dict:
    """The program's tree (each stack of layers on its leading axis) under
    the names ``leaves`` gives the reference's, on the host."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        keys = [str(p.key) for p in path]
        leaf = np.asarray(leaf)
        if keys[0] in ("dense_layers", "layers"):
            for i in range(leaf.shape[0]):
                out[f"{keys[0]}.{i}." + ".".join(keys[1:])] = leaf[i]
        else:
            out[".".join(keys)] = leaf
    return out


# ------------------------------------------------------------ the model

def _rope(x, pos, theta):
    """x: (S, heads, r); half rotation over r with r/2 pairs."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / x.shape[-1])
    ang = pos[:, None].astype(jnp.float32) * freq
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def attention_blocks(mm, q, k, v):
    """Causal attention, one block of ``Q_BLOCK`` queries at a time (each
    block recomputed on the way back): q, k (S, H, dk); v (S, H, dv) ->
    (S, H * dv), scores over sqrt(dk)."""
    S, H, dk = q.shape
    qb = min(Q_BLOCK, S)
    assert S % qb == 0

    @jax.checkpoint
    def block(args):
        qi, i0 = args
        s = mm("qhd,khd->hqk", qi, k) / np.sqrt(dk)
        visible = (i0 + jnp.arange(qb))[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(visible[None], s, -jnp.inf)
        return mm("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(block, (q.reshape(S // qb, qb, H, dk), jnp.arange(0, S, qb)))
    return out.reshape(S, -1)


def _attention(m, mm, p, h, pos):
    z = _sizes(m)
    S, H, nd, rd, kvr = h.shape[0], z["H"], z["nd"], z["rd"], z["kvr"]
    theta, eps = m["rope_theta"], m["rms_norm_eps"]
    q = mm("sd,dk->sk", h, p["wq"]).reshape(S, H, nd + rd)
    q = jnp.concatenate([q[..., :nd], _rope(q[..., nd:], pos, theta)], -1)
    kv = mm("sd,dk->sk", h, p["wkv_a"])
    c = C.rmsnorm(kv[:, :kvr], p["kv_norm"], eps)
    k_rope = _rope(kv[:, None, kvr:], pos, theta)
    kvb = mm("sr,rk->sk", c, p["wkv_b"]).reshape(S, H, nd + z["vd"])
    k = jnp.concatenate([kvb[..., :nd], jnp.broadcast_to(k_rope, (S, H, rd))], -1)
    return mm("sk,kd->sd", attention_blocks(mm, q, k, kvb[..., nd:]), p["wo"])


def route(m, mm, router, h, bias):
    """Scores s (S, E), the chosen experts (S, k) and their weights."""
    z = _sizes(m)
    s = jax.nn.sigmoid(mm("sd,de->se", h, router))
    _, idx = jax.lax.top_k(s + bias, z["K"])
    g = jnp.take_along_axis(s, idx, -1)
    if m["norm_topk_prob"]:
        g = g / (g.sum(-1, keepdims=True) + 1e-20)
    return s, idx, g * m["routed_scaling_factor"]


def experts(m, mm, p, h, bias):
    """(the layer's MLP output, per-expert load (E,), balance loss)."""
    z = _sizes(m)
    s, idx, g = route(m, mm, p["router"], h, bias)
    chosen = jax.nn.one_hot(idx, z["E"]).sum(1)                    # (S, E)
    gate = (jax.nn.one_hot(idx, z["E"]) * g[..., None]).sum(1)     # (S, E)
    gate = gate[:, z["first"]:z["first"] + z["held"]]
    a = jax.nn.silu(mm("sd,edf->esf", h, p["wg"])) * mm("sd,edf->esf", h, p["wu"])
    out = jnp.einsum("se,esd->sd", gate, mm("esf,efd->esd", a, p["wd"]),
                     precision=C.HI)
    out = out + C.swiglu_mlp(mm, p["shared"], h)
    f = chosen.sum(0) * z["E"] / (z["K"] * h.shape[0])
    P = (s / s.sum(-1, keepdims=True)).mean(0)
    return out, chosen.sum(0), jnp.sum(f * P)


def layer(m, mm, p, x, pos, bias=None):
    """One layer over one sequence: (x, per-expert load, balance loss);
    load and loss are zero in a dense layer."""
    eps = m["rms_norm_eps"]
    x = x + _attention(m, mm, p["attn"], C.rmsnorm(x, p["norm1"], eps), pos)
    h2 = C.rmsnorm(x, p["norm2"], eps)
    if "mlp" in p:
        return x + C.swiglu_mlp(mm, p["mlp"], h2), jnp.zeros(()), jnp.zeros(())
    out, load, bal = experts(m, mm, p["moe"], h2, bias)
    return x + out, load, bal


def row_loss(m, mm_dtype, params, bias, tokens):
    """(loss of one sequence, (its per-layer loads (L_moe, E), its CE))."""
    mm = C.matmul(mm_dtype)
    pos = jnp.arange(tokens.shape[0])
    x = params["embed"][tokens]
    for p in params["dense_layers"]:
        x, _, _ = jax.checkpoint(functools.partial(layer, m, mm))(p, x, pos)
    loads, bal = [], 0.0
    for p, b in zip(params["layers"], bias):
        x, load, lb = jax.checkpoint(functools.partial(layer, m, mm))(p, x, pos, b)
        loads.append(load)
        bal = bal + lb
    x = C.rmsnorm(x, params["final_norm"], m["rms_norm_eps"])
    logits = mm("sd,dv->sv", x, params["unembed"])[:-1]
    ll = jnp.take_along_axis(logits, tokens[1:, None], -1)[:, 0]
    ce = jnp.mean(jax.nn.logsumexp(logits, -1) - ll)
    return ce + m["aux_loss_alpha"] * bal, (jnp.stack(loads), ce)


def make_loss_and_grad(m: dict, mm_dtype: str = "float32"):
    """Loss, gradient and per-layer loads over a (B, S) batch, jitted; the
    loss and gradient are the means over rows, the loads their sums."""
    vg_row = jax.value_and_grad(functools.partial(row_loss, m, mm_dtype), has_aux=True)

    @jax.jit
    def loss_and_grad(params, bias, tokens):
        def body(acc, row):
            (l, (load, _ce)), g = vg_row(params, bias, row)
            return (acc[0] + l, jax.tree.map(jnp.add, acc[1], g), acc[2] + load), None
        zero = (jnp.float32(0), jax.tree.map(jnp.zeros_like, params), jnp.zeros_like(bias))
        (l, g, load), _ = jax.lax.scan(body, zero, tokens)
        n = tokens.shape[0]
        return l / n, jax.tree.map(lambda t: t / n, g), load

    return loss_and_grad


@functools.partial(jax.jit, donate_argnums=(0,))
def _adamw_leaf(p, g, mi, vi, scale, t, lr, b1, b2, eps, wd):
    g = g * scale
    mi = b1 * mi + (1 - b1) * g
    vi = b2 * vi + (1 - b2) * g * g
    delta = (mi / (1 - b1 ** t)) / (jnp.sqrt(vi / (1 - b2 ** t)) + eps) + wd * p
    return p - lr * delta, mi, vi


def adamw(params, grads, mom, vel, t, opt):
    """One AdamW step (global-norm clipping first), t counted from 1, one
    leaf at a time: params and grads on the device, the moments on the
    host."""
    gn = C.global_norm(grads)
    scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gn, 1e-9))
    hyper = [jnp.float32(x) for x in (t, opt["lr"], opt["b1"], opt["b2"], opt["eps"],
                                      opt["weight_decay"])]
    flat_p, tree = jax.tree.flatten(params)
    flat_g, flat_m, flat_v = (jax.tree.leaves(x) for x in (grads, mom, vel))
    out_p, out_m, out_v = [], [], []
    for i in range(len(flat_p)):
        p, mi, vi = _adamw_leaf(flat_p[i], flat_g[i], flat_m[i], flat_v[i], scale, *hyper)
        flat_p[i] = flat_g[i] = None
        out_p.append(p)
        out_m.append(np.asarray(mi))
        out_v.append(np.asarray(vi))
    return tuple(jax.tree.unflatten(tree, x) for x in (out_p, out_m, out_v))


def update_bias(m, bias, load):
    """b_i += gamma * sign(mean load - load_i), per expert layer."""
    return bias + m["bias_update_speed"] * jnp.sign(load.mean(-1, keepdims=True) - load)


def _host_norms(tree, minus=None) -> dict:
    out = {}
    for k, v in leaves(tree).items():
        a = np.asarray(v, np.float64)
        if minus is not None:
            a = a - np.asarray(minus[k], np.float64)
        out[k] = float(np.sqrt(np.sum(a * a)))
    return out


def first_steps(m: dict, opt: dict, key, batches, *, mm_dtype="float32",
                fault: str = ""):
    """The first ``len(batches)`` steps from the seeded initialisation:
    AdamW, then the router-bias update.

    Returns each step's loss, the norm of each leaf of the first gradient
    as the optimizer gets it (clipped: its first moment over 1 - b1), the
    norm of each leaf's change over all the steps, and the router biases
    after them (``bias``, (expert layers, E)).  ``fault`` plants one of the
    faults a timed path can have, for the check's own tests: ``unchanged``
    (the step returns its state unchanged) or ``half_batch`` (half of the
    rows left out, the mean taken over the rest)."""
    z = _sizes(m)
    with jax.default_matmul_precision("highest"):
        lg = make_loss_and_grad(m, mm_dtype)
        params = init_params(m, key)
        p0 = leaves(jax.tree.map(np.asarray, params))
        mom = jax.tree.map(lambda x: np.zeros(x.shape, np.float32), params)
        vel = jax.tree.map(lambda x: np.zeros(x.shape, np.float32), params)
        bias = jnp.zeros((z["L"] - z["dense"], z["E"]), jnp.float32)
        losses, grad = [], None
        for t, tokens in enumerate(batches, start=1):
            tokens = jnp.asarray(tokens)
            if fault == "half_batch":
                tokens = tokens[: max(1, tokens.shape[0] // 2)]
            loss, grads, load = lg(params, bias, tokens)
            losses.append(float(loss))
            if fault != "unchanged":
                params, mom, vel = adamw(params, grads, mom, vel, t, opt)
                bias = update_bias(m, bias, load)
            del grads
            if grad is None:
                grad = {k: v / (1 - opt["b1"]) for k, v in _host_norms(mom).items()}
        change = _host_norms(jax.tree.map(np.asarray, params), minus=p0)
        del params, p0, mom, vel
    return {"losses": losses, "grad": grad, "change": change, "bias": np.asarray(bias)}


# ------------------------------------------------------------ counting

def step_flops(m: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one training step, from shapes alone: every projection
    of the MLA block, the dense layers' SwiGLU, in each expert layer the
    router, the shared experts and the expected routed rows of the held
    experts (k * held / E of each token, the mean under any routing), the
    untied output head, and causal attention (half of the S x S score and
    value products); forward once, backward twice the forward.
    Recomputation, rows of other chips' experts and elementwise work are
    not model FLOPs."""
    z = _sizes(m)
    d, H = z["d"], z["H"]
    attn = 2 * (d * H * (z["nd"] + z["rd"]) + d * (z["kvr"] + z["rd"])
                + z["kvr"] * H * (z["nd"] + z["vd"]) + H * z["vd"] * d)
    dense_mlp = 2 * 3 * d * z["f"]
    moe = 2 * d * z["E"] + 2 * 3 * d * z["fs"] \
        + z["K"] * z["held"] / z["E"] * 2 * 3 * d * z["fe"]
    n_moe = z["L"] - z["dense"]
    scores = 2 * H * seq * seq * ((z["nd"] + z["rd"]) + z["vd"]) / 2      # causal
    forward = batch * seq * (z["L"] * attn + z["dense"] * dense_mlp + n_moe * moe
                             + 2 * d * z["V"]) + batch * z["L"] * scores
    return 3.0 * forward


def expert_flops(m: dict, rows: float) -> float:
    """FLOPs of the held experts' grouped matmuls over ``rows`` (token,
    expert) rows, summed over the expert layers: three products of
    2 * rows * d * f forward, each twice that backward."""
    return 18.0 * rows * m["hidden_size"] * m["moe_intermediate_size"]


def expert_bytes(m: dict, rows: float) -> float:
    """HBM bytes of the same matmuls in bfloat16: each product reads its
    rows and the held experts' weights and writes its rows (d or f wide),
    forward, and about as much for each of its two backward products."""
    z = _sizes(m)
    d, f = z["d"], z["fe"]
    weights = 3 * z["held"] * d * f * (z["L"] - z["dense"])
    return 3 * 2.0 * (3 * rows * (d + f) + weights)
