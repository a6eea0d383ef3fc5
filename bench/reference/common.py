"""What the plain references of the benchmark's configurations share: a
decoder's loss over one sequence, its gradient over a batch, AdamW, the
first steps of a job from a seeded initialisation, and the naming of leaves.

Written from the equations alone and importing nothing of the program under
test.  Each ``bench/reference/<name>.py`` gives the parts that make its
architecture: ``init_params``, one ``layer``, ``step_flops``, and through
this module ``first_steps``, ``leaves`` and ``program_leaves``.

``mm_dtype`` selects the precision of every matrix product: ``float32``
(the reference, at ``highest`` precision) or ``float8_e4m3fn`` (the
control, as fp8 training computes: both operands rounded to e4m3 with a
per-tensor scale and multiplied exactly, and the gradients flowing back
into them rounded to e5m2 under their own scale).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def normal(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(fan_in)


def _round(x, dtype):
    """Round to an fp8 type under a per-tensor scale (amax -> its max)."""
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, float(jnp.finfo(dtype).max) / amax, 1.0)
    return (x * s).astype(dtype).astype(jnp.float32) / s


@jax.custom_vjp
def _round_fp8(x):
    """fp8 training's rounding: e4m3 on the way forward, and the gradient
    that flows back through it rounded to e5m2 under its own scale."""
    return _round(x, jnp.float8_e4m3fn)


def _round_fp8_fwd(x):
    return _round(x, jnp.float8_e4m3fn), None


def _round_fp8_bwd(_, g):
    return (_round(g, jnp.float8_e5m2),)


_round_fp8.defvjp(_round_fp8_fwd, _round_fp8_bwd)


def matmul(mm_dtype):
    low = jnp.dtype(mm_dtype) != jnp.float32

    def mm(spec, a, b):
        if low:
            a, b = _round_fp8(a), _round_fp8(b)
        return jnp.einsum(spec, a, b, precision=HI,
                          preferred_element_type=jnp.float32)
    return mm


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def causal_attention(mm, q, k, v):
    """q, k: (S, H, dk); v: (S, H, dv) -> (S, H * dv), scores over sqrt(dk)."""
    S = q.shape[0]
    s = mm("qhd,khd->hqk", q, k) / np.sqrt(q.shape[-1])
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s, -jnp.inf)
    return mm("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v).reshape(S, -1)


def swiglu_mlp(mm, p, h):
    g = mm("sd,df->sf", h, p["wg"])
    u = mm("sd,df->sf", h, p["wu"])
    return mm("sf,fd->sd", jax.nn.silu(g) * u, p["wd"])


def row_loss(layer, m, mm_dtype, params, tokens):
    """Mean next-token loss of one sequence (tokens: (S,) int32) through
    ``layer(m, mm, p, x, pos)`` for each layer, a final rmsnorm and the
    output head (``unembed``, or the tied embedding)."""
    mm = matmul(mm_dtype)
    pos = jnp.arange(tokens.shape[0])
    x = params["embed"][tokens]
    for p in params["layers"]:
        x = jax.checkpoint(functools.partial(layer, m, mm))(p, x, pos)
    x = rmsnorm(x, params["final_norm"], m["rms_norm_eps"])
    if "unembed" in params:
        logits = mm("sd,dv->sv", x, params["unembed"])[:-1]
    else:
        logits = mm("sd,vd->sv", x, params["embed"])[:-1]
    ll = jnp.take_along_axis(logits, tokens[1:, None], -1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - ll)


def make_loss_and_grad(layer, m: dict, mm_dtype: str = "float32"):
    """Loss and gradient over a (B, S) batch, jitted; the gradient is the
    mean of the per-row gradients (rows have equal length, so this is the
    gradient of the mean over all positions).  Rows are taken one at a time,
    so that the reference fits beside nothing else on one chip."""
    vg_row = jax.value_and_grad(functools.partial(row_loss, layer, m, mm_dtype))

    @jax.jit
    def loss_and_grad(params, tokens):
        def body(acc, row):
            l, g = vg_row(params, row)
            return (acc[0] + l, jax.tree.map(jnp.add, acc[1], g)), None
        zero = (jnp.float32(0), jax.tree.map(jnp.zeros_like, params))
        (l, g), _ = jax.lax.scan(body, zero, tokens)
        n = tokens.shape[0]
        return l / n, jax.tree.map(lambda t: t / n, g)

    return loss_and_grad


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(t)) for t in jax.tree.leaves(tree)))


@functools.partial(jax.jit, static_argnames=("opt",))
def adamw(params, grads, m, v, t, opt):
    """One AdamW step (global-norm clipping first), t counted from 1."""
    o = dict(opt)
    gn = global_norm(grads)
    scale = jnp.minimum(1.0, o["clip_norm"] / jnp.maximum(gn, 1e-9))
    g = jax.tree.map(lambda x: x * scale, grads)
    m = jax.tree.map(lambda a, b: o["b1"] * a + (1 - o["b1"]) * b, m, g)
    v = jax.tree.map(lambda a, b: o["b2"] * a + (1 - o["b2"]) * b * b, v, g)
    c1, c2 = 1 - o["b1"] ** t, 1 - o["b2"] ** t

    def upd(p, mi, vi):
        delta = (mi / c1) / (jnp.sqrt(vi / c2) + o["eps"]) + o["weight_decay"] * p
        return p - o["lr"] * delta
    return jax.tree.map(upd, params, m, v), m, v


def leaves(tree) -> dict:
    """A reference parameter tree by leaf: each top-level leaf by its name,
    each layer's as ``layers.<i>.<name>``."""
    out = {k: v for k, v in tree.items() if k != "layers"}
    for i, layer in enumerate(tree["layers"]):
        out.update({f"layers.{i}.{k}": v for k, v in layer.items()})
    return out


def leaf_norms(tree) -> dict:
    return {k: float(jnp.linalg.norm(v)) for k, v in leaves(tree).items()}


def program_leaves(tree) -> dict:
    """A parameter tree laid out as the program lays out a decoder (layers
    stacked on the leading axis, grouped by sub-block) keyed as ``leaves``
    keys the reference's: by each leaf's own name, on the host."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        keys = [p.key for p in path]
        leaf = np.asarray(leaf)
        if keys[0] == "layers":
            for i in range(leaf.shape[0]):
                out[f"layers.{i}.{keys[-1]}"] = leaf[i]
        else:
            out[keys[-1]] = leaf
    return out


def first_steps(init_params, layer, m: dict, opt: dict, key, batches, *,
                mm_dtype="float32", fault: str = ""):
    """The first ``len(batches)`` AdamW steps from the seeded initialisation.

    Returns each step's loss, the norm of each leaf of the first gradient
    as the optimizer gets it (clipped: its first moment over 1 - b1), and
    the norm of each leaf's change over all the steps.  ``fault`` plants one
    of the faults a timed path can have, for the check's own tests:
    ``unchanged`` (the step returns its state unchanged) or ``half_batch``
    (half of the rows left out, the mean taken over the rest)."""
    opt_t = tuple(sorted(opt.items()))
    with jax.default_matmul_precision("highest"):
        lg = make_loss_and_grad(layer, m, mm_dtype)
        params = init_params(m, key)
        p0 = params
        mom = jax.tree.map(jnp.zeros_like, params)
        vel = jax.tree.map(jnp.zeros_like, params)
        losses, grad = [], None
        for t, tokens in enumerate(batches, start=1):
            tokens = jnp.asarray(tokens)
            if fault == "half_batch":
                tokens = tokens[: max(1, tokens.shape[0] // 2)]
            loss, grads = lg(params, tokens)
            losses.append(float(loss))
            if fault != "unchanged":
                params, mom, vel = adamw(params, grads, mom, vel,
                                         jnp.float32(t), opt_t)
            del grads
            if grad is None:
                grad = leaf_norms(jax.tree.map(lambda x: x / (1 - opt["b1"]), mom))
        change = leaf_norms(jax.tree.map(jnp.subtract, params, p0))
        del params, p0, mom, vel
    return {"losses": losses, "grad": grad, "change": change}
