"""Step builders: train_step / prefill_step / serve_step, with the sharding
trees needed to jit them on the production mesh."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.registry import Model
from repro.optim import grad_compress
from repro.optim.adamw import AdamW, apply_updates
from repro.parallel import context as pctx
from repro.parallel import sharding as shd


def bind_mesh(fn, mesh):
    """Make ``mesh`` visible to mesh-aware model code (shard_map EP MoE)
    while ``fn`` is being traced."""
    if mesh is None:
        return fn

    def wrapped(*args, **kwargs):
        with pctx.with_mesh(mesh):
            return fn(*args, **kwargs)

    return wrapped


def init_train_state(model: Model, optimizer: AdamW, key):
    """{params, opt}, and ``buffers`` for a model that has state outside
    the optimizer (a DeepSeek-V3 router's bias)."""
    params = model.init(key)
    state = {"params": params, "opt": optimizer.init(params)}
    buffers = model.init_buffers()
    if buffers is not None:
        state["buffers"] = buffers
    return state


def abstract_train_state(model: Model, optimizer: AdamW):
    return jax.eval_shape(lambda: init_train_state(
        model, optimizer, jax.random.PRNGKey(0)))


def make_train_step(model: Model, optimizer: AdamW, *, compress: bool = False):
    def step(state, batch):
        buffers = state.get("buffers")
        (loss, metrics), grads = jax.value_and_grad(
            model.loss, has_aux=True)(state["params"], batch, buffers)
        with jax.named_scope("optimizer"):
            if compress:
                grads = grad_compress.compress_tree(grads)
            updates, opt, om = optimizer.update(grads, state["opt"], state["params"])
            params = apply_updates(state["params"], updates)
            new = {"params": params, "opt": opt}
            if buffers is not None:
                new["buffers"] = model.update_buffers(buffers, metrics.pop("load"))
        metrics = dict(metrics, loss=loss, **om)
        return new, metrics

    return step


def make_prefill_step(model: Model, max_len: int):
    def step(params, batch):
        return model.prefill(params, batch, max_len)
    return step


def make_serve_step(model: Model):
    def step(params, cache, tokens):
        return model.decode_step(params, cache, tokens)
    return step


# ---------------------------------------------------------------- shardings

def train_shardings(model: Model, optimizer: AdamW, mesh, batch_spec_like,
                    *, fsdp: bool = True):
    """(in_shardings, out_shardings) for ``make_train_step``'s jit."""
    state = abstract_train_state(model, optimizer)
    pspec = shd.param_specs(state["params"], mesh, fsdp=fsdp)
    mspec = shd.param_specs(state["opt"]["m"], mesh, fsdp=fsdp)
    state_spec = {"params": pspec,
                  "opt": {"m": mspec, "v": mspec, "step": shd.P()}}
    if "buffers" in state:
        state_spec["buffers"] = jax.tree.map(lambda _: shd.P(), state["buffers"])
    bspec = shd.batch_specs(batch_spec_like, mesh)
    metrics_spec = None     # replicated scalars
    return (shd.named(mesh, state_spec), shd.named(mesh, bspec)), \
        (shd.named(mesh, state_spec), metrics_spec), state


def serve_shardings(model: Model, mesh, cache_like, batch_like=None,
                    *, fsdp: bool = False):
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    pspec = shd.param_specs(params, mesh, fsdp=fsdp)
    cspec = shd.cache_specs(cache_like, mesh)
    out = {"params": shd.named(mesh, pspec), "cache": shd.named(mesh, cspec)}
    if batch_like is not None:
        out["batch"] = shd.named(mesh, shd.batch_specs(batch_like, mesh))
    return out, params
