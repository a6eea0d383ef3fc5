"""Pipeline parallelism: GPipe schedule over a mesh axis via shard_map +
collective-permute (the rotating-buffer formulation).

Layers are split into ``n_stages`` contiguous groups; stage s holds its
group's params (leading dim sharded over the stage axis).  Microbatches
enter at stage 0, activations rotate stage->stage+1 each tick, outputs
drain from the last stage.  The whole schedule is differentiable
(``ppermute`` has a transpose), so ``jax.grad`` through
:func:`pipeline_apply` runs the reverse schedule automatically — the
1F1B-style memory optimization is left as a further §Perf iteration.

Intended mapping at production scale: ``pod`` axis = stage axis (pods are
the slow-link tier, and PP's point-to-point activations are the cheapest
traffic to put there); within a stage the usual DP/TP shardings apply.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map


def pipeline_apply(mesh, axis: str, stage_fn, stage_params, microbatches):
    """Run ``microbatches`` (M, mb, ...) through ``n_stages`` of
    ``stage_fn(params_slice, x) -> y``.

    ``stage_params``: pytree whose leaves have leading dim n_stages ==
    mesh axis size.  Returns (M, mb, ...) outputs.
    """
    n = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    M = microbatches.shape[0]
    perm = [(i, (i + 1) % n) for i in range(n)]

    def local(params, mbs):
        # params: this stage's slice (leading dim 1); mbs: full microbatches
        params = jax.tree.map(lambda a: a[0], params)
        stage = jax.lax.axis_index(axis)
        buf = jnp.zeros_like(mbs[0])
        outs = jnp.zeros_like(mbs)
        for t in range(M + n - 1):
            x_in = jnp.where(stage == 0,
                             mbs[min(t, M - 1)] if t < M else jnp.zeros_like(buf),
                             buf)
            y = stage_fn(params, x_in)
            buf = jax.lax.ppermute(y, axis, perm)
            # after the rotate, stage 0 holds what the LAST stage produced
            # at tick t, which is microbatch t-(n-1) fully processed
            o = t - (n - 1)
            if o >= 0:
                outs = outs.at[o].set(jnp.where(stage == 0, buf, outs[o]))
        # only stage 0 holds real outputs (others kept zeros); a psum makes
        # the result replicated so out_specs can be P()
        outs = jax.lax.psum(outs, axis)
        return outs

    P = jax.sharding.PartitionSpec
    pspec = jax.tree.map(lambda _: P(axis), stage_params)
    f = _shard_map(local, mesh=mesh, in_specs=(pspec, P()), out_specs=P(),
                   check_vma=False)
    return f(stage_params, microbatches)


def split_stages(stacked_layer_params, n_stages: int):
    """Reshape scan-stacked layer params (L, ...) -> (n_stages, L/stages, ...)."""
    def r(a):
        L = a.shape[0]
        assert L % n_stages == 0, "layers must divide stages"
        return a.reshape(n_stages, L // n_stages, *a.shape[1:])
    return jax.tree.map(r, stacked_layer_params)
