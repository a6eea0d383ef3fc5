"""The main path's Pallas kernels, compiled at real widths for a described
TPU v5e (no chip needed): what interpret mode cannot show — TPU block-shape
rules, Mosaic lowering — is refused here.  Each case checks that the Mosaic
kernel (``tpu_custom_call``) is in the compiled program."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, SingleDeviceSharding

from bench.timeline import scope_of
from repro.configs.registry import get_config
from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention_pallas
from repro.models.registry import build
from repro.optim.adamw import AdamW
from repro.train import steps as tsteps
from repro.kernels.quantize import quantize_pallas
from repro.kernels.ssd_scan import ssd_pallas


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2, with the persistent compile cache off (an entry
    compiled for a described chip cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield topo
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def debug_mesh(topo):
    """The 2x2 (data, model) mesh of ``launch.mesh.make_debug_mesh`` over the
    described chips."""
    return Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def test_ssd_compiles_at_mamba2_780m_widths(one_chip):
    # 48 heads, head_dim 64, state 128, one group, chunk 256, 2048 tokens
    b, s, h, p, g, n = 1, 2048, 48, 64, 1, 128
    _compile(lambda *a: ssd_pallas(*a, chunk=256), one_chip,
             ((b, s, h, p), jnp.bfloat16), ((b, s, h), jnp.float32),
             ((h,), jnp.float32), ((b, s, g, n), jnp.bfloat16),
             ((b, s, g, n), jnp.bfloat16))


def test_quantize_compiles_at_llama3_2_1b_leaf(one_chip):
    _compile(quantize_pallas, one_chip, ((2048, 8192), jnp.float32))


def test_flash_attention_compiles_at_llama3_2_1b_widths(one_chip):
    # 32 query heads, 8 kv heads (G = 4), head_dim 64, 2048 tokens; forward
    # and the backward's dQ and dK/dV kernels
    def loss(q, k, v):
        return jnp.sum(flash_attention_pallas(q, k, v).astype(jnp.float32))

    text = _compile(jax.grad(loss, (0, 1, 2)), one_chip,
                    ((1, 2048, 32, 64), jnp.bfloat16), ((1, 2048, 8, 64), jnp.bfloat16),
                    ((1, 2048, 8, 64), jnp.bfloat16))
    assert text.count('custom_call_target="tpu_custom_call"') == 3


@pytest.mark.parametrize("arch,seq,cut", [
    ("minicpm3-4b", 2048, {}),
    # Moonlight's attention and remat over a dense stack: its expert layers'
    # grouped matmuls are Mosaic calls of their own, outside ``attention``
    ("moonlight-16b-a3b", 4096, {"family": "dense", "first_dense": 0}),
], ids=["minicpm3-4b", "moonlight-16b-a3b"])
def test_train_step_attention_kernels_sit_under_the_attention_scope(
        one_chip, monkeypatch, arch, seq, cut):
    """jax.grad of the model's loss at a benchmark cell's attention widths
    (2 x seq tokens; two layers under the model's remat and layer scan; a
    small vocabulary) takes the flash kernel, forward and backward, and
    every Mosaic call carries an ``op_name`` whose scope is ``attention``:
    what ``step_attn_ms`` reads."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    cfg = dataclasses.replace(get_config(arch), n_layers=2, vocab=512, **cut)
    model = build(cfg)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    shapes = [(p.shape, p.dtype) for p in jax.tree.leaves(params)]
    treedef = jax.tree.structure(params)

    def grad(tokens, *leaves):
        p = jax.tree.unflatten(treedef, leaves)
        return jax.grad(lambda p: model.loss(p, {"tokens": tokens})[0])(p)

    _assert_attention_kernels(_compile(grad, one_chip, ((2, seq), jnp.int32), *shapes))


def _assert_attention_kernels(text):
    """The flash kernel's forward, dQ and dK/dV calls are in the program,
    and every Mosaic call's ``op_name`` is scoped ``attention``."""
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) >= 3
    for line in calls:
        op_name = re.search(r'op_name="([^"]*)"', line)
        assert scope_of(op_name and op_name.group(1)) == "attention", line[:200]


def test_train_step_takes_the_kernel_on_the_debug_mesh(debug_mesh, monkeypatch):
    """The whole train step, jitted over the 2x2 mesh as ``train.loop`` jits
    it (state and batch sharded, the mesh bound while tracing): the flash
    kernel runs per device under ``shard_map``, since a Mosaic call cannot
    be partitioned automatically.  MiniCPM3's widths, two layers, a small
    vocabulary, 4 x 2048 tokens."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    cfg = dataclasses.replace(get_config("minicpm3-4b"), n_layers=2, vocab=512)
    model, opt = build(cfg), AdamW()
    batch = {"tokens": jax.ShapeDtypeStruct((4, 2048), jnp.int32)}
    (in_sh, b_sh), (out_sh, _), state = tsteps.train_shardings(
        model, opt, debug_mesh, batch)
    step = jax.jit(tsteps.bind_mesh(tsteps.make_train_step(model, opt), debug_mesh),
                   in_shardings=(in_sh, b_sh), out_shardings=(out_sh, None))
    _assert_attention_kernels(step.lower(state, batch).compile().as_text())
