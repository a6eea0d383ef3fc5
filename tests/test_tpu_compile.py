"""The main path's Pallas kernels, compiled at real widths for a described
TPU v5e (no chip needed): what interpret mode cannot show — TPU block-shape
rules, Mosaic lowering — is refused here.  Each case checks that the Mosaic
kernel (``tpu_custom_call``) is in the compiled program."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.quantize import quantize_pallas
from repro.kernels.ssd_scan import ssd_pallas


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compile cache
    off (an entry compiled for a described chip cannot be read back)."""
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
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


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
    # 32 query heads, 8 kv heads, head_dim 64, 2048 tokens
    _compile(flash_attention_pallas, one_chip,
             ((1, 2048, 32, 64), jnp.bfloat16), ((1, 2048, 8, 64), jnp.bfloat16),
             ((1, 2048, 8, 64), jnp.bfloat16))
