"""Jit'd dispatch wrappers for the Pallas kernels.

The choice is made on ``jax.default_backend()`` alone: on the TPU the
compiled Pallas kernels run; on the CPU the jnp oracles from ``ref.py`` run
(same semantics — the CPU test path).  The kernels themselves are checked
against the oracles in interpret mode by ``tests/test_kernels.py``, and
compiled for a described v5e by ``tests/test_tpu_compile.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------- flash attention

def flash_attention(q, k, v, *, causal=True, window=0, scale=None):
    if _on_tpu():
        from repro.kernels.flash_attention import flash_attention_pallas
        return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                      scale=scale)
    return _ref.attention_ref(q, k, v, causal=causal, window=window, scale=scale)


# --------------------------------------------------------------------- SSD

def ssd(x, dt, A, B, C, *, chunk=256):
    if _on_tpu():
        from repro.kernels.ssd_scan import ssd_pallas
        return ssd_pallas(x, dt, A, B, C, chunk=chunk)
    return _ref.ssd_ref(x, dt, A, B, C, chunk=chunk)


# ----------------------------------------------------------------- quantize

def quantize(x, *, group=256):
    if _on_tpu():
        from repro.kernels.quantize import quantize_pallas
        return quantize_pallas(x, group=group)
    return _ref.quantize_ref(x, group=group)


def dequantize(q, scale, *, group=256, dtype=jnp.float32):
    return _ref.dequantize_ref(q, scale, group=group, dtype=dtype)
