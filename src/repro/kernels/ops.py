"""Jit'd dispatch wrappers for the Pallas kernels.

The choice is made on ``jax.default_backend()`` alone: on the TPU the
compiled Pallas kernels run; on the CPU the jnp oracles from ``ref.py`` run
(same semantics — the CPU test path).  Attention's dispatch, which also
asks whether the flash kernel can take the call, is
``repro.models.attention.attention``.  The kernels themselves are checked
against the oracles in interpret mode by ``tests/test_kernels.py``, and
compiled for a described v5e by ``tests/test_tpu_compile.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# --------------------------------------------------------------------- SSD

def ssd(x, dt, A, B, C, *, chunk=256):
    if on_tpu():
        from repro.kernels.ssd_scan import ssd_pallas
        return ssd_pallas(x, dt, A, B, C, chunk=chunk)
    return _ref.ssd_ref(x, dt, A, B, C, chunk=chunk)


# ----------------------------------------------------------------- quantize

def quantize(x, *, group=256):
    if on_tpu():
        from repro.kernels.quantize import quantize_pallas
        return quantize_pallas(x, group=group)
    return _ref.quantize_ref(x, group=group)


def dequantize(q, scale, *, group=256, dtype=jnp.float32):
    return _ref.dequantize_ref(q, scale, group=group, dtype=dtype)
