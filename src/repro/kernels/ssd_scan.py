"""Mamba-2 SSD chunked scan as a Pallas TPU kernel.

TPU-native adaptation of the SSD algorithm: one grid cell = (batch·head,
chunk).  The chunk dimension is the innermost, "arbitrary" grid axis; the
running inter-chunk state (P x N, fp32) lives in VMEM scratch and carries
across chunks — the sequential recurrence never touches HBM.  The
intra-chunk block (Q x Q decay-masked attention-like matmul) is MXU work;
Q=chunk, P=head_dim, N=state are all 128-aligned for the production config
(mamba2-780m: Q=256, P=64, N=128).

Mosaic layout rules shape the kernel: ``dt`` arrives twice, as a (Q, 1)
column and a (1, Q) row, so every per-position quantity is either a column
(broadcast along lanes) or a row (broadcast along sublanes) and never a
(1, 1) tile broadcast both ways.  The cumulative sums are reductions against
the causal mask, and the per-head ``A`` is a scalar read from SMEM.

Oracle: ``repro.kernels.ref.ssd_ref`` (also the CPU execution path).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dtc_ref, dtr_ref, A_ref, B_ref, C_ref, y_ref, st_out_ref,
            state_scr, *, nchunks, chunk, heads):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    a = A_ref[pl.program_id(0) % heads]         # scalar (SMEM)
    x = x_ref[0].astype(jnp.float32)            # (Q, P)
    dt_col = dtc_ref[0].astype(jnp.float32)     # (Q, 1)
    dA_col = dt_col * a                         # (Q, 1)
    dA_row = dtr_ref[0].astype(jnp.float32) * a  # (1, Q)
    Bm = B_ref[0].astype(jnp.float32)           # (Q, N)
    Cm = C_ref[0].astype(jnp.float32)           # (Q, N)
    p = x.shape[1]

    i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = i >= j
    # cums[i] = sum_{k<=i} dA[k], once as a column and once as a row
    cums_col = jnp.sum(jnp.where(tri, dA_row, 0.0), axis=1, keepdims=True)
    cums_row = jnp.sum(jnp.where(i <= j, dA_col, 0.0), axis=0, keepdims=True)
    # decay[i] = e^{sum_{k>i} dA[k]}: from position i to the chunk's end
    decay = jnp.exp(jnp.sum(jnp.where(j > i, dA_row, 0.0), axis=1,
                            keepdims=True))                            # (Q,1)
    # e^{sum dA} over the whole chunk, one copy per state row
    chunk_decay = jnp.exp(jnp.sum(jnp.broadcast_to(dA_row, (p, chunk)),
                                  axis=1, keepdims=True))              # (P,1)
    xd = x * dt_col

    # intra-chunk: L[i,j] = exp(cums[i]-cums[j]) for i>=j else 0
    # (mask before exp: above-diagonal seg is large-positive)
    L = jnp.exp(jnp.where(tri, cums_col - cums_row, -jnp.inf))
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ()))) * L  # (Q,Q)
    y = jax.lax.dot(scores, xd)                                        # (Q,P)

    # inter-chunk contribution from the carried state
    state = state_scr[...]                                             # (P,N)
    y += jnp.exp(cums_col) * jax.lax.dot_general(
        Cm, state, (((1,), (1,)), ((), ())))                           # (Q,P)

    # state update: state' = e^{sum dA} * state + sum_i decay_i xd_i B_i^T
    upd = jax.lax.dot_general(xd * decay, Bm,
                              (((0,), (0,)), ((), ())))                # (P,N)
    state_scr[...] = chunk_decay * state + upd

    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(ci == nchunks - 1)
    def _emit_state():
        st_out_ref[0] = state_scr[...]


def ssd_pallas(x, dt, A, B, C, *, chunk=256, interpret=False):
    """Same contract as ``ref.ssd_ref``: x (b,s,h,p), dt (b,s,h), A (h,),
    B/C (b,s,g,n).  Returns (y (b,s,h,p), final_state (b,h,p,n))."""
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = h // g
    assert s % chunk == 0
    nc = s // chunk

    # layout: one row per (batch, head)
    xr = x.transpose(0, 2, 1, 3).reshape(b * h, s, p)
    dtr = dt.transpose(0, 2, 1).reshape(b * h, s)
    Br = B.transpose(0, 2, 1, 3).reshape(b * g, s, n)
    Cr = C.transpose(0, 2, 1, 3).reshape(b * g, s, n)

    kern = functools.partial(_kernel, nchunks=nc, chunk=chunk, heads=h)
    y, st = pl.pallas_call(
        kern,
        grid=(b * h, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda r, c: (r, c, 0)),
            pl.BlockSpec((1, chunk, 1), lambda r, c: (r, c, 0)),
            pl.BlockSpec((1, 1, chunk), lambda r, c: (r, 0, c)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, chunk, n), lambda r, c, rep=rep: (r // rep, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda r, c, rep=rep: (r // rep, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), lambda r, c: (r, c, 0)),
            pl.BlockSpec((1, p, n), lambda r, c: (r, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, p), x.dtype),
            jax.ShapeDtypeStruct((b * h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(xr, dtr[:, :, None], dtr[:, None, :], A.astype(jnp.float32), Br, Cr)
    y = y.reshape(b, h, s, p).transpose(0, 2, 1, 3)
    st = st.reshape(b, h, p, n)
    return y, st
