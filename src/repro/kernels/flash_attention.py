"""Flash attention as Pallas TPU kernels: a forward, and a backward that
makes it trainable (``jax.custom_vjp``).

TPU-native adaptation: the working set per grid cell is one q-tile
(blk_q x D) and one kv-tile (blk_k x D) held in VMEM; each score tile lives
only in VMEM, never in HBM.  The forward keeps the running max /
denominator / accumulator in VMEM scratch with the kv-sequence as the
innermost ("arbitrary") grid dim, and keeps each row's log-sum-exp and
its float32 output as the backward's residuals.  The backward recomputes
each tile's probabilities from q, k and that log-sum-exp: one kernel
accumulates dQ over kv-tiles, one accumulates dK/dV over q-tiles (and over
the G query heads of a GQA group), both with ``D = rowsum(dO * O)`` (O in
float32: its bf16 rounding would move ``D``, and with it dQ and dK).

Tiles that are fully masked (causal / sliding window) are skipped with
``pl.when``, and their index maps repeat the last block that is used, so a
skipped tile costs no DMA either.  Only tiles that straddle the diagonal,
the window's edge or the padded tail of the sequence pay for a mask.
MXU operands are the inputs' dtype (bf16 q/k/v in training; probabilities
and dS are cast to it); accumulation and softmax statistics are float32.

The jnp oracle is ``repro.kernels.ref.attention_ref``; CPU tests run these
kernels with ``interpret=True``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")
_LANES = 128
_NT = (((1,), (1,)), ((), ()))          # a @ b.T
_NN = (((1,), (0,)), ((), ()))          # a @ b
# the forward's residuals, by name: a remat policy that saves them keeps the
# backward from running the forward kernel again
RESIDUALS = ("flash_out", "flash_lse")


# the default tile edge, for q and kv alike: the fastest forward and backward
# of edges 256-1024 on a v5e at head dims 96/64 (Sq 2048, 48 heads) and
# 192/128 (Sq 4096, 16 heads)
_BLOCK = 1024


class _Static(NamedTuple):
    scale: float
    causal: bool
    window: Optional[int]
    blk_q: int
    blk_k: int
    seq_q: int                          # unpadded lengths
    seq_kv: int
    groups: int                         # G = H // KV
    interpret: bool


# ------------------------------------------------------ tile bookkeeping

def _relevant(st: _Static, q_start, k_start):
    """Whether any (query, key) pair of the tile is visible."""
    rel = True
    if st.causal:
        rel = k_start <= q_start + st.blk_q - 1
    if st.window is not None:
        rel = jnp.logical_and(rel, k_start + st.blk_k - 1 > q_start - st.window)
    return rel


def _needs_mask(st: _Static, q_start, k_start):
    """Whether some pair of a relevant tile is masked: the tile straddles the
    diagonal, the window's edge or the padded keys."""
    need = k_start + st.blk_k > st.seq_kv
    if st.causal:
        need = jnp.logical_or(need, k_start + st.blk_k - 1 > q_start)
    if st.window is not None:
        need = jnp.logical_or(need, k_start <= q_start + st.blk_q - 1 - st.window)
    return need


def _mask(st: _Static, q_start, k_start, shape, q_axis):
    """Visibility of the tile's pairs; the query index runs along
    ``q_axis`` (0 for a (q, k) tile, 1 for a (k, q) tile)."""
    iq = q_start + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    jk = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    mask = jk < st.seq_kv
    if st.causal:
        mask = jnp.logical_and(mask, jk <= iq)
    if st.window is not None:
        mask = jnp.logical_and(mask, jk > iq - st.window)
    return mask


def _kv_block(st: _Static, i, j):
    """The kv-tile that q-tile ``i``'s ``j``-th step loads: ``j`` itself
    where the tile is relevant, else the nearest relevant one."""
    if st.causal:
        j = jnp.minimum(j, ((i + 1) * st.blk_q - 1) // st.blk_k)
    if st.window is not None:
        j = jnp.maximum(j, jnp.maximum(i * st.blk_q - st.window + 1, 0) // st.blk_k)
    return j


def _q_block(st: _Static, j, i, n_q):
    """The q-tile that kv-tile ``j``'s step over q-tile ``i`` loads."""
    if st.causal:
        i = jnp.maximum(i, (j * st.blk_k) // st.blk_q)
    if st.window is not None:
        i = jnp.minimum(i, jnp.minimum(
            (j * st.blk_k + st.blk_k + st.window - 2) // st.blk_q, n_q - 1))
    return i


def _branches(st: _Static, q_start, k_start, body):
    """Run ``body(masked)`` on a relevant tile, masking only where needed."""
    rel = _relevant(st, q_start, k_start)
    need = _needs_mask(st, q_start, k_start)
    pl.when(jnp.logical_and(rel, need))(lambda: body(True))
    pl.when(jnp.logical_and(rel, jnp.logical_not(need)))(lambda: body(False))


def _params(interpret):
    return dict(compiler_params=pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret)


# --------------------------------------------------------------- forward

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                st: _Static, n_kv):
    qi, ki = pl.program_id(1), pl.program_id(2)
    q_start, k_start = qi * st.blk_q, ki * st.blk_k

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def body(masked):
        v = v_ref[0]
        s = jax.lax.dot_general(q_ref[0], k_ref[0], _NT,
                                preferred_element_type=jnp.float32) * st.scale
        if masked:
            s = jnp.where(_mask(st, q_start, k_start, s.shape, 0), s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe)                 # masked pairs give exp(-inf) = 0
        alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, _NN, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    _branches(st, q_start, k_start, body)

    @pl.when(ki == n_kv - 1)
    def _out():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        # a row with no visible key gets +inf, so the backward's exp gives 0
        lse = jnp.where(l > 0, m_scr[...] + jnp.log(jnp.maximum(l, 1e-30)),
                        float("inf"))
        lse_ref[0] = jnp.broadcast_to(lse, (st.blk_q, _LANES)).T[:1]


def _forward(qr, kr, vr, st: _Static):
    BH, Sq_p, D = qr.shape
    _, Skv_p, Dv = vr.shape
    G, n_q, n_kv = st.groups, Sq_p // st.blk_q, Skv_p // st.blk_k

    def kv_map(b, i, j):
        return b // G, _kv_block(st, i, j), 0

    return pl.pallas_call(
        functools.partial(_fwd_kernel, st=st, n_kv=n_kv),
        grid=(BH, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, st.blk_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, st.blk_k, D), kv_map),
            pl.BlockSpec((1, st.blk_k, Dv), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, st.blk_q, Dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, st.blk_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[jax.ShapeDtypeStruct((BH, Sq_p, Dv), jnp.float32),
                   jax.ShapeDtypeStruct((BH, 1, Sq_p), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((st.blk_q, 1), jnp.float32),
            pltpu.VMEM((st.blk_q, 1), jnp.float32),
            pltpu.VMEM((st.blk_q, Dv), jnp.float32),
        ],
        **_params(st.interpret),
    )(qr, kr, vr)


# -------------------------------------------------------------- backward

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, acc_scr, *,
               st: _Static, n_kv):
    qi, ki = pl.program_id(1), pl.program_id(2)
    q_start, k_start = qi * st.blk_q, ki * st.blk_k

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def body(masked):
        k = k_ref[0]
        s = jax.lax.dot_general(q_ref[0], k, _NT,
                                preferred_element_type=jnp.float32) * st.scale
        if masked:
            s = jnp.where(_mask(st, q_start, k_start, s.shape, 0), s, NEG_INF)
        p = jnp.exp(s - lse_ref[0][0][:, None])
        dp = jax.lax.dot_general(do_ref[0], v_ref[0], _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - di_ref[0][0][:, None])
        acc_scr[...] += jax.lax.dot_general(ds.astype(k.dtype), k, _NN,
                                            preferred_element_type=jnp.float32)

    _branches(st, q_start, k_start, body)

    @pl.when(ki == n_kv - 1)
    def _out():
        dq_ref[0] = (acc_scr[...] * st.scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_scr, dv_scr, *, st: _Static, n_q):
    ki, t = pl.program_id(1), pl.program_id(2)      # t: (head in group, q-tile)
    qi = t % n_q
    q_start, k_start = qi * st.blk_q, ki * st.blk_k

    @pl.when(t == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def body(masked):
        q, do = q_ref[0], do_ref[0]
        s = jax.lax.dot_general(k_ref[0], q, _NT,        # (blk_k, blk_q)
                                preferred_element_type=jnp.float32) * st.scale
        if masked:
            s = jnp.where(_mask(st, q_start, k_start, s.shape, 1), s, NEG_INF)
        p = jnp.exp(s - lse_ref[0])
        dv_scr[...] += jax.lax.dot_general(p.astype(do.dtype), do, _NN,
                                           preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v_ref[0], do, _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - di_ref[0])
        dk_scr[...] += jax.lax.dot_general(ds.astype(q.dtype), q, _NN,
                                           preferred_element_type=jnp.float32)

    _branches(st, q_start, k_start, body)

    @pl.when(t == st.groups * n_q - 1)
    def _out():
        dk_ref[0] = (dk_scr[...] * st.scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _backward(qr, kr, vr, o, lse, do, st: _Static):
    BH, Sq_p, D = qr.shape
    BKV, Skv_p, Dv = vr.shape
    G, n_q, n_kv = st.groups, Sq_p // st.blk_q, Skv_p // st.blk_k
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), -1)[:, None, :]

    def kv_map(b, i, j):
        return b // G, _kv_block(st, i, j), 0

    def row_q(b, i, j):
        return b, 0, i

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, st=st, n_kv=n_kv),
        grid=(BH, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, st.blk_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, st.blk_k, D), kv_map),
            pl.BlockSpec((1, st.blk_k, Dv), kv_map),
            pl.BlockSpec((1, st.blk_q, Dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, st.blk_q), row_q),
            pl.BlockSpec((1, 1, st.blk_q), row_q),
        ],
        out_specs=pl.BlockSpec((1, st.blk_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(qr.shape, qr.dtype),
        scratch_shapes=[pltpu.VMEM((st.blk_q, D), jnp.float32)],
        **_params(st.interpret),
    )(qr, kr, vr, do, lse, di)

    def q_map(b, j, t):
        return b * G + t // n_q, _q_block(st, j, t % n_q, n_q), 0

    def row_kv(b, j, t):
        return b * G + t // n_q, 0, _q_block(st, j, t % n_q, n_q)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, st=st, n_q=n_q),
        grid=(BKV, n_kv, G * n_q),
        in_specs=[
            pl.BlockSpec((1, st.blk_q, D), q_map),
            pl.BlockSpec((1, st.blk_k, D), lambda b, j, t: (b, j, 0)),
            pl.BlockSpec((1, st.blk_k, Dv), lambda b, j, t: (b, j, 0)),
            pl.BlockSpec((1, st.blk_q, Dv), q_map),
            pl.BlockSpec((1, 1, st.blk_q), row_kv),
            pl.BlockSpec((1, 1, st.blk_q), row_kv),
        ],
        out_specs=[pl.BlockSpec((1, st.blk_k, D), lambda b, j, t: (b, j, 0)),
                   pl.BlockSpec((1, st.blk_k, Dv), lambda b, j, t: (b, j, 0))],
        out_shape=[jax.ShapeDtypeStruct(kr.shape, kr.dtype),
                   jax.ShapeDtypeStruct(vr.shape, vr.dtype)],
        scratch_shapes=[pltpu.VMEM((st.blk_k, D), jnp.float32),
                        pltpu.VMEM((st.blk_k, Dv), jnp.float32)],
        **_params(st.interpret),
    )(qr, kr, vr, do, lse, di)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(qr, kr, vr, st: _Static):
    return _forward(qr, kr, vr, st)[0].astype(qr.dtype)


def _flash_fwd(qr, kr, vr, st):
    o, lse = _forward(qr, kr, vr, st)
    o, lse = checkpoint_name(o, RESIDUALS[0]), checkpoint_name(lse, RESIDUALS[1])
    return o.astype(qr.dtype), (qr, kr, vr, o, lse)


def _flash_bwd(st, res, do):
    return _backward(*res, do, st)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention_pallas(q, k, v, *, causal=True, window=None, scale=None,
                           blk_q=None, blk_k=None, interpret=False):
    """q: (B, Sq, H, D); k, v: (B, Skv, KV, D/Dv).  Returns (B, Sq, H, Dv).

    Key j is visible to query i iff j <= i (``causal``) and
    i - window < j (a static ``window`` > 0), as in ``blocked_attention``
    with no ``q_offset``.  Differentiable in q, k and v.  Tiles default to
    ``_BLOCK``, or the whole sequence where it is shorter; Sq and Skv are
    padded to tile multiples."""
    B, Sq, H, D = q.shape
    _, Skv, KV, Dv = v.shape
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    blk_q = min(blk_q or _BLOCK, Sq)
    blk_k = min(blk_k or _BLOCK, Skv)
    st = _Static(scale=float(scale), causal=bool(causal), window=window or None,
                 blk_q=blk_q, blk_k=blk_k, seq_q=Sq, seq_kv=Skv, groups=H // KV,
                 interpret=interpret)

    pad_q = (-Sq) % blk_q
    pad_k = (-Skv) % blk_k
    qr = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kr = k.transpose(0, 2, 1, 3).reshape(B * KV, Skv, D)
    vr = v.transpose(0, 2, 1, 3).reshape(B * KV, Skv, Dv)
    if pad_q:
        qr = jnp.pad(qr, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kr = jnp.pad(kr, ((0, 0), (0, pad_k), (0, 0)))
        vr = jnp.pad(vr, ((0, 0), (0, pad_k), (0, 0)))
    out = _flash(qr, kr, vr, st)
    return out[:, :Sq].reshape(B, H, Sq, Dv).transpose(0, 2, 1, 3)
