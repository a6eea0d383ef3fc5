"""Pallas kernels vs jnp oracles: shape/dtype sweeps in interpret mode."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_smoke
from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.quantize import quantize_pallas
from repro.kernels.ssd_scan import ssd_pallas
from repro.models import attention as attn
from repro.models.layers import blocked_attention
from repro.models.registry import build

KEY = jax.random.PRNGKey(42)


@pytest.mark.parametrize("B,Sq,Skv,H,KV,D", [
    (1, 32, 32, 2, 2, 16),
    (2, 64, 64, 4, 2, 32),
    (1, 48, 96, 4, 1, 64),      # MQA + cross-length
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, None), (False, None), (True, 24)])
def test_flash_attention_matches_oracle(B, Sq, Skv, H, KV, D, dtype, causal, window):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), dtype)
    k = jax.random.normal(ks[1], (B, Skv, KV, D), dtype)
    v = jax.random.normal(ks[2], (B, Skv, KV, D), dtype)
    got = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 blk_q=16, blk_k=16, interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal, window=window or 0)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("B,S,H,KV,D,Dv,causal,window,blk", [
    (1, 64, 2, 2, 96, 64, True, None, (16, 32)),       # MiniCPM3's head dims
    (1, 64, 2, 2, 192, 128, True, None, (32, 16)),     # Moonlight's head dims
    (2, 48, 2, 2, 32, 32, False, None, (16, 16)),      # bidirectional
    (1, 64, 4, 2, 32, 16, True, None, (16, 16)),       # GQA, G = 2
    (1, 64, 4, 1, 32, 16, False, None, (32, 16)),      # MQA, G = 4
    (1, 64, 2, 2, 32, 32, True, 20, (16, 16)),         # static window
    (1, 70, 4, 2, 24, 16, True, 20, (16, 32)),         # window, GQA, Sq not a tile multiple
    (2, 50, 2, 2, 24, 16, True, None, (16, 16)),       # Sq not a tile multiple
], ids=["minicpm3_dims", "moonlight_dims", "bidirectional", "gqa", "mqa",
        "window", "window_gqa_ragged", "ragged"])
def test_flash_attention_vjp_matches_oracle(B, S, H, KV, D, Dv, causal, window, blk):
    """The kernel's output and its gradients in q, k and v against the
    materialized oracle and against ``blocked_attention``."""
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, KV, D))
    v = jax.random.normal(ks[2], (B, S, KV, Dv))
    g = jax.random.normal(ks[3], (B, S, H, Dv))

    def vjp(fn):
        o, pull = jax.vjp(fn, q, k, v)
        return (o, *pull(g))

    got = vjp(lambda q, k, v: flash_attention_pallas(
        q, k, v, causal=causal, window=window, blk_q=blk[0], blk_k=blk[1],
        interpret=True))
    oracle = vjp(lambda q, k, v: ref.attention_ref(q, k, v, causal=causal,
                                                   window=window or 0))
    blocked = vjp(lambda q, k, v: blocked_attention(q, k, v, causal=causal,
                                                    window=window, block=16))
    for name, a, b, c in zip("o dq dk dv".split(), got, oracle, blocked):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-4, err_msg=name)
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), rtol=1e-4,
                                   atol=1e-4, err_msg=name)


def _bf16_pass(x):
    """An MXU operand as the TPU's default precision takes it: one bf16 pass."""
    return x.astype(jnp.bfloat16).astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _bf16_pass_attention(q, k, v, causal, window):
    """Materialized attention that rounds every product's operands to bf16
    and keeps softmax and accumulation in float32, forward and backward:
    the arithmetic of ``blocked_attention``'s einsums on the TPU, where
    default precision is one bf16 pass."""
    return _bf16_pass_fwd(q, k, v, causal, window)[0]


def _bf16_pass_fwd(q, k, v, causal, window):
    B, Sq, H, D = q.shape
    G = H // k.shape[2]
    kr, vr = (jnp.repeat(a, G, 2).astype(jnp.float32) for a in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", _bf16_pass(q), _bf16_pass(kr)) / D ** 0.5
    iq, jk = jnp.arange(Sq)[:, None], jnp.arange(k.shape[1])[None, :]
    mask = (jk <= iq if causal else jk >= 0) & (jk > iq - window if window else True)
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
    o = jnp.einsum("bhqk,bkhd->bqhd", _bf16_pass(p), _bf16_pass(vr))
    return o.astype(q.dtype), (q, k, kr, vr, p)


def _bf16_pass_bwd(causal, window, res, do):
    q, k, kr, vr, p = res
    B, S, KV, D = k.shape
    do = _bf16_pass(do)
    dv = jnp.einsum("bhqk,bqhd->bkhd", _bf16_pass(p), do)
    dp = jnp.einsum("bqhd,bkhd->bhqk", do, _bf16_pass(vr))
    ds = _bf16_pass(p * (dp - jnp.sum(dp * p, -1, keepdims=True))) / D ** 0.5
    dq = jnp.einsum("bhqk,bkhd->bqhd", ds, _bf16_pass(kr))
    dk = jnp.einsum("bhqk,bqhd->bkhd", ds, _bf16_pass(q))

    def per_kv(x):                      # sum over the G heads of a group
        return x.reshape(B, S, KV, -1, x.shape[-1]).sum(3).astype(k.dtype)

    return dq.astype(q.dtype), per_kv(dk), per_kv(dv)


_bf16_pass_attention.defvjp(_bf16_pass_fwd, _bf16_pass_bwd)


@pytest.mark.parametrize("S,H,KV,D,Dv,window", [
    (256, 2, 2, 96, 64, None),          # MiniCPM3's head dims
    (256, 2, 2, 192, 128, None),        # Moonlight's head dims
    (200, 4, 2, 32, 32, 60),            # window, GQA, Sq not a tile multiple
], ids=["minicpm3_dims", "moonlight_dims", "window_gqa_ragged"])
def test_flash_attention_vjp_bf16_no_farther_than_parent(S, H, KV, D, Dv, window):
    """bf16 q, k, v and dO, as the model trains: the kernel's output and
    dq/dk/dv are no farther from a float32 reference than the parent's
    blocked_attention, emulated with its TPU products (one bf16 pass of
    each operand), by relative L2 error.  The 10% of room is the spread of
    where the two round: the kernel casts p and dS, the parent its operands."""
    ks = jax.random.split(KEY, 4)
    q, k, v, g = (jax.random.normal(kk, shape).astype(jnp.bfloat16) for kk, shape in
                  zip(ks, [(1, S, H, D), (1, S, KV, D), (1, S, KV, Dv), (1, S, H, Dv)]))

    def vjp(fn, *args):
        o, pull = jax.vjp(fn, *args)
        return (o, *pull(g.astype(o.dtype)))

    want = vjp(lambda q, k, v: ref.attention_ref(q, k, v, causal=True, window=window or 0),
               *(a.astype(jnp.float32) for a in (q, k, v)))
    parent = vjp(lambda q, k, v: _bf16_pass_attention(q, k, v, True, window), q, k, v)
    got = vjp(lambda q, k, v: flash_attention_pallas(
        q, k, v, causal=True, window=window, blk_q=64, blk_k=64, interpret=True), q, k, v)

    def rel(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return np.linalg.norm(a - b) / np.linalg.norm(b)

    for name, a, b, c in zip("o dq dk dv".split(), got, parent, want):
        assert a.dtype == jnp.bfloat16, name
        assert rel(a, c) <= 1.1 * rel(b, c), (name, rel(a, c), rel(b, c))


def _qkv(B, Sq, Skv, H, KV, D):
    ks = jax.random.split(KEY, 3)
    return (jax.random.normal(ks[0], (B, Sq, H, D)),
            jax.random.normal(ks[1], (B, Skv, KV, D)),
            jax.random.normal(ks[2], (B, Skv, KV, D)))


@pytest.fixture
def tpu_dispatch(monkeypatch):
    """Attention as it dispatches on the TPU, with the flash kernel run in
    interpret mode (and SSD on its oracle); gives the list of calls that
    took the flash kernel."""
    taken = []

    def kernel(*a, **kw):
        taken.append(kw)
        return flash_attention_pallas(*a, **kw, blk_q=16, blk_k=16, interpret=True)

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    monkeypatch.setattr(attn, "flash_attention_pallas", kernel)
    monkeypatch.setattr(ops, "ssd", ref.ssd_ref)
    return taken


@pytest.mark.parametrize("kw,Skv,takes", [
    ({"causal": True}, 48, True),
    ({"causal": False}, 48, True),
    ({"causal": True, "window": 20}, 48, True),
    ({"causal": True, "q_offset": 8}, 48, False),
    ({"causal": True, "kv_len": jnp.int32(40)}, 48, False),
    ({"causal": True, "window": "traced"}, 48, False),
    ({"causal": False}, 80, False),                     # cross-attention
], ids=["causal", "bidirectional", "static_window", "q_offset", "kv_len",
        "traced_window", "cross_attention"])
def test_attention_dispatch_on_tpu(tpu_dispatch, kw, Skv, takes):
    """Which calls take the kernel on the TPU; each gives blocked_attention's
    answer, the kernel to rounding and a fallback bit for bit."""
    q, k, v = _qkv(1, 48, Skv, 4, 2, 32)
    if kw.get("window") == "traced":
        run = jax.jit(lambda q, k, v, w: attn.attention(q, k, v, causal=True, window=w,
                                                         block=16))
        got, want = run(q, k, v, 20), blocked_attention(q, k, v, causal=True,
                                                        window=20, block=16)
    else:
        got = attn.attention(q, k, v, block=16, **kw)
        want = blocked_attention(q, k, v, block=16, **kw)
    assert bool(tpu_dispatch) == takes
    if takes:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)
    else:
        assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("arch,takes", [
    ("minicpm3-4b", True), ("moonlight-16b-a3b", True), ("llama3.2-1b", True),
    ("whisper-small", True), ("hymba-1.5b", False),
])
def test_model_attention_takes_kernel_on_tpu(monkeypatch, tpu_dispatch, arch, takes):
    """The train path of each family: MLA and GQA stacks with one window for
    every layer take the kernel (Whisper's self-attention too); a hybrid
    stack of traced per-layer windows keeps blocked_attention.  The loss
    agrees with the CPU path's."""
    from repro.configs.shapes import Shape, concrete_inputs
    cfg = get_smoke(arch)
    model = build(cfg)
    params = model.init(KEY)
    batch = concrete_inputs(cfg, Shape("train", "train", 32, 2))
    loss = float(model.loss(params, batch)[0])
    assert bool(tpu_dispatch) == takes
    monkeypatch.setattr(ops, "on_tpu", lambda: False)
    np.testing.assert_allclose(loss, float(model.loss(params, batch)[0]), rtol=2e-2)


@pytest.mark.parametrize("kw", [
    {"causal": True}, {"causal": False}, {"causal": True, "window": 20},
    {"causal": True, "q_offset": 8, "kv_len": jnp.int32(40), "recompute": True},
], ids=["causal", "bidirectional", "window", "offset_len_recompute"])
def test_attention_on_cpu_is_blocked_attention_bit_for_bit(kw):
    q, k, v = _qkv(2, 48, 48, 4, 2, 32)
    got = attn.attention(q, k, v, block=16, **kw)
    assert np.array_equal(np.asarray(got),
                          np.asarray(blocked_attention(q, k, v, block=16, **kw)))


@pytest.mark.parametrize("arch,tpu,static", [
    ("llama3.2-1b", False, True), ("granite-20b", False, True),
    ("llama3.2-1b", True, True), ("granite-20b", True, True),
    ("hymba-1.5b", True, False), ("hymba-1.5b", False, False),
])
def test_attention_window_static_only_where_the_kernel_may_take_it(
        monkeypatch, arch, tpu, static):
    """A stack whose layers share one window hands attention that window
    static (None for full attention), on every backend; a hybrid stack
    that mixes windowed and global layers scans them per layer."""
    from repro.configs.shapes import Shape, concrete_inputs
    seen = []
    real = attn.attention

    def spy(*a, window=None, **kw):
        seen.append(window is None or isinstance(window, int))
        return real(*a, window=window, **kw)

    monkeypatch.setattr(ops, "on_tpu", lambda: tpu)
    monkeypatch.setattr(attn, "attention", spy)
    monkeypatch.setattr(attn, "flash_attention_pallas", functools.partial(
        flash_attention_pallas, blk_q=16, blk_k=16, interpret=True))
    monkeypatch.setattr(ops, "ssd", ref.ssd_ref)
    cfg = get_smoke(arch)
    model = build(cfg)
    model.loss(model.init(KEY), concrete_inputs(cfg, Shape("train", "train", 32, 2)))
    assert seen and all(s == static for s in seen)


@pytest.mark.parametrize("b,s,h,p,g,n,chunk", [
    (1, 32, 2, 8, 1, 8, 8),
    (2, 64, 4, 16, 2, 16, 16),
    (1, 128, 4, 32, 1, 32, 32),
])
def test_ssd_matches_oracle(b, s, h, p, g, n, chunk):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    B = jax.random.normal(ks[3], (b, s, g, n))
    C = jax.random.normal(ks[4], (b, s, g, n))
    y1, st1 = ssd_pallas(x, dt, A, B, C, chunk=chunk, interpret=True)
    y2, st2 = ref.ssd_ref(x, dt, A, B, C, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(st1), np.asarray(st2), rtol=2e-3, atol=2e-3)


def test_ssd_chunked_equals_sequential_recurrence():
    """The chunked SSD algorithm is exactly the sequential SSM recurrence."""
    b, s, h, p, g, n = 1, 24, 2, 4, 1, 8
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    B = jax.random.normal(ks[3], (b, s, g, n))
    C = jax.random.normal(ks[4], (b, s, g, n))
    y, fin = ref.ssd_ref(x, dt, A, B, C, chunk=8)
    state = jnp.zeros((b, h, p, n))
    ys = []
    for t in range(s):
        yt, state = ref.ssd_decode_ref(x[:, t], dt[:, t], A, B[:, t], C[:, t], state)
        ys.append(yt)
    np.testing.assert_allclose(np.asarray(y), np.asarray(jnp.stack(ys, 1)),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(fin), np.asarray(state), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("shape,group", [((64, 512), 256), ((3, 5, 256), 128),
                                         ((1024,), 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quantize_matches_oracle(shape, group, dtype):
    x = jax.random.normal(KEY, shape, dtype) * 3
    q1, s1 = quantize_pallas(x, group=group, blk_r=16, interpret=True)
    q2, s2 = ref.quantize_ref(x, group=group)
    assert bool(jnp.all(q1 == q2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-6)


def test_quantize_roundtrip_error_bound():
    x = jax.random.normal(KEY, (128, 512)) * 5
    q, s = ref.quantize_ref(x, group=256)
    back = ref.dequantize_ref(q, s, group=256)
    err = jnp.abs(back - x)
    bound = jnp.abs(x).reshape(128, 2, 256).max(-1).repeat(256, -1).reshape(128, 512) / 127
    assert bool(jnp.all(err <= bound + 1e-6))
