"""Per-architecture smoke tests: reduced config of the same family, one
forward/train step + prefill + decode on CPU; shapes and finiteness."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import all_archs, get_config, get_smoke
from repro.configs.shapes import SHAPES, Shape, applicable, concrete_inputs
from repro.models.registry import build

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch", all_archs())
def test_smoke_train_step(arch):
    cfg = get_smoke(arch)
    model = build(cfg)
    params = model.init(KEY)
    batch = concrete_inputs(cfg, Shape("train_4k", "train", 64, 2))
    loss, metrics = model.loss(params, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), f"{arch}: non-finite loss"
    grads = jax.grad(lambda p: model.loss(p, batch)[0])(params)
    gn = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree.leaves(grads))
    assert gn > 0 and jnp.isfinite(gn), f"{arch}: bad grads"


@pytest.mark.parametrize("arch", all_archs())
def test_smoke_prefill_then_decode(arch):
    cfg = get_smoke(arch)
    model = build(cfg)
    params = model.init(KEY)
    batch = concrete_inputs(cfg, Shape("prefill_32k", "prefill", 32, 2))
    logits, cache = model.prefill(params, batch, 48)
    assert logits.shape == (2, 1, cfg.vocab)
    for _ in range(3):
        tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
        logits, cache = model.decode_step(params, cache, tok)
    assert bool(jnp.all(jnp.isfinite(logits))), f"{arch}: non-finite decode"
    prompt = 8 if cfg.family == "encdec" else 32   # whisper dec prompt is 8
    assert int(cache["pos"]) == prompt + 3


@pytest.mark.parametrize("arch", ["llama3.2-1b", "hymba-1.5b", "mamba2-780m",
                                  "minicpm3-4b", "moonlight-16b-a3b"])
def test_decode_matches_forward(arch):
    """Teacher-forced decode must reproduce the train-path logits."""
    cfg = get_smoke(arch)
    tol = 2e-1
    if cfg.family == "moe":
        # a top-k router flips its choice near a tie under bfloat16
        # rounding, which moves a token's logits by far more than rounding:
        # compare in float32, where the two paths differ by rounding alone
        cfg, tol = dataclasses.replace(cfg, compute_dtype="float32"), 1e-4
    model = build(cfg)
    params = model.init(KEY)
    S = 16
    toks = (jax.random.randint(KEY, (1, S), 1, cfg.vocab - 1)).astype(jnp.int32)
    full_logits, _ = model.forward(params, {"tokens": toks})
    _, cache = model.prefill(params, {"tokens": toks[:, :1]}, S + 2)
    outs = []
    for t in range(1, S):
        lg, cache = model.decode_step(params, cache, toks[:, t:t + 1])
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, axis=1)                      # logits at positions 1..S-1
    ref = full_logits[:, 1:S]
    err = jnp.max(jnp.abs(dec - ref))
    assert float(err) < tol, f"{arch}: decode/forward divergence {float(err)}"


@pytest.mark.parametrize("arch", all_archs())
def test_full_config_consistency(arch):
    """The FULL configs match the assignment table (never instantiated)."""
    cfg = get_config(arch)
    table = {
        "llama3.2-1b": (16, 2048, 32, 8, 8192, 128256),
        "minicpm3-4b": (62, 2560, 40, 40, 6400, 73448),
        "granite-20b": (52, 6144, 48, 1, 24576, 49152),
        "minitron-8b": (32, 4096, 32, 8, 16384, 256000),
        "whisper-small": (24, 768, 12, 12, 3072, 51865),
        "arctic-480b": (35, 7168, 56, 8, 4864, 32000),
        "qwen3-moe-30b-a3b": (48, 2048, 32, 4, 0, 151936),
        "hymba-1.5b": (32, 1600, 25, 5, 5504, 32001),
        "qwen2-vl-7b": (28, 3584, 28, 4, 18944, 152064),
        "mamba2-780m": (48, 1536, 0, 0, 0, 50280),
        "moonlight-16b-a3b": (27, 2048, 16, 16, 11264, 163840),
    }[arch]
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff,
           cfg.vocab)
    assert got == table
    if arch == "mamba2-780m":
        assert cfg.ssm_state == 128
    if arch == "hymba-1.5b":
        assert cfg.ssm_state == 16
    if arch.startswith("qwen3") or arch.startswith("arctic"):
        assert cfg.n_experts == 128
        assert cfg.top_k == (8 if arch.startswith("qwen3") else 2)
    if arch == "moonlight-16b-a3b":
        assert (cfg.n_experts, cfg.top_k, cfg.d_expert, cfg.n_shared_experts) == (64, 6, 1408, 2)
        assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.qk_rope_dim,
                cfg.v_head_dim) == (None, 512, 128, 64, 128)
        assert (cfg.first_dense, cfg.held, cfg.router, cfg.routed_scale) == (1, 64, "sigmoid", 2.446)


def test_param_counts_plausible():
    """Analytic param counts in the right ballpark for known models."""
    assert 1.1e9 < get_config("llama3.2-1b").param_count() < 1.4e9
    assert 0.7e9 < get_config("mamba2-780m").param_count() < 0.9e9
    assert 380e9 < get_config("arctic-480b").param_count() < 520e9
    a = get_config("qwen3-moe-30b-a3b")
    assert 25e9 < a.param_count() < 36e9
    assert 2e9 < a.active_param_count() < 5e9
    # 27 layers at the published widths: 13.76 M of attention each, a dense
    # SwiGLU of 69.21 M, and 26 expert layers of 64 experts (3*2048*1408
    # each), a shared SwiGLU of 2816 and the router; embedding and head
    m = get_config("moonlight-16b-a3b")
    assert abs(m.param_count() / 15.96e9 - 1) < 0.05
    assert 2.5e9 < m.active_param_count() < 3.5e9          # "A3B"


def test_long_500k_applicability():
    long = SHAPES["long_500k"]
    runs = [a for a in all_archs() if applicable(get_config(a), long)[0]]
    assert sorted(runs) == ["hymba-1.5b", "mamba2-780m"]
