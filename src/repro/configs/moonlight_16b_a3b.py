"""moonlight-16b-a3b [moe, MLA] — 27L d_model=2048 16H, vocab=163840
untied [hf:moonshotai/Moonlight-16B-A3B, model_type deepseek_v3].

The DeepSeek-V3 block at small widths: MLA without query compression
(q_lora_rank null; kv_lora 512, qk_nope 128, qk_rope 64, v_head 128); one
leading dense layer (SwiGLU 11264); then 26 expert layers of 64 routed
experts (width 1408, 6 per token) and 2 shared ones (one SwiGLU of 2816).
Router: float32 sigmoid scores, noaux_tc selection on score + bias with one
group, top-6 weights normalised and times 2.446; sequence-wise balance loss
(alpha 1e-4, DeepSeek-V3 §4.2) and the bias updated by gamma 1e-3 a step.
rope_theta 50000, rms_norm_eps 1e-5."""
from repro.models.common import ModelConfig

CONFIG = ModelConfig(
    arch="moonlight-16b-a3b", family="moe", attn_kind="mla",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=11264, vocab=163840, head_dim=192,       # qk_nope + qk_rope
    q_lora_rank=None, kv_lora_rank=512, qk_rope_dim=64, qk_nope_dim=128,
    v_head_dim=128, rope_theta=50000.0,
    n_experts=64, top_k=6, d_expert=1408, router="sigmoid", routed_scale=2.446,
    n_shared_experts=2, first_dense=1, aux_weight=1e-4, bias_rate=1e-3,
    # one chip's share at 2 x 4096 tokens a step holds 6.8 GB of state: only
    # whole-layer remat and recomputed attention blocks leave room for the
    # step (7.4 GB of temporaries, against 10.1 GB with remat "dots")
    remat="full", attn_recompute=True,
)

SMOKE = ModelConfig(
    arch="moonlight-16b-a3b-smoke", family="moe", attn_kind="mla",
    n_layers=3, d_model=64, n_heads=4, n_kv_heads=4,
    d_ff=96, vocab=256, head_dim=24,
    q_lora_rank=None, kv_lora_rank=16, qk_rope_dim=8, qk_nope_dim=16,
    v_head_dim=16, rope_theta=50000.0,
    n_experts=16, top_k=4, d_expert=32, router="sigmoid", routed_scale=2.446,
    n_shared_experts=1, first_dense=1, experts_held=4, aux_weight=1e-4,
    bias_rate=1e-3, attn_block=32, remat="full", attn_recompute=True,
)
