"""moonlight-16b-a3b [moe]: DeepSeek-V3 block (latent attention, sigmoid
top-6 of 64 small experts plus 2 shared, one leading dense layer).

27L d_model=2048 16H, MLA kv_lora_rank=512 qk_nope=v=128 qk_rope=64,
dense layer 0 d_ff=11264, experts 64 x 1408 top-6 + shared 2 x 1408,
vocab=163840 untied, rope_theta=50000
[hf:moonshotai/Moonlight-16B-A3B config.json, model_type deepseek_v3].

``moonlight-16b-a3b`` is the published model, every expert held.
``moonlight-16b-a3b-ep4`` is one chip's share of a four-chip
expert-parallel deployment (one v5e-4 host): each MoE layer's 64 routed
experts are divided over 4 chips and this chip holds experts 0-15; the
router still scores all 64 at top-6, and picks on experts 16-63 add
nothing here. Attention, the shared experts, the dense layer and the
vocabulary are whole on every chip.

Departures from the published model: rotary pairs are the two halves of
the 64 rope dims (the HF code de-interleaves its weight columns first — a
fixed relabelling of random weights); ``e_score_correction_bias`` is kept
in the parameter dtype.
"""
import dataclasses

from repro.configs.base import ATTN, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    n_layers=27,
    n_dense_layers=1,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=128,
    kv_lora_rank=512,
    rope_dim=64,
    d_ff=11264,
    vocab_size=163840,
    pattern=(ATTN,),
    moe=MoEConfig(n_experts=64, top_k=6, d_expert=1408, d_shared=2816,
                  scoring="sigmoid", score_bias=True, scale=2.446),
    rope_theta=50_000.0,
    norm_eps=1e-5,
    tie_embeddings=False,
    max_position=8192,
    sub_quadratic=False,
)

EP4 = dataclasses.replace(
    CONFIG, name="moonlight-16b-a3b-ep4",
    moe=dataclasses.replace(CONFIG.moe, held=(0, 16)))
