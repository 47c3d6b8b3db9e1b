"""moonlight-16b-a3b [Moonshot AI, arXiv:2502.16982; config
https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json]
— the DeepSeek-V3 block (arXiv:2412.19437 §2.1): MLA without q-LoRA,
64 routed experts (top-6, sigmoid router with score-correction bias,
normalised, scaled 2.446) plus 2 shared experts, one leading dense layer.

`moonlight-16b-a3b-ep8` is one chip's share of Moonlight served
expert-parallel over 8 chips. Each chip holds 8 of each MoE layer's 64
routed experts and 1/8 of the vocabulary; attention, the dense layer,
the shared experts and the router are replicated on every chip
(data-parallel attention). This chip holds layer 0 (dense) and 8 of the
26 MoE layers; the other 18 would lie on further pipeline stages. Keys
changed from the published config: n_layers 27 -> 9, experts_held
64 -> 8 (experts 0-7, expert_offset 0), vocab 163840 -> 20480. Every
width, the router's 64 outputs, top-6, both shared experts and the
scaling stay as published.
"""
import dataclasses

from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    arch_type="moe",
    source="https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=11264,
    moe_d_ff=1408,
    vocab=163840,
    attn_kind="mla",
    q_lora_rank=0,
    kv_lora_rank=512,
    qk_rope_dim=64,
    qk_nope_dim=128,
    v_head_dim=128,
    rope_theta=50_000.0,
    n_experts=64,
    experts_per_tok=6,
    n_shared_experts=2,
    first_k_dense=1,
    router_score="sigmoid",
    routed_scale=2.446,
    n_group=1,
    topk_group=1,
    norm_eps=1e-5,
    embed_scale=False,
    tie_embeddings=False,
)

EP8 = dataclasses.replace(
    CONFIG,
    name="moonlight-16b-a3b-ep8",
    n_layers=9,
    experts_held=8,
    expert_offset=0,
    vocab=20480,
)
