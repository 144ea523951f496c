"""Kimi K2 — trillion-parameter MoE with the DeepSeek-V3 block.

Source: https://huggingface.co/moonshotai/Kimi-K2-Instruct/blob/main/config.json
61 layers, of which the first is dense (d_ff 18432); hidden 7168, 64
heads of latent attention (q_lora_rank 1536, kv_lora_rank 512, nope/rope/v
head dims 128/64/128); 384 routed experts of width 2048, top-8, 1 shared
expert; a sigmoid ``noaux_tc`` router (one group) with the selected scores
normalised and scaled by 2.827; RMSNorm eps 1e-6; RoPE theta 50000.
Vocab 163840. The YaRN rope scaling of the 128k context is not modelled.
"""

from repro.configs.base import ModelConfig, register


@register("kimi-k2-1t-a32b")
def config() -> ModelConfig:
    return ModelConfig(
        name="kimi-k2-1t-a32b",
        family="moe",
        n_layers=61,
        d_model=7168,
        n_heads=64,
        n_kv_heads=64,
        d_ff=0,
        vocab_size=163840,
        q_lora_rank=1536,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        n_experts=384,
        top_k=8,
        moe_d_ff=2048,
        n_shared_experts=1,
        dense_d_ff=18432,
        first_dense_layers=1,
        router_score="sigmoid",
        norm_topk_prob=True,
        routed_scale=2.827,
        rope_theta=5e4,
        rms_eps=1e-6,
        source="https://huggingface.co/moonshotai/Kimi-K2-Instruct",
    )
