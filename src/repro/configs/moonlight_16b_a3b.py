"""Moonlight-16B-A3B — the Kimi family's small MoE with the DeepSeek-V3 block.

Source: https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json
(``model_type: deepseek_v3``; equations in arXiv:2412.19437 and
arXiv:2502.16982). 27 layers, of which the first is dense (d_ff 11264);
hidden 2048, 16 heads; latent attention with no query LoRA (kv_lora_rank
512, nope/rope/v head dims 128/64/128); 64 routed experts of width 1408,
top-6, 2 shared experts (one SwiGLU of width 2816); a sigmoid ``noaux_tc``
router (top-6 of score + correction bias, selected scores normalised and
scaled by 2.446, one group); RMSNorm eps 1e-5; RoPE theta 50000 with no
scaling; context 8192; untied head; vocabulary 163,840.

Departures for random weights: the correction bias is drawn N(0, 0.01^2)
(``router_bias_std``; the published one is trained) and the sequence-level
auxiliary loss is left out (its coefficient is not in the config).
"""

from repro.configs.base import ModelConfig, register


@register("moonlight-16b-a3b")
def config() -> ModelConfig:
    return ModelConfig(
        name="moonlight-16b-a3b",
        family="moe",
        n_layers=27,
        d_model=2048,
        n_heads=16,
        n_kv_heads=16,
        vocab_size=163840,
        q_lora_rank=0,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        n_experts=64,
        top_k=6,
        moe_d_ff=1408,
        n_shared_experts=2,
        dense_d_ff=11264,
        first_dense_layers=1,
        router_score="sigmoid",
        router_bias_std=0.01,
        norm_topk_prob=True,
        routed_scale=2.446,
        rope_theta=5e4,
        rms_eps=1e-5,
        max_position=8192,
        source="https://huggingface.co/moonshotai/Moonlight-16B-A3B",
    )
