"""deepseek-v2-lite [moe] — 27L d_model=2048 16H, multi-head latent
attention (kv_lora_rank=512, no q_lora_rank, qk nope/rope head dims
128/64, v_head_dim=128), layer 0 a dense SwiGLU MLP (d_ff=10944), layers
1-26 MoE: 64 routed experts of width 1408, softmax router, greedy top-6
(norm_topk_prob false), 2 shared experts; untied lm_head, vocab=102400.
[hf:deepseek-ai/DeepSeek-V2-Lite;
https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json]
Equations: DeepSeek-V2, arXiv:2405.04434, Sec. 2.1 (MLA), Sec. 2.2
(DeepSeekMoE).

Priced through the cost-model lowering only (``Workload("deepseek-v2-lite")``,
``models/frontends.lower_llm``): it is not in ``ARCHS``, the architectures
the runtime model code (``models/transformer.py``) instantiates, since that
code has no latent attention."""
from repro.models.common import ModelConfig


def get_config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite", family="moe",
        n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, d_ff=10944,
        vocab_size=102400, rope_theta=1e4,
        kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        n_experts=64, top_k=6, moe_d_ff=1408, n_shared_experts=2,
        # first_k_dense_replace = 1: one dense layer, then 26 MoE layers
        block_pattern=("attn",) + ("attn+moe",) * 26,
        tie_embeddings=False,
    )
