"""glm-4.5-air — paper Table 2 simulator workload, and the served
`glm-4.5-air-stage` benchmark configuration's published values.

Source: https://huggingface.co/zai-org/GLM-4.5-Air (`config.json`,
model_type `glm4_moe`; equations as in `transformers`'
`modeling_glm4_moe.py`). 46 layers, hidden 4096, GQA 96 query / 8 KV
heads of 128 with q/k/v biases, rotary on the first half of each head
(`partial_rotary_factor` 0.5, theta 1e6), RMSNorm eps 1e-5, vocab
151552, untied head. Layer 0 is a dense SwiGLU of width 10944
(`first_k_dense_replace` 1); layers 1-45 route each token to 8 of 128
experts of width 1408 plus 1 shared expert: float32 router logits,
sigmoid scores, top-8 chosen on scores + `e_score_correction_bias`,
gates = the chosen unbiased scores over their sum, times
`routed_scaling_factor` 1.0 (`n_group` = `topk_group` = 1). The one MTP
layer (`num_nextn_predict_layers`) only drafts and is not modelled.
"""
from repro.configs.base import ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="glm-4.5-air",
    family="moe",
    n_layers=46,
    d_model=4096,
    n_heads=96,
    n_kv_heads=8,
    head_dim=128,
    d_ff=10944,
    vocab_size=151552,
    rope_theta=1e6,
    rotary_frac=0.5,
    norm_eps=1e-5,
    qkv_bias=True,
    tie_embeddings=False,
    moe=MoEConfig(n_experts=128, top_k=8, d_expert=1408, n_shared=1,
                  layer_pattern="all_but_first", scoring="sigmoid",
                  selection_bias=True),
)
