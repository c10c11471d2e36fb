"""moonshot-v1-16b-a3b [moe]: Moonlight-16B-A3B (``model_type``
deepseek_v3).  27 layers, d_model 2048, 16 heads of multi-head latent
attention (kv_lora_rank 512, q/k 128 + 64 rope, v 128, no q LoRA); layer 0
dense (SwiGLU 11264), layers 1-26 64 routed experts of 1408, top 6, sigmoid
gate (noaux_tc, one group, normalised, x2.446) and 2 shared experts;
vocab 163840, RoPE theta 50000, untied head.
[hf:moonshotai/Moonlight-16B-A3B]"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="moonshot-v1-16b-a3b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=0,
    d_ff_expert=1408,
    n_experts=64,
    top_k=6,
    vocab=163840,
    rope_theta=50_000.0,
    router="sigmoid",
    routed_scaling=2.446,
    norm_topk=True,
    seq_aux_alpha=1e-4,
    n_shared_experts=2,
    first_k_dense=1,
    d_ff_dense=11264,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
)
