"""deepseek-v2-236b [moe] — MLA (q_lora 1536, kv_lora 512, rope 64 with
YaRN x40) + 160 routed experts top-6 (softmax, group-limited: 3 of 8
groups, gates x16) + 2 shared.  60L d_model=5120 128H vocab=102400
expert d_ff=1536.  First layer dense (d_ff=12288).

Source: https://huggingface.co/deepseek-ai/DeepSeek-V2/blob/main/config.json
(and arXiv:2405.04434, §2.1-2.2)."""
from ..models.config import MLAConfig, ModelConfig, MoEConfig, YaRNConfig

CONFIG = ModelConfig(
    name="deepseek-v2-236b",
    family="moe",
    n_layers=60,
    d_model=5120,
    n_heads=128, n_kv_heads=128,
    d_ff=1536,
    vocab=102400,
    rope_theta=10000.0,
    rope_scaling=YaRNConfig(factor=40.0, original_max_position=4096,
                            beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                            mscale_all_dim=0.707),
    rms_eps=1e-6,
    mla=MLAConfig(kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64,
                  v_head_dim=128, q_lora_rank=1536),
    moe=MoEConfig(num_experts=160, top_k=6, expert_d_ff=1536,
                  num_shared=2, shared_d_ff=1536, capacity_factor=1.5,
                  scoring="softmax", n_group=8, topk_group=3,
                  routed_scaling=16.0),
    first_k_dense=1,
    first_dense_d_ff=12288,
)
