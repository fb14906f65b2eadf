"""DeepSeek-V2-Lite-16B [arXiv:2405.04434; huggingface.co/deepseek-ai/
DeepSeek-V2-Lite config.json] — MLA kv_lora=512 (no q-lora) with YaRN
rotary (factor 40 over 4096 positions), 1 dense layer then MoE: 2 shared
+ 64 routed top-6 experts of width 1408, gated by a softmax over all 64
whose top-6 values are not renormalised, with the sequence-wise balance
loss (alpha 0.001, from the paper) and no z-loss."""
from repro.models.base import ArchConfig, MLACfg, MoECfg, YarnCfg

CONFIG = ArchConfig(
    name="deepseek-v2-lite-16b", arch_type="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, d_ff=10944,
    vocab_size=102400,
    norm="rmsnorm", act="silu", gated_mlp=True,
    rope_theta=10000.0,
    rope_scaling=YarnCfg(factor=40.0, original_max_pos=4096, beta_fast=32.0,
                         beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    max_seq=163840,
    mla=MLACfg(kv_lora_rank=512, q_lora_rank=0, qk_nope_dim=128,
               qk_rope_dim=64, v_dim=128),
    moe=MoECfg(n_experts=64, top_k=6, d_expert=1408, n_shared=2,
               first_dense=1, score_fn="softmax", norm_topk=False,
               routed_scale=1.0, aux_form="seq", aux_loss_coef=0.001,
               z_loss_coef=0.0),
    source="DeepSeek-V2 [arXiv:2405.04434]",
)
