"""DeepSeek-V2-236B [arXiv:2405.04434] — MLA + 2 shared / 160 routed top-6.

Routing is the plain greedy top-6 over all 160 experts: the published
group-limited routing (`n_group` 8, `topk_group` 3) is not implemented."""
from repro.configs.base import ArchConfig
from repro.nn.attention import Yarn

CONFIG = ArchConfig(
    name="deepseek-v2-236b", family="moe",
    n_layers=60, d_model=5120, n_heads=128, n_kv_heads=128, head_dim=192,
    d_ff=1536, vocab=102400,
    n_experts=160, top_k=6, n_shared=2, dense_d_ff=12288, first_dense=1,
    norm_topk_prob=False, routed_scaling_factor=16.0,
    attn_kind="mla", q_lora_rank=1536, kv_lora_rank=512,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    yarn=Yarn(factor=40.0, original_max=4096, beta_fast=32.0, beta_slow=1.0,
              mscale=0.707, mscale_all_dim=0.707),
    long_window=8192,
    default_cut=4,
    source="arXiv:2405.04434")
