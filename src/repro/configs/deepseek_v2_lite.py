"""DeepSeek-V2-Lite [arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite] —
MLA without q compression, YaRN rope, 1 dense + 26 MoE layers of 64
routed experts top-6 (softmax, greedy, no renormalization) + 2 shared.

The program rotates the rope halves of a head, (i, i + d/2); the
published code rotates interleaved pairs (2i, 2i + 1).  The two differ
by a fixed permutation of the 64 rope columns of `wq` and `wkv_a`."""
from repro.configs.base import ArchConfig
from repro.nn.attention import Yarn

CONFIG = ArchConfig(
    name="deepseek-v2-lite", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, head_dim=192,
    d_ff=1408, vocab=102400,
    n_experts=64, top_k=6, n_shared=2, dense_d_ff=10944, first_dense=1,
    norm_topk_prob=False, routed_scaling_factor=1.0,
    attn_kind="mla", q_lora_rank=0, kv_lora_rank=512,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    rope_theta=10000.0,
    yarn=Yarn(factor=40.0, original_max=4096, beta_fast=32.0, beta_slow=1.0,
              mscale=0.707, mscale_all_dim=0.707),
    default_cut=1,
    source="arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite")
