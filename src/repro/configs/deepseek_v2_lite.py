"""deepseek-v2-lite [moe] — latent attention (MLA), DeepSeekMoE.

27L d_model=2048 16H, kv_lora_rank=512, qk_nope=128 qk_rope=64 v=128, no
q_lora; layer 0 dense (d_ff 10944), then 26 MoE layers of 64 routed
experts (d_ff 1408) top-6 softmax, unnormalised, plus 2 shared experts;
YaRN rope (factor 40 over 4096 positions); vocab 102400, untied
[arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite]

One chip's share of eight-way expert parallelism: the router keeps its 64
outputs and top-6, and this chip holds experts 0-7 of every MoE layer
(``experts_held``); attention, layer 0, the shared experts, the router,
the embedding and the head are whole here, as data-parallel attention
replicates them on every chip.
"""
from repro.configs.base import ModelConfig
from repro.models.layers import Yarn

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=192,                 # qk_nope + qk_rope
    d_ff=1408,
    vocab=102_400,
    rope_theta=10_000.0,
    norm_eps=1e-6,
    n_experts=64,
    top_k=6,
    n_shared_experts=2,
    d_shared_ff=2 * 1408,
    experts_held=tuple(range(8)),
    first_dense_layers=1,
    d_ff_dense=10_944,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    yarn=Yarn(factor=40.0, original_max_position=4096, beta_fast=32.0,
              beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    tie_embeddings=False,
)
