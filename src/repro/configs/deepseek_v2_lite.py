"""deepseek-v2-lite [moe] — DeepSeek-V2-Lite, 15.7B parameters with 2.4B
active a token [hf:deepseek-ai/DeepSeek-V2-Lite, arXiv:2405.04434].

Its published ``config.json``: 27 layers of width 2048; multi-head latent
attention (MLA) with 16 heads, a 512-d key/value latent, queries without
LoRA, 128-d no-rope and 64-d rope query/key heads, 128-d value heads; YaRN
rope scaling (factor 40, beta_fast 32, beta_slow 1, mscale and
mscale_all_dim 0.707, 4096 original positions, theta 10000); the first
layer dense (SwiGLU 10944), the other 26 with 64 routed experts of width
1408 (softmax router, greedy top-6, gates not renormalised, routed scale
1) and 2 shared experts; a per-sequence balance loss (``seq_aux``, alpha
0.001); vocabulary 102,400, untied; RMSNorm eps 1e-6.

``CONFIG`` holds every one of the 64 experts (``n_held`` 64): the
dropless path computes all of them on one device. A chip's share of
expert parallelism is ``base.cut`` of it (the launcher's
``--experts-held``/``--held-offset``).
"""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, YarnConfig

CONFIG = ModelConfig(
    arch_id="deepseek-v2-lite",
    family="moe",
    source="https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/"
           "config.json",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,    # MLA: logical heads; KV cached as a 512-d latent
    d_ff=1408,        # per-expert FFN hidden dim
    vocab_size=102_400,
    rope_theta=10_000.0,
    rope_yarn=YarnConfig(factor=40.0, beta_fast=32.0, beta_slow=1.0,
                         mscale=0.707, mscale_all_dim=0.707,
                         original_max_position=4096),
    rms_eps=1e-6,
    moe=MoEConfig(n_routed=64, top_k=6, n_shared=2, d_expert=1408,
                  first_k_dense=1, d_dense=10_944, norm_topk=False,
                  routed_scale=1.0, seq_aux=True, router_aux_coef=0.001,
                  n_held=64),
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=0, rope_head_dim=64,
                  nope_head_dim=128, v_head_dim=128),
    lars_per_published_tensor=True,
    # 2048-token attention blocks: at 4 x 8k tokens on a v5e the 1024-token
    # default's backward holds 1.1 GiB more (CPU compile for the chip)
    attn_chunk=2048,
)
