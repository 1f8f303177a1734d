"""deepseek-v2-236b [moe] — MLA kv_lora=512, 2 shared + 160 routed top-6
[arXiv:2405.04434].

From its published ``config.json``: the first layer dense (SwiGLU 12288),
gates not renormalised and scaled by 16 (``routed_scaling_factor``), YaRN
rope scaling as DeepSeek-V2-Lite's. Unsupported here, and so left out:
the queries' LoRA (``q_lora_rank`` 1536; the queries are full rank) and
group-limited routing (8 groups, top 3; routing is greedy over all 160
experts).
"""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, YarnConfig

CONFIG = ModelConfig(
    arch_id="deepseek-v2-236b",
    family="moe",
    source="arXiv:2405.04434",
    n_layers=60,
    d_model=5120,
    n_heads=128,
    n_kv_heads=128,   # MLA: logical heads; KV cached as a 512-d latent
    d_ff=1536,        # per-expert FFN hidden dim
    vocab_size=102_400,
    rope_yarn=YarnConfig(),
    moe=MoEConfig(n_routed=160, top_k=6, n_shared=2, d_expert=1536,
                  first_k_dense=1, d_dense=12_288, norm_topk=False,
                  routed_scale=16.0),
    mla=MLAConfig(kv_lora_rank=512, rope_head_dim=64,
                  nope_head_dim=128, v_head_dim=128),
    fsdp=True,
)
