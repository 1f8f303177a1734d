"""The DeepSeek-V2-Lite cell at a size a test on the CPU can hold: d 64, 4
heads, rope 16, 16 routed experts of which 4 are held, vocabulary 256,
sequences of 64, one dense and two MoE layers."""
from __future__ import annotations

from perfbench import spec

CELL = "deepseek-v2-lite.l5.s8192.b4"
CONFIG = {"hidden_size": 64, "num_attention_heads": 4,
          "num_key_value_heads": 4, "qk_rope_head_dim": 16,
          "qk_nope_head_dim": 16, "v_head_dim": 16, "kv_lora_rank": 32,
          "intermediate_size": 128, "moe_intermediate_size": 32,
          "n_routed_experts": 16, "experts_held": 4, "vocab_size": 256,
          "num_hidden_layers": 3}
TRAFFIC = {"seq_len": 64, "batch_per_chip": 2, "warm_steps": 1}
#: the small cell in bfloat16 against the float32 reference, seeds
#: 2^31 + 20..22 on the CPU: loss 4.1e-4, worst tensor's gradient 7.2e-3,
#: median tensor's change 7.5e-4 at the most; its float8 control 1.6e-3,
#: 5.4e-2 and 2.7e-3 at the least, half a batch 1.0e-2, 0.38 and 1.0e-2
LIMITS = {"loss_gap": 1e-3, "grad_gap": 2.5e-2, "change_gap_median": 1.6e-3}


def cell(limits: dict = None, **config):
    """The cell at its small size (``config`` over ``CONFIG``); with
    ``limits``, those in place of its limits file."""
    c = spec.Cell(spec.benchmark(), CELL)
    c.config = dict(c.config, **CONFIG, **config)
    c.traffic = dict(c.traffic, **TRAFFIC)
    if limits is not None:
        c.limits = {"limits": dict(limits)}
    return c
