"""DeepSeek-V2-Lite cut to one chip's share: how the program and the
reference are built from ``deepseek-v2-lite.l5.json``, what one token
costs, and what each grouped product over the held experts costs.

The cut (``published`` in the file): 5 of 27 layers (the dense one and 4
MoE layers), 8 of each MoE layer's 64 experts held (the router still
scores all 64), the first 12,800 of 102,400 token ids. Every width is
published."""
from __future__ import annotations

import dataclasses
import functools

from perfbench.reference import mla_moe

ITEMS = "tokens"


def dims(c: dict) -> dict:
    """The configuration's sizes under the names the reference uses."""
    return {"d_model": c["hidden_size"], "vocab_size": c["vocab_size"],
            "n_layers": c["num_hidden_layers"],
            "k_dense": c["first_k_dense_replace"],
            "n_heads": c["num_attention_heads"],
            "kv_lora_rank": c["kv_lora_rank"],
            "rope_dim": c["qk_rope_head_dim"],
            "nope_dim": c["qk_nope_head_dim"], "v_dim": c["v_head_dim"],
            "d_dense": c["intermediate_size"],
            "d_expert": c["moe_intermediate_size"],
            "n_shared": c["n_shared_experts"],
            "n_routed": c["n_routed_experts"],
            "top_k": c["num_experts_per_tok"],
            "n_held": c["experts_held"], "held_offset": c["held_offset"],
            "routed_scale": c["routed_scaling_factor"],
            "rms_norm_eps": c["rms_norm_eps"], "rope_theta": c["rope_theta"],
            "yarn": c["rope_scaling"],
            "aux_alpha": c["assumed"]["aux_loss_alpha"]}


def sizes(c: dict) -> dict:
    return {"vocab_size": c["vocab_size"]}


def program_config(c: dict):
    """``get_config('deepseek-v2-lite')`` with the file's sizes, cut by
    ``configs.base.cut`` as the file says (the launcher's ``--layers``,
    ``--vocab``, ``--experts-held`` and ``--held-offset``)."""
    from repro.configs import get_config
    from repro.configs.base import YarnConfig, cut
    d, y = dims(c), c["rope_scaling"]
    assert y["type"] == "yarn" and c["q_lora_rank"] is None
    base = get_config("deepseek-v2-lite")
    cfg = dataclasses.replace(
        base, d_model=d["d_model"], n_heads=d["n_heads"],
        n_kv_heads=d["n_heads"], d_ff=d["d_expert"],
        rms_eps=d["rms_norm_eps"], rope_theta=d["rope_theta"],
        rope_yarn=YarnConfig(
            factor=y["factor"], beta_fast=y["beta_fast"],
            beta_slow=y["beta_slow"], mscale=y["mscale"],
            mscale_all_dim=y["mscale_all_dim"],
            original_max_position=y["original_max_position_embeddings"]),
        mla=dataclasses.replace(
            base.mla, kv_lora_rank=d["kv_lora_rank"],
            rope_head_dim=d["rope_dim"], nope_head_dim=d["nope_dim"],
            v_head_dim=d["v_dim"]),
        moe=dataclasses.replace(
            base.moe, n_routed=d["n_routed"], top_k=d["top_k"],
            n_shared=d["n_shared"], d_expert=d["d_expert"],
            first_k_dense=d["k_dense"], d_dense=d["d_dense"],
            norm_topk=c["norm_topk_prob"], routed_scale=d["routed_scale"],
            seq_aux=c["seq_aux"], router_aux_coef=d["aux_alpha"]))
    return cut(cfg, n_layers=d["n_layers"], vocab_size=d["vocab_size"],
               n_held=d["n_held"], held_offset=d["held_offset"])


def reference(c: dict, traffic: dict, chips: int, compute: str):
    """(parameter spec, loss(params, batch), the tensors stacked by layer
    or by (layer, expert), each of which LARS scales on its own)."""
    spec = mla_moe.param_spec(dims(c))
    return spec, functools.partial(mla_moe.loss, c=dims(c),
                                   smoothing=traffic["smoothing"],
                                   compute=compute), mla_moe.stacked(spec)


def items_per_row(c: dict, traffic: dict) -> int:
    return traffic["seq_len"]


def held_rows(c: dict, traffic: dict, batch: int) -> float:
    """Routed slots that reach the held experts in a step, at a uniform
    router: T k n_held / n_routed (24,576 at 4 x 8,192 tokens)."""
    d = dims(c)
    return (batch * traffic["seq_len"] * d["top_k"] * d["n_held"]
            / d["n_routed"])


def flops_per_item(c: dict, traffic: dict) -> float:
    """Training FLOPs of one token on this chip (forward and the two
    backward products of every matrix product; recomputation not
    counted): MLA's projections and its causal score and value products
    (a query at position i reads i + 1 keys), the dense layer's SwiGLU,
    each MoE layer's router, shared experts and its held experts at a
    uniform router's share of the token's slots (top_k n_held /
    n_routed), the output head."""
    d = dims(c)
    D, H, r, rope = d["d_model"], d["n_heads"], d["kv_lora_rank"], d["rope_dim"]
    nope, v = d["nope_dim"], d["v_dim"]
    moe_layers = d["n_layers"] - d["k_dense"]
    proj = (D * H * (nope + rope) + D * (r + rope) + r * H * nope
            + r * H * v + H * v * D)
    keys = (traffic["seq_len"] + 1) / 2
    attn = d["n_layers"] * (2 * proj + 2 * H * (nope + rope + v) * keys)
    dense = d["k_dense"] * 2 * 3 * D * d["d_dense"]
    held_share = d["top_k"] * d["n_held"] / d["n_routed"]
    moe = moe_layers * (2 * D * d["n_routed"]
                        + 2 * 3 * D * d["n_shared"] * d["d_expert"]
                        + held_share * 2 * 3 * D * d["d_expert"])
    head = 2 * D * d["vocab_size"]
    return 3 * (attn + dense + moe + head)


def expert_passes(c: dict, traffic: dict, batch: int):
    """[(FLOPs, bytes)] of every grouped product over the held experts in
    a training step, from the rows routed here (``held_rows``): per MoE
    layer and per weight (gate, up, down), its forward, data-gradient and
    weight-gradient pass, each reading its two bfloat16 operands and
    writing its result once. Recomputation is not counted."""
    d = dims(c)
    rows = held_rows(c, traffic, batch)
    D, f, n = d["d_model"], d["d_expert"], d["n_held"]
    out = []
    for _ in range(d["n_layers"] - d["k_dense"]):
        for k_in, k_out in ((D, f), (D, f), (f, D)):
            flops = 2 * rows * k_in * k_out
            elems = rows * k_in + n * k_in * k_out + rows * k_out
            out += [(flops, 2 * elems)] * 3
    return out
