"""Mistral-NeMo-12B cut to two layers: how the program and the reference
are built from ``mistral-nemo-12b.l2.json``, and what one token costs."""
from __future__ import annotations

import dataclasses
import functools

from perfbench import counts
from perfbench.reference import dense_lm

ITEMS = "tokens"


def dims(c: dict) -> dict:
    """The configuration's sizes under the names the reference uses."""
    return {"d_model": c["hidden_size"], "d_ff": c["intermediate_size"],
            "n_heads": c["num_attention_heads"],
            "n_kv_heads": c["num_key_value_heads"],
            "head_dim": c["head_dim"], "n_layers": c["num_hidden_layers"],
            "vocab_size": c["vocab_size"], "rope_theta": c["rope_theta"],
            "rms_norm_eps": c["rms_norm_eps"]}


def sizes(c: dict) -> dict:
    return {"vocab_size": c["vocab_size"]}


def program_config(c: dict):
    from repro.configs import get_config
    d = dims(c)
    return dataclasses.replace(
        get_config("mistral-nemo-12b"), n_layers=d["n_layers"],
        d_model=d["d_model"], n_heads=d["n_heads"],
        n_kv_heads=d["n_kv_heads"], head_dim=d["head_dim"], d_ff=d["d_ff"],
        vocab_size=d["vocab_size"], rope_theta=d["rope_theta"],
        rms_eps=d["rms_norm_eps"])


def reference(c: dict, traffic: dict, chips: int, compute: str):
    """(parameter spec, loss(params, batch), the tensors stacked by
    layer, each layer of which LARS scales on its own)."""
    spec = dense_lm.param_spec(dims(c))
    return spec, functools.partial(dense_lm.loss, c=dims(c),
                                   smoothing=traffic["smoothing"],
                                   compute=compute), dense_lm.stacked(spec)


def items_per_row(c: dict, traffic: dict) -> int:
    return traffic["seq_len"]


def flops_per_item(c: dict, traffic: dict) -> float:
    return counts.decoder_flops_per_token(dims(c), traffic["seq_len"])
