"""ResNet-50: how the program and the reference are built from
``resnet50.json``, and what one image costs."""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

from perfbench import counts
from perfbench.reference import resnet

ITEMS = "images"


def sizes(c: dict) -> dict:
    return {"image_size": c["image_size"], "n_classes": c["n_classes"]}


def program_config(c: dict):
    from repro.configs import get_config
    return dataclasses.replace(
        get_config("resnet50"), image_size=c["image_size"],
        n_classes=c["n_classes"], width=c["width"],
        bn_momentum=c["bn_momentum"], sync_bn=False)


def reference(c: dict, traffic: dict, chips: int, compute: str):
    """(parameter spec, loss(params, batch), stacked tensors: none).
    Batch-norm statistics are taken per chip on an explicit data-parallel
    step, over the whole batch under ``xla``."""
    groups = 1 if traffic["comm"] == "xla" else chips
    spec = resnet.param_spec(c["width"], c["n_classes"])
    loss = functools.partial(resnet.loss, smoothing=traffic["smoothing"],
                             groups=groups, compute=compute)
    return spec, loss, frozenset()


def first_batch_stats(state, c: dict) -> dict:
    """The batch statistics of the program's first step, {layer: (mean,
    variance)}, from its batch-norm state after that step: the running
    averages start at mean 0 and variance 1 and move by 1 - bn_momentum
    (in float32; undone here in float64)."""
    m = c["bn_momentum"]
    out = {}
    for block, layers in state.bn_state.items():
        for name, st in layers.items():
            mean = np.asarray(st["mean"], np.float64)
            var = np.asarray(st["var"], np.float64)
            out[f"{block}/{name}"] = (mean / (1 - m), (var - m) / (1 - m))
    return out


def items_per_row(c: dict, traffic: dict) -> int:
    return 1


def flops_per_item(c: dict, traffic: dict) -> float:
    return counts.resnet_train_flops_per_image(
        width=c["width"], image=c["image_size"], n_classes=c["n_classes"])
