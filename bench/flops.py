"""Operations that training needs, counted from a configuration's shapes.

One sample's forward pass is the multiply-adds of its matrix products and
convolutions, two operations each. Training one sample adds the backward
pass's two products per layer: the weights' gradient everywhere, and the
input's gradient everywhere but in a first layer that reads the data
itself (nothing upstream needs it). Elementwise work, normalisation,
softmax, recomputation, the optimizer, the mix and evaluation are not
counted.
"""
from __future__ import annotations

from typing import Tuple


def ffn_macs(cfg: dict) -> Tuple[int, int]:
    """(forward multiply-adds of one sample, those of the first layer)."""
    widths = [cfg["in_dim"]] + [cfg["hidden_size"]] * (cfg["n_layers"] - 1) \
        + [cfg["n_classes"]]
    layers = [a * b for a, b in zip(widths[:-1], widths[1:])]
    return sum(layers), layers[0]


def vgg16_macs(cfg: dict) -> Tuple[int, int]:
    """3×3 convolutions with "SAME" padding: along an axis of size h the
    kernel's taps fall inside the image 3h - 2 times, so only those
    multiply-adds are counted (the padding's zeros add nothing)."""
    h, w, ch = cfg["data"]["shape"]
    layers = []
    for spec in cfg["plan"]:
        if spec == "M":
            h, w = h // 2, w // 2
            continue
        out = max(8, int(spec * cfg["width_mult"]))
        layers.append((3 * h - 2) * (3 * w - 2) * ch * out)
        ch = out
    layers += [ch * cfg["fc_width"], cfg["fc_width"] * cfg["n_classes"]]
    return sum(layers), layers[0]


def gpt2_macs(cfg: dict) -> Tuple[int, int]:
    """One sequence of the data's length: the projections, the MLP and
    the head per position, and the full S×S attention products per layer.
    The first products read the embedding, a parameter, so every input
    gradient is needed."""
    s = cfg["data"]["max_len"]
    d, ff, v = cfg["n_embd"], cfg["n_inner"], cfg["vocab_size"]
    per_token = cfg["n_layer"] * (4 * d * d + 2 * d * ff) + d * v
    attention = cfg["n_layer"] * 2 * s * s * d
    return s * per_token + attention, 0


MACS = {"ffn": ffn_macs, "vgg16": vgg16_macs, "gpt2": gpt2_macs}


def forward_flops_per_sample(cfg: dict) -> float:
    return 2.0 * MACS[cfg["model"]](cfg)[0]


def train_flops_per_sample(cfg: dict) -> float:
    """Forward plus backward operations of one training sample."""
    total, first = MACS[cfg["model"]](cfg)
    return 2.0 * (3 * total - first)
