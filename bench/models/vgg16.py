"""VGG-16 of ``bench/configs/vgg16.json``: configuration D's 3×3
convolutions with ReLU and 2×2 max pools (``plan``), each width times
``width_mult``, then the configuration's head ``fc_width``-``n_classes``."""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from bench.reference.models import trunc_normal


def macs(cfg: dict) -> Tuple[int, int]:
    """(forward multiply-adds of one sample, those of the first layer).
    3×3 convolutions with "SAME" padding: along an axis of size h the
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


def scale(cfg: dict) -> dict:
    """The program's ``BenchScale`` fields that this configuration sets:
    its channel width multiplier."""
    return {"vgg_width": cfg["width_mult"]}


def init(cfg: dict, key, dtype):
    convs = []
    ch = cfg["in_channels"]
    for spec in cfg["plan"]:
        if spec == "M":
            continue
        out = max(8, int(spec * cfg["width_mult"]))
        key, sub = jax.random.split(key)
        std = math.sqrt(2.0 / (9 * ch))
        convs.append({"w": (jax.random.normal(sub, (3, 3, ch, out), jnp.float32)
                            * std).astype(dtype),
                      "b": jnp.zeros((out,), dtype)})
        ch = out
    k1, k2 = jax.random.split(key)
    fc = cfg["fc_width"]
    return {"convs": convs,
            "fc1": {"w": trunc_normal(k1, (ch, fc), 1.0 / math.sqrt(ch), dtype),
                    "b": jnp.zeros((fc,), dtype)},
            "fc2": {"w": trunc_normal(k2, (fc, cfg["n_classes"]),
                                      1.0 / math.sqrt(fc), dtype),
                    "b": jnp.zeros((cfg["n_classes"],), dtype)}}


def apply(cfg: dict, params, x):
    convs = iter(params["convs"])
    for spec in cfg["plan"]:
        if spec == "M":
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                      (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
            continue
        layer = next(convs)
        x = jax.lax.conv_general_dilated(
            x, layer["w"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        x = jax.nn.relu(x + layer["b"][None, None, None])
    x = jnp.mean(x, axis=(1, 2))
    x = jax.nn.relu(x @ params["fc1"]["w"] + params["fc1"]["b"][None])
    return x @ params["fc2"]["w"] + params["fc2"]["b"][None]
