"""The feed-forward net of ``bench/configs/ffn3.json``: ``in_dim`` inputs,
``n_layers - 1`` hidden layers of ``hidden_size`` with ReLU, then
``n_classes`` logits."""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from bench.reference.models import trunc_normal


def macs(cfg: dict) -> Tuple[int, int]:
    """(forward multiply-adds of one sample, those of the first layer)."""
    widths = [cfg["in_dim"]] + [cfg["hidden_size"]] * (cfg["n_layers"] - 1) \
        + [cfg["n_classes"]]
    layers = [a * b for a, b in zip(widths[:-1], widths[1:])]
    return sum(layers), layers[0]


def init(cfg: dict, key, dtype):
    widths = [cfg["in_dim"]] + [cfg["hidden_size"]] * (cfg["n_layers"] - 1) \
        + [cfg["n_classes"]]
    ks = jax.random.split(key, cfg["n_layers"])
    return [{"w": trunc_normal(k, (a, b), 1.0 / math.sqrt(a), dtype),
             "b": jnp.zeros((b,), dtype)}
            for k, a, b in zip(ks, widths[:-1], widths[1:])]


def apply(cfg: dict, params, x):
    h = x.reshape(x.shape[0], -1)
    for i, layer in enumerate(params):
        h = h @ layer["w"] + layer["b"][None]
        if i < len(params) - 1:
            h = jax.nn.relu(h)
    return h
