"""Operations that training needs, counted from a configuration's shapes.

One sample's forward pass is the multiply-adds of its matrix products and
convolutions, two operations each. Training one sample adds the backward
pass's two products per layer: the weights' gradient everywhere, and the
input's gradient everywhere but in a first layer that reads the data
itself (nothing upstream needs it). Elementwise work, normalisation,
softmax, recomputation, the optimizer, the mix and evaluation are not
counted.

Each model's multiply-adds are its file's ``macs(cfg)``
(``bench/models/<model>.py``): (those of one sample's forward pass, those
of its first layer).
"""
from __future__ import annotations

from bench import spec


def train_flops_per_sample(cfg: dict) -> float:
    """Forward plus backward operations of one training sample."""
    total, first = spec.model(cfg).macs(cfg)
    return 2.0 * (3 * total - first)
