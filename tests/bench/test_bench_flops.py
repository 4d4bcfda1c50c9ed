"""The benchmark's training-operation counts against XLA's own count.

``bench.flops`` counts one sample's forward and backward matrix products
and convolutions from the configuration's shapes. XLA's
``cost_analysis()`` of one loop-free forward and backward pass of the
reference model also counts elementwise work (activations, bias adds,
pooling, softmax, normalisation, the loss), which ``bench.flops`` leaves
out on purpose, so XLA reads a little higher: 0.2% to 0.7% for these
configurations on the CPU. The tolerance, up to 3% below XLA's count and
never above it, allows that elementwise share and no more: a missing layer
or a forgotten backward product would be off by far more than that, and a
count above XLA's would claim work that is not done.
"""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops
from bench.reference import models

ROOT = Path(__file__).resolve().parents[2]
CONFIGS = ["vgg16", "gpt2s-1l", "ffn3"]
#: training operations of one sample, counted by hand from the shapes
TRAIN_FLOPS = {"vgg16": 1_482_064_896, "gpt2s-1l": 6_588_518_400,
               "ffn3": 507_392}
#: the reference's initial parameters (seed 2147483001) and logits of a
#: fixed batch of two, recorded on the CPU from ``bench/reference/models.py``
#: before each model's code moved into its own file under ``bench/models/``
DIGEST = json.loads((ROOT / "tests" / "bench" / "data"
                     / "reference_digest.json").read_text())


def _config(name: str) -> dict:
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


def _inputs(cfg: dict, batch: int) -> dict:
    data = cfg["data"]
    if data["kind"] == "lm":
        return {"tokens": jnp.zeros((batch, data["max_len"]), jnp.int32)}
    return {"x": jnp.zeros((batch,) + tuple(data["shape"]), jnp.float32),
            "y": jnp.zeros((batch,), jnp.int32)}


@pytest.mark.parametrize("name", CONFIGS)
def test_train_flops_match_xla_cost_analysis(name):
    cfg = _config(name)
    init, _ = models.model(cfg)
    loss, _ = models.loss_and_accuracy(cfg)
    params = jax.eval_shape(lambda k: init(k, jnp.float32), jax.random.key(0))
    batch = 2
    compiled = jax.jit(jax.value_and_grad(loss)).lower(
        params, _inputs(cfg, batch)).compile()
    cost = compiled.cost_analysis()
    xla = (cost[0] if isinstance(cost, list) else cost)["flops"]
    ours = batch * flops.train_flops_per_sample(cfg)
    assert 0.97 * xla <= ours <= xla, (ours, xla, ours / xla)


@pytest.mark.parametrize("name", CONFIGS)
def test_train_flops_per_sample_exact(name):
    assert flops.train_flops_per_sample(_config(name)) == TRAIN_FLOPS[name]


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_init_and_logits_as_recorded(name):
    cfg, want = _config(name), DIGEST[name]
    init, apply = models.model(cfg)
    params = init(jax.random.key(2147483001), jnp.float32)
    leaves = [np.asarray(x, np.float64) for x in jax.tree.leaves(params)]
    rng = np.random.default_rng(5)
    if cfg["data"]["kind"] == "lm":
        x = jnp.asarray(rng.integers(0, cfg["vocab_size"],
                                     (2, cfg["data"]["max_len"])), jnp.int32)
    else:
        x = jnp.asarray(rng.random((2,) + tuple(cfg["data"]["shape"])),
                        jnp.float32)
    logits = np.asarray(apply(params, x), np.float64)
    # the same code on the same backend: equal to float32 rounding of the sums
    close = lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-9)
    close([np.sum(v) for v in leaves], want["leaf_sums"])
    close([np.sum(np.abs(v)) for v in leaves], want["leaf_abs_sums"])
    close(logits.reshape(-1)[:16], want["logits_first"])
    close([logits.sum(), np.abs(logits).sum()],
          [want["logits_sum"], want["logits_abs_sum"]])
