"""The reference's models, losses and optimizers, in plain ``jax.numpy``.

Written from each configuration file's description and importing nothing
of the program under test. Each model's ``init`` and ``apply`` are in its
own file, ``bench/models/<model>.py``; the loss and accuracy follow the
data's kind. Initial weights follow the configuration's stated init rule
from the cell's seed, so the reference starts where the program starts
without taking its weights.

``dtype`` is the compute dtype: float32 for the reference, bfloat16 for
its control (weights, activations and optimizer state all held in it).
A configuration whose ``optimizer`` states a ``"state_dtype"`` has Adam's
moments held in that dtype instead, whatever the compute dtype.
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from bench import spec


def trunc_normal(key, shape, std, dtype):
    """Truncated normal in [-3σ, 3σ]: the configurations' dense init."""
    return (jax.random.truncated_normal(key, -3, 3, shape, jnp.float32)
            * std).astype(dtype)


def model(cfg: dict) -> Tuple[Callable, Callable]:
    """(init(key, dtype), apply(params, inputs)) of the configuration,
    from its model's file (``bench/models/<model>.py``)."""
    mod = spec.model(cfg)
    return (lambda key, dtype: mod.init(cfg, key, dtype),
            lambda params, x: mod.apply(cfg, params, x))


# ----------------------------------------------------------------------
# losses and accuracies on a batch dict
# ----------------------------------------------------------------------
def loss_and_accuracy(cfg: dict) -> Tuple[Callable, Callable]:
    """(loss(params, batch), accuracy(params, batch)): cross-entropy and
    arg-max accuracy over labels (data of kind ``"image"``) or over next
    tokens (``"lm"``), the LM's weighted by the batch's ``mask`` where it
    has one. The loss is computed in the dtype of the logits."""
    _, apply = model(cfg)
    kind = cfg["data"]["kind"]
    if kind not in ("image", "lm"):
        raise KeyError(f"data kind {kind!r}; have 'image', 'lm'")
    if kind == "image":
        def loss(p, b):
            logp = jax.nn.log_softmax(apply(p, b["x"]))
            return -jnp.mean(jnp.take_along_axis(logp, b["y"][:, None],
                                                 axis=-1))

        def acc(p, b):
            return jnp.mean((jnp.argmax(apply(p, b["x"]), -1) == b["y"])
                            .astype(jnp.float32))
        return loss, acc

    def _mask(b, tgt):
        return b["mask"] if "mask" in b else jnp.ones(tgt.shape, jnp.float32)

    def loss(p, b):
        logits = apply(p, b["tokens"])[:, :-1]
        tgt = b["tokens"][:, 1:]
        logp = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
        m = _mask(b, tgt).astype(nll.dtype)
        return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)

    def acc(p, b):
        pred = jnp.argmax(apply(p, b["tokens"])[:, :-1], -1)
        tgt = b["tokens"][:, 1:]
        m = _mask(b, tgt)
        return jnp.sum((pred == tgt) * m) / jnp.maximum(jnp.sum(m), 1.0)
    return loss, acc


# ----------------------------------------------------------------------
# optimizers: (init(params), update(grads, state) -> (new delta, state))
# ----------------------------------------------------------------------
def optimizer(spec: dict, dtype):
    """SGD, or Adam with its moments in the compute dtype ``dtype``. Where
    ``spec`` states a ``"state_dtype"``, Adam holds its moments in that
    dtype and computes in float32: each step the stored moments advance in
    float32, the update is taken from those float32 moments and then cast
    to ``dtype``, and the moments are rounded to the stated dtype to be
    kept (as optax's ``mu_dtype`` keeps its first moment)."""
    lr = spec["lr"]
    if spec["name"] == "sgd":
        return (lambda p: jnp.zeros((), jnp.int32),
                lambda g, s: (jax.tree.map(lambda x: -lr * x, g), s + 1))
    b1, b2, eps = spec["b1"], spec["b2"], spec["eps"]
    stated = spec.get("state_dtype")
    # the moments' dtype, and the dtype the step computes in
    keep = jnp.dtype(stated) if stated else dtype
    work = jnp.float32 if stated else dtype

    def init(p):
        z = jax.tree.map(lambda x: jnp.zeros(x.shape, keep), p)
        return (jnp.zeros((), jnp.int32), z, z)

    def update(g, state):
        step, mu, nu = state
        step = step + 1
        mu = jax.tree.map(
            lambda m, x: (b1 * m.astype(work) + (1 - b1) * x.astype(work))
            .astype(work), mu, g)
        nu = jax.tree.map(
            lambda v, x: (b2 * v.astype(work)
                          + (1 - b2) * x.astype(work) * x.astype(work))
            .astype(work), nu, g)
        t = step.astype(jnp.float32)
        c1, c2 = 1.0 / (1.0 - b1 ** t), 1.0 / (1.0 - b2 ** t)
        upd = jax.tree.map(
            lambda m, v: (-lr * (m * c1) / (jnp.sqrt(v * c2) + eps))
            .astype(dtype), mu, nu)
        cast = lambda t: jax.tree.map(lambda x: x.astype(keep), t)
        return upd, (step, cast(mu), cast(nu))
    return init, update


def batch_dtype(batch: Dict[str, jnp.ndarray], dtype) -> Dict[str, jnp.ndarray]:
    """Float inputs in the compute dtype; labels and tokens as they are."""
    return {k: (v.astype(dtype) if jnp.issubdtype(v.dtype, jnp.floating)
                and k != "mask" else v) for k, v in batch.items()}
