"""Per-layer ``jax.checkpoint`` applies only to stacks of more than one
layer.

A single layer's backward runs right after the head and the loss, so a
checkpoint there saves no memory and recomputes the whole layer forward
(18% of the 1-layer GPT-2 TinyMem node step's FLOPs).  Deeper stacks keep
their per-layer remat.  The one-layer gradients are the same math either
way.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.walker import count_primitives
from repro.models.paper_models import gpt2_tinymem_config, lm_loss
from repro.models.transformer import ForwardOptions, forward, init_params

#: the primitive ``jax.checkpoint`` binds, as this JAX names it
CHECKPOINT = jax.make_jaxpr(jax.checkpoint(lambda x: x))(1.0).eqns[0] \
    .primitive.name


def _batch(cfg, b=2, s=12, seed=0):
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (b, s), 0,
                                cfg.vocab_size)
    return {"tokens": tokens}


def _loss_with(cfg, opts):
    """``lm_loss`` with explicit forward options."""
    def loss(params, batch):
        logits, aux = forward(params, cfg, {"tokens": batch["tokens"]},
                              opts=opts)
        logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32))
        tgt = batch["tokens"][:, 1:]
        nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
        return jnp.mean(nll) + aux
    return loss


def _checkpoints(loss, cfg) -> int:
    params = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.random.PRNGKey(0))
    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss))(params, _batch(cfg))
    return count_primitives(jaxpr, names=[CHECKPOINT])[CHECKPOINT]


@pytest.mark.parametrize("n_layers,options,checkpointed", [
    (1, "default", False),
    (1, "remat", False),
    (2, "default", True),
    (2, "remat", True),
    (2, "no_remat", False),
])
def test_layer_checkpoint_follows_depth(n_layers, options, checkpointed):
    cfg = dataclasses.replace(gpt2_tinymem_config(), n_layers=n_layers)
    if options == "default":
        loss = lm_loss(cfg)
    else:
        loss = _loss_with(cfg, ForwardOptions(remat=options == "remat"))
    got = _checkpoints(loss, cfg)
    assert (got > 0) == checkpointed, (n_layers, options, got)


def test_one_layer_grads_equal_checkpointed():
    cfg = gpt2_tinymem_config()
    assert cfg.n_layers == 1
    params = init_params(jax.random.PRNGKey(1), cfg)
    batch = _batch(cfg, seed=2)
    loss = lm_loss(cfg)
    plain = jax.jit(jax.value_and_grad(loss))(params, batch)
    remat = jax.jit(jax.value_and_grad(jax.checkpoint(loss)))(params, batch)
    np.testing.assert_allclose(plain[0], remat[0], rtol=1e-6)
    for a, b in zip(jax.tree.leaves(plain[1]), jax.tree.leaves(remat[1])):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
