"""The reference's models, losses and optimizers, in plain ``jax.numpy``.

Written from each configuration file's description and importing nothing
of the program under test. Initial weights follow the configuration's
stated init rule from the cell's seed, so the reference starts where the
program starts without taking its weights.

``dtype`` is the compute dtype: float32 for the reference, bfloat16 for
its control (weights, activations and optimizer state all held in it).
"""
from __future__ import annotations

import math
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp


def trunc_normal(key, shape, std, dtype):
    """Truncated normal in [-3σ, 3σ]: the configurations' dense init."""
    return (jax.random.truncated_normal(key, -3, 3, shape, jnp.float32)
            * std).astype(dtype)


# ----------------------------------------------------------------------
# FFN
# ----------------------------------------------------------------------
def ffn_init(cfg: dict, key, dtype):
    widths = [cfg["in_dim"]] + [cfg["hidden_size"]] * (cfg["n_layers"] - 1) \
        + [cfg["n_classes"]]
    ks = jax.random.split(key, cfg["n_layers"])
    return [{"w": trunc_normal(k, (a, b), 1.0 / math.sqrt(a), dtype),
             "b": jnp.zeros((b,), dtype)}
            for k, a, b in zip(ks, widths[:-1], widths[1:])]


def ffn_apply(cfg: dict, params, x):
    h = x.reshape(x.shape[0], -1)
    for i, layer in enumerate(params):
        h = h @ layer["w"] + layer["b"][None]
        if i < len(params) - 1:
            h = jax.nn.relu(h)
    return h


# ----------------------------------------------------------------------
# VGG-16 (configuration D convolutions, then the configuration's head)
# ----------------------------------------------------------------------
def vgg_init(cfg: dict, key, dtype):
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


def vgg_apply(cfg: dict, params, x):
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


# ----------------------------------------------------------------------
# GPT-2-small widths, pre-LayerNorm blocks, rotary positions
# ----------------------------------------------------------------------
def gpt2_init(cfg: dict, key, dtype):
    d, h, ff, v = (cfg["n_embd"], cfg["n_head"], cfg["n_inner"],
                   cfg["vocab_size"])
    hd = d // h
    ks = jax.random.split(key, 8)
    ln = lambda: {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}
    layers = []
    for i in range(cfg["n_layer"]):
        lk = jax.random.split(jax.random.fold_in(ks[3], i), 6)
        ak = jax.random.split(lk[0], 4)
        mk = jax.random.split(lk[3], 3)
        layers.append({
            "ln1": ln(), "ln2": ln(),
            # std 1/sqrt(first axis) for every projection, as the
            # configuration's init rule states (h for the output one)
            "wq": trunc_normal(ak[0], (d, h, hd), 1.0 / math.sqrt(d), dtype),
            "wk": trunc_normal(ak[1], (d, h, hd), 1.0 / math.sqrt(d), dtype),
            "wv": trunc_normal(ak[2], (d, h, hd), 1.0 / math.sqrt(d), dtype),
            "wo": trunc_normal(ak[3], (h, hd, d), 1.0 / math.sqrt(h), dtype),
            "wi": trunc_normal(mk[0], (d, ff), 1.0 / math.sqrt(d), dtype),
            "wf": trunc_normal(mk[1], (ff, d), 1.0 / math.sqrt(ff), dtype),
        })
    return {"embed": trunc_normal(ks[0], (v, d), 0.02, dtype),
            "head": trunc_normal(ks[1], (d, v), 1.0 / math.sqrt(d), dtype),
            "ln_f": ln(), "layers": layers}


def _layernorm(p, x, eps, acc):
    xf = x.astype(acc)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(acc)[None, None]
            + p["bias"].astype(acc)[None, None]).astype(x.dtype)


def _rotary(x, theta, acc):
    """Rotate the two halves of each head's features by position."""
    s, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.cos(ang).astype(acc)[None, :, None, :]
    sin = jnp.sin(ang).astype(acc)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(acc), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def gpt2_apply(cfg: dict, params, tokens):
    """Logits of every position. Normalisation, attention scores and the
    logits are computed in the weights' dtype: float32 for the reference
    (as the program computes them), bfloat16 for the control."""
    d, eps = cfg["n_embd"], cfg["layer_norm_epsilon"]
    acc = params["embed"].dtype
    x = jnp.take(params["embed"], tokens, axis=0)
    x = x * jnp.sqrt(jnp.asarray(d, jnp.float32)).astype(x.dtype)
    s = tokens.shape[1]
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    for lp in params["layers"]:
        h = _layernorm(lp["ln1"], x, eps, acc)
        q = _rotary(jnp.einsum("bsd,dhk->bshk", h, lp["wq"]), cfg["rope_theta"],
                    acc)
        k = _rotary(jnp.einsum("bsd,dhk->bshk", h, lp["wk"]), cfg["rope_theta"],
                    acc)
        v = jnp.einsum("bsd,dhk->bshk", h, lp["wv"])
        hd = q.shape[-1]
        logits = jnp.einsum("bshk,bthk->bhst", q.astype(acc),
                            k.astype(acc)) / math.sqrt(hd)
        logits = jnp.where(causal[None, None], logits, jnp.asarray(-1e30, acc))
        probs = jax.nn.softmax(logits, axis=-1)
        att = jnp.einsum("bhst,bthk->bshk", probs, v.astype(acc))
        x = x + jnp.einsum("bshk,hkd->bsd", att.astype(x.dtype), lp["wo"])
        h = _layernorm(lp["ln2"], x, eps, acc)
        x = x + jax.nn.gelu(h @ lp["wi"], approximate=True) @ lp["wf"]
    x = _layernorm(params["ln_f"], x, eps, acc)
    return (x @ params["head"]).astype(acc)


MODELS = {"ffn": (ffn_init, ffn_apply), "vgg16": (vgg_init, vgg_apply),
          "gpt2": (gpt2_init, gpt2_apply)}


def model(cfg: dict) -> Tuple[Callable, Callable]:
    """(init(key, dtype), apply(params, inputs)) of the configuration."""
    init, apply = MODELS[cfg["model"]]
    return (lambda key, dtype: init(cfg, key, dtype),
            lambda params, x: apply(cfg, params, x))


# ----------------------------------------------------------------------
# losses and accuracies on a batch dict
# ----------------------------------------------------------------------
def loss_and_accuracy(cfg: dict) -> Tuple[Callable, Callable]:
    """(loss(params, batch), accuracy(params, batch)): cross-entropy and
    arg-max accuracy over labels (images) or over next tokens, the LM's
    weighted by the batch's ``mask`` where it has one. The loss is
    computed in the dtype of the logits."""
    _, apply = model(cfg)
    if cfg["model"] != "gpt2":
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
    lr = spec["lr"]
    if spec["name"] == "sgd":
        return (lambda p: jnp.zeros((), jnp.int32),
                lambda g, s: (jax.tree.map(lambda x: -lr * x, g), s + 1))
    b1, b2, eps = spec["b1"], spec["b2"], spec["eps"]

    def init(p):
        z = jax.tree.map(lambda x: jnp.zeros(x.shape, dtype), p)
        return (jnp.zeros((), jnp.int32), z, z)

    def update(g, state):
        step, mu, nu = state
        step = step + 1
        mu = jax.tree.map(lambda m, x: (b1 * m + (1 - b1) * x).astype(dtype),
                          mu, g)
        nu = jax.tree.map(lambda v, x: (b2 * v + (1 - b2) * x * x).astype(dtype),
                          nu, g)
        t = step.astype(jnp.float32)
        c1, c2 = 1.0 / (1.0 - b1 ** t), 1.0 / (1.0 - b2 ** t)
        upd = jax.tree.map(
            lambda m, v: (-lr * (m * c1) / (jnp.sqrt(v * c2) + eps))
            .astype(dtype), mu, nu)
        return upd, (step, mu, nu)
    return init, update


def batch_dtype(batch: Dict[str, jnp.ndarray], dtype) -> Dict[str, jnp.ndarray]:
    """Float inputs in the compute dtype; labels and tokens as they are."""
    return {k: (v.astype(dtype) if jnp.issubdtype(v.dtype, jnp.floating)
                and k != "mask" else v) for k, v in batch.items()}
