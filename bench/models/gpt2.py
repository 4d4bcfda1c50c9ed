"""The GPT-2-small-width stack of ``bench/configs/gpt2s-1l.json``:
pre-LayerNorm blocks with rotary positions, as the file's ``departures``
list them, on sequences of the data's ``max_len`` tokens."""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from bench.reference.models import trunc_normal


def macs(cfg: dict) -> Tuple[int, int]:
    """One sequence of the data's length: the projections, the MLP and
    the head per position, and the full S×S attention products per layer.
    The first products read the embedding, a parameter, so every input
    gradient is needed."""
    s = cfg["data"]["max_len"]
    d, ff, v = cfg["n_embd"], cfg["n_inner"], cfg["vocab_size"]
    per_token = cfg["n_layer"] * (4 * d * d + 2 * d * ff) + d * v
    attention = cfg["n_layer"] * 2 * s * s * d
    return s * per_token + attention, 0


def init(cfg: dict, key, dtype):
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


def apply(cfg: dict, params, tokens):
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
