"""Pallas TPU kernel: causal flash attention with GQA, sliding window and
logit softcap (gemma2) — the prefill hot-spot for the 32k shapes.

TPU-native tiling (MXU 128×128):
  grid = (batch, q_heads, S/bq, S/bkv); the kv axis is the innermost
  (sequential, "arbitrary" semantics) dimension so the online-softmax
  carry (m, l, acc) lives in VMEM scratch across kv steps.
  q blocks: (bq, hd); kv blocks: (bkv, hd) — hd padded to 128 by caller.
  GQA: kv-head index = q-head // (H/KV) via the BlockSpec index_map —
  no materialized head repetition (saves KV·(groups−1) HBM reads).

VMEM per program ≈ bq·hd(q) + 2·bkv·hd(kv) + bq·bkv(logits) + bq·hd(acc)
f32 ≈ 0.6 MiB at bq=bkv=256, hd=128.

Validated against ref.flash_attention_ref in interpret mode (CPU) across
shape/dtype/window/softcap sweeps.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gossip_mix import default_interpret

__all__ = ["flash_attention_pallas"]

_NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, out_ref, m_scr, l_scr, acc_scr, *,
            scale, bq, bkv, causal, window, softcap, seq_len):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)           # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)           # (bkv, hd)
    v = v_ref[0, 0].astype(jnp.float32)

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                      # (bq, bkv)
    if softcap > 0:
        logits = jnp.tanh(logits / softcap) * softcap

    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
    kpos = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    ok = kpos < seq_len
    if causal:
        ok &= kpos <= qpos
    if window > 0:
        ok &= kpos > qpos - window
    logits = jnp.where(ok, logits, _NEG)

    m_prev = m_scr[...]                            # (bq, 1)
    m_cur = jnp.max(logits, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(logits - m_new)                    # (bq, bkv)
    alpha = jnp.exp(m_prev - m_new)                # (bq, 1)

    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, -1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)
        out_ref[0, 0] = (acc_scr[...] / denom).astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "logit_softcap", "bq", "bkv", "interpret"),
)
def flash_attention_pallas(q, k, v, causal: bool = True, window: int = 0,
                           logit_softcap: float = 0.0,
                           bq: int = 256, bkv: int = 256,
                           interpret: Optional[bool] = None):
    """q: (B, S, H, hd); k/v: (B, S, KV, hd) → (B, S, H, hd).

    interpret: None → auto (compiled on TPU/GPU, interpret on CPU).
    """
    if interpret is None:
        interpret = default_interpret()
    b, s, h, hd = q.shape
    kv = k.shape[2]
    groups = h // kv
    scale = 1.0 / math.sqrt(hd)

    bq = min(bq, s)
    bkv = min(bkv, s)
    ps = (s + max(bq, bkv) - 1) // max(bq, bkv) * max(bq, bkv)
    if ps != s:
        pad = ((0, 0), (0, ps - s), (0, 0), (0, 0))
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)

    # layout: (B, H, S, hd) for clean per-head blocking
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _kernel, scale=scale, bq=bq, bkv=bkv, causal=causal,
        window=window, softcap=logit_softcap, seq_len=s,
    )

    out = pl.pallas_call(
        kernel,
        grid=(b, h, ps // bq, ps // bkv),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bkv, hd),
                         lambda bi, hi, qi, ki, g=groups: (bi, hi // g, ki, 0)),
            pl.BlockSpec((1, 1, bkv, hd),
                         lambda bi, hi, qi, ki, g=groups: (bi, hi // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, ps, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),    # running max m
            pltpu.VMEM((bq, 1), jnp.float32),    # running sum l
            pltpu.VMEM((bq, hd), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)[:, :s]
