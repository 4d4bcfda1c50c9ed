"""Pallas TPU kernel: MLA (multi-head latent attention) prefill — the
deepseek-v2 hot-spot (128 heads × 32k context in a rank-512 latent space).

Latent-space flash attention: keys AND values are the same compressed
latent c_kv (B,T,r) — the kernel never materializes per-head K/V.  Per
(batch, head, q-block) program, kv blocks stream through VMEM with an
online-softmax carry:

  logits = q_lat·c_kvᵀ + q_rope·k_ropeᵀ        (two MXU GEMMs, (bq, bkv))
  acc    = Σ softmax(logits)·c_kv              (latent context, (bq, r))

The up-projection (r → v_head_dim) and output projection stay outside
(they are batched GEMMs XLA already does well); the kernel removes the
O(S·T) logits HBM traffic which dominates at 32k.

VMEM/program ≈ bq·(r+dr) + bkv·(r+dr) + bq·bkv + bq·r  f32
             ≈ 1.6 MiB at bq=bkv=256, r=512 — fits comfortably.

Validated against ``ref.mla_attention_ref`` in interpret mode.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gossip_mix import default_interpret

__all__ = ["mla_attention_pallas"]

_NEG = -1e30


def _kernel(ql_ref, qr_ref, ck_ref, kr_ref, out_ref, m_scr, l_scr, acc_scr, *,
            scale, bq, bkv, seq_len):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    ql = ql_ref[0, 0].astype(jnp.float32)          # (bq, r)
    qr = qr_ref[0, 0].astype(jnp.float32)          # (bq, dr)
    ck = ck_ref[0].astype(jnp.float32)             # (bkv, r)
    kr = kr_ref[0].astype(jnp.float32)             # (bkv, dr)

    logits = jax.lax.dot_general(ql, ck, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    logits += jax.lax.dot_general(qr, kr, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    logits *= scale

    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
    kpos = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    ok = (kpos <= qpos) & (kpos < seq_len)
    logits = jnp.where(ok, logits, _NEG)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(logits, -1, keepdims=True))
    p = jnp.exp(logits - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, -1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, ck, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        out_ref[0, 0] = (acc_scr[...] /
                         jnp.maximum(l_scr[...], 1e-30)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bq", "bkv", "interpret"))
def mla_attention_pallas(q_lat, q_rope, c_kv, k_rope,
                         bq: int = 256, bkv: int = 256,
                         interpret: Optional[bool] = None):
    """q_lat: (B,S,H,r) — queries absorbed into the latent basis;
    q_rope: (B,S,H,dr); c_kv: (B,T,r); k_rope: (B,T,dr).
    Returns latent context (B,S,H,r), causal.
    interpret: None → auto (compiled on TPU/GPU, interpret on CPU).
    """
    if interpret is None:
        interpret = default_interpret()
    b, s, h, r = q_lat.shape
    dr = q_rope.shape[-1]
    t = c_kv.shape[1]
    # 1/sqrt(qk_nope + qk_rope) is applied by the CALLER by pre-scaling q
    # (keeps the kernel dimension-agnostic).
    scale = 1.0

    bq = min(bq, s)
    bkv = min(bkv, t)
    ps = (s + bq - 1) // bq * bq
    pt = (t + bkv - 1) // bkv * bkv
    if ps != s:
        q_lat = jnp.pad(q_lat, ((0, 0), (0, ps - s), (0, 0), (0, 0)))
        q_rope = jnp.pad(q_rope, ((0, 0), (0, ps - s), (0, 0), (0, 0)))
    if pt != t:
        c_kv = jnp.pad(c_kv, ((0, 0), (0, pt - t), (0, 0)))
        k_rope = jnp.pad(k_rope, ((0, 0), (0, pt - t), (0, 0)))

    qlt = q_lat.transpose(0, 2, 1, 3)   # (B,H,S,r)
    qrt = q_rope.transpose(0, 2, 1, 3)  # (B,H,S,dr)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, bq=bq, bkv=bkv, seq_len=s),
        grid=(b, h, ps // bq, pt // bkv),
        in_specs=[
            pl.BlockSpec((1, 1, bq, r), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bq, dr), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, bkv, r), lambda bi, hi, qi, ki: (bi, ki, 0)),
            pl.BlockSpec((1, bkv, dr), lambda bi, hi, qi, ki: (bi, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, r), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, ps, r), q_lat.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, r), jnp.float32),
        ],
        interpret=interpret,
    )(qlt, qrt, c_kv, k_rope)
    return out.transpose(0, 2, 1, 3)[:, :s]
