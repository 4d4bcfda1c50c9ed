"""Public jit'd kernel entry points.

Model code calls these; each dispatches to the Pallas kernel, which
compiles on TPU/GPU and runs in Pallas interpret mode on CPU (every
kernel's ``interpret=None`` default).  Signatures match the pure-jnp
oracles in ``ref.py`` one-for-one.
"""
from __future__ import annotations

from typing import Optional

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.gossip_mix import (
    gossip_mix_pallas,
    gossip_plane_pallas,
    mix_plane_pallas,
)
from repro.kernels.mla_attention import mla_attention_pallas
from repro.kernels.ssm_scan import rwkv_scan_pallas

__all__ = ["flash_attention", "gossip_mix", "gossip_plane", "mix_plane",
           "rwkv_scan", "mla_attention"]


def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    logit_softcap: float = 0.0):
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, logit_softcap=logit_softcap)


def gossip_mix(blocks, weights):
    return gossip_mix_pallas(blocks, weights)


def gossip_plane(plane, coeffs, bt: Optional[int] = None):
    """Fused flat-plane mix: ``coeffs @ plane`` as ONE pallas_call."""
    return gossip_plane_pallas(plane, coeffs, bt=bt)


def mix_plane(params, coeffs, bt: Optional[int] = None):
    """Pytree-level fused mix (pack → one kernel → unpack)."""
    return mix_plane_pallas(params, coeffs, bt=bt)


def rwkv_scan(r, k, v, w, u, state, chunk: int = 64):
    return rwkv_scan_pallas(r, k, v, w, u, state, chunk=chunk)


def mla_attention(q_lat, q_rope, c_kv, k_rope):
    return mla_attention_pallas(q_lat, q_rope, c_kv, k_rope)
