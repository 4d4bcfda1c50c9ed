"""Pallas TPU kernel: chunked RWKV-6 scan (data-dependent-decay linear
attention) — the train-time hot-spot for the SSM/hybrid architectures.

TPU adaptation (DESIGN.md §6): the GPU reference implementations lean on
warp-level scans; the TPU-native formulation is *chunked* so the inner work
is dense GEMMs on the MXU:

With per-step decay w_t ∈ (0,1) and inclusive cumprod P_t = Π_{s≤t} w_s,
for one chunk with incoming state S₀ (hd_k × hd_v):

  y_t   = (r_t ⊙ P_{t-1}) · S₀                      ← state term  (GEMM)
        + Σ_{s<t} [(r_t ⊙ P_{t-1}/P_s) · k_s] v_s    ← intra term  (GEMM, masked)
        + (r_t · (u ⊙ k_t)) v_t                      ← bonus diag
  S_out = diag(P_T) S₀ + Σ_s ((P_T/P_s) ⊙ k_s) v_sᵀ  ← state update (GEMM)

Grid = (B·H, S/chunk): the chunk axis is innermost/sequential so S carries
in VMEM scratch.  Numerics: P_t is computed in f32 log-space,
exp(Σ_{s≤t} log w_s), the cumulative sum as a lower-triangular (T, T)
matmul at full f32 precision (Mosaic lowers neither cumsum nor cumprod);
chunk=64 keeps P_T ≥ e^{-64·|log w|} in range for the decay regimes
RWKV-6 produces (w = exp(-exp(·)) ≈ 0.9–0.999).

Validated against ref.rwkv_scan_ref (sequential scan) in interpret mode.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gossip_mix import default_interpret

__all__ = ["rwkv_scan_pallas"]


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sT_ref, state_scr,
            *, chunk):
    ci = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = s0_ref[0].astype(jnp.float32)

    r = r_ref[0].astype(jnp.float32)     # (T, hd)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)     # (1, hd)
    s0 = state_scr[...]                  # (hd, hd)

    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # inclusive cumulative decay P_t = exp(Σ_{s≤t} log w_s), (T, hd)
    log_w = jnp.log(w)
    log_p = jax.lax.dot_general(
        (s_idx <= t_idx).astype(jnp.float32), log_w,
        (((1,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    p = jnp.exp(log_p)
    p_prev = jnp.exp(log_p - log_w)      # P_{t-1} (P_0 = 1)

    r_dec = r * p_prev                   # r̃_t
    k_dec = k / p                        # k̃_s

    # state term: (T, hd_k) @ (hd_k, hd_v)
    y = jax.lax.dot_general(r_dec, s0, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # intra-chunk term with strict lower mask
    a = jax.lax.dot_general(r_dec, k_dec, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (T, T)
    a = jnp.where(s_idx < t_idx, a, 0.0)
    y += jax.lax.dot_general(a, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    # bonus diagonal term
    y += jnp.sum(r * u * k, axis=-1, keepdims=True) * v
    y_ref[0] = y.astype(y_ref.dtype)

    # state update: P_T as a row (1, hd_k) and as a column (hd_k, 1)
    log_p_total = log_p[chunk - 1:chunk]
    log_p_total_col = jax.lax.dot_general(
        log_w, jnp.ones((chunk, 1), jnp.float32), (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    k_scaled = k * jnp.exp(log_p_total - log_p)           # (T, hd)
    s_new = s0 * jnp.exp(log_p_total_col) + jax.lax.dot_general(
        k_scaled, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    state_scr[...] = s_new

    @pl.when(ci == nc - 1)
    def _finish():
        sT_ref[0] = s_new


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv_scan_pallas(r, k, v, w, u, state, chunk: int = 64,
                     interpret: Optional[bool] = None):
    """r,k,v,w: (B, S, H, hd); u: (H, hd); state: (B, H, hd, hd) f32.

    Returns (y (B,S,H,hd), final_state (B,H,hd,hd) f32).
    S is padded to a chunk multiple with w=1, k=0 (identity steps).
    interpret: None → auto (compiled on TPU/GPU, interpret on CPU).
    """
    if interpret is None:
        interpret = default_interpret()
    b, s, h, hd = r.shape
    ps = (s + chunk - 1) // chunk * chunk
    if ps != s:
        pad = ((0, 0), (0, ps - s), (0, 0), (0, 0))
        r = jnp.pad(r, pad)
        v = jnp.pad(v, pad)
        k = jnp.pad(k, pad)
        w = jnp.pad(w, pad, constant_values=1.0)

    # (B, S, H, hd) → (B·H, S, hd)
    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, ps, hd)

    rf, kf, vf, wf = map(fold, (r, k, v, w))
    uf = jnp.broadcast_to(u[None], (b, h, hd)).reshape(b * h, 1, hd)
    s0 = state.reshape(b * h, hd, hd).astype(jnp.float32)

    y, s_t = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=(b * h, ps // chunk),
        in_specs=[
            pl.BlockSpec((1, chunk, hd), lambda bi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, hd), lambda bi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, hd), lambda bi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, hd), lambda bi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, 1, hd), lambda bi, ci: (bi, 0, 0)),
            pl.BlockSpec((1, hd, hd), lambda bi, ci: (bi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, hd), lambda bi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, hd, hd), lambda bi, ci: (bi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, ps, hd), r.dtype),
            jax.ShapeDtypeStruct((b * h, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret,
    )(rf, kf, vf, wf, uf, s0)

    y = y.reshape(b, h, ps, hd).transpose(0, 2, 1, 3)[:, :s]
    return y, s_t.reshape(b, h, hd, hd)
