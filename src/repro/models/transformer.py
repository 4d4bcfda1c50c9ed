"""Model assembly: init / forward / decode for every architecture family.

One code path, config-driven:

  dense   — [norm → GQA attn → +res] [norm → MLP → +res]        (× L)
  moe     — attention (GQA or MLA) + routed expert MLP
  ssm     — RWKV-6 time-mix + channel-mix (attention-free)
  hybrid  — parallel attention & Mamba heads (Hymba), then MLP
  vlm/audio — dense trunk consuming stub frontend embeddings

Layers are stacked along a leading L axis and iterated with ``lax.scan``
(keeps HLO size O(1) in depth — essential for the 48–60 layer archs) with
optional per-layer ``jax.checkpoint`` (remat).  Heterogeneous layer kinds
(gemma2 local/global alternation, deepseek first-dense) are handled with a
per-layer static side-channel: window sizes ride along the scan as an (L,)
array, and structurally-different layers (dense-vs-MoE MLP) are split into
separate scan groups.

Decode threads a per-layer cache through the same scan.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import ssm as ssm_lib
from repro.models.layers import (
    attention_init,
    dense_init,
    mla_apply,
    mla_decode,
    mla_init,
    mlp_apply,
    mlp_init,
    norm_apply,
    norm_init,
    softcap,
)
from repro.models.moe import moe_apply, moe_init

__all__ = ["init_params", "forward", "init_cache", "decode_step", "ForwardOptions"]

Params = Dict[str, Any]


# ======================================================================
# init
# ======================================================================
def _layer_init(key, cfg: ModelConfig, dtype, moe: bool) -> Params:
    ks = jax.random.split(key, 6)
    p: Params = {"norm1": norm_init(cfg.norm_kind, cfg.d_model, dtype),
                 "norm2": norm_init(cfg.norm_kind, cfg.d_model, dtype)}
    if cfg.family == "ssm":
        p["time_mix"] = ssm_lib.rwkv_init(ks[0], cfg, dtype)
        p["channel_mix"] = ssm_lib.rwkv_channel_init(ks[1], cfg, dtype)
        return p
    if cfg.use_mla:
        p["attn"] = mla_init(ks[0], cfg, dtype)
    else:
        p["attn"] = attention_init(ks[0], cfg, dtype)
    if cfg.hybrid_ssm:
        p["mamba"] = ssm_lib.mamba_init(ks[1], cfg, dtype)
    if moe:
        p["moe"] = moe_init(ks[2], cfg, dtype)
    else:
        p["mlp"] = mlp_init(ks[3], cfg.d_model, cfg.d_ff, cfg.mlp_kind, dtype)
    return p


def init_params(key, cfg: ModelConfig) -> Params:
    dtype = cfg.weight_dtype
    ks = jax.random.split(key, 8)
    p: Params = {
        "embed": dense_init(ks[0], (cfg.vocab_size, cfg.d_model), dtype, scale=0.02),
        "final_norm": norm_init(cfg.norm_kind, cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        p["head"] = dense_init(ks[1], (cfg.d_model, cfg.vocab_size), dtype)
    if cfg.frontend is not None:
        p["frontend_proj"] = dense_init(ks[2], (cfg.frontend_dim, cfg.d_model), dtype)

    n_dense = cfg.first_k_dense if cfg.is_moe else cfg.n_layers
    n_moe = cfg.n_layers - n_dense if cfg.is_moe else 0

    def stack(count, moe, base_key):
        layers = [
            _layer_init(jax.random.fold_in(base_key, i), cfg, dtype, moe)
            for i in range(count)
        ]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)

    if n_dense:
        p["dense_layers"] = stack(n_dense, False, ks[3])
    if n_moe:
        p["moe_layers"] = stack(n_moe, True, ks[4])
    return p


def _layer_windows(cfg: ModelConfig):
    """(L,) host array: sliding-window size per layer, 0 = global.
    Kept as numpy so impl dispatch can treat windows as static."""
    import numpy as np

    kinds = cfg.layer_kinds()
    return np.array(
        [cfg.window_size if k == "local" else 0 for k in kinds], np.int32
    )


# ======================================================================
# forward (train / prefill)
# ======================================================================
class ForwardOptions:
    """Static knobs threaded through forward (perf levers for §Perf).

    attn_impl: "einsum"  — full (S,T) logits (small-seq baseline);
               "chunked" — online-softmax scan, O(bq·bkv) memory (the
                           lowering path for 32k/500k shapes);
               "pallas"  — the flash_attention TPU kernel.
    remat:     checkpoint each layer (``jax.checkpoint``) so the backward
               pass recomputes its activations instead of storing them.
               Applies only when the stack has more than one layer: a
               single layer's backward runs right after the head and loss,
               so recomputed residuals are live exactly when saved ones
               would be.  For the 1-layer GPT-2 TinyMem node step (3 nodes,
               batch 32 x 150 tokens, XLA cost analysis on the CPU) the
               checkpoint saved no memory (1.643 vs 1.622 GB temp) and
               recomputed the layer forward: 18.4% of the step's FLOPs
               (7.804e11 vs 6.371e11) and 16.1% of its bytes.
    """

    def __init__(self, use_flash: bool = False, remat: bool = True,
                 use_scan: bool = True, use_ssm_kernel: bool = False,
                 remat_policy: Optional[str] = None,
                 attn_impl: Optional[str] = None):
        self.use_flash = use_flash
        self.remat = remat
        self.use_scan = use_scan
        self.use_ssm_kernel = use_ssm_kernel
        self.remat_policy = remat_policy  # None | "dots" | "nothing"
        self.attn_impl = attn_impl or ("pallas" if use_flash else "einsum")

    def policy(self):
        if self.remat_policy == "dots":
            return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return None


def _attn_block(layer_p, cfg, x, positions, window, opts: ForwardOptions):
    """window: per-layer scalar (0 = global); traced in the einsum path
    (branch-free mask shared by the layer scan), static in the chunked /
    pallas paths (those split the scan by attention kind instead)."""
    from repro.models.layers import _qkv, _sdpa, _sdpa_chunked

    h = norm_apply(cfg.norm_kind, layer_p["norm1"], x, cfg.norm_eps)
    if cfg.use_mla:
        impl = opts.attn_impl if opts.attn_impl in ("chunked", "pallas") \
            else "einsum"
        return mla_apply(layer_p["attn"], cfg, h, positions, impl=impl)
    q, k, v = _qkv(layer_p["attn"], cfg, h, positions)
    if opts.attn_impl == "pallas":
        from repro.kernels.ops import flash_attention

        out = flash_attention(
            q, k, v, causal=True, window=int(window),
            logit_softcap=cfg.attn_logit_softcap)
    elif opts.attn_impl == "chunked":
        out = _sdpa_chunked(cfg, q, k, v, window=int(window))
    else:
        s = x.shape[1]
        qi = jnp.arange(s)[:, None]
        ki = jnp.arange(s)[None, :]
        ok = ki <= qi
        ok &= (window == 0) | (ki > qi - window)
        mask = jnp.where(ok, 0.0, -1e30).astype(jnp.float32)
        out = _sdpa(cfg, q, k, v, mask)
    return jnp.einsum("bshk,hkd->bsd", out, layer_p["attn"]["wo"])


def _ffn_block(layer_p, cfg, x, moe: bool):
    h = norm_apply(cfg.norm_kind, layer_p["norm2"], x, cfg.norm_eps)
    if moe:
        out, aux = moe_apply(layer_p["moe"], cfg, h)
        return out, aux
    return mlp_apply(layer_p["mlp"], h, cfg.mlp_kind), jnp.zeros((), jnp.float32)


def _make_layer_fn(cfg: ModelConfig, moe: bool, opts: ForwardOptions,
                   window_static: Optional[int] = None):
    def layer_fn(x, layer_p, window, positions):
        if window_static is not None:
            window = window_static
        if cfg.family == "ssm":
            h = norm_apply(cfg.norm_kind, layer_p["norm1"], x, cfg.norm_eps)
            tm, _, _ = ssm_lib.rwkv_time_mix(
                layer_p["time_mix"], cfg, h, use_kernel=opts.use_ssm_kernel
            )
            x = x + tm
            h = norm_apply(cfg.norm_kind, layer_p["norm2"], x, cfg.norm_eps)
            cm, _ = ssm_lib.rwkv_channel_mix(layer_p["channel_mix"], h)
            return x + cm, jnp.zeros((), jnp.float32)
        attn_out = _attn_block(layer_p, cfg, x, positions, window, opts)
        if cfg.hybrid_ssm:
            h = norm_apply(cfg.norm_kind, layer_p["norm1"], x, cfg.norm_eps)
            m_out, _ = ssm_lib.mamba_apply(layer_p["mamba"], cfg, h)
            attn_out = 0.5 * (attn_out + m_out)
        x = x + attn_out
        ffn_out, aux = _ffn_block(layer_p, cfg, x, moe)
        return x + ffn_out, aux

    # One layer gains no memory from a checkpoint and pays an 18% FLOP
    # recompute (see ForwardOptions), so only deeper stacks remat.
    if opts.remat and cfg.n_layers > 1:
        layer_fn = jax.checkpoint(layer_fn, policy=opts.policy())
    return layer_fn


def _run_group(x, group_p, windows, positions, cfg, moe, opts: ForwardOptions):
    """Run a stack of structurally-identical layers.

    einsum attention takes the window as a traced scan side-channel
    (branch-free mask).  The chunked/pallas impls need STATIC windows, so
    heterogeneous patterns scan over whole pattern-periods with the period
    unrolled inside the body (remainder layers unrolled outside).
    """
    win_list = [int(w) for w in windows]
    n = len(win_list)
    if n == 0:
        return x, jnp.zeros((), jnp.float32)

    def run_unrolled(x, group_p, wins, offset=0):
        aux_total = jnp.zeros((), jnp.float32)
        for i, w in enumerate(wins):
            lp = jax.tree.map(lambda a: a[offset + i], group_p)
            fn = _make_layer_fn(cfg, moe, opts, window_static=w)
            x, aux = fn(x, lp, w, positions)
            aux_total += aux
        return x, aux_total

    if not opts.use_scan:
        return run_unrolled(x, group_p, win_list)

    if opts.attn_impl == "einsum" or cfg.family == "ssm":
        layer_fn = _make_layer_fn(cfg, moe, opts)

        def body(carry, xs):
            lp, w = xs
            y, aux = layer_fn(carry, lp, w, positions)
            return y, aux

        x, auxs = jax.lax.scan(body, x, (group_p, jnp.asarray(windows)))
        return x, jnp.sum(auxs)

    # static-window path: scan over pattern periods
    uniq = sorted(set(win_list))
    if len(uniq) == 1:
        period = 1
        pattern = (uniq[0],)
    else:
        period = len(cfg.attn_pattern)
        pattern = tuple(win_list[:period])
    n_full = n // period
    rem = n - n_full * period

    aux_total = jnp.zeros((), jnp.float32)
    if n_full:
        stacked = jax.tree.map(
            lambda a: a[: n_full * period].reshape(
                (n_full, period) + a.shape[1:]), group_p)
        fns = [_make_layer_fn(cfg, moe, opts, window_static=w) for w in pattern]

        def body(carry, lp_period):
            y = carry
            aux = jnp.zeros((), jnp.float32)
            for j, fn in enumerate(fns):
                lp = jax.tree.map(lambda a: a[j], lp_period)
                y, a = fn(y, lp, pattern[j], positions)
                aux += a
            return y, aux

        x, auxs = jax.lax.scan(body, x, stacked)
        aux_total += jnp.sum(auxs)
    if rem:
        x, a = run_unrolled(x, group_p, win_list[-rem:], offset=n_full * period)
        aux_total += a
    return x, aux_total


def _embed_inputs(params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray]):
    if "embeddings" in batch:  # modality-frontend stub path (audio / vlm)
        x = batch["embeddings"].astype(cfg.activation_dtype) @ params["frontend_proj"]
    else:
        x = jnp.take(params["embed"], batch["tokens"], axis=0)
        x = x * jnp.sqrt(jnp.asarray(cfg.d_model, jnp.float32)).astype(x.dtype)
    return x.astype(cfg.activation_dtype)


def _unembed(params, cfg: ModelConfig, x):
    x = norm_apply(cfg.norm_kind, params["final_norm"], x, cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = (x @ head).astype(jnp.float32)
    return softcap(logits, cfg.final_logit_softcap)


def forward(params: Params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray],
            opts: Optional[ForwardOptions] = None,
            return_hidden: bool = False):
    """Full-sequence forward.  Returns (logits, aux_loss) — or
    (hidden, aux_loss) when ``return_hidden`` (for chunked CE)."""
    opts = opts or ForwardOptions()
    x = _embed_inputs(params, cfg, batch)
    b, s, _ = x.shape
    positions = jnp.arange(s)

    windows = _layer_windows(cfg)
    aux = jnp.zeros((), jnp.float32)
    n_dense = cfg.first_k_dense if cfg.is_moe else cfg.n_layers
    if "dense_layers" in params:
        x, a = _run_group(x, params["dense_layers"], windows[:n_dense],
                          positions, cfg, False, opts)
        aux += a
    if "moe_layers" in params:
        x, a = _run_group(x, params["moe_layers"], windows[n_dense:],
                          positions, cfg, True, opts)
        aux += a
    if return_hidden:
        return x, aux
    return _unembed(params, cfg, x), aux


# ======================================================================
# decode (single token, cached)
# ======================================================================
def init_cache(cfg: ModelConfig, batch_size: int, max_seq: int) -> Params:
    """Allocate the per-layer decode cache, stacked along L.

    dense/moe : K/V (L, B, T, KV, hd) — local layers get T=window (ring).
    mla       : latent (L, B, T, r) + rope-k (L, B, T, dr).
    ssm       : rwkv state (L, B, H, hd, hd) + token-shift carries.
    hybrid    : attn cache + mamba (ssm_state, conv_state).
    """
    dt = cfg.activation_dtype
    L = cfg.n_layers
    kinds = cfg.layer_kinds()
    cache: Params = {"position": jnp.zeros((batch_size,), jnp.int32)}
    if cfg.family == "ssm":
        h = cfg.d_model // cfg.rwkv_head_dim
        cache["rwkv_state"] = jnp.zeros((L, batch_size, h, cfg.rwkv_head_dim,
                                         cfg.rwkv_head_dim), jnp.float32)
        cache["tm_prev"] = jnp.zeros((L, batch_size, cfg.d_model), dt)
        cache["cm_prev"] = jnp.zeros((L, batch_size, cfg.d_model), dt)
        return cache
    if cfg.use_mla:
        cache["ckv"] = jnp.zeros((L, batch_size, max_seq, cfg.kv_lora_rank), dt)
        cache["kr"] = jnp.zeros((L, batch_size, max_seq, cfg.qk_rope_head_dim), dt)
    else:
        # per-layer cache length: window for local layers, max_seq otherwise.
        # lax.scan needs homogeneous shapes → use the max over layers and
        # let local layers ring-index within their window (t dim is still
        # uniform; real saving comes from uniform-local patterns like
        # hymba where all layers are local or ssm).
        lens = [cfg.window_size if k == "local" else max_seq for k in kinds]
        t = max(lens) if lens else max_seq
        if all(k == "local" for k in kinds):
            t = min(cfg.window_size, max_seq)
        cache["k"] = jnp.zeros((L, batch_size, t, cfg.n_kv_heads, cfg.head_dim_), dt)
        cache["v"] = jnp.zeros((L, batch_size, t, cfg.n_kv_heads, cfg.head_dim_), dt)
    if cfg.hybrid_ssm:
        di = cfg.ssm_expand * cfg.d_model
        cache["ssm_state"] = jnp.zeros((L, batch_size, di, cfg.ssm_state_dim), jnp.float32)
        cache["conv_state"] = jnp.zeros((L, batch_size, cfg.ssm_conv_dim - 1, di), dt)
    return cache


def decode_step(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                cache: Params, opts: Optional[ForwardOptions] = None
                ) -> Tuple[jnp.ndarray, Params]:
    """One decode step: tokens (B, 1) → (logits (B, 1, V), new cache)."""
    opts = opts or ForwardOptions(remat=False)
    x = jnp.take(params["embed"], tokens, axis=0)
    x = (x * jnp.sqrt(jnp.asarray(cfg.d_model, jnp.float32))).astype(cfg.activation_dtype)
    position = cache["position"]
    windows = _layer_windows(cfg)

    n_dense = cfg.first_k_dense if cfg.is_moe else cfg.n_layers

    def layer_decode(x, lp, layer_cache, window, moe):
        new_cache = dict(layer_cache)
        if cfg.family == "ssm":
            h = norm_apply(cfg.norm_kind, lp["norm1"], x, cfg.norm_eps)
            tm, st, prev = ssm_lib.rwkv_time_mix_decode(
                lp["time_mix"], cfg, h, layer_cache["rwkv_state"],
                layer_cache["tm_prev"])
            new_cache["rwkv_state"], new_cache["tm_prev"] = st, prev
            x = x + tm
            h = norm_apply(cfg.norm_kind, lp["norm2"], x, cfg.norm_eps)
            cm, prev = ssm_lib.rwkv_channel_mix(
                lp["channel_mix"], h, layer_cache["cm_prev"])
            new_cache["cm_prev"] = prev
            return x + cm, new_cache
        h = norm_apply(cfg.norm_kind, lp["norm1"], x, cfg.norm_eps)
        if cfg.use_mla:
            a_out, ckv, kr = mla_decode(lp["attn"], cfg, h, layer_cache["ckv"],
                                        layer_cache["kr"], position)
            new_cache["ckv"], new_cache["kr"] = ckv, kr
        else:
            # window side-channel: local layers ring-index (kind resolved
            # per layer below — scan carries windows array)
            kind = "local"  # mask logic keys off `window>0` inside
            a_out, k_new, v_new = _attn_decode_traced(
                lp["attn"], cfg, h, layer_cache["k"], layer_cache["v"],
                position, window)
            new_cache["k"], new_cache["v"] = k_new, v_new
        if cfg.hybrid_ssm:
            m_out, (st, cv) = ssm_lib.mamba_decode(
                lp["mamba"], cfg, h, layer_cache["ssm_state"],
                layer_cache["conv_state"])
            new_cache["ssm_state"], new_cache["conv_state"] = st, cv
            a_out = 0.5 * (a_out + m_out)
        x = x + a_out
        ffn_out, _ = _ffn_block(lp, cfg, x, moe)
        return x + ffn_out, new_cache

    def run_group(x, group_p, group_cache, group_windows, moe):
        def body(carry, xs):
            lp, lc, w = xs
            y, nc = layer_decode(carry, lp, lc, w, moe)
            return y, nc

        if opts.use_scan:
            x, new_cache = jax.lax.scan(body, x, (group_p, group_cache, group_windows))
            return x, new_cache
        new_caches = []
        for i in range(group_windows.shape[0]):
            lp = jax.tree.map(lambda a: a[i], group_p)
            lc = jax.tree.map(lambda a: a[i], group_cache)
            x, nc = layer_decode(x, lp, lc, group_windows[i], moe)
            new_caches.append(nc)
        return x, jax.tree.map(lambda *xs: jnp.stack(xs), *new_caches)

    layer_cache_keys = [k for k in cache if k != "position"]
    stacked_cache = {k: cache[k] for k in layer_cache_keys}

    new_cache: Params = {"position": position + 1}
    if "dense_layers" in params and "moe_layers" in params:
        head_cache = {k: v[:n_dense] for k, v in stacked_cache.items()}
        tail_cache = {k: v[n_dense:] for k, v in stacked_cache.items()}
        x, hc = run_group(x, params["dense_layers"], head_cache, windows[:n_dense], False)
        x, tc = run_group(x, params["moe_layers"], tail_cache, windows[n_dense:], True)
        for k in layer_cache_keys:
            new_cache[k] = jnp.concatenate([hc[k], tc[k]], axis=0)
    elif "moe_layers" in params:
        x, nc = run_group(x, params["moe_layers"], stacked_cache, windows, True)
        new_cache.update(nc)
    else:
        x, nc = run_group(x, params["dense_layers"], stacked_cache, windows, False)
        new_cache.update(nc)

    return _unembed(params, cfg, x), new_cache


def _attn_decode_traced(p, cfg, x, cache_k, cache_v, position, window):
    """attention_decode with a *traced* window: slot/validity math is
    branch-free so global (window==0) and local layers share a scan body."""
    from repro.models.layers import _sdpa, apply_rope, rmsnorm, rope

    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    cos, sin = rope(position[:, None], cfg.head_dim_, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)

    t = cache_k.shape[1]
    is_local = window > 0
    slot = jnp.where(is_local, position % t, jnp.minimum(position, t - 1))
    oh = jax.nn.one_hot(slot, t, dtype=cache_k.dtype)
    new_k = cache_k * (1 - oh[:, :, None, None]) + oh[:, :, None, None] * k
    new_v = cache_v * (1 - oh[:, :, None, None]) + oh[:, :, None, None] * v

    kpos = jnp.arange(t)[None, :]
    age = (slot[:, None] - kpos) % t
    ok_local = (age <= jnp.minimum(position, t - 1)[:, None]) & (age < window)
    ok_global = kpos <= position[:, None]
    ok = jnp.where(is_local, ok_local, ok_global)
    mask = jnp.where(ok, 0.0, -1e30).astype(jnp.float32)[:, None, None, None, :]
    out = _sdpa(cfg, q, new_k, new_v, mask)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), new_k, new_v
