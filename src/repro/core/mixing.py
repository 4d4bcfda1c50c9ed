"""Apply a mixing matrix to a stacked model pytree.

All n node-models live in ONE pytree whose leaves carry a leading node axis
``(n, ...)`` — the TPU-native formulation of the paper's "n independent
models" (see DESIGN.md §3.1).  Eq. (2) of the paper,

    m_i^{t+1} = Σ_{j∈N_i} C[i,j] · m_j^{t+1/2},

is then a single contraction ``M' = C @ M`` applied leaf-wise.

Two schedules are provided:

* :func:`mix_dense` — paper-faithful: einsum against the dense (n, n)
  matrix.  Under pjit with the node axis sharded over mesh ``data``, XLA
  lowers this to an all-gather + local GEMM.
* :func:`mix_sparse` — beyond-paper: circulant decomposition of the sparse
  mixing matrix into ring offsets; inside ``shard_map`` each offset becomes
  one ``lax.ppermute`` with on-the-fly weighted accumulation, so ICI bytes
  scale with the number of distinct offsets (≈ max degree) instead of n.
  The offset SET is static (derived from the topology's neighbourhood
  support via :func:`sparse_offsets`) while the per-offset weights are
  gathered from the traced coefficients at each call — so one compiled
  schedule serves every round of a time-varying stack or in-scan
  coefficient program whose support stays within the nominal topology
  (link failure only shrinks support: dropped edges contribute weight 0).
  Reachable as ``DecentralizedConfig(mix_impl="sparse")``
  (``repro.core.decentralized.make_mix_fn``), which falls back to
  :func:`mix_dense` when the offset count exceeds max degree + slack —
  near-circulant graphs (rings, WS) win, unstructured support does not.

* :func:`mix_edges` — the general sparse schedule: padded-ELL edge-list
  tables (``repro.core.topology.padded_neighbor_tables``) are static
  trace-time data, per-edge coefficients are gathered from the live
  (n, n) matrix (:func:`edge_weights`), and each destination row
  accumulates its ≤ dmax neighbours — O(n·dmax·|leaf|) work instead of
  the dense O(n²·|leaf|), with no circulant-structure requirement.  This
  is ``DecentralizedConfig(mix_impl="edges")`` and the jnp reference of
  the Pallas segment kernel
  (``repro.kernels.gossip_mix.mix_edges_pallas``, DESIGN.md §12).

A further backend lives in ``repro.kernels.gossip_mix``: the fused
flat-plane Pallas kernel (``mix_impl="pallas"`` — the whole mix as ONE
``pallas_call`` over a packed ``(n, P)`` parameter plane, DESIGN.md §11).

All are pure functions of (params, coefficients) and agree to float
tolerance — property-tested in tests/test_mixing.py.
"""
from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "mix_dense",
    "mix_sparse",
    "mix_sparse_host",
    "mix_edges",
    "edge_weights",
    "sparse_offsets",
    "circulant_decomposition",
    "CirculantSchedule",
    "mixing_collective_bytes",
    "ROBUST_MODES",
    "oddeven_sort_pairs",
    "robust_combine",
    "mix_robust_tables",
    "plane_norms",
    "norm_clip_coeffs",
]

#: robust-aggregation rules accepted by
#: ``repro.core.decentralized.make_mix_fn(robust=...)`` (DESIGN.md §16):
#: "mean" is the untouched weighted average; "trimmed"/"median" are the
#: coordinate-wise order statistics below; "norm_clip" is the
#: :func:`norm_clip_coeffs` coefficient transform.
ROBUST_MODES = ("mean", "trimmed", "median", "norm_clip")

# nonfinite sanitization bound for the robust sort keys: corrupted
# (NaN/±Inf) coordinates are clamped to ±_ROBUST_BIG so every comparison
# in the sort network is well-defined and a poisoned value behaves as a
# maximally extreme outlier (bounded influence).  Padding / zero-weight
# slots get _ROBUST_PAD, strictly beyond the clamp, so they sort past
# every real value.
_ROBUST_BIG = 1e30
_ROBUST_PAD = 2e30


def _precision(acc_dtype):
    """Full precision for f32 contractions (a TPU's default rounds f32
    operands to bf16); the default for low-precision ones."""
    if jnp.dtype(acc_dtype) == jnp.float32:
        return jax.lax.Precision.HIGHEST
    return None


def _leaf_mix(c: jnp.ndarray, leaf: jnp.ndarray,
              mix_in_float32: bool = True) -> jnp.ndarray:
    """out[i, ...] = Σ_j c[i, j] · leaf[j, ...], preserving leaf dtype.

    ``mix_in_float32=True`` (default) accumulates in f32 — aggregation of
    bf16 params in low precision loses knowledge exactly where the paper
    needs it (small OOD deltas) — with full-f32 products: a TPU's default
    matmul precision would round the f32 operands to bf16.  False
    accumulates in the leaf dtype (the low-precision-aggregation
    ablation, ``DecentralizedConfig(mix_in_float32=False)``).
    """
    acc_dtype = jnp.float32 if mix_in_float32 else leaf.dtype
    acc = jnp.tensordot(c.astype(acc_dtype), leaf.astype(acc_dtype),
                        axes=(1, 0), precision=_precision(acc_dtype))
    return acc.astype(leaf.dtype)


def mix_dense(params, coeffs: jnp.ndarray, mix_in_float32: bool = True):
    """Dense gossip: every leaf contracted against the (n, n) matrix.

    Args:
      params: pytree with leaves of shape (n, ...).
      coeffs: (n, n) row-stochastic mixing matrix (device array or numpy).
      mix_in_float32: accumulation dtype — see :func:`_leaf_mix`.
    """
    c = jnp.asarray(coeffs)
    return jax.tree.map(lambda leaf: _leaf_mix(c, leaf, mix_in_float32),
                        params)


# ----------------------------------------------------------------------
# circulant (ring-offset) decomposition — sparse gossip schedule
# ----------------------------------------------------------------------
class CirculantSchedule:
    """Decomposition of an (n, n) mixing matrix into ring offsets.

    For each distinct offset ``k`` with any nonzero ``C[i, (i+k) % n]`` we
    store the per-destination coefficient vector ``w_k[i] = C[i, (i+k)%n]``.
    Then ``(C @ M)[i] = Σ_k w_k[i] · M[(i+k) % n]`` — i.e. a sum of weighted
    ring shifts, each of which is a single ``collective_permute`` on the ICI
    ring when the node axis is the mesh ``data`` axis.
    """

    def __init__(self, offsets: Sequence[int], weights: np.ndarray, n: int):
        self.offsets: Tuple[int, ...] = tuple(int(o) for o in offsets)
        self.weights = np.asarray(weights, dtype=np.float32)  # (K, n)
        self.n = n
        assert self.weights.shape == (len(self.offsets), n)

    def __len__(self) -> int:
        return len(self.offsets)

    def __repr__(self) -> str:
        return f"CirculantSchedule(n={self.n}, offsets={self.offsets})"


def circulant_decomposition(coeffs: np.ndarray) -> CirculantSchedule:
    """Exact decomposition of any (n, n) matrix into ring offsets.

    Every matrix decomposes into ≤ n offsets; sparse neighbourhood matrices
    on scale-free graphs typically use far fewer distinct offsets than n
    (BA n=16 p=2 → ~9 offsets vs 15 all-gather hops).  Offset 0 is the
    self-weight and costs no communication.
    """
    c = np.asarray(coeffs, dtype=np.float32)
    n = c.shape[0]
    offsets: List[int] = []
    weights: List[np.ndarray] = []
    for k in range(n):
        w = c[np.arange(n), (np.arange(n) + k) % n]
        if np.any(w != 0):
            offsets.append(k)
            weights.append(w)
    return CirculantSchedule(offsets, np.stack(weights), n)


def sparse_offsets(support: np.ndarray) -> Tuple[int, ...]:
    """Distinct ring offsets covering a 0/1 support mask (adjacency plus
    self-loops): offset k is needed iff any ``support[i, (i+k) % n] > 0``.
    Static metadata — compute once per topology, reuse for every round."""
    s = np.asarray(support)
    n = s.shape[0]
    rows = np.arange(n)
    return tuple(k for k in range(n)
                 if np.any(s[rows, (rows + k) % n] > 0))


def mix_sparse(params, coeffs: jnp.ndarray, offsets: Sequence[int],
               mix_in_float32: bool = True):
    """Circulant gossip with STATIC offsets and TRACED weights.

    ``offsets`` fixes the ring-shift schedule at trace time (it comes from
    the topology support, :func:`sparse_offsets`); the per-destination
    weights ``w_k[i] = coeffs[i, (i+k) % n]`` are gathered from the live
    (n, n) matrix, so per-round matrices (Random resampling, link
    failure, in-scan coefficient programs) reuse one compiled schedule.
    Requires ``offsets`` ⊇ the support of ``coeffs`` — entries outside
    the offset set are silently dropped (callers derive offsets from the
    nominal topology, whose support only ever shrinks under churn).
    Accumulates in f32 like :func:`mix_dense` (``mix_in_float32=False``
    accumulates in the leaf dtype, matching the other backends' ablation
    knob).
    """
    c = jnp.asarray(coeffs).astype(jnp.float32)
    n = c.shape[0]
    rows = jnp.arange(n)
    weights = [c[rows, (rows + k) % n] for k in offsets]

    def leaf_fn(leaf: jnp.ndarray) -> jnp.ndarray:
        acc_dtype = jnp.float32 if mix_in_float32 else leaf.dtype
        acc = jnp.zeros(leaf.shape, acc_dtype)
        extra = (1,) * (leaf.ndim - 1)
        for k, w in zip(offsets, weights):
            # destination i receives source (i+k) % n  ==  roll by -k
            shifted = jnp.roll(leaf, shift=-k, axis=0) if k else leaf
            acc = acc + (w.astype(acc_dtype).reshape((n,) + extra)
                         * shifted.astype(acc_dtype))
        return acc.astype(leaf.dtype)

    return jax.tree.map(leaf_fn, params)


# ----------------------------------------------------------------------
# padded edge-list (ELL) gossip — the general sparse schedule
# ----------------------------------------------------------------------
def edge_weights(coeffs: jnp.ndarray, nbr_idx: jnp.ndarray,
                 nbr_mask: jnp.ndarray) -> jnp.ndarray:
    """Per-edge coefficients ``w[i, d] = coeffs[i, nbr_idx[i, d]]``
    (masked): the (n, dmax) gather that turns a live (n, n) mixing matrix
    into the edge-list schedule's traced operand.  The tables come from
    ``repro.core.topology.padded_neighbor_tables`` and are STATIC; only
    this O(n·dmax) gather runs per round, so time-varying matrices (Random
    resampling, link failure, in-scan coefficient programs) reuse one
    compiled schedule.  Entries outside the table support are dropped —
    callers derive tables from the nominal topology, whose support only
    ever shrinks under churn (``SweepEngine.run`` validates this)."""
    c = jnp.asarray(coeffs)
    rows = jnp.arange(c.shape[0])[:, None]
    return c[rows, nbr_idx] * nbr_mask.astype(c.dtype)


def mix_edges(params, coeffs: jnp.ndarray, nbr_idx: jnp.ndarray,
              nbr_mask: jnp.ndarray, mix_in_float32: bool = True):
    """Edge-list gossip with STATIC padded-ELL tables and TRACED weights —
    the jnp reference of the Pallas segment kernel
    (``repro.kernels.gossip_mix.mix_edges_pallas``); property-tested equal
    to :func:`mix_dense` to 1e-6 in tests/test_mixing.py.

    ``(C @ M)[i] = Σ_d w[i, d] · M[nbr_idx[i, d]]`` — an O(n·dmax·|leaf|)
    gather-accumulate instead of the dense O(n²·|leaf|) contraction,
    which is what makes n ≥ 1024 topologies reachable (dmax ≈ max degree
    + 1 ≪ n on the paper's BA/WS graphs).  Accumulates in f32 like
    :func:`mix_dense` (``mix_in_float32=False`` accumulates in the leaf
    dtype — the shared low-precision-aggregation ablation knob).
    """
    idx = jnp.asarray(nbr_idx)
    w = edge_weights(jnp.asarray(coeffs).astype(jnp.float32), idx,
                     jnp.asarray(nbr_mask))

    def leaf_fn(leaf: jnp.ndarray) -> jnp.ndarray:
        acc_dtype = jnp.float32 if mix_in_float32 else leaf.dtype
        gathered = jnp.take(leaf.astype(acc_dtype), idx, axis=0)
        wk = w.astype(acc_dtype).reshape(w.shape + (1,) * (leaf.ndim - 1))
        return (wk * gathered).sum(axis=1).astype(leaf.dtype)

    return jax.tree.map(leaf_fn, params)


# ----------------------------------------------------------------------
# robust aggregation: coordinate-wise order statistics over neighbours
# ----------------------------------------------------------------------
def oddeven_sort_pairs(keys: Sequence[jnp.ndarray],
                       vals: Sequence[jnp.ndarray]):
    """Sort the slot lists ``(keys, vals)`` ascending by ``keys`` with a
    fixed odd-even transposition network — ``d`` passes of elementwise
    compare-exchanges between neighbouring slots of a static length-``d``
    list (each slot an equally shaped array).

    The network is stable (equal keys never swap), so the jnp reference
    and the Pallas kernel, which run it on differently blocked slots, are
    bit-identical.  Callers must pre-sanitize keys to finite values (NaN
    never satisfies ``lo > hi`` consistently and would oscillate
    forever); see :func:`robust_combine`.
    """
    keys, vals = list(keys), list(vals)
    d = len(keys)
    for p in range(d):
        for i in range(p % 2, d - 1, 2):
            swap = keys[i] > keys[i + 1]
            keys[i], keys[i + 1] = (jnp.where(swap, keys[i + 1], keys[i]),
                                    jnp.where(swap, keys[i], keys[i + 1]))
            vals[i], vals[i + 1] = (jnp.where(swap, vals[i + 1], vals[i]),
                                    jnp.where(swap, vals[i], vals[i + 1]))
    return keys, vals


def _slot_sum(xs: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """Σ over slots, in slot order (the order a reduction over axis 0
    takes)."""
    acc = jnp.zeros_like(xs[0])
    for x in xs:
        acc = acc + x
    return acc


def robust_combine(vals: Sequence[jnp.ndarray], w: Sequence[jnp.ndarray],
                   self_vals: jnp.ndarray, op: str,
                   trim_k: int = 1) -> jnp.ndarray:
    """Coordinate-wise robust aggregate of gathered neighbour rows.

    vals: d slots, each (m, t) — slot d's value for destination row m,
      coordinate t (gathered from the padded-ELL tables; padding slots
      carry weight 0).
    w: d slots of per-slot mixing weights, each (m, 1) or (m, t) — a
      slot participates iff w > 0.
    self_vals: (m, t) — each destination's own row (the fallback when
      every slot is trimmed away or the support is empty).
    op: ``"trimmed"`` — drop the ``trim_k`` smallest and largest values
      among the occupied slots, weighted mean of the survivors with the
      weight mass renormalized; ``"median"`` — unweighted coordinate-wise
      median of the occupied slots (weights only define occupancy).

    Nonfinite values are clamped to ±1e30 before sorting (bounded
    influence — a NaN plane behaves as an extreme outlier instead of
    poisoning the comparisons), and the whole computation is the SAME op
    sequence inside the Pallas kernel and the jnp reference, so the two
    are bit-identical (tests/test_robust_mix.py).

    This function is called from inside a Pallas kernel body, so it must
    stay jnp-only with static shapes and 2-D slots (no host control flow
    on traced values, no cumsum, no strided slices or gathers — Mosaic
    lowers none of them; the rank scan and slot sums are unrolled loops).
    """
    if op not in ("trimmed", "median"):
        raise ValueError(f"robust_combine op {op!r} not in "
                         f"('trimmed', 'median')")
    d = len(vals)
    shape = self_vals.shape
    acc_dtype = self_vals.dtype
    big = jnp.asarray(_ROBUST_BIG, acc_dtype)
    pad = jnp.full(shape, _ROBUST_PAD, acc_dtype)
    zero = jnp.zeros(shape, acc_dtype)
    keys, ws = [], []
    for v, wi in zip(vals, w):
        valid = jnp.broadcast_to(wi > 0, shape)
        key = jnp.clip(jnp.nan_to_num(v, nan=_ROBUST_BIG, posinf=_ROBUST_BIG,
                                      neginf=-_ROBUST_BIG), -big, big)
        keys.append(jnp.where(valid, key, pad))
        ws.append(jnp.where(valid, jnp.broadcast_to(wi, shape).astype(
            acc_dtype), zero))
    keys, ws = oddeven_sort_pairs(keys, ws)
    occupied = [wi > 0 for wi in ws]
    # unrolled rank scan: 1-based rank of each slot among the occupied
    rank = jnp.zeros(shape, jnp.int32)
    r_lo = []
    for occ in occupied:
        rank = rank + occ.astype(jnp.int32)
        r_lo.append(rank)
    cnt = rank                               # occupied slots per (m, t)
    if op == "median":
        lo = (cnt - 1) // 2
        hi = cnt // 2
        med = (_slot_sum([jnp.where(lo == i, keys[i], zero)
                          for i in range(d)])
               + _slot_sum([jnp.where(hi == i, keys[i], zero)
                            for i in range(d)]))
        half = jnp.asarray(0.5, acc_dtype)
        return jnp.where(cnt > 0, half * med, self_vals)
    wk = []
    for i in range(d):
        r_hi = cnt - r_lo[i] + occupied[i].astype(jnp.int32)
        keep = occupied[i] & (r_lo[i] > trim_k) & (r_hi > trim_k)
        wk.append(jnp.where(keep, ws[i], zero))
    mass = _slot_sum(wk)
    num = _slot_sum([wi * k for wi, k in zip(wk, keys)])
    safe = jnp.where(mass > 0, mass, jnp.ones_like(mass))
    return jnp.where(mass > 0, num / safe, self_vals)


@functools.partial(jax.jit, static_argnames=("op", "trim_k", "mix_in_float32"))
def mix_robust_tables(params, coeffs: jnp.ndarray, nbr_idx: jnp.ndarray,
                      nbr_mask: jnp.ndarray, op: str, trim_k: int = 1,
                      mix_in_float32: bool = True):
    """Masked-sort REFERENCE of the robust edge-list gossip — Eq. (2)
    with the weighted mean replaced by :func:`robust_combine` over each
    destination's padded-ELL neighbour slots (self included; slots whose
    per-round weight is 0 — dropped links, quarantined columns, padding —
    are excluded from the order statistics).

    Same tables and traced-weights contract as :func:`mix_edges`; the
    Pallas counterpart is ``repro.kernels.gossip_mix.mix_robust_pallas``
    and the two are bit-identical (same op sequence, see
    :func:`robust_combine`).  O(n·dmax·|leaf|) memory for the gathered
    value slots — fine at sweep scale (dmax ≪ n), not a kernel.

    Always compiled, like the kernel body: under jit XLA may contract the
    weighted sum into fused multiply-adds (on hosts that have them), so
    an op-by-op reference would round differently from the kernel.
    """
    idx = jnp.asarray(nbr_idx)
    w = edge_weights(jnp.asarray(coeffs).astype(jnp.float32), idx,
                     jnp.asarray(nbr_mask))
    n, d = idx.shape

    def leaf_fn(leaf: jnp.ndarray) -> jnp.ndarray:
        acc_dtype = jnp.float32 if mix_in_float32 else leaf.dtype
        flat = leaf.reshape(n, -1).astype(acc_dtype)
        vals = [jnp.take(flat, idx[:, k], axis=0) for k in range(d)]
        ws = [w[:, k:k + 1].astype(acc_dtype) for k in range(d)]
        out = robust_combine(vals, ws, flat, op, trim_k=trim_k)
        return out.astype(leaf.dtype).reshape(leaf.shape)

    return jax.tree.map(leaf_fn, params)


def plane_norms(params) -> jnp.ndarray:
    """(n,) f32 L2 norm of each node's full parameter row — the plane
    magnitude the ``norm_clip`` robust rule and the quarantine health
    screen compare against (DESIGN.md §16)."""
    leaves = jax.tree.leaves(params)
    n = leaves[0].shape[0]
    sq = jnp.zeros((n,), jnp.float32)
    for leaf in leaves:
        flat = leaf.reshape(n, -1).astype(jnp.float32)
        sq = sq + jnp.sum(flat * flat, axis=1)
    return jnp.sqrt(sq)


def norm_clip_coeffs(coeffs: jnp.ndarray, norms: jnp.ndarray,
                     clip_mult: float = 1.0) -> jnp.ndarray:
    """Row-norm clipping as a coefficient transform: neighbour j's weight
    in row i is scaled by ``min(1, clip_mult·‖x_i‖/‖x_j‖)`` — a
    neighbour whose plane is larger than the destination's own row can
    contribute at most a clipped fraction of its mass.  Neighbours with
    nonfinite norms are dropped outright (their scale is meaningless);
    self weights are never clipped; rows that were scaled are
    renormalized (fallback self-weight 1), rows left untouched are
    returned BIT-identical — so a round where nothing clips reproduces
    the plain mean exactly.

    Because this is a pure (n, n) → (n, n) transform, every mix backend
    (einsum/pallas/sparse/edges) reuses its existing kernel on the
    clipped matrix — ``make_mix_fn(robust="norm_clip")`` composes it in
    front of the selected impl.
    """
    from repro.core.strategies import renormalize_rows

    c = jnp.asarray(coeffs)
    n = c.shape[-1]
    norms = jnp.asarray(norms, jnp.float32)
    finite = jnp.isfinite(norms)
    denom = jnp.where(norms > 0, norms, jnp.ones_like(norms))
    ratio = (jnp.asarray(clip_mult, jnp.float32) * norms[:, None]
             / denom[None, :])
    # zero-norm neighbours pass unclipped (nothing to scale); nonfinite
    # destination norms disable clipping for that row (self is suspect —
    # the quarantine screen, not the clip rule, handles that case)
    factor = jnp.where(norms[None, :] > 0, jnp.minimum(ratio, 1.0), 1.0)
    factor = jnp.where(jnp.isfinite(factor), factor, 1.0)
    factor = jnp.where(finite[None, :], factor, 0.0)
    eye = jnp.eye(n, dtype=bool)
    factor = jnp.where(eye, 1.0, factor).astype(c.dtype)
    scaled = c * factor
    changed = (scaled != c).any(axis=-1, keepdims=True)
    return jnp.where(changed, renormalize_rows(scaled, xp=jnp), c)


def mix_sparse_host(params, schedule: CirculantSchedule):
    """Single-host reference of the circulant schedule (jnp.roll stands in
    for collective_permute).  The distributed version lives in
    ``repro.core.gossip.gossip_step_sparse`` inside shard_map."""

    def leaf_fn(leaf: jnp.ndarray) -> jnp.ndarray:
        acc = jnp.zeros(leaf.shape, jnp.float32)
        extra = (1,) * (leaf.ndim - 1)
        for k, w in zip(schedule.offsets, schedule.weights):
            wk = jnp.asarray(w).reshape((schedule.n,) + extra)
            # destination i receives source (i+k) % n  ==  roll by -k
            shifted = jnp.roll(leaf, shift=-k, axis=0) if k else leaf
            acc = acc + wk * shifted.astype(jnp.float32)
        return acc.astype(leaf.dtype)

    return jax.tree.map(leaf_fn, params)


def mixing_collective_bytes(
    n_nodes: int,
    param_bytes_per_node: int,
    schedule: CirculantSchedule | None = None,
) -> dict:
    """Napkin-math ICI bytes per node for the two gossip schedules.

    dense  : ring all-gather moves (n-1)/n of the full stacked params past
             every node → ≈ (n-1) · P bytes in, per node.
    sparse : one permute per non-zero offset (excluding 0) → K' · P bytes.
    """
    dense = (n_nodes - 1) * param_bytes_per_node
    out = {"dense_bytes_per_node": dense}
    if schedule is not None:
        nonzero = sum(1 for o in schedule.offsets if o != 0)
        out["sparse_bytes_per_node"] = nonzero * param_bytes_per_node
        out["sparse_offsets"] = nonzero
    return out
