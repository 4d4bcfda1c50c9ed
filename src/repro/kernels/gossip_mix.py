"""Pallas TPU kernels for the gossip aggregation hot spot (Eq. 2).

Three generations live here:

* :func:`gossip_edges_pallas` / :func:`mix_edges_pallas` — the **edge-list
  segment mix** (DESIGN.md §12): per-destination neighbour tables
  (padded ELL, ``repro.core.topology.padded_neighbor_tables``) replace
  the dense (n, n) coefficient block, so each plane tile re-fetches
  ``n·dmax·8`` table bytes instead of ``n²·4`` — the path that makes
  n ≥ 1024 topologies affordable (``DecentralizedConfig(
  mix_impl="edges")``).

* :func:`gossip_plane_pallas` / :func:`mix_plane_pallas` — the **fused
  flat-plane mix** (DESIGN.md §11).  The stacked pytree is packed into one
  contiguous ``(n, P)`` plane (:class:`repro.core.plane.PlaneLayout`) and
  the whole round's aggregation ``out = C @ plane`` runs as ONE
  ``pallas_call``: grid over parameter tiles ``⌈P/bt⌉``, each program
  loading the full ``(n, n)`` coefficient block plus an ``(n, bt)`` plane
  slab into VMEM and producing all n destination rows with f32
  accumulation (``mix_in_float32=False`` accumulates in the plane dtype —
  the low-precision-aggregation ablation).  Modeled HBM traffic:
  ``2·n·P·b`` for the kernel stream (read + write the plane once) plus
  ``⌈P/bt⌉·n²·4`` coefficient re-fetches; the pack/unpack copies around
  the kernel add ``4·n·P·b`` end-to-end (see :func:`mix_modeled_hbm_bytes`
  — measured alongside wall-clock in ``benchmarks/gossip_cost.run_mix``,
  tracked as ``benchmarks/artifacts/BENCH_mix.json``).  This is the
  ``DecentralizedConfig(mix_impl="pallas")`` path.

* :func:`gossip_mix_pallas` / :func:`mix_dense_pallas` — the **legacy
  per-row kernel family**, kept as the benchmark baseline.  Honest cost:
  ``mix_dense_pallas`` tree-maps over leaves and vmaps a ``bm=1`` kernel
  over the n destination rows, so one mix issues ``n_leaves × n`` kernel
  programs and every destination row re-reads its full ``(n, |leaf|)``
  slab — ~``n·(n+1)·|P|`` bytes of HBM traffic versus the fused path's
  ~``2·n·|P|`` streaming floor, plus an n²-unrolled-MAC compile blow-up
  from the static K loop.  (An earlier docstring advertised a
  ``(K+1)·|P|`` floor for this wrapper; that figure described ONE
  ``gossip_mix_pallas`` call, not the n-row × n_leaves fan-out the mix
  actually performs.)

VMEM per program: the plane slab and the output tile, each
``n_pad·bt·b`` and double-buffered, their f32 working copies, plus the
plane kernel's double-buffered ``n_pad²·4`` coefficient block and as
much again for Mosaic's working copies of it.
:func:`_tile_width` picks the widest ``bt`` (≤ 2048) that keeps this
under ``_VMEM_BUDGET`` — 2048 at n=33, 128 at n=1024 in f32 — and raises
the kernel's VMEM limit where even ``bt=128`` does not fit.

The edge-list kernels gather neighbour rows with dynamic row reads of
the plane slab, driven by the flattened neighbour table passed as
scalar prefetch (SMEM); a row of a packed (bf16) slab is read as its
aligned sublane block and selected, since Mosaic only takes dynamic row
offsets on 32-bit data.

Backend selection: ``interpret=None`` (the default) auto-detects — the
kernels compile for real on TPU/GPU backends and fall back to Pallas
interpret mode on CPU, so the same call sites work everywhere.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.plane import PlaneLayout

__all__ = [
    "gossip_plane_pallas",
    "mix_plane_pallas",
    "gossip_edges_pallas",
    "mix_edges_pallas",
    "gossip_robust_pallas",
    "mix_robust_pallas",
    "gossip_mix_pallas",
    "mix_dense_pallas",
    "mix_modeled_hbm_bytes",
    "mix_eqn_budget",
    "mix_accum_upcasts",
    "default_interpret",
]


def mix_eqn_budget(mix_impl: str, n_leaves: int = 1,
                   robust: str = "mean") -> dict:
    """Trace-time equation budget ONE aggregation (Eq. 2) contributes to a
    round body — the fusion contract as introspectable metadata, consumed
    by ``repro.analysis`` fusion-budget rules (DESIGN.md §13) instead of
    hand-counted assertions.

    * ``"einsum"`` — one XLA GEMM (``dot_general``) per pytree leaf
      (``repro.core.mixing.mix_dense`` tensordots leaf-wise), zero Pallas
      launches.
    * ``"pallas"`` — the fused flat-plane kernel: exactly ONE
      ``pallas_call`` for the whole mix, regardless of leaf count (the
      §11 contract); the kernel's internal MAC is not an XLA GEMM.
    * ``"edges"`` — the edge-list segment kernel: also exactly ONE
      ``pallas_call`` (§12); the per-edge weight gather is indexing, not
      a contraction.
    * ``"sparse"`` — the circulant schedule is rolls + multiplies: zero
      of both.  (The dense fallback is an *einsum* budget — resolve it
      with ``repro.core.decentralized.mix_impl_budget``, which knows the
      support.)

    ``robust`` (DESIGN.md §16) modulates the contract: ``"norm_clip"``
    is a pure coefficient transform in front of the unchanged impl (same
    budget); ``"trimmed"``/``"median"`` replace the contraction with the
    sort-network path — the einsum reference becomes gathers + selects
    (zero GEMMs) and the edges impl swaps its kernel for the robust one
    (still exactly ONE ``pallas_call``).
    """
    budgets = {
        "einsum": {"pallas_call": 0, "dot_general": n_leaves},
        "pallas": {"pallas_call": 1, "dot_general": 0},
        "edges": {"pallas_call": 1, "dot_general": 0},
        "sparse": {"pallas_call": 0, "dot_general": 0},
    }
    if mix_impl not in budgets:
        raise KeyError(f"unknown mix_impl {mix_impl!r}; "
                       f"have {sorted(budgets)}")
    if robust in ("trimmed", "median"):
        if mix_impl == "einsum":
            return {"pallas_call": 0, "dot_general": 0}
        if mix_impl == "edges":
            return {"pallas_call": 1, "dot_general": 0}
        raise ValueError(f"robust={robust!r} has no {mix_impl!r} path "
                         f"(supported: einsum reference, edges kernel)")
    return budgets[mix_impl]


def mix_accum_upcasts(mix_impl: str, mix_in_float32: bool,
                      plane_low_precision: bool):
    """Declared accumulation-point policy for the dtype-flow rule: should
    the Pallas kernel body contain small-float→f32 upcasts?

    ``True``: yes — f32 accumulation of a low-precision plane upcasts at
    the declared accumulation points (``mix_in_float32=True`` on a bf16
    plane).  ``False``: no — the low-precision ablation must stay in the
    plane dtype end to end.  ``None``: nothing to check (no Pallas kernel
    in this impl, or the plane is f32-native so no upcast can exist).
    """
    if mix_impl not in ("pallas", "edges") or not plane_low_precision:
        return None
    return bool(mix_in_float32)


def default_interpret() -> bool:
    """True when no Pallas-compiling backend is present (CPU → interpret)."""
    return jax.default_backend() not in ("tpu", "gpu")


# ----------------------------------------------------------------------
# fused flat-plane mix: the whole round's aggregation in ONE pallas_call
# ----------------------------------------------------------------------
def _plane_kernel(acc_dtype, c_ref, p_ref, o_ref):
    """One (n_pad, bt) output tile: all destination rows of one parameter
    slab.  c_ref: (n_pad, n_pad) f32 VMEM; p_ref: (n_pad, bt) plane slab;
    o_ref: (n_pad, bt).  ``acc_dtype`` fixes the MAC precision (f32 by
    default; the plane dtype under mix_in_float32=False)."""
    c = c_ref[...].astype(acc_dtype)
    p = p_ref[...].astype(acc_dtype)
    # full-f32 products under f32 accumulation (a TPU's default matmul
    # precision would round the f32 operands to bf16)
    precision = (jax.lax.Precision.HIGHEST
                 if jnp.dtype(acc_dtype) == jnp.float32 else None)
    o_ref[...] = jnp.dot(c, p, precision=precision,
                         preferred_element_type=acc_dtype).astype(o_ref.dtype)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


#: VMEM bytes a mix program's buffers may take: under the 16 MiB scoped
#: VMEM default of a v5e TensorCore, leaving room for Mosaic's own
#: scratch.
_VMEM_BUDGET = 12 * 2**20
_MAX_TILE = 2048


def _sublanes(dtype) -> int:
    """Rows per native (sublane × 128) tile: 8 for 32-bit, 16 for bf16."""
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def _tile_width(n_pad: int, p: int, dtype, fixed_bytes: int = 0,
                lane_bytes: int = 0,
                bt: Optional[int] = None) -> Tuple[int, Optional[int]]:
    """``(bt, vmem_limit_bytes)`` for a kernel streaming ``(n_pad, bt)``
    plane tiles: the widest lane multiple up to ``bt`` (default 2048, and
    never wider than the padded plane) whose double-buffered input and
    output tiles, their f32 working copies, ``lane_bytes`` more per lane
    of tile and ``fixed_bytes`` (e.g. the plane kernel's coefficient
    block) fit :data:`_VMEM_BUDGET`.  Where
    even a 128-lane tile does not fit, the tile is 128 and the returned
    limit raises the kernel's VMEM allowance to what it needs; otherwise
    the limit is None (the compiler default)."""
    item = jnp.dtype(dtype).itemsize
    per_lane = n_pad * (4 * item + 4 + (4 if item < 4 else 0)) + lane_bytes
    cap = _round_up(min(bt or _MAX_TILE, _round_up(p, 128)), 128)
    fit = (_VMEM_BUDGET - fixed_bytes) // per_lane // 128 * 128
    bt = max(128, min(cap, fit))
    need = fixed_bytes + per_lane * bt
    if need <= _VMEM_BUDGET:
        return bt, None
    return bt, need + _VMEM_BUDGET // 2


@functools.partial(jax.jit,
                   static_argnames=("bt", "interpret", "mix_in_float32"))
def gossip_plane_pallas(plane: jnp.ndarray, coeffs: jnp.ndarray,
                        bt: Optional[int] = None,
                        interpret: Optional[bool] = None,
                        mix_in_float32: bool = True) -> jnp.ndarray:
    """``out = coeffs @ plane`` as ONE ``pallas_call``.

    plane: (n, P) — all n node-models' parameters, one row each.
    coeffs: (n, n) row-stochastic mixing matrix.
    bt: widest plane tile to use (grid = ⌈P/bt⌉ programs; each holds the
      full coefficient block plus one (n, bt) slab in VMEM).  None → 2048;
      either way narrowed by :func:`_tile_width` to fit VMEM at this n.
    interpret: None → auto (compiled on TPU/GPU, interpret on CPU).
    mix_in_float32: False accumulates in the plane dtype instead of f32
      (the low-precision-aggregation ablation; see
      ``DecentralizedConfig.mix_in_float32``).

    n and P are padded internally (zeros — padded coefficient rows/cols
    carry no weight) and the (n, P) result sliced back out.
    """
    if interpret is None:
        interpret = default_interpret()
    n, p = plane.shape
    # sublane multiple for the plane dtype (f32: 8, bf16: 16); the f32
    # coefficient block is (n_pad, n_pad) which then also satisfies its
    # own 8-row constraint.
    n_pad = _round_up(n, _sublanes(plane.dtype))
    # a lane (128) multiple: a non-multiple tile would pass in interpret
    # mode but fail Mosaic lowering on the TPU backend
    # the coefficient block double-buffered, plus as much again for the
    # working copies Mosaic makes of it for the full-precision product
    # (measured: at n=1024 a bf16 plane with bt=256 needs 23 MiB)
    bt, vmem_limit = _tile_width(n_pad, p, plane.dtype,
                                 fixed_bytes=4 * n_pad * n_pad * 4, bt=bt)
    p_pad = _round_up(p, bt)
    if (n_pad, p_pad) != (n, p):
        plane = jnp.pad(plane, ((0, n_pad - n), (0, p_pad - p)))
    c = jnp.asarray(coeffs, jnp.float32)
    if n_pad != n:
        c = jnp.pad(c, ((0, n_pad - n), (0, n_pad - n)))
    acc_dtype = jnp.float32 if mix_in_float32 else plane.dtype

    out = pl.pallas_call(
        functools.partial(_plane_kernel, acc_dtype),
        grid=(p_pad // bt,),
        in_specs=[
            pl.BlockSpec((n_pad, n_pad), lambda j: (0, 0)),  # coeff block
            pl.BlockSpec((n_pad, bt), lambda j: (0, j)),     # plane slab
        ],
        out_specs=pl.BlockSpec((n_pad, bt), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((n_pad, p_pad), plane.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        interpret=interpret,
    )(c, plane)
    return out[:n, :p]


def mix_plane_pallas(params, coeffs: jnp.ndarray,
                     bt: Optional[int] = None,
                     plane_dtype=None,
                     interpret: Optional[bool] = None,
                     mix_in_float32: bool = True):
    """Eq. (2) over a stacked pytree via the fused flat-plane kernel:
    pack once → ONE :func:`gossip_plane_pallas` → unpack once, per mix —
    one kernel launch regardless of leaf count (asserted by jaxpr
    inspection in tests/test_kernels.py).

    ``plane_dtype``: plane storage dtype (None → widest leaf dtype;
    ``jnp.bfloat16`` halves the kernel's HBM traffic while f32
    accumulation is preserved — low-precision *accumulation* is a
    separate knob, ``mix_in_float32=False``).

    Drop-in replacement for :func:`repro.core.mixing.mix_dense` (same
    f32 accumulation by default, same output dtypes); selected by
    ``DecentralizedConfig(mix_impl="pallas")``.  The
    :class:`repro.core.plane.PlaneLayout` is static metadata derived
    from the tree structure at trace time, so scans over rounds and
    vmaps over experiments reuse one layout and one compiled kernel.
    """
    layout = PlaneLayout.from_tree(params)
    plane = layout.pack(params, dtype=plane_dtype)
    mixed = gossip_plane_pallas(plane, coeffs, bt=bt, interpret=interpret,
                                mix_in_float32=mix_in_float32)
    return layout.unpack(mixed)


# ----------------------------------------------------------------------
# edge-list segment mix: sparse gather-accumulate over the flat plane
# ----------------------------------------------------------------------
def _row(p_ref, j):
    """Row ``j`` (traced) of a VMEM plane slab as a (1, bt) value.  Mosaic
    takes a dynamic row offset only on 32-bit data; a packed (bf16) row
    is read as its aligned sublane block and selected from it, exactly."""
    sub = _sublanes(p_ref.dtype)
    if sub == 8:
        return p_ref[pl.ds(j, 1), :]
    base = pl.multiple_of(j // sub * sub, sub)
    blk = p_ref[pl.ds(base, sub), :]
    pick = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 0) == j - base
    return jnp.sum(jnp.where(pick, blk, jnp.zeros_like(blk)), axis=0,
                   keepdims=True)


def _row_blocks(o_ref, block_fn):
    """Fill ``o_ref`` one aligned block of sublane rows at a time:
    ``block_fn(row0)`` gives the (sub, bt) block of rows row0..row0+sub."""
    sub = _sublanes(o_ref.dtype)

    def body(b, carry):
        row0 = pl.multiple_of(b * sub, sub)
        o_ref[pl.ds(row0, sub), :] = block_fn(row0).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, o_ref.shape[0] // sub, body, 0)


def _edges_kernel(acc_dtype, d, i_ref, w_ref, p_ref, o_ref):
    """One (n_pad, bt) output tile of the edge-list mix.  i_ref / w_ref:
    the (n_pad·d,) neighbour indices (int32) and per-edge weights (f32),
    destination-major, in SMEM; p_ref: (n_pad, bt) plane slab.  Each
    destination row accumulates its d neighbour rows, read from the slab
    at their table offsets, under their weights — a segment-sum over the
    padded-ELL edge list, O(n·dmax·bt) MACs instead of the dense n²·bt."""
    sub, bt = _sublanes(o_ref.dtype), o_ref.shape[1]

    def block(row0):
        rows = []
        for r in range(sub):
            e = (row0 + r) * d
            acc = jnp.zeros((1, bt), acc_dtype)
            for k in range(d):  # d is static → unrolled
                wk = jnp.full((1, bt), w_ref[e + k], jnp.float32)
                acc = acc + (wk.astype(acc_dtype)
                             * _row(p_ref, i_ref[e + k]).astype(acc_dtype))
            rows.append(acc)
        return jnp.concatenate(rows, axis=0)

    _row_blocks(o_ref, block)


def _edge_table_call(kernel, plane, weights, nbr_idx, bt, interpret,
                     lane_bytes: int = 0):
    """Run an edge-list ``kernel(i_ref, w_ref, p_ref, o_ref)`` over
    (n_pad, bt) plane tiles, with the (n, dmax) neighbour indices and
    weights flattened destination-major into scalar prefetch (padded
    destination rows gather row 0 under weight 0; they are sliced away)."""
    if interpret is None:
        interpret = default_interpret()
    n, p = plane.shape
    n_pad = _round_up(n, _sublanes(plane.dtype))
    bt, vmem_limit = _tile_width(n_pad, p, plane.dtype,
                                 lane_bytes=lane_bytes, bt=bt)
    p_pad = _round_up(p, bt)
    if (n_pad, p_pad) != (n, p):
        plane = jnp.pad(plane, ((0, n_pad - n), (0, p_pad - p)))
    rows = ((0, n_pad - n), (0, 0))
    idx = jnp.pad(jnp.asarray(nbr_idx, jnp.int32), rows).reshape(-1)
    w = jnp.pad(jnp.asarray(weights, jnp.float32), rows).reshape(-1)

    tile = pl.BlockSpec((n_pad, bt), lambda j, i_ref, w_ref: (0, j))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(p_pad // bt,),
            in_specs=[tile], out_specs=tile),
        out_shape=jax.ShapeDtypeStruct((n_pad, p_pad), plane.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        interpret=interpret,
    )(idx, w, plane)
    return out[:n, :p]


@functools.partial(jax.jit,
                   static_argnames=("bt", "interpret", "mix_in_float32"))
def gossip_edges_pallas(plane: jnp.ndarray, weights: jnp.ndarray,
                        nbr_idx: jnp.ndarray, bt: Optional[int] = None,
                        interpret: Optional[bool] = None,
                        mix_in_float32: bool = True) -> jnp.ndarray:
    """``out[i] = Σ_d weights[i, d] · plane[nbr_idx[i, d]]`` as ONE
    ``pallas_call`` — the sparse counterpart of
    :func:`gossip_plane_pallas`.

    plane: (n, P) — all n node-models' parameters, one row each.
    weights: (n, dmax) per-edge coefficients, already masked
      (``repro.core.mixing.edge_weights`` — zeros on padding slots).
    nbr_idx: (n, dmax) int32 neighbour tables
      (``repro.core.topology.padded_neighbor_tables``; padding = own row).
    bt / interpret / mix_in_float32: as :func:`gossip_plane_pallas`.

    Each grid program streams one (n, bt) plane slab; the (n, dmax)
    weight/index tables sit in SMEM for the whole call — O(|E|·P) HBM
    bytes instead of the dense kernel's O(n²) coefficient re-fetches per
    tile (``mix_modeled_hbm_bytes`` models both; the crossover is
    2·dmax < n).
    """
    acc_dtype = jnp.float32 if mix_in_float32 else plane.dtype
    return _edge_table_call(
        functools.partial(_edges_kernel, acc_dtype, weights.shape[1]),
        plane, weights, nbr_idx, bt, interpret)


def mix_edges_pallas(params, coeffs: jnp.ndarray, nbr_idx, nbr_mask,
                     bt: Optional[int] = None,
                     plane_dtype=None,
                     interpret: Optional[bool] = None,
                     mix_in_float32: bool = True):
    """Eq. (2) over a stacked pytree via the edge-list segment kernel:
    pack once → per-edge weight gather
    (``repro.core.mixing.edge_weights``, O(n·dmax)) → ONE
    :func:`gossip_edges_pallas` → unpack once.  Drop-in replacement for
    ``repro.core.mixing.mix_dense`` / :func:`mix_plane_pallas` on any
    support; selected by ``DecentralizedConfig(mix_impl="edges")``.  The
    tables are static trace-time data (baked into scans and vmaps); the
    coefficients stay traced, so per-round matrices reuse one compiled
    kernel.  Agrees with the dense einsum to 1e-6
    (tests/test_mix_equivalence.py)."""
    from repro.core.mixing import edge_weights

    layout = PlaneLayout.from_tree(params)
    plane = layout.pack(params, dtype=plane_dtype)
    w = edge_weights(jnp.asarray(coeffs, jnp.float32),
                     jnp.asarray(nbr_idx), jnp.asarray(nbr_mask))
    mixed = gossip_edges_pallas(plane, w, jnp.asarray(nbr_idx), bt=bt,
                                interpret=interpret,
                                mix_in_float32=mix_in_float32)
    return layout.unpack(mixed)


# ----------------------------------------------------------------------
# robust edge-list mix: in-register sort network over the neighbour axis
# ----------------------------------------------------------------------
def _robust_kernel(op, trim_k, acc_dtype, d, i_ref, w_ref, p_ref, o_ref):
    """One (n_pad, bt) output tile of the robust edge-list mix.  Same
    operands as :func:`_edges_kernel` — SMEM index/weight tables, (n_pad,
    bt) plane slab — but instead of the weighted accumulate, each block
    of destination rows gathers its d (sub, bt) neighbour slots and
    reduces them by ``repro.core.mixing.robust_combine``: an odd-even
    transposition sort over the STATIC d axis followed by the
    trimmed-mean / coordinate-median selection with weight-mass
    renormalization.  Slots of weight 0 are excluded from the order
    statistics; the destination's own row is the fallback when everything
    is trimmed."""
    from repro.core.mixing import robust_combine

    sub, bt = _sublanes(o_ref.dtype), o_ref.shape[1]

    def block(row0):
        vals, ws = [], []
        for k in range(d):  # d is static → unrolled
            es = [(row0 + r) * d + k for r in range(sub)]
            vals.append(jnp.concatenate(
                [_row(p_ref, i_ref[e]) for e in es], axis=0
            ).astype(acc_dtype))
            ws.append(jnp.concatenate(
                [jnp.full((1, bt), w_ref[e], jnp.float32) for e in es],
                axis=0).astype(acc_dtype))
        own = p_ref[pl.ds(row0, sub), :].astype(acc_dtype)
        return robust_combine(vals, ws, own, op, trim_k=trim_k)

    _row_blocks(o_ref, block)


@functools.partial(jax.jit,
                   static_argnames=("op", "trim_k", "bt", "interpret",
                                    "mix_in_float32"))
def gossip_robust_pallas(plane: jnp.ndarray, weights: jnp.ndarray,
                         nbr_idx: jnp.ndarray, op: str = "trimmed",
                         trim_k: int = 1, bt: int = 512,
                         interpret: Optional[bool] = None,
                         mix_in_float32: bool = True) -> jnp.ndarray:
    """Robust Eq. (2) over the padded-ELL tables as ONE ``pallas_call`` —
    the Byzantine-resilient counterpart of :func:`gossip_edges_pallas`
    (DESIGN.md §16).

    plane / weights / nbr_idx / interpret / mix_in_float32: exactly as
    :func:`gossip_edges_pallas` (slots of weight 0 are excluded by
    occupancy rather than by multiplying to zero).
    op / trim_k: the robust rule — see
    ``repro.core.mixing.robust_combine``.
    bt: widest plane tile; narrower than the mean kernels' default
      because each block of rows holds its dmax (sub, bt) sorted pairs
      in VMEM besides the slab (:func:`_tile_width` narrows it further).

    Bit-identical to the masked-sort reference
    ``repro.core.mixing.mix_robust_tables`` — the same op sequence over
    the same slots (tests/test_robust_mix.py).
    """
    acc_dtype = jnp.float32 if mix_in_float32 else plane.dtype
    d = weights.shape[1]
    # ~8 live f32 values per slot while the sort network runs
    sort_bytes = 8 * d * _sublanes(plane.dtype) * 4
    return _edge_table_call(
        functools.partial(_robust_kernel, op, trim_k, acc_dtype, d),
        plane, weights, nbr_idx, bt, interpret, lane_bytes=sort_bytes)


def mix_robust_pallas(params, coeffs: jnp.ndarray, nbr_idx, nbr_mask,
                      op: str = "trimmed", trim_k: int = 1, bt: int = 512,
                      plane_dtype=None,
                      interpret: Optional[bool] = None,
                      mix_in_float32: bool = True):
    """Robust Eq. (2) over a stacked pytree: pack once → per-edge weight
    gather → ONE :func:`gossip_robust_pallas` → unpack once.  Drop-in
    peer of :func:`mix_edges_pallas` selected by
    ``repro.core.decentralized.make_mix_fn(mix_impl="edges",
    robust="trimmed"|"median")``; bit-identical to the jnp reference
    ``repro.core.mixing.mix_robust_tables``."""
    from repro.core.mixing import edge_weights

    layout = PlaneLayout.from_tree(params)
    plane = layout.pack(params, dtype=plane_dtype)
    w = edge_weights(jnp.asarray(coeffs, jnp.float32),
                     jnp.asarray(nbr_idx), jnp.asarray(nbr_mask))
    mixed = gossip_robust_pallas(plane, w, jnp.asarray(nbr_idx), op=op,
                                 trim_k=trim_k, bt=bt, interpret=interpret,
                                 mix_in_float32=mix_in_float32)
    return layout.unpack(mixed)


def mix_modeled_hbm_bytes(impl: str, n: int, p_floats: int,
                          itemsize: int = 4, n_leaves: int = 1,
                          bt: int = 2048, max_neighbors: Optional[int] = None,
                          n_offsets: Optional[int] = None) -> int:
    """Modeled HBM bytes for one mix of an n-node model with ``p_floats``
    parameters per node (``itemsize`` bytes each, split over ``n_leaves``
    pytree leaves) — the numbers ``BENCH_mix.json`` tracks.

    * ``"einsum"``   — one XLA GEMM per leaf: stream the stacked params
      in and out once, re-reading the (n, n) matrix per leaf:
      ``2·n·P·b + n_leaves·n²·4``.
    * ``"pallas_rows"`` — the legacy ``mix_dense_pallas`` fan-out: every
      destination row of every leaf re-reads its full (n, |leaf|) slab:
      ``n·(n+1)·P·b`` plus per-program weight vectors (``n²·4·n_leaves``).
    * ``"pallas_plane"`` — the fused kernel: stream the plane in and out
      once plus per-tile coefficient re-fetches:
      ``2·n·P·b + ⌈P/bt⌉·n²·4``.
    * ``"pallas_plane_e2e"`` — fused kernel plus the pack/unpack copies
      around it (each a read + write of the plane): ``6·n·P·b + ...`` —
      the honest end-to-end figure when the mix is used leaf-in/leaf-out.
    * ``"edges"`` — the edge-list segment kernel
      (:func:`gossip_edges_pallas`; needs ``max_neighbors`` = the table
      width dmax): stream the plane in and out once plus per-tile table
      re-fetches (f32 weight + int32 index per edge slot):
      ``2·n·P·b + ⌈P/bt⌉·n·dmax·8``.  Beats ``"pallas_plane"`` exactly
      when ``2·dmax < n`` — every paper topology from n ≈ 64 up.
    * ``"edges_robust"`` — the robust sort-network kernel
      (:func:`gossip_robust_pallas`; needs ``max_neighbors``): identical
      HBM traffic to ``"edges"`` — each neighbour row is still gathered
      exactly once per tile and the sort runs entirely in registers/VMEM
      — so robustness costs compute and VMEM working set
      (O(dmax·n·bt) sorted pairs), never extra HBM.  Dominance
      (robust ≥ edges, and < pallas_plane whenever 2·dmax < n) is pinned
      in tests/test_robust_mix.py.
    * ``"sparse"`` — the circulant ring-offset schedule
      (``repro.core.mixing.mix_sparse``; needs ``n_offsets`` = the static
      offset count K incl. 0): each offset reads the full plane once and
      the accumulator is written once — ``(K+1)·n·P·b`` plus the K
      per-offset weight vectors (``K·n·4``).
    """
    coeff = n * n * 4
    if impl == "einsum":
        return 2 * n * p_floats * itemsize + n_leaves * coeff
    if impl == "pallas_rows":
        return n * (n + 1) * p_floats * itemsize + n_leaves * n * n * 4
    if impl == "sparse":
        if n_offsets is None:
            raise ValueError("impl='sparse' needs n_offsets (the circulant "
                             "schedule's static offset count, incl. 0)")
        return ((n_offsets + 1) * n * p_floats * itemsize
                + n_offsets * n * 4)
    tiles = -(-p_floats // bt)
    if impl in ("edges", "edges_robust"):
        if max_neighbors is None:
            raise ValueError(f"impl={impl!r} needs max_neighbors (the "
                             "padded-ELL table width dmax)")
        return (2 * n * p_floats * itemsize
                + tiles * n * max_neighbors * 8)
    if impl == "pallas_plane":
        return 2 * n * p_floats * itemsize + tiles * coeff
    if impl == "pallas_plane_e2e":
        return 6 * n * p_floats * itemsize + tiles * coeff
    raise KeyError(f"unknown impl {impl!r}")


# ----------------------------------------------------------------------
# legacy per-row kernel family (benchmark baseline)
# ----------------------------------------------------------------------
def _kernel(w_ref, blocks_ref, out_ref):
    """blocks_ref: (K, bm, bn) VMEM; w_ref: (K,) SMEM-ish; out: (bm, bn)."""
    k = blocks_ref.shape[0]
    acc = jnp.zeros(out_ref.shape, jnp.float32)
    for i in range(k):  # K is static → unrolled MACs
        acc += w_ref[i] * blocks_ref[i].astype(jnp.float32)
    out_ref[...] = acc.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def gossip_mix_pallas(blocks: jnp.ndarray, weights: jnp.ndarray,
                      bm: int = 256, bn: int = 512,
                      interpret: Optional[bool] = None) -> jnp.ndarray:
    """out = Σ_k weights[k] · blocks[k]  (legacy K-way MAC kernel).

    blocks: (K, M, N) — K neighbour copies of one parameter tile-matrix.
    weights: (K,) f32.  M, N padded to tile multiples internally.
    interpret: None → auto (compiled on TPU/GPU, interpret on CPU).

    One call streams its (K, M, N) input once — bytes ≈ (K+1)·M·N·b — but
    the :func:`mix_dense_pallas` wrapper issues n of these per leaf, so
    the *mix* is ~n·(K+1)·|P| bytes; use :func:`mix_plane_pallas` for the
    fused single-call path.
    """
    if interpret is None:
        interpret = default_interpret()
    k, m, n = blocks.shape
    bm = min(bm, m)
    bn = min(bn, n)
    pm = (m + bm - 1) // bm * bm
    pn = (n + bn - 1) // bn * bn
    if (pm, pn) != (m, n):
        blocks = jnp.pad(blocks, ((0, 0), (0, pm - m), (0, pn - n)))

    out = pl.pallas_call(
        _kernel,
        grid=(pm // bm, pn // bn),
        in_specs=[
            pl.BlockSpec((k,), lambda i, j: (0,)),           # weights: tiny, replicated
            pl.BlockSpec((k, bm, bn), lambda i, j: (0, i, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((pm, pn), blocks.dtype),
        interpret=interpret,
    )(weights.astype(jnp.float32), blocks)
    return out[:m, :n]


def mix_dense_pallas(params, coeffs: jnp.ndarray,
                     interpret: Optional[bool] = None):
    """LEGACY Eq. (2) path, kept as the ``BENCH_mix`` baseline: for each
    leaf ``(n, ...)``, destination row i is the K=n-way MAC
    ``Σ_j C[i,j]·leaf[j]`` — one :func:`gossip_mix_pallas` call vmapped
    over destination rows, i.e. ``n_leaves × n`` kernel programs per mix,
    each re-reading the full leaf slab (~``n·(n+1)·|P|`` HBM bytes; see
    :func:`mix_modeled_hbm_bytes`).  Production aggregation routes
    through :func:`mix_plane_pallas` instead
    (``DecentralizedConfig(mix_impl="pallas")``).
    """
    c = jnp.asarray(coeffs, jnp.float32)
    n = c.shape[0]

    def leaf_fn(leaf: jnp.ndarray) -> jnp.ndarray:
        flat = leaf.reshape(n, 1, -1)  # (K=n, M=1, N=prod(rest))
        out = jax.vmap(
            lambda w: gossip_mix_pallas(flat, w, bm=1, interpret=interpret)
        )(c)  # (n, 1, N)
        return out.reshape(leaf.shape).astype(leaf.dtype)

    return jax.tree.map(leaf_fn, params)
