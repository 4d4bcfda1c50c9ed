"""Decentralized learning runtime — Algorithm 1 of the paper.

All n node-models are held as ONE stacked pytree (leaves ``(n, ...)``).
Each round:

  1. **LocalTrain** (Eq. 1): every node runs E epochs of minibatch SGD/Adam
     on its own data shard — ``vmap`` over the node axis, ``lax.scan`` over
     batches.
  2. **Aggregation** (Eq. 2): the stacked params are contracted against the
     strategy's row-stochastic mixing matrix (dense einsum on a single
     device; ``repro.core.gossip`` collectives under a mesh).

Two execution modes (DESIGN.md §7):

* **scanned** (default): the whole R-round schedule is ONE jitted
  ``lax.scan``.  The per-round mixing matrices are precomputed host-side
  into an ``(R, n, n)`` stack (:func:`coeffs_stack`), so the Random
  baseline's per-round resampling and ``core.dynamic`` link-failure
  matrices become *data* consumed by the scan instead of host-side control
  flow.  Per-round batches are stacked along a leading round axis and
  evaluation runs inside the scan, so metrics come back as ``(R, n)``
  arrays with a single device dispatch for the whole run.
* **unrolled** (``DecentralizedConfig(unroll_eval=True)``): the legacy
  per-round Python loop — one dispatch per round, incremental history.
  Useful for streaming metrics while debugging, and for very long
  schedules where the stacked ``(R, ...)`` batch tensor would not fit in
  host memory.

Both modes produce identical histories — asserted in tests/test_sweep.py.

Each part of a round runs under a ``jax.named_scope`` placed around its
call site, outside any ``vmap``/``cond``, so that its loop ops carry the
scope too: ``local_train``, ``mix``, ``coeffs`` (in-scan coefficient
programs), ``eval`` and ``analytics`` (``batch_gather`` in
``repro.core.sweep``).  A device profile reads them from each op's name
(``bench/scopes.py``); they change op metadata only, never a value.
The vmap-over-experiments axis on top of the scanned mode lives in
``repro.core.sweep``.

The trainer is model-agnostic: it takes a ``loss_fn(params, batch)``
and an ``Optimizer``.  Evaluation after every round measures each node's
accuracy on the shared ``test_iid`` / ``test_ood`` sets — the accuracy-AUC
across rounds is the paper's knowledge-propagation metric.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.mixing import mix_dense, mix_sparse, sparse_offsets
from repro.core.strategies import (
    AggregationStrategy,
    mixing_matrix,
    random_round_seed,
)
from repro.core.topology import Topology
from repro.training.optimizer import Optimizer, apply_updates

__all__ = [
    "DecentralizedConfig",
    "RoundMetrics",
    "DecentralizedTrainer",
    "stack_params",
    "unstack_params",
    "round_coeffs",
    "coeffs_stack",
    "make_local_train_fn",
    "make_round_fn",
    "make_participation_round_fn",
    "participation_carry_init",
    "make_fault_round_fn",
    "fault_carry_init",
    "make_mix_fn",
    "mix_impl_budget",
    "edges_schedule",
    "make_scan_fn",
    "eval_round_indices",
]


def stack_params(params_list) -> object:
    """[pytree] * n  →  stacked pytree with leading node axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *params_list)


def unstack_params(stacked, n: int):
    return [jax.tree.map(lambda x: x[i], stacked) for i in range(n)]


@dataclasses.dataclass(frozen=True)
class DecentralizedConfig:
    rounds: int = 40           # R in the paper
    local_epochs: int = 5      # E in the paper
    eval_every: int = 1
    resample_random_each_round: bool = True   # paper's Random baseline redraws
    # True (default): Eq. (2) accumulates in f32 whatever the param dtype
    # (bf16 aggregation in low precision loses exactly the small OOD
    # deltas the paper studies).  False: accumulate in the native param /
    # plane dtype — the low-precision-aggregation ablation.  Routed to
    # every mixing backend via make_round_fn → make_mix_fn.
    mix_in_float32: bool = True
    unroll_eval: bool = False  # True → legacy per-round Python loop
    # "einsum" | "pallas" (fused flat-plane kernel, kernels.gossip_mix:
    # one pallas_call per mix — DESIGN.md §11) | "sparse" (circulant
    # ring-offset schedule from the topology support; dense fallback for
    # supports that don't decompose compactly — see make_mix_fn) |
    # "edges" (padded edge-list segment kernel over the flat plane,
    # kernels.gossip_mix.mix_edges_pallas — O(n·dmax) table bytes per
    # plane tile instead of n², any support, no fallback — DESIGN.md §12)
    mix_impl: str = "einsum"
    # mix_impl="sparse" fallback slack: dense fallback when the non-self
    # ring-offset count exceeds max degree + sparse_slack (see
    # make_mix_fn / sparse_schedule).
    sparse_slack: int = 4
    # Robust aggregation (DESIGN.md §16): "mean" (default — the paper's
    # Eq. (2), untouched callables so degenerate configs stay
    # bit-identical) | "trimmed" (coordinate-wise trimmed mean over
    # neighbour rows, robust_trim extremes cut per side) | "median"
    # (coordinate-wise weighted median) | "norm_clip" (scale each
    # neighbour column so its row norm is at most robust_clip × the
    # receiver's own — a pure (n, n) coefficient transform composing
    # with every mix_impl).  "trimmed"/"median" sort per coordinate and
    # are served by mix_impl="einsum" (jnp reference) or "edges"
    # (Pallas kernel) only.
    robust: str = "mean"
    robust_trim: int = 1
    robust_clip: float = 1.0
    # True (default): the pipeline supplies E *distinct* epoch passes per
    # round (``NodeBatcher(local_epochs=E)``) and LocalTrain consumes them
    # as-is — the paper's Eq. (1).  False: legacy behavior — one epoch of
    # batches tiled E times, i.e. the identical batch order replayed every
    # local epoch (kept for the bit-exact equivalence tests).
    epoch_shuffle: bool = True


@dataclasses.dataclass
class RoundMetrics:
    round: int
    iid_acc: np.ndarray   # (n,) per-node accuracy on test_iid
    ood_acc: np.ndarray   # (n,) per-node accuracy on test_ood
    train_loss: np.ndarray  # (n,)


# ----------------------------------------------------------------------
# mixing-matrix schedules: per-round matrices as precomputed data
# ----------------------------------------------------------------------
def round_coeffs(
    topo: Topology,
    strategy: AggregationStrategy,
    round_idx: int,
    data_counts: Optional[np.ndarray] = None,
    coeffs_fn: Optional[Callable[[int], np.ndarray]] = None,
    resample_random: bool = True,
) -> np.ndarray:
    """Mixing matrix for one round.  Random redraws per round (seed mixed
    through :func:`repro.core.strategies.random_round_seed`); all other
    strategies are static unless a ``coeffs_fn`` (e.g. core.dynamic
    link-failure matrices) overrides.

    Program-supported strategies (``repro.core.coeffs.PROGRAM_KINDS``)
    route through the device-side coefficient program — float32, the same
    values the in-scan path generates — so unrolled, scanned, and
    program-driven runs consume identical matrices.  Other kinds
    (metropolis, ``register_strategy`` plugins) keep the host numpy path.
    """
    if coeffs_fn is not None:
        return np.asarray(coeffs_fn(round_idx))
    from repro.core.coeffs import PROGRAM_KINDS, program_for

    if strategy.kind in PROGRAM_KINDS:
        program, state = program_for(topo, strategy,
                                     data_counts=data_counts,
                                     resample_random=resample_random)
        return program.materialize(
            state, round_indices=np.array([round_idx]))[0]
    # host-path guard: unreachable while "random" is program-supported,
    # kept so the fallback stays round-correct if PROGRAM_KINDS shrinks
    if strategy.kind == "random" and resample_random:
        strategy = dataclasses.replace(
            strategy, seed=random_round_seed(strategy.seed, round_idx))
    return mixing_matrix(topo, strategy, data_counts)


def coeffs_stack(
    topo: Topology,
    strategy: AggregationStrategy,
    rounds: int,
    data_counts: Optional[np.ndarray] = None,
    coeffs_fn: Optional[Callable[[int], np.ndarray]] = None,
    resample_random: bool = True,
) -> np.ndarray:
    """(R, n, n) stack of per-round mixing matrices — the scanned trainer's
    data-not-control-flow representation of time-varying aggregation.

    For program-supported strategies this IS
    ``CoeffProgram.materialize(rounds)`` (DESIGN.md §9) — the legacy slab
    API survives as the materialized view of the coefficient program; the
    host numpy loop remains for ``coeffs_fn`` overrides and non-program
    strategies."""
    from repro.core.coeffs import PROGRAM_KINDS, program_for

    if coeffs_fn is None and strategy.kind in PROGRAM_KINDS:
        program, state = program_for(topo, strategy,
                                     data_counts=data_counts,
                                     resample_random=resample_random)
        return program.materialize(state, rounds)
    return np.stack([
        round_coeffs(topo, strategy, r, data_counts, coeffs_fn,
                     resample_random)
        for r in range(rounds)
    ])


# ----------------------------------------------------------------------
# round-step factories (shared by the trainer and repro.core.sweep)
# ----------------------------------------------------------------------
def make_mix_fn(mix_impl: str = "einsum",
                mix_support: Optional[np.ndarray] = None,
                sparse_slack: int = 4,
                mix_in_float32: bool = True,
                robust: str = "mean",
                robust_trim: int = 1,
                robust_clip: float = 1.0) -> Callable:
    """Aggregation backend: XLA einsum (default), the fused flat-plane
    Pallas kernel (``kernels.gossip_mix.mix_plane_pallas`` — the whole
    mix as ONE ``pallas_call``, DESIGN.md §11; interpret-mode on CPU,
    compiled on TPU/GPU), or the circulant ring-offset schedule
    (``mixing.mix_sparse``).

    ``"sparse"`` needs ``mix_support`` — the (n, n) neighbourhood mask
    (adjacency + self-loops) that fixes the static offset set.  When the
    non-self offset count exceeds ``max degree + sparse_slack`` the
    decomposition moves no fewer bytes than a dense all-gather, so this
    falls back to :func:`repro.core.mixing.mix_dense` (unstructured
    supports don't circulant-decompose compactly; rings/WS graphs do).

    ``"edges"`` also needs ``mix_support`` and fixes the padded-ELL
    neighbour tables at trace time instead
    (``repro.core.topology.padded_neighbor_tables`` with the diagonal
    forced in); per-round coefficients are gathered through the tables,
    so any support works — no structural fallback — and the mix runs as
    ONE Pallas segment kernel over the flat parameter plane
    (``kernels.gossip_mix.mix_edges_pallas``).  Like the circulant path,
    weight outside the tables would be silently dropped;
    ``SweepEngine.run`` validates coefficients against the support.

    ``mix_in_float32=False`` switches every backend's accumulation from
    f32 to the native param/plane dtype
    (``DecentralizedConfig.mix_in_float32`` — the low-precision
    aggregation ablation).

    ``robust`` (DESIGN.md §16) selects Byzantine-resilient aggregation:

    * ``"mean"`` (default) — Eq. (2) exactly; this function returns the
      SAME callables it always has, so every degenerate robustness
      config (fault rate 0.0) is bit-identical to the synchronous path.
    * ``"norm_clip"`` — a pure ``(n, n)`` coefficient transform
      (:func:`repro.core.mixing.norm_clip_coeffs`): each neighbour
      column is scaled so its published row norm is at most
      ``robust_clip`` × the receiver's own, then rows renormalize.
      Composes with EVERY ``mix_impl``.
    * ``"trimmed"`` / ``"median"`` — coordinate-wise trimmed mean
      (``robust_trim`` extremes cut per side) / weighted median over
      the padded-ELL neighbour tables.  Needs ``mix_support`` (tables
      fixed at trace time like ``"edges"``); served by
      ``mix_impl="einsum"`` (jnp reference,
      :func:`repro.core.mixing.mix_robust_tables`) or ``"edges"``
      (Pallas sort-network kernel,
      ``kernels.gossip_mix.mix_robust_pallas``) — the two are
      bit-identical (tests/test_robust_mix.py); other impls raise.
    """
    from repro.core.mixing import ROBUST_MODES

    if robust not in ROBUST_MODES:
        raise ValueError(f"unknown robust mode {robust!r}; "
                         f"have {ROBUST_MODES}")
    if robust in ("trimmed", "median"):
        if mix_impl not in ("einsum", "edges"):
            raise ValueError(
                f"robust={robust!r} has no mix_impl={mix_impl!r} path — "
                f"the per-coordinate sort runs over padded neighbour "
                f"tables; use mix_impl='einsum' (jnp reference) or "
                f"'edges' (Pallas kernel)")
        if mix_support is None:
            raise ValueError(
                f"robust={robust!r} needs mix_support (the (n, n) "
                f"neighbourhood mask, adjacency + self-loops) to fix "
                f"the padded-ELL neighbour tables at trace time")
        nbr_idx, nbr_mask = edges_schedule(mix_support)
        idx, msk = jnp.asarray(nbr_idx), jnp.asarray(nbr_mask)
        trim_k = int(robust_trim) if robust == "trimmed" else 0
        if mix_impl == "einsum":
            from repro.core.mixing import mix_robust_tables

            return lambda params, coeffs: mix_robust_tables(
                params, coeffs, idx, msk, robust, trim_k=trim_k,
                mix_in_float32=mix_in_float32)
        from repro.kernels.gossip_mix import mix_robust_pallas

        return lambda params, coeffs: mix_robust_pallas(
            params, coeffs, idx, msk, op=robust, trim_k=trim_k,
            mix_in_float32=mix_in_float32)
    if robust == "norm_clip":
        from repro.core.mixing import norm_clip_coeffs, plane_norms

        base = make_mix_fn(mix_impl, mix_support=mix_support,
                           sparse_slack=sparse_slack,
                           mix_in_float32=mix_in_float32)
        clip = float(robust_clip)

        def clipped_mix(params, coeffs):
            return base(params,
                        norm_clip_coeffs(coeffs, plane_norms(params), clip))

        return clipped_mix
    if mix_impl == "einsum":
        if mix_in_float32:
            return mix_dense
        return functools.partial(mix_dense, mix_in_float32=False)
    if mix_impl == "pallas":
        from repro.kernels.gossip_mix import mix_plane_pallas

        return functools.partial(mix_plane_pallas,
                                 mix_in_float32=mix_in_float32)
    if mix_impl == "sparse":
        if mix_support is None:
            raise ValueError(
                "mix_impl='sparse' needs mix_support (the (n, n) "
                "neighbourhood mask, adjacency + self-loops) to fix the "
                "ring-offset schedule at trace time")
        offsets, _ = sparse_schedule(mix_support, sparse_slack)
        if offsets is None:
            return make_mix_fn("einsum", mix_in_float32=mix_in_float32)
        return lambda params, coeffs: mix_sparse(
            params, coeffs, offsets, mix_in_float32=mix_in_float32)
    if mix_impl == "edges":
        if mix_support is None:
            raise ValueError(
                "mix_impl='edges' needs mix_support (the (n, n) "
                "neighbourhood mask, adjacency + self-loops) to fix the "
                "padded-ELL neighbour tables at trace time")
        from repro.kernels.gossip_mix import mix_edges_pallas

        nbr_idx, nbr_mask = edges_schedule(mix_support)
        idx, msk = jnp.asarray(nbr_idx), jnp.asarray(nbr_mask)
        return lambda params, coeffs: mix_edges_pallas(
            params, coeffs, idx, msk, mix_in_float32=mix_in_float32)
    raise KeyError(f"unknown mix_impl {mix_impl!r}; "
                   f"have 'einsum', 'pallas', 'sparse', 'edges'")


def mix_impl_budget(mix_impl: str, n_leaves: int = 1,
                    mix_support: Optional[np.ndarray] = None,
                    sparse_slack: int = 4,
                    robust: str = "mean") -> dict:
    """The trace-time equation budget a configured mix contributes to one
    round body — ``repro.kernels.gossip_mix.mix_eqn_budget`` with the
    circulant path's dense-fallback decision resolved exactly the way
    :func:`make_mix_fn` resolves it (offset count vs max degree + slack).
    This is the introspectable source of truth for ``repro.analysis``
    fusion-budget rules: when the fallback fires, the *einsum* budget is
    the contract, not the sparse one."""
    from repro.kernels.gossip_mix import mix_eqn_budget

    if mix_impl == "sparse" and mix_support is not None:
        offsets, _ = sparse_schedule(mix_support, sparse_slack)
        if offsets is None:
            return mix_eqn_budget("einsum", n_leaves, robust=robust)
    return mix_eqn_budget(mix_impl, n_leaves, robust=robust)


def sparse_schedule(mix_support, sparse_slack: int = 4):
    """``(offsets, covered)`` for a support mask, or ``(None, None)`` when
    the dense fallback applies (non-self offset count > max degree +
    slack).  ``covered`` is the (n, n) bool mask of positions the ring
    schedule can express — ``SweepEngine.run`` checks coefficients
    against it so off-schedule weight raises instead of being silently
    dropped by ``mix_sparse``."""
    support = np.asarray(mix_support)
    n = support.shape[0]
    offsets = sparse_offsets(support)
    off_diag = support * (1.0 - np.eye(n))
    max_degree = int(off_diag.sum(axis=1).max())
    nonzero_offsets = len(offsets) - (1 if 0 in offsets else 0)
    if nonzero_offsets > max_degree + sparse_slack:
        return None, None
    rows = np.arange(n)
    covered = np.zeros((n, n), bool)
    for k in offsets:
        covered[rows, (rows + k) % n] = True
    return offsets, covered


def edges_schedule(mix_support) -> Tuple[np.ndarray, np.ndarray]:
    """``(nbr_idx, nbr_mask)`` padded-ELL tables for a support mask with
    the diagonal forced in (every node keeps a self-slot, so row-
    stochastic matrices always have somewhere to put their self-weight).
    The edge-list analogue of :func:`sparse_schedule` — static trace-time
    metadata; the coverage mask for ``SweepEngine.run``'s off-support
    check is simply ``support ∪ diag`` (no structural fallback)."""
    support = np.asarray(mix_support)
    n = support.shape[0]
    from repro.core.topology import padded_neighbor_tables

    return padded_neighbor_tables(np.maximum(support, np.eye(n)))


def make_local_train_fn(loss_fn: Callable, optimizer: Optimizer,
                        local_epochs: int,
                        epoch_shuffle: bool = True) -> Callable:
    """LocalTrain (Eq. 1) for ONE node: E epochs over its batches as a
    ``lax.scan`` over the (E·steps,) batch axis.

    ``epoch_shuffle=True``: the incoming batches already carry all E
    epochs on the leading axis (each a distinct shuffle —
    ``NodeBatcher(local_epochs=E)``) and are consumed as-is.
    ``epoch_shuffle=False`` (legacy): one epoch of batches is tiled E
    times, replaying the identical order every epoch.
    """

    def local_train(params, opt_state, batches):
        def step(carry, batch):
            p, s = carry
            loss, grads = jax.value_and_grad(loss_fn)(p, batch)
            updates, s = optimizer.update(grads, s, p)
            p = apply_updates(p, updates)
            return (p, s), loss

        if epoch_shuffle:
            total = jax.tree.leaves(batches)[0].shape[0]
            if total % local_epochs:
                raise ValueError(
                    f"epoch_shuffle=True expects the pipeline to supply "
                    f"local_epochs={local_epochs} distinct epoch passes "
                    f"(NodeBatcher(local_epochs=...)), but the {total}-step "
                    f"batch axis is not divisible by {local_epochs}")
            rep = batches
        else:
            # legacy: repeat the epoch's batches E times along the scan axis
            rep = jax.tree.map(
                lambda x: jnp.concatenate([x] * local_epochs, axis=0),
                batches)
        (params, opt_state), losses = jax.lax.scan(
            step, (params, opt_state), rep)
        return params, opt_state, jnp.mean(losses)

    return local_train


def make_round_fn(loss_fn: Callable, optimizer: Optimizer, local_epochs: int,
                  mix_impl: str = "einsum",
                  epoch_shuffle: bool = True,
                  mix_support: Optional[np.ndarray] = None,
                  sparse_slack: int = 4,
                  mix_in_float32: bool = True,
                  robust: str = "mean",
                  robust_trim: int = 1,
                  robust_clip: float = 1.0) -> Callable:
    """One full round — vmapped LocalTrain then aggregation — as a pure
    function ``(stacked_params, stacked_opt, node_batches, coeffs) →
    (mixed_params, opt, losses)``.  ``mix_support`` is consulted by
    ``mix_impl='sparse'`` and ``'edges'`` (``sparse_slack`` by the former
    only); ``mix_in_float32``
    selects every backend's accumulation dtype (see
    :func:`make_mix_fn`)."""
    local_train = make_local_train_fn(loss_fn, optimizer, local_epochs,
                                      epoch_shuffle)
    mix = make_mix_fn(mix_impl, mix_support=mix_support,
                      sparse_slack=sparse_slack,
                      mix_in_float32=mix_in_float32,
                      robust=robust, robust_trim=robust_trim,
                      robust_clip=robust_clip)

    def round_fn(stacked_params, stacked_opt, node_batches, coeffs):
        with jax.named_scope("local_train"):
            params, opt, losses = jax.vmap(local_train)(
                stacked_params, stacked_opt, node_batches)
        with jax.named_scope("mix"):
            params = mix(params, coeffs)
        return params, opt, losses

    return round_fn


def participation_carry_init(params, rate, pseed) -> dict:
    """Per-experiment participation carry (the traced half of
    :class:`repro.core.dynamic.ParticipationSpec`, DESIGN.md §15):

    * ``rate`` / ``pseed`` — the per-experiment activation rate and PRNG
      seed (carried, not static, so one compiled program serves a whole
      rate grid and both shard on the experiment axis);
    * ``pub`` — the *published* plane: each node's row as last seen by
      its neighbours.  A COPY of the initial stacked params (the engines
      donate the params argument, so aliasing it here would hand XLA the
      same buffer twice);
    * ``staleness`` — rounds since each node last participated (0 right
      after an active round);
    * ``staleness_sum`` — Σ over rounds of post-round staleness (host
      side divides by R for the mean);
    * ``rounds_active`` / ``local_steps`` — participation and
      time-skewed local-step counts per node.
    """
    n = jax.tree.leaves(params)[0].shape[0]
    zeros = jnp.zeros((n,), jnp.int32)
    return {
        "rate": jnp.asarray(rate, jnp.float32),
        "pseed": jnp.asarray(pseed, jnp.uint32),
        "pub": jax.tree.map(lambda x: jnp.asarray(x).copy(), params),
        "staleness": zeros,
        "staleness_sum": zeros,
        "rounds_active": zeros,
        "local_steps": zeros,
    }


def make_participation_round_fn(loss_fn: Callable, optimizer: Optimizer,
                                local_epochs: int,
                                participation,
                                mix_impl: str = "einsum",
                                epoch_shuffle: bool = True,
                                mix_support: Optional[np.ndarray] = None,
                                sparse_slack: int = 4,
                                mix_in_float32: bool = True,
                                robust: str = "mean",
                                robust_trim: int = 1,
                                robust_clip: float = 1.0) -> Callable:
    """Partial-participation round (DESIGN.md §15): ``(stacked_params,
    stacked_opt, pcarry, node_batches, coeffs, round_idx) → (params, opt,
    pcarry, losses)``.

    Per round: draw the active set from ``participation`` (a
    ``repro.core.dynamic.ParticipationSpec``), run LocalTrain on every
    node (the scan needs fixed shapes; inactive results are discarded by
    an elementwise select on the plane row), publish active nodes' fresh
    post-train rows into the stale plane ``pcarry["pub"]``, mix the
    published plane (so active nodes gossip against each neighbour's
    LAST published row — stale if that neighbour sat out), and select:
    active rows take the mixed result + fresh optimizer state, inactive
    rows keep params/opt/published row untouched.  Inactive losses
    report 0 (same convention as skipped evals).

    Because ``jnp.where`` with an all-true mask is elementwise-exact and
    ``rate=1.0`` activates every node exactly (see
    ``ParticipationSpec.active_mask``), a participation-1.0 run is
    BIT-IDENTICAL to :func:`make_round_fn`'s synchronous round under
    every mixing backend — the equivalence tests in
    tests/test_participation.py hold to ``==``, not allclose.
    """
    local_train = make_local_train_fn(loss_fn, optimizer, local_epochs,
                                      epoch_shuffle)
    mix = make_mix_fn(mix_impl, mix_support=mix_support,
                      sparse_slack=sparse_slack,
                      mix_in_float32=mix_in_float32,
                      robust=robust, robust_trim=robust_trim,
                      robust_clip=robust_clip)
    from repro.core.coeffs import participation_renormalize  # no cycle

    def select(active, new, old):
        # explicit reshape: rank-promoting broadcasts are disabled
        # repo-wide (jax_numpy_rank_promotion="raise")
        def sel(a, b):
            return jnp.where(
                active.reshape(active.shape + (1,) * (a.ndim - 1)), a, b)
        return jax.tree.map(sel, new, old)

    def round_fn(stacked_params, stacked_opt, pcarry, node_batches,
                 coeffs, round_idx):
        n = jax.tree.leaves(stacked_params)[0].shape[0]
        steps = jax.tree.leaves(node_batches)[0].shape[1]
        active = participation.active_mask(
            pcarry["rate"], pcarry["pseed"], round_idx, n)
        with jax.named_scope("local_train"):
            trained, opt_t, losses = jax.vmap(local_train)(
                stacked_params, stacked_opt, node_batches)
        pub = select(active, trained, pcarry["pub"])
        if not participation.stale_mixing:
            coeffs = participation_renormalize(coeffs, active)
        with jax.named_scope("mix"):
            mixed = mix(pub, coeffs)
        params = select(active, mixed, stacked_params)
        opt = select(active, opt_t, stacked_opt)
        losses = jnp.where(active, losses, jnp.zeros((), losses.dtype))
        act = active.astype(jnp.int32)
        staleness = jnp.where(active, 0, pcarry["staleness"] + 1)
        pcarry = {
            **pcarry,
            "pub": pub,
            "staleness": staleness,
            "staleness_sum": pcarry["staleness_sum"] + staleness,
            "rounds_active": pcarry["rounds_active"] + act,
            "local_steps": pcarry["local_steps"] + act * steps,
        }
        return params, opt, pcarry, losses

    return round_fn


def fault_carry_init(params, rate, fseed) -> dict:
    """Per-experiment fault/quarantine carry (the traced half of
    :class:`repro.core.dynamic.FaultSpec`, DESIGN.md §16):

    * ``rate`` / ``fseed`` — the per-experiment fault rate and PRNG seed
      (carried, not static, so one compiled program serves a whole
      fault-rate grid and both shard on the experiment axis);
    * ``qtimer`` — probation countdown per node; a node is quarantined
      while ``qtimer > 0`` (re-flagging resets it to
      ``FaultSpec.probation``, healthy rounds decrement it);
    * ``norm_ema`` — EMA of each node's published row norm, the
      baseline for the spike screen.  0.0 means "not yet seeded";
      updated only on rounds the node passes the screen, so a
      quarantined node's garbage never drags its own baseline;
    * ``rounds_quarantined`` / ``fault_rounds`` /
      ``quar_fault_rounds`` — per-node counts of quarantined rounds,
      actually-faulty rounds, and rounds both at once (host side turns
      these into false-positive rates);
    * ``first_fault`` / ``first_quar`` — first round each node was
      faulty / quarantined (−1 sentinel = never); their difference is
      the detection lag.
    """
    n = jax.tree.leaves(params)[0].shape[0]
    zeros = jnp.zeros((n,), jnp.int32)
    return {
        "rate": jnp.asarray(rate, jnp.float32),
        "fseed": jnp.asarray(fseed, jnp.uint32),
        "qtimer": zeros,
        "norm_ema": jnp.zeros((n,), jnp.float32),
        "rounds_quarantined": zeros,
        "fault_rounds": zeros,
        "quar_fault_rounds": zeros,
        "first_fault": jnp.full((n,), -1, jnp.int32),
        "first_quar": jnp.full((n,), -1, jnp.int32),
    }


def make_fault_round_fn(loss_fn: Callable, optimizer: Optimizer,
                        local_epochs: int,
                        fault,
                        participation=None,
                        mix_impl: str = "einsum",
                        epoch_shuffle: bool = True,
                        mix_support: Optional[np.ndarray] = None,
                        sparse_slack: int = 4,
                        mix_in_float32: bool = True,
                        robust: str = "mean",
                        robust_trim: int = 1,
                        robust_clip: float = 1.0) -> Callable:
    """Byzantine-fault round (DESIGN.md §16).  Signature without
    participation: ``(stacked_params, stacked_opt, fcarry, node_batches,
    coeffs, round_idx) → (params, opt, fcarry, losses)``; with a
    ``ParticipationSpec`` the participation carry slots in before the
    fault carry on both sides.

    Per round: LocalTrain every node, publish (through the PR 9 stale
    plane when ``participation`` is set), then draw the faulty set from
    ``fault`` (a :class:`repro.core.dynamic.FaultSpec`, PRNG fold index
    3) and overwrite faulty nodes' PUBLISHED rows with
    ``FaultSpec.corrupt`` garbage — neighbours gossip against the
    corruption while the faulty node's own params follow local
    semantics (it keeps its honest locally-trained state, exactly like
    a node whose outbound link is compromised but whose replica is
    fine).  With ``fault.quarantine`` the in-scan health screen runs on
    the published plane: a row is flagged when it contains nonfinite
    values or its norm exceeds ``spike_ratio`` × that node's healthy
    EMA; flagged rows start a ``probation``-round quarantine during
    which their column is excised from the mixing matrix
    (:func:`repro.core.coeffs.quarantine_renormalize`), their plane row
    is zero-substituted (so ``0 × NaN`` cannot poison the dense
    contraction), and the quarantined node itself keeps training
    locally — self-healing: after probation it rejoins automatically.

    ``rate=0.0`` draws an exactly-empty faulty set (uniform < 0.0) and
    every select collapses bitwise, so a zero-fault run is
    BIT-IDENTICAL to :func:`make_round_fn` /
    :func:`make_participation_round_fn` under every mixing backend —
    tests/test_fault.py holds this to ``==``.

    Note: with ``robust="mean"`` and no quarantine, a NaN/Inf fault
    poisons every destination of the dense contraction (``0 × NaN =
    NaN``), not just graph neighbours — that IS the failure mode the
    robust aggregators and the quarantine screen exist to contain.
    """
    local_train = make_local_train_fn(loss_fn, optimizer, local_epochs,
                                      epoch_shuffle)
    mix = make_mix_fn(mix_impl, mix_support=mix_support,
                      sparse_slack=sparse_slack,
                      mix_in_float32=mix_in_float32,
                      robust=robust, robust_trim=robust_trim,
                      robust_clip=robust_clip)
    from repro.core.coeffs import (  # no cycle
        participation_renormalize,
        quarantine_renormalize,
    )
    from repro.core.mixing import plane_norms

    def select(mask, new, old):
        # explicit reshape: rank-promoting broadcasts are disabled
        # repo-wide (jax_numpy_rank_promotion="raise")
        def sel(a, b):
            return jnp.where(
                mask.reshape(mask.shape + (1,) * (a.ndim - 1)), a, b)
        return jax.tree.map(sel, new, old)

    def row_nonfinite(plane, n):
        cnt = jnp.zeros((n,), jnp.int32)
        for leaf in jax.tree.leaves(plane):
            flat = leaf.reshape((n, -1))
            cnt = cnt + jnp.sum(~jnp.isfinite(flat), axis=1,
                                dtype=jnp.int32)
        return cnt

    def round_fn(stacked_params, stacked_opt, *state_and_xs):
        if participation is not None:
            pcarry, fcarry, node_batches, coeffs, round_idx = state_and_xs
        else:
            pcarry = None
            fcarry, node_batches, coeffs, round_idx = state_and_xs
        n = jax.tree.leaves(stacked_params)[0].shape[0]
        with jax.named_scope("local_train"):
            trained, opt_t, losses = jax.vmap(local_train)(
                stacked_params, stacked_opt, node_batches)
        if participation is not None:
            steps = jax.tree.leaves(node_batches)[0].shape[1]
            active = participation.active_mask(
                pcarry["rate"], pcarry["pseed"], round_idx, n)
            pub = select(active, trained, pcarry["pub"])
            if not participation.stale_mixing:
                coeffs = participation_renormalize(coeffs, active)
        else:
            pub = trained
        faulty = fault.faulty_mask(fcarry["rate"], fcarry["fseed"],
                                   round_idx, n)
        # the corruption lands on the PUBLISHED plane (and persists in
        # pcarry["pub"] until the node republishes — garbage stays
        # visible to neighbours exactly as long as a stale row would)
        pub = select(faulty, fault.corrupt(pub, fcarry["fseed"], round_idx),
                     pub)
        fcarry = dict(fcarry)
        fint = faulty.astype(jnp.int32)
        r32 = jnp.asarray(round_idx, jnp.int32)
        fcarry["fault_rounds"] = fcarry["fault_rounds"] + fint
        fcarry["first_fault"] = jnp.where(
            (fcarry["first_fault"] < 0) & faulty, r32,
            fcarry["first_fault"])
        if fault.quarantine:
            norms = plane_norms(pub)
            ema = fcarry["norm_ema"]
            suspicious = ((row_nonfinite(pub, n) > 0)
                          | ~jnp.isfinite(norms)
                          | ((ema > 0.0) & (norms > fault.spike_ratio * ema)))
            qtimer = jnp.where(suspicious, fault.probation,
                               jnp.maximum(fcarry["qtimer"] - 1, 0))
            quarantined = qtimer > 0
            # EMA advances only on rounds the node passes the screen —
            # a quarantined node's garbage never drags its baseline
            healthy = jnp.where(
                ema > 0.0,
                fault.ema_beta * ema + (1.0 - fault.ema_beta) * norms,
                norms)
            qint = quarantined.astype(jnp.int32)
            fcarry["norm_ema"] = jnp.where(suspicious, ema, healthy)
            fcarry["qtimer"] = qtimer
            fcarry["rounds_quarantined"] = (
                fcarry["rounds_quarantined"] + qint)
            fcarry["quar_fault_rounds"] = (
                fcarry["quar_fault_rounds"] + qint * fint)
            fcarry["first_quar"] = jnp.where(
                (fcarry["first_quar"] < 0) & quarantined, r32,
                fcarry["first_quar"])
            coeffs = quarantine_renormalize(coeffs, quarantined)
            # zero-substitute quarantined rows BEFORE the contraction:
            # an excised column still participates in dense tensordot
            # and 0 × NaN = NaN would re-poison every destination
            pub_mix = select(quarantined,
                             jax.tree.map(jnp.zeros_like, pub), pub)
            keep_local = faulty | quarantined
        else:
            pub_mix = pub
            keep_local = faulty
        with jax.named_scope("mix"):
            mixed = mix(pub_mix, coeffs)
        params = select(keep_local, trained, mixed)
        opt = opt_t
        if participation is not None:
            params = select(active, params, stacked_params)
            opt = select(active, opt_t, stacked_opt)
            losses = jnp.where(active, losses, jnp.zeros((), losses.dtype))
            act = active.astype(jnp.int32)
            staleness = jnp.where(active, 0, pcarry["staleness"] + 1)
            pcarry = {
                **pcarry,
                "pub": pub,
                "staleness": staleness,
                "staleness_sum": pcarry["staleness_sum"] + staleness,
                "rounds_active": pcarry["rounds_active"] + act,
                "local_steps": pcarry["local_steps"] + act * steps,
            }
            return params, opt, pcarry, fcarry, losses
        return params, opt, fcarry, losses

    return round_fn


def make_scan_fn(round_fn: Callable, evaluate: Callable,
                 make_batch: Optional[Callable] = None,
                 coeff_fn: Optional[Callable] = None,
                 analytics=None,
                 keep_history: bool = True,
                 participation=None,
                 fault=None) -> Callable:
    """Scan-over-rounds factory shared by ``DecentralizedTrainer`` (stacked
    batches) and ``repro.core.sweep`` (per-round index gather).

    ``round_fn``: :func:`make_round_fn` output; ``evaluate``:
    ``(stacked_params, test_iid, test_ood) → (iid, ood)``;  ``make_batch``
    maps the per-round scan slice to node batches (identity for
    pre-stacked batches, a bank gather for the sweep engine).

    ``coeff_fn`` switches the mixing-matrix source from *data* to
    *program* (DESIGN.md §9): when set, the ``coeffs`` argument carries
    absolute int32 round indices ``(R,)`` instead of an ``(R, n, n)``
    slab, and each scan step computes its matrix in-scan as
    ``coeff_fn(round_idx)`` — e.g. ``lambda r:
    CoeffProgram.matrix(state, r)`` — so per-round matrices (Random
    resampling, reactive link failure) never materialize on the host.

    ``analytics`` (a ``repro.core.analytics.AnalyticsSpec``) grows the
    scan carry by the streaming-analytics accumulators (DESIGN.md §10):
    every eval round is folded into O(n) online state (running trapezoid
    AUC, arrival rounds) instead of — or in addition to — the stacked
    ``(R, n)`` metric outputs.  The scan then consumes two extra inputs:
    ``round_idx`` (the ``(R,)`` ABSOLUTE round indices, so chunked
    execution cannot shift the stream) and ``analytics_carry`` (from
    ``AnalyticsSpec.init``, threaded back out for chunk chaining).
    ``keep_history=False`` (requires ``analytics``) drops the per-round
    ys entirely — the scan's memory footprint for metrics becomes O(n).

    ``participation`` (a ``repro.core.dynamic.ParticipationSpec``)
    switches ``round_fn`` to the extended
    :func:`make_participation_round_fn` signature and grows the carry by
    the participation state (``participation_carry`` ←
    :func:`participation_carry_init`, threaded back out for chunk
    chaining like the analytics carry); the scan then also consumes the
    ``round_idx`` absolute-round input (the active-set draw folds it).

    ``fault`` (a ``repro.core.dynamic.FaultSpec``) switches ``round_fn``
    to the :func:`make_fault_round_fn` signature and grows the carry by
    the fault/quarantine state (``fault_carry`` ←
    :func:`fault_carry_init`, threaded back out for chunk chaining);
    like participation, the fault draw folds the absolute round index
    so chunked execution cannot shift the corruption schedule.

    Returns ``scan_fn(params, opt, batch_xs, coeffs, eval_mask, test_iid,
    test_ood[, round_idx, analytics_carry, participation_carry,
    fault_carry])`` → ``(params, opt[, participation_carry]
    [, fault_carry][, analytics_carry][, losses, iid, ood])`` — the
    participation carry slots in before the fault carry, which slots in
    before the analytics carry; the per-round history tail is present
    unless ``keep_history=False``, and the
    no-analytics/no-participation/no-fault order is unchanged from the
    original ``(params, opt, losses, iid, ood)``.

    The carries come back out so callers can chain round-chunks (chunked
    mode donates them back in, keeping device accumulators bounded at one
    chunk).  ``eval_mask`` gates eval to the rounds ``eval_every`` keeps;
    skipped rounds report zeros (and leave the analytics carry untouched).
    Eval ALWAYS covers every node — an inactive node's frozen model is
    still a model the arrival analytics must see.
    """
    if make_batch is None:
        make_batch = lambda b: b
    if not keep_history and analytics is None:
        raise ValueError("keep_history=False without an analytics spec "
                         "would return no metrics at all")
    needs_rounds = (analytics is not None or participation is not None
                    or fault is not None)

    def scan_fn(params, opt, batch_xs, coeffs, eval_mask, test_iid,
                test_ood, round_idx=None, analytics_carry=None,
                participation_carry=None, fault_carry=None):
        n = jax.tree.leaves(params)[0].shape[0]

        def body(carry, xs):
            carry = list(carry)
            p, o = carry[0], carry[1]
            slot = 2
            pc = fc = None
            if participation is not None:
                pc = carry[slot]
                slot += 1
            if fault is not None:
                fc = carry[slot]
                slot += 1
            ac = carry[-1] if analytics is not None else None
            if needs_rounds:
                bx, c, do_eval, r_abs = xs
            else:
                bx, c, do_eval = xs
            if coeff_fn is not None:
                with jax.named_scope("coeffs"):
                    c = coeff_fn(c)  # c is this step's absolute round index
            if fault is not None:
                if participation is not None:
                    p, o, pc, fc, losses = round_fn(
                        p, o, pc, fc, make_batch(bx), c, r_abs)
                else:
                    p, o, fc, losses = round_fn(
                        p, o, fc, make_batch(bx), c, r_abs)
            elif participation is None:
                p, o, losses = round_fn(p, o, make_batch(bx), c)
            else:
                p, o, pc, losses = round_fn(p, o, pc, make_batch(bx), c,
                                            r_abs)
            with jax.named_scope("eval"):
                iid, ood = jax.lax.cond(
                    do_eval,
                    lambda q: evaluate(q, test_iid, test_ood),
                    lambda q: (jnp.zeros((n,)), jnp.zeros((n,))),
                    p)
            out = [p, o]
            if participation is not None:
                out.append(pc)
            if fault is not None:
                out.append(fc)
            if analytics is not None:
                with jax.named_scope("analytics"):
                    out.append(analytics.update(ac, r_abs, do_eval, iid,
                                                ood))
            ys = ((losses, iid, ood)
                  if (keep_history or analytics is None) else None)
            return tuple(out), ys

        carry0 = [params, opt]
        if participation is not None:
            carry0.append(participation_carry)
        if fault is not None:
            carry0.append(fault_carry)
        if analytics is not None:
            carry0.append(analytics_carry)
        xs = ((batch_xs, coeffs, eval_mask, round_idx) if needs_rounds
              else (batch_xs, coeffs, eval_mask))
        final, ys = jax.lax.scan(body, tuple(carry0), xs)
        out = list(final)
        if ys is not None:
            out.extend(ys)   # losses, iid, ood
        return tuple(out)

    return scan_fn


def eval_round_indices(rounds: int, eval_every: int) -> List[int]:
    """Rounds at which the legacy loop recorded metrics (kept identical so
    scanned histories line up bit-for-bit with unrolled ones)."""
    return [r for r in range(rounds)
            if (r + 1) % eval_every == 0 or r == rounds - 1]


class DecentralizedTrainer:
    """Runs Alg. 1 over a topology with a pluggable aggregation strategy.

    Args:
      topology: the communication graph.
      strategy: aggregation strategy (mixing-matrix factory).
      optimizer: a ``repro.training.optimizer.Optimizer``.
      loss_fn: ``(params, batch) -> scalar loss``;  batch is whatever the
        data pipeline yields per node per step.
      eval_fn: ``(params, test_batch) -> accuracy`` scalar in [0, 1].
      config: round/epoch counts + execution mode (scanned vs unrolled).
    """

    def __init__(
        self,
        topology: Topology,
        strategy: AggregationStrategy,
        optimizer: Optimizer,
        loss_fn: Callable,
        eval_fn: Callable,
        config: DecentralizedConfig = DecentralizedConfig(),
        data_counts: Optional[np.ndarray] = None,
        coeffs_fn: Optional[Callable[[int], np.ndarray]] = None,
    ):
        self.topology = topology
        self.strategy = strategy
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.eval_fn = eval_fn
        self.config = config
        self.data_counts = data_counts
        self.coeffs_fn = coeffs_fn  # e.g. core.dynamic link-failure matrices
        mix_support = None
        if (config.mix_impl in ("sparse", "edges")
                or config.robust in ("trimmed", "median")):
            # support = neighbourhoods ∪ the strategy's actual round-0
            # support: kinds with off-neighbourhood weight (fl's dense
            # 1/n, register_strategy plugins, coeffs_fn overrides) would
            # otherwise have mass silently dropped by the static schedule
            # (sub-stochastic mixing).  Built-in supports never grow
            # across rounds; exotic coeffs_fn schedules that do should
            # use mix_impl="einsum".
            n = topology.n_nodes
            m0 = round_coeffs(topology, strategy, 0, data_counts,
                              coeffs_fn, config.resample_random_each_round)
            mix_support = np.maximum(
                topology.adjacency + np.eye(n),
                (np.abs(np.asarray(m0)) > 1e-12).astype(np.float64))
        self._round_fn = make_round_fn(
            loss_fn, optimizer, config.local_epochs, config.mix_impl,
            config.epoch_shuffle, mix_support=mix_support,
            sparse_slack=config.sparse_slack,
            mix_in_float32=config.mix_in_float32,
            robust=config.robust, robust_trim=config.robust_trim,
            robust_clip=config.robust_clip)
        self._train_round = jax.jit(self._round_fn)
        self._evaluate = jax.jit(self._evaluate_impl)
        self._scan_fn = make_scan_fn(self._round_fn, self._evaluate_impl)
        self._run_scan = jax.jit(self._run_scan_impl)

    # ------------------------------------------------------------------
    def coeffs_for_round(self, r: int) -> jnp.ndarray:
        """Mixing matrix for round r (see :func:`round_coeffs`)."""
        return jnp.asarray(round_coeffs(
            self.topology, self.strategy, r, self.data_counts,
            self.coeffs_fn, self.config.resample_random_each_round))

    def coeffs_stack(self, rounds: Optional[int] = None) -> np.ndarray:
        """(R, n, n) stack of this run's per-round mixing matrices."""
        return coeffs_stack(
            self.topology, self.strategy,
            self.config.rounds if rounds is None else rounds,
            self.data_counts, self.coeffs_fn,
            self.config.resample_random_each_round)

    # ------------------------------------------------------------------
    def _evaluate_impl(self, stacked_params, test_iid, test_ood):
        with jax.named_scope("eval"):
            iid = jax.vmap(lambda p: self.eval_fn(p, test_iid))(
                stacked_params)
            ood = jax.vmap(lambda p: self.eval_fn(p, test_ood))(
                stacked_params)
        return iid, ood

    def _run_scan_impl(self, stacked_params, stacked_opt, batches, coeffs,
                       eval_mask, test_iid, test_ood):
        """All R rounds as one ``lax.scan`` (:func:`make_scan_fn`);
        batches/coeffs carry a leading (R,) axis; eval is folded into the
        scan body so metrics come back stacked as (R, n).  ``eval_mask``
        gates the eval forward passes to the rounds the history actually
        keeps (``eval_every``); skipped rounds report zeros and are
        dropped before building the history."""
        return self._scan_fn(stacked_params, stacked_opt, batches, coeffs,
                             eval_mask, test_iid, test_ood)

    # ------------------------------------------------------------------
    def run(
        self,
        stacked_params,
        node_batches_fn: Callable[[int], object],
        test_iid,
        test_ood,
    ) -> Tuple[object, List[RoundMetrics]]:
        """Train for R rounds.

        Args:
          stacked_params: pytree with leaves (n, ...).
          node_batches_fn: ``round -> pytree`` of per-node batch stacks with
            leaves (n, steps_per_epoch, batch, ...) — lets the pipeline
            reshuffle per round.
          test_iid / test_ood: shared global test batches.

        Scanned mode stacks all R rounds of batches on the leading axis
        (host memory ≈ R × one round of batches); set
        ``config.unroll_eval=True`` to stream rounds instead.
        """
        if self.config.unroll_eval:
            return self.run_unrolled(
                stacked_params, node_batches_fn, test_iid, test_ood)

        rounds = self.config.rounds
        coeffs = jnp.asarray(self.coeffs_stack())
        batches = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[node_batches_fn(r) for r in range(rounds)])
        eval_mask = np.zeros(rounds, bool)
        eval_mask[eval_round_indices(rounds, self.config.eval_every)] = True
        stacked_opt = jax.vmap(self.optimizer.init)(stacked_params)
        stacked_params, _, losses, iid, ood = self._run_scan(
            stacked_params, stacked_opt, batches, coeffs,
            jnp.asarray(eval_mask), test_iid, test_ood)
        losses, iid, ood = (np.asarray(losses), np.asarray(iid),
                            np.asarray(ood))
        history = [
            RoundMetrics(round=r, iid_acc=iid[r], ood_acc=ood[r],
                         train_loss=losses[r])
            for r in eval_round_indices(rounds, self.config.eval_every)
        ]
        return stacked_params, history

    def run_unrolled(
        self,
        stacked_params,
        node_batches_fn: Callable[[int], object],
        test_iid,
        test_ood,
    ) -> Tuple[object, List[RoundMetrics]]:
        """Legacy per-round Python loop (incremental history API)."""
        stacked_opt = jax.vmap(self.optimizer.init)(stacked_params)
        history: List[RoundMetrics] = []

        for r in range(self.config.rounds):
            coeffs = self.coeffs_for_round(r)
            batches = node_batches_fn(r)
            stacked_params, stacked_opt, losses = self._train_round(
                stacked_params, stacked_opt, batches, coeffs
            )
            if (r + 1) % self.config.eval_every == 0 or r == self.config.rounds - 1:
                iid, ood = self._evaluate(stacked_params, test_iid, test_ood)
                history.append(
                    RoundMetrics(
                        round=r,
                        iid_acc=np.asarray(iid),
                        ood_acc=np.asarray(ood),
                        train_loss=np.asarray(losses),
                    )
                )
        return stacked_params, history
