"""Batched experiment-sweep engine: vmap over experiments × scan over rounds.

The paper's findings are all *sweeps* — over strategies (Fig. 4), OOD
placements (Fig. 5), topologies (Fig. 6), and seeds.  Every cell of such a
grid runs the same program shape (same n, R, model, batch geometry); only
the *data* differs: initial params, per-round mixing matrices, sample
indices, test batches.  This module exploits that: ONE jitted program —
``vmap`` over the experiment axis E of the ``lax.scan`` over rounds from
``repro.core.decentralized`` — evaluates a whole figure's grid in a single
device dispatch (DESIGN.md §7).

Inputs per experiment (leading axis E):

* ``params0``   — stacked initial node models, leaves ``(E, n, ...)``;
* ``coeffs``    — EITHER an ``(E, R, n, n)`` stack of per-round mixing
  matrices (:func:`repro.core.decentralized.coeffs_stack`; Random
  resampling and ``core.dynamic`` link-failure schedules are just
  different stacks) OR a :class:`repro.core.coeffs.ProgramCoeffs` — a
  device-side coefficient program plus compact per-experiment state
  (leaves ``(E, ...)``, ~n² floats instead of R·n²), whose matrices are
  generated *inside* the scan (DESIGN.md §9; required for reactive
  link-failure strategies, bit-identical to the materialized stack for
  everything else);
* ``data_idx``  — ``(E,)`` row into the shared data bank;
* ``test_iid`` / ``test_ood`` — per-experiment test batches, leaves
  ``(E, b, ...)``.

Shared across experiments:

* ``bank``      — padded per-node sample bank, leaves ``(D, n, cap, ...)``
  (``NodeBatcher.sample_bank``); experiments sharing a data configuration
  (same seed/OOD placement) share a bank row, so memory scales with the
  number of *distinct* datasets D, not with E;
* ``indices``   — ``(D, R, n, S)`` per-round sample indices
  (``NodeBatcher.all_round_indices``) — batches are a per-round gather
  inside the scan, never materialized as an ``(E, R, ...)`` tensor.

Three execution modes of the same program family (DESIGN.md §7/§8), all
bit-for-bit identical (tests/test_sweep.py, tests/test_sweep_sharded.py):

* **scanned** (default): ``jit(vmap_E(scan_R(round)))`` on one device;
* **sharded-scanned** (``mesh=...``): the E axis is laid across a 1-D
  device mesh (``repro.launch.mesh.make_sweep_mesh``) with ``shard_map``
  — E is padded to a multiple of the mesh size with dummy experiments
  (copies of experiment 0, masked out of the returned result) and each
  device runs the identical per-experiment program on its slice, so
  sharding cannot change any real experiment's arithmetic;
* **unrolled** (``unroll_eval=True``): the legacy per-round Python loop,
  preserving the incremental history API (one dispatch per round,
  metrics available as they stream).

Orthogonally, ``chunk_rounds=c`` scans the round schedule in ``⌈R/c⌉``
chunks: the device-resident ``(R, n, S)`` index schedule, ``(R, n, n)``
coefficient slab, and ``(R, n)`` eval accumulators stay bounded at one
chunk while the host concatenates per-chunk metrics — the long-run mode.
The ``(params, opt)`` carry is donated back into each chunk (and into
the one-shot scans) on backends that support buffer donation, so the
scan never double-allocates the model/optimizer state.

Host spans (``jax.profiler.TraceAnnotation``, recorded only while a
profile is taken) mark the engine's host work: ``repro.engine.prepare``
(input preparation), ``repro.engine.chunk`` (each program dispatch, with
the first round, rounds, experiments and nodes as arguments; the first
chunk of a run holds its trace, lowering and cache load) and
``repro.engine.fetch`` (the host waiting for a chunk's history).
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.analytics import AnalyticsSpec
from repro.core.coeffs import CoeffProgram, ProgramCoeffs
from repro.core.decentralized import (
    DecentralizedConfig,
    RoundMetrics,
    eval_round_indices,
    fault_carry_init,
    make_fault_round_fn,
    make_participation_round_fn,
    make_round_fn,
    make_scan_fn,
    participation_carry_init,
)
from repro.core.dynamic import FaultSpec, ParticipationSpec
from repro.training.optimizer import Optimizer

__all__ = ["SweepEngine", "SweepResult", "gather_round_batch",
           "pad_experiments", "donation_supported",
           "DONATED_CARRY_ARGNUMS"]

#: The (params, opt) carry positions the chunked and sharded modes donate
#: (DESIGN.md §8) — introspectable metadata shared by the jit wrappers
#: below and the ``repro.analysis`` donation rule, so the analyzer checks
#: the same contract the engine declares.
DONATED_CARRY_ARGNUMS: Tuple[int, ...] = (0, 1)


def donation_supported() -> bool:
    """Buffer donation is a no-op (with a warning) on CPU; only donate
    where XLA actually reuses the buffers."""
    return jax.default_backend() in ("gpu", "tpu")


def pad_experiments(tree: Any, pad: int) -> Any:
    """Grow every leaf's leading E axis by ``pad`` dummy experiments —
    copies of experiment 0, so the padded program is numerically valid and
    the padding rows are simply dropped from the result.  Identity when
    ``pad == 0``."""
    if pad == 0:
        return tree
    return jax.tree.map(
        lambda x: jnp.concatenate(
            [jnp.asarray(x),
             jnp.broadcast_to(jnp.asarray(x)[:1],
                              (pad,) + tuple(np.shape(x)[1:]))], axis=0),
        tree)


def gather_round_batch(bank: Dict[str, jnp.ndarray], data_idx: jnp.ndarray,
                       idx_r: jnp.ndarray, batch_size: int):
    """One round of per-node batches for one experiment, gathered straight
    from the (D, n, cap, ...) bank.

    ``idx_r``: (n, S) sample indices (S = steps·batch) into each node's
    bank row.  Returns the exact pytree ``NodeBatcher.round_batches``
    yields — leaves (n, steps, batch, ...) — including the all-ones LM
    loss mask.
    """
    n, s = idx_r.shape
    steps = s // batch_size
    rows = jnp.arange(n)[:, None]

    def g(leaf: jnp.ndarray) -> jnp.ndarray:
        out = leaf[data_idx, rows, idx_r]  # (n, S, ...)
        return out.reshape((n, steps, batch_size) + leaf.shape[3:])

    with jax.named_scope("batch_gather"):
        batch = {k: g(v) for k, v in bank.items()}
        if "tokens" in batch:  # LM: trainer consumes an all-ones train mask
            seq = batch["tokens"].shape[-1]
            batch["mask"] = jnp.ones((n, steps, batch_size, seq - 1),
                                     jnp.float32)
    return batch


def _finalize_analytics(analytics: Optional[AnalyticsSpec], acarry,
                        n_exp: int) -> Optional[Dict[str, np.ndarray]]:
    """Vmapped ``AnalyticsSpec.finalize`` over the E axis, padding rows
    dropped — the ``SweepResult.analytics`` payload."""
    if analytics is None:
        return None
    out = jax.vmap(analytics.finalize)(acarry)
    return {k: np.asarray(v)[:n_exp] for k, v in out.items()}


def _finalize_participation(participation: Optional[ParticipationSpec],
                            pcarry, n_exp: int,
                            rounds: int) -> Optional[Dict[str, np.ndarray]]:
    """Host digest of the participation carry, padding rows dropped — the
    ``SweepResult.participation`` payload (all ``(E, n)``)."""
    if participation is None:
        return None
    return {
        "rounds_active": np.asarray(pcarry["rounds_active"])[:n_exp],
        "final_staleness": np.asarray(pcarry["staleness"])[:n_exp],
        "mean_staleness": (np.asarray(pcarry["staleness_sum"], np.float64)
                           [:n_exp] / max(rounds, 1)),
        "local_steps": np.asarray(pcarry["local_steps"])[:n_exp],
    }


def _finalize_fault(fault: Optional[FaultSpec], fcarry,
                    n_exp: int) -> Optional[Dict[str, np.ndarray]]:
    """Host digest of the fault/quarantine carry, padding rows dropped —
    the ``SweepResult.fault`` payload (all ``(E, n)``; consumed by
    ``repro.core.analytics.quarantine_summary``)."""
    if fault is None:
        return None
    return {k: np.asarray(fcarry[k])[:n_exp]
            for k in ("fault_rounds", "rounds_quarantined",
                      "quar_fault_rounds", "first_fault", "first_quar")}


def _split_engine_out(out, participation, analytics, fault=None):
    """Unpack a ``make_scan_fn`` output tuple — ``(params, opt[, pcarry]
    [, fcarry][, acarry][, losses, iid, ood])`` — into its six slots
    (missing ones come back ``None``/``{}``/history ``None``)."""
    params, opt = out[0], out[1]
    rest = list(out[2:])
    pcarry = rest.pop(0) if participation is not None else None
    fcarry = rest.pop(0) if fault is not None else None
    acarry = rest.pop(0) if analytics is not None else {}
    return (params, opt, pcarry, fcarry, acarry,
            (tuple(rest) if rest else None))


def _save_sweep_checkpoint(directory, rounds_done, params, opt, acarry,
                           pcarry, fcarry, losses, iids, oods,
                           keep_history) -> str:
    """Persist the FULL chunk-boundary scan state — model, optimizer,
    every carry, and the host-side history so far — as one atomic
    checkpoint (``repro.training.checkpoint.save_checkpoint``: tmp +
    rename, so a crash mid-write leaves the previous checkpoint intact).
    The state pytree rides the ``params`` slot; the variable-length
    history rides the ``opt_state`` slot (its round count is recorded in
    the metadata so restore can rebuild an exact skeleton)."""
    from repro.training.checkpoint import save_checkpoint

    state = {"params": params, "opt": opt, "acarry": acarry,
             "pcarry": pcarry, "fcarry": fcarry}
    hist = ({"losses": np.concatenate(losses, axis=1),
             "iids": np.concatenate(iids, axis=1),
             "oods": np.concatenate(oods, axis=1)}
            if keep_history and losses else None)
    return save_checkpoint(
        directory, rounds_done, state, hist,
        metadata={"rounds_done": int(rounds_done),
                  "keep_history": bool(keep_history)})


def _load_sweep_checkpoint(path, params, opt, acarry, pcarry, fcarry,
                           keep_history):
    """Inverse of :func:`_save_sweep_checkpoint` — restores into
    skeletons built from the CURRENT run's (post-padding) inputs, so a
    checkpoint from a differently-shaped run fails loudly with the
    offending tree path instead of resuming garbage."""
    import json
    import zipfile
    import zlib

    from repro.training.checkpoint import load_checkpoint

    try:
        with np.load(path, allow_pickle=False) as z:
            meta = json.loads(str(z["__meta__"]))
    except (zipfile.BadZipFile, zlib.error, EOFError) as e:
        raise ValueError(f"{path}: truncated or corrupt checkpoint ({e})")
    done = int(meta["rounds_done"])
    skel = {"params": params, "opt": opt, "acarry": acarry,
            "pcarry": pcarry, "fcarry": fcarry}
    if keep_history and done:
        e, n = np.shape(jax.tree.leaves(params)[0])[:2]
        h = np.zeros((e, done, n), np.float32)
        state, hist, meta = load_checkpoint(
            path, skel, {"losses": h, "iids": h, "oods": h})
        hist = {k: np.asarray(v) for k, v in hist.items()}
    else:
        state, _, meta = load_checkpoint(path, skel)
        hist = None
    return state, hist, meta


@dataclasses.dataclass
class SweepResult:
    """Stacked metrics for an E-experiment sweep.

    ``train_loss`` / ``iid_acc`` / ``ood_acc`` are ``(E, R, n)``;
    ``params`` is the final stacked pytree with leaves ``(E, n, ...)``.
    Accuracy rows are only populated at the rounds ``eval_every`` keeps
    (eval is gated inside the scan; skipped rounds are zeros).
    ``history(e)`` rebuilds the legacy per-experiment ``List[RoundMetrics]``
    (subsampled at ``eval_every`` exactly like ``DecentralizedTrainer.run``)
    for ``repro.core.propagation``.

    ``analytics`` (``SweepEngine.run(analytics=...)``) holds the finalized
    in-scan streaming summaries (DESIGN.md §10) — ``(E, n)`` arrays keyed
    ``iid_auc`` / ``ood_auc`` / ``gap_pct`` / ``iid_arrival`` /
    ``ood_arrival`` / ``final_iid_acc`` / ``final_ood_acc``.  With
    ``keep_history=False`` these are the ONLY metrics: the per-round
    arrays come back zero-length (``(E, 0, n)``, ``history(e) == []``),
    so a sweep's metric memory is O(E·n) instead of O(E·R·n).

    ``participation`` (``SweepEngine.run(participation=...)``) holds the
    per-node participation digest (DESIGN.md §15) — ``(E, n)`` arrays
    keyed ``rounds_active`` / ``final_staleness`` / ``mean_staleness``
    (Σ post-round staleness / R) / ``local_steps``.

    ``fault`` (``SweepEngine.run(fault=...)``) holds the per-node
    fault/quarantine digest (DESIGN.md §16) — ``(E, n)`` arrays keyed
    ``fault_rounds`` / ``rounds_quarantined`` / ``quar_fault_rounds`` /
    ``first_fault`` / ``first_quar`` (−1 = never), the inputs to
    ``repro.core.analytics.quarantine_summary``.
    """

    train_loss: np.ndarray
    iid_acc: np.ndarray
    ood_acc: np.ndarray
    params: Any
    eval_every: int = 1
    analytics: Optional[Dict[str, np.ndarray]] = None
    participation: Optional[Dict[str, np.ndarray]] = None
    fault: Optional[Dict[str, np.ndarray]] = None

    @property
    def n_experiments(self) -> int:
        return self.train_loss.shape[0]

    @property
    def rounds(self) -> int:
        return self.train_loss.shape[1]

    def history(self, e: int) -> List[RoundMetrics]:
        return [
            RoundMetrics(round=r, iid_acc=self.iid_acc[e, r],
                         ood_acc=self.ood_acc[e, r],
                         train_loss=self.train_loss[e, r])
            for r in eval_round_indices(self.rounds, self.eval_every)
        ]

    def experiment_params(self, e: int):
        return jax.tree.map(lambda x: x[e], self.params)


class SweepEngine:
    """Compiles (strategy × seed × placement × topology) grids into one
    program: ``jit(vmap_E(scan_R(round)))``.

    Args:
      optimizer / loss_fn / eval_fn: exactly as ``DecentralizedTrainer``.
      config: round/epoch counts; ``mix_impl="pallas"`` routes aggregation
        through ``kernels.gossip_mix``; ``unroll_eval=True`` makes
        :meth:`run` default to the incremental per-round loop.
      mix_support: required by ``mix_impl="sparse"`` and ``"edges"`` —
        the (n, n) union support mask fixing the static schedule (ring
        offsets / padded-ELL neighbour tables).  :meth:`run` validates
        every grid's coefficients against the schedule's coverage and
        raises rather than let off-schedule weight be silently dropped.
    """

    def __init__(
        self,
        optimizer: Optimizer,
        loss_fn: Callable,
        eval_fn: Callable,
        config: DecentralizedConfig = DecentralizedConfig(),
        mix_support: Optional[np.ndarray] = None,
    ):
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.eval_fn = eval_fn
        self.config = config
        self._mix_support = mix_support
        self._round_fn = make_round_fn(
            loss_fn, optimizer, config.local_epochs, config.mix_impl,
            config.epoch_shuffle, mix_support=mix_support,
            sparse_slack=config.sparse_slack,
            mix_in_float32=config.mix_in_float32,
            robust=config.robust, robust_trim=config.robust_trim,
            robust_clip=config.robust_clip)
        self._run_jit = jax.jit(
            self._run_impl,
            static_argnames=("batch_size", "program", "analytics",
                             "keep_history", "participation", "fault"))
        self._round_jit = jax.jit(
            self._one_round_impl,
            static_argnames=("batch_size", "do_eval", "program",
                             "analytics", "participation", "fault"))
        self._chunk_jit: Dict[bool, Callable] = {}
        self._sharded_cache: Dict[Tuple[Any, ...], Callable] = {}
        self._part_round_fns: Dict[ParticipationSpec, Callable] = {}
        self._fault_round_fns: Dict[Tuple[Any, ...], Callable] = {}

    def _participation_round_fn(self, spec: ParticipationSpec) -> Callable:
        """Lazily-built (and cached — the fn's identity keys the jit
        traces) partial-participation round for this engine's config."""
        fn = self._part_round_fns.get(spec)
        if fn is None:
            fn = make_participation_round_fn(
                self.loss_fn, self.optimizer, self.config.local_epochs,
                spec, mix_impl=self.config.mix_impl,
                epoch_shuffle=self.config.epoch_shuffle,
                mix_support=self._mix_support,
                sparse_slack=self.config.sparse_slack,
                mix_in_float32=self.config.mix_in_float32,
                robust=self.config.robust,
                robust_trim=self.config.robust_trim,
                robust_clip=self.config.robust_clip)
            self._part_round_fns[spec] = fn
        return fn

    def _fault_round_fn(self, spec: FaultSpec,
                        participation: Optional[ParticipationSpec],
                        ) -> Callable:
        """Lazily-built (and cached) Byzantine-fault round — keyed on both
        specs since participation changes the round signature."""
        key = (spec, participation)
        fn = self._fault_round_fns.get(key)
        if fn is None:
            fn = make_fault_round_fn(
                self.loss_fn, self.optimizer, self.config.local_epochs,
                spec, participation=participation,
                mix_impl=self.config.mix_impl,
                epoch_shuffle=self.config.epoch_shuffle,
                mix_support=self._mix_support,
                sparse_slack=self.config.sparse_slack,
                mix_in_float32=self.config.mix_in_float32,
                robust=self.config.robust,
                robust_trim=self.config.robust_trim,
                robust_clip=self.config.robust_clip)
            self._fault_round_fns[key] = fn
        return fn

    # ------------------------------------------------------------------
    def _check_sparse_support(self, coeffs, program, states) -> None:
        """mix_impl='sparse' / 'edges' silently drop weight outside their
        static schedule (ring offsets / padded-ELL neighbour tables) —
        refuse grids whose coefficients the caller-supplied
        ``mix_support`` cannot express (sub-stochastic mixing would
        return quietly wrong results).  The circulant path's
        dense-fallback schedule covers everything, so no check applies
        there; the edge-list tables cover exactly ``support ∪ diag``."""
        from repro.core.coeffs import PROGRAM_KINDS
        from repro.core.decentralized import sparse_schedule

        if self._mix_support is None:
            return  # make_round_fn already raised in __init__
        if (self.config.mix_impl == "edges"
                or self.config.robust in ("trimmed", "median")):
            s = np.asarray(self._mix_support)
            covered = (s > 0) | np.eye(s.shape[0], dtype=bool)
        else:
            _, covered = sparse_schedule(self._mix_support,
                                         self.config.sparse_slack)
            if covered is None:
                return  # fell back to mix_dense
        if program is None:
            used = np.asarray(
                jnp.any(jnp.abs(coeffs) > 1e-12, axis=(0, 1)))
        else:
            adj = np.asarray(jax.tree.map(jnp.asarray, states)["adj"])
            n = adj.shape[-1]
            used = (np.abs(adj).max(axis=0) > 0) | np.eye(n, dtype=bool)
            if np.any(np.asarray(states["kind"])
                      == PROGRAM_KINDS.index("fl")):
                used = np.ones_like(used)  # fl's matrix is dense 1/n
        if np.any(used & ~covered):
            raise ValueError(
                f"mix_impl={self.config.mix_impl!r}: coefficients carry "
                "weight outside the mix_support schedule (ring offsets / "
                "neighbour tables), which the sparse mix would silently "
                "drop (sub-stochastic mixing); widen mix_support or use "
                "mix_impl='einsum'")

    # ------------------------------------------------------------------
    def _eval(self, stacked_params, test_iid, test_ood):
        # node by node: a vmapped eval would hold every node's activations
        # over the whole test batch at once (n·eval_n samples — GBs for
        # VGG-16 or GPT-2 at n=33, more than one chip's HBM)
        iid = jax.lax.map(lambda p: self.eval_fn(p, test_iid),
                          stacked_params)
        ood = jax.lax.map(lambda p: self.eval_fn(p, test_ood),
                          stacked_params)
        return iid, ood

    def _experiment_scan(self, bank, batch_size, eval_mask, rounds_idx,
                         params, opt, coeffs_e, idx_e, data_idx, test_iid,
                         test_ood, acarry_e, pcarry_e, fcarry_e=None,
                         program=None, state_e=None, analytics=None,
                         keep_history=True, participation=None,
                         fault=None):
        """All R rounds of ONE experiment (vmapped over E by the callers):
        :func:`repro.core.decentralized.make_scan_fn` with the per-round
        batch realized as an in-scan gather from the shared bank.  With a
        ``program``, ``coeffs_e`` carries the (R,) absolute round indices
        and each step's matrix is computed in-scan from ``state_e``.  With
        an ``analytics`` spec, ``acarry_e`` is this experiment's streaming
        accumulator carry and ``rounds_idx`` the (R,) absolute indices;
        with a ``participation`` spec, ``pcarry_e`` its participation
        carry (stale plane + staleness counters, DESIGN.md §15); with a
        ``fault`` spec, ``fcarry_e`` its fault/quarantine carry
        (DESIGN.md §16)."""
        coeff_fn = (None if program is None
                    else (lambda r: program.matrix(state_e, r)))
        if fault is not None:
            round_fn = self._fault_round_fn(fault, participation)
        elif participation is not None:
            round_fn = self._participation_round_fn(participation)
        else:
            round_fn = self._round_fn
        scan_fn = make_scan_fn(
            round_fn, self._eval,
            make_batch=lambda ix: gather_round_batch(
                bank, data_idx, ix, batch_size),
            coeff_fn=coeff_fn, analytics=analytics,
            keep_history=keep_history, participation=participation,
            fault=fault)
        kwargs = {}
        if analytics is not None:
            kwargs.update(round_idx=rounds_idx, analytics_carry=acarry_e)
        if participation is not None:
            kwargs.update(round_idx=rounds_idx,
                          participation_carry=pcarry_e)
        if fault is not None:
            kwargs.update(round_idx=rounds_idx, fault_carry=fcarry_e)
        return scan_fn(params, opt, idx_e, coeffs_e, eval_mask,
                       test_iid, test_ood, **kwargs)

    def _run_impl(self, params0, opt0, coeffs, indices, data_idx, eval_mask,
                  rounds_idx, bank, test_iid, test_ood, states, acarry,
                  pcarry, fcarry={}, *, batch_size, program=None,
                  analytics=None, keep_history=True, participation=None,
                  fault=None):
        run_one = lambda p, o, c, ix, d, ti, to, st, ac, pc, fc: (
            self._experiment_scan(
                bank, batch_size, eval_mask, rounds_idx, p, o, c, ix, d,
                ti, to, ac, pc, fc, program, st, analytics, keep_history,
                participation, fault))
        return jax.vmap(run_one)(
            params0, opt0, coeffs, indices, data_idx, test_iid, test_ood,
            states, acarry, pcarry, fcarry)

    def _one_round_impl(self, params, opt, coeffs_r, idx_r, data_idx, bank,
                        test_iid, test_ood, states, acarry, pcarry, fcarry,
                        round_r, *, batch_size, do_eval, program=None,
                        analytics=None, participation=None, fault=None):
        def one(p, o, c, ix, d, ti, to, st, ac, pc, fc):
            if program is not None:
                with jax.named_scope("coeffs"):
                    c = program.matrix(st, c)  # c is this round's index
            batch = gather_round_batch(bank, d, ix, batch_size)
            if fault is not None:
                if participation is not None:
                    p, o, pc, fc, losses = self._fault_round_fn(
                        fault, participation)(p, o, pc, fc, batch, c,
                                              round_r)
                else:
                    p, o, fc, losses = self._fault_round_fn(
                        fault, None)(p, o, fc, batch, c, round_r)
            elif participation is None:
                p, o, losses = self._round_fn(p, o, batch, c)
            else:
                p, o, pc, losses = self._participation_round_fn(
                    participation)(p, o, pc, batch, c, round_r)
            if do_eval:
                with jax.named_scope("eval"):
                    iid, ood = self._eval(p, ti, to)
            else:
                n = jax.tree.leaves(p)[0].shape[0]
                iid = ood = jnp.zeros((n,))
            if analytics is not None and do_eval:
                with jax.named_scope("analytics"):
                    ac = analytics.update(ac, round_r, True, iid, ood)
            return p, o, losses, iid, ood, ac, pc, fc

        return jax.vmap(one)(
            params, opt, coeffs_r, idx_r, data_idx, test_iid, test_ood,
            states, acarry, pcarry, fcarry)

    # ------------------------------------------------------------------
    # sharded / chunked mode
    # ------------------------------------------------------------------
    def _sharded_body(self, mesh, batch_size: int,
                      program: Optional[CoeffProgram],
                      analytics: Optional[AnalyticsSpec],
                      keep_history: bool,
                      participation: Optional[ParticipationSpec] = None,
                      fault: Optional[FaultSpec] = None,
                      ) -> Callable:
        """The un-jitted ``shard_map(vmap_E(scan_R(...)))`` program over
        the mesh's single experiment axis — shared by the executing
        wrapper below and by :meth:`traceable` for static analysis."""
        from jax.sharding import PartitionSpec as P

        exp, rep = P(mesh.axis_names[0]), P()

        def body(params, opt, coeffs, idx, data_idx, eval_mask, rounds_idx,
                 bank, test_iid, test_ood, states, acarry, pcarry, fcarry):
            return self._run_impl(params, opt, coeffs, idx, data_idx,
                                  eval_mask, rounds_idx, bank, test_iid,
                                  test_ood, states, acarry, pcarry, fcarry,
                                  batch_size=batch_size, program=program,
                                  analytics=analytics,
                                  keep_history=keep_history,
                                  participation=participation,
                                  fault=fault)

        # outputs: (params, opt[, pcarry][, fcarry][, acarry][, losses,
        # iid, ood]) — all exp
        n_out = 2 + (1 if participation is not None else 0) \
            + (1 if fault is not None else 0) \
            + (1 if analytics is not None else 0) \
            + (3 if keep_history else 0)
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(exp, exp, exp, exp, exp, rep, rep, rep, exp, exp,
                      exp, exp, exp, exp),
            out_specs=(exp,) * n_out, check_vma=False)

    def _make_sharded_fn(self, mesh, batch_size: int,
                         program: Optional[CoeffProgram],
                         analytics: Optional[AnalyticsSpec],
                         keep_history: bool, donate: bool,
                         participation: Optional[ParticipationSpec],
                         fault: Optional[FaultSpec] = None,
                         ) -> Callable:
        """``jit(shard_map(vmap_E(scan_R(...))))``.  Per-experiment
        inputs/outputs — including the coefficient-program states and the
        analytics/participation/fault carries — shard on E; the sample
        bank, eval mask, and absolute round indices are replicated (every
        experiment reads them whole).  The (params, opt) carry is donated
        when ``donate`` (``DONATED_CARRY_ARGNUMS``)."""
        key = (mesh, batch_size, program, analytics, keep_history, donate,
               participation, fault)
        if key in self._sharded_cache:
            return self._sharded_cache[key]
        fn = jax.jit(
            self._sharded_body(mesh, batch_size, program, analytics,
                               keep_history, participation, fault),
            donate_argnums=DONATED_CARRY_ARGNUMS if donate else ())
        self._sharded_cache[key] = fn
        return fn

    def _make_chunk_fn(self, batch_size: int,
                       program: Optional[CoeffProgram],
                       analytics: Optional[AnalyticsSpec],
                       keep_history: bool, donate: bool,
                       participation: Optional[ParticipationSpec],
                       fault: Optional[FaultSpec] = None,
                       ) -> Callable:
        """Single-device chunk step: the scanned program with a donated
        (params, opt) carry, re-dispatched per round-chunk."""
        if donate not in self._chunk_jit:
            self._chunk_jit[donate] = jax.jit(
                self._run_impl,
                static_argnames=("batch_size", "program", "analytics",
                                 "keep_history", "participation", "fault"),
                donate_argnums=DONATED_CARRY_ARGNUMS if donate else ())
        chunk_jit = self._chunk_jit[donate]
        return lambda *args: chunk_jit(
            *args, batch_size=batch_size, program=program,
            analytics=analytics, keep_history=keep_history,
            participation=participation, fault=fault)

    def _run_sharded(self, params0, opt0, coeffs, idx, data_idx, eval_mask,
                     bank, test_iid, test_ood, batch_size, mesh,
                     chunk_rounds: Optional[int], states, program,
                     acarry, analytics: Optional[AnalyticsSpec],
                     keep_history: bool, donate: bool, pcarry,
                     participation: Optional[ParticipationSpec],
                     fcarry={}, fault: Optional[FaultSpec] = None,
                     checkpoint_dir: Optional[str] = None,
                     resume: bool = False,
                     donate_params0: bool = False,
                     ) -> SweepResult:
        """Sharded and/or chunked execution.  Bit-identical to the scanned
        path: padding rows are dropped, each chunk resumes the exact scan
        carry — (params, opt) AND the analytics/participation/fault
        accumulators — round indices stay absolute in program, analytics,
        participation and fault mode, and per-shard programs are the same
        per-experiment math.

        ``checkpoint_dir`` makes the run crash-safe (DESIGN.md §16): the
        FULL scan state — params, optimizer, every carry, and the history
        accumulated so far — is persisted atomically
        (``repro.training.checkpoint``) at every chunk boundary, entirely
        outside the jitted scan.  ``resume=True`` restarts from
        ``latest_checkpoint`` and — because each chunk consumes absolute
        round indices and the carries resume exactly — reproduces the
        uninterrupted run bit-identically (tests/test_fault.py kills a
        sweep mid-run and proves it).  With no checkpoint on disk,
        ``resume=True`` degrades to a fresh start."""
        n_exp, rounds = coeffs.shape[:2]
        n_nodes = idx.shape[2]
        test_iid = jax.tree.map(jnp.asarray, test_iid)
        test_ood = jax.tree.map(jnp.asarray, test_ood)
        rounds_idx = jnp.arange(rounds, dtype=jnp.int32)

        if mesh is not None:
            n_dev = int(np.prod(list(mesh.shape.values())))
            pad = (-n_exp) % n_dev
            (params0, opt0, coeffs, idx, data_idx, test_iid, test_ood,
             states, acarry, pcarry, fcarry) = (
                pad_experiments(t, pad)
                for t in (params0, opt0, coeffs, idx, data_idx,
                          test_iid, test_ood, states, acarry, pcarry,
                          fcarry))
            from jax.sharding import NamedSharding, PartitionSpec as P

            exp_sh = NamedSharding(mesh, P(mesh.axis_names[0]))
            rep_sh = NamedSharding(mesh, P())
            put = lambda t, s: jax.tree.map(
                lambda x: jax.device_put(jnp.asarray(x), s), t)
            # device_put materializes fresh buffers laid out on the mesh,
            # so donating the carry never invalidates caller arrays.
            (params0, opt0, coeffs, idx, data_idx, test_iid, test_ood,
             states, acarry, pcarry, fcarry) = (
                put(t, exp_sh)
                for t in (params0, opt0, coeffs, idx, data_idx,
                          test_iid, test_ood, states, acarry, pcarry,
                          fcarry))
            bank = put(bank, rep_sh)
            rounds_idx = put(rounds_idx, rep_sh)
            fn = self._make_sharded_fn(mesh, batch_size, program,
                                       analytics, keep_history, donate,
                                       participation, fault)
            reput = lambda t: put(t, exp_sh)
        else:
            if donate and not donate_params0:
                # chunk 0 would donate the caller's params0 — copy once
                params0 = jax.tree.map(
                    lambda x: jnp.asarray(x).copy(), params0)
            fn = self._make_chunk_fn(batch_size, program, analytics,
                                     keep_history, donate, participation,
                                     fault)
            reput = lambda t: jax.tree.map(jnp.asarray, t)

        chunk = chunk_rounds or rounds
        params, opt = params0, opt0
        losses, iids, oods = [], [], []
        start, chunks_done = 0, 0
        if checkpoint_dir is not None and resume:
            from repro.training.checkpoint import latest_checkpoint

            ck = latest_checkpoint(checkpoint_dir)
            if ck is not None:
                state, hist_np, meta = _load_sweep_checkpoint(
                    ck, params, opt, acarry, pcarry, fcarry, keep_history)
                params = reput(state["params"])
                opt = reput(state["opt"])
                if analytics is not None:
                    acarry = reput(state["acarry"])
                if participation is not None:
                    pcarry = reput(state["pcarry"])
                if fault is not None:
                    fcarry = reput(state["fcarry"])
                if keep_history and int(meta["rounds_done"]):
                    losses = [hist_np["losses"]]
                    iids = [hist_np["iids"]]
                    oods = [hist_np["oods"]]
                start = int(meta["rounds_done"])
        crash_after = int(os.environ.get(
            "REPRO_SWEEP_CRASH_AFTER_CHUNKS", "0"))
        for a in range(start, rounds, chunk):
            b = min(a + chunk, rounds)
            with TraceAnnotation("repro.engine.chunk", first_round=a,
                                 rounds=b - a, experiments=n_exp,
                                 nodes=n_nodes):
                out = fn(
                    params, opt, coeffs[:, a:b], idx[:, a:b], data_idx,
                    jnp.asarray(eval_mask[a:b]), rounds_idx[a:b], bank,
                    test_iid, test_ood, states, acarry, pcarry, fcarry)
            params, opt, pc_out, fc_out, ac_out, hist = _split_engine_out(
                out, participation, analytics, fault)
            if participation is not None:
                pcarry = pc_out
            if fault is not None:
                fcarry = fc_out
            if analytics is not None:
                acarry = ac_out
            if keep_history:
                l_c, iid_c, ood_c = hist
                with TraceAnnotation("repro.engine.fetch"):
                    losses.append(np.asarray(l_c))
                    iids.append(np.asarray(iid_c))
                    oods.append(np.asarray(ood_c))
            chunks_done += 1
            if checkpoint_dir is not None and b < rounds:
                _save_sweep_checkpoint(
                    checkpoint_dir, b, params, opt, acarry, pcarry,
                    fcarry, losses, iids, oods, keep_history)
                if crash_after and chunks_done >= crash_after:
                    # test hook: die WITHOUT cleanup, exactly like a
                    # preempted host (tests/test_fault.py kill-and-resume)
                    os._exit(17)

        out_params = jax.tree.map(lambda x: x[:n_exp], params)
        if keep_history:
            cat = lambda xs: np.concatenate(xs, axis=1)[:n_exp]
            l, i, o = cat(losses), cat(iids), cat(oods)
        else:
            n = jax.tree.leaves(out_params)[0].shape[1]
            l = i = o = np.zeros((n_exp, 0, n), np.float32)
        return SweepResult(
            train_loss=l, iid_acc=i, ood_acc=o, params=out_params,
            eval_every=self.config.eval_every,
            analytics=_finalize_analytics(analytics, acarry, n_exp),
            participation=_finalize_participation(
                participation, pcarry, n_exp, rounds),
            fault=_finalize_fault(fault, fcarry, n_exp))

    # ------------------------------------------------------------------
    def _prepare_inputs(self, params0, coeffs, bank, indices, data_idx,
                        analytics: Optional[AnalyticsSpec],
                        keep_history: bool,
                        participation: Optional[ParticipationSpec] = None,
                        participation_rates=None,
                        participation_seeds=None,
                        fault: Optional[FaultSpec] = None,
                        fault_rates=None,
                        fault_seeds=None):
        """Shared input normalization for :meth:`run` and
        :meth:`traceable` — program/stack resolution, support validation,
        index gathering, optimizer/analytics/participation/fault carry
        construction."""
        if fault is not None:
            # build (and cache) the fault round fn OUTSIDE any jit trace
            # (same trace-time-constant reasoning as participation below)
            self._fault_round_fn(fault, participation)
        elif participation is not None:
            # build (and cache) the participation round fn OUTSIDE any jit
            # trace: make_mix_fn bakes trace-time constants (e.g. the
            # padded-ELL neighbour tables) into the closure, which must
            # not be tracers of whichever program first used the fn
            self._participation_round_fn(participation)
        program: Optional[CoeffProgram] = None
        states: Any = {}
        if isinstance(coeffs, ProgramCoeffs):
            program = coeffs.program
            # a kind-pruned program silently remaps unlisted kinds — refuse
            program.validate_state_kinds(coeffs.states)
            states = jax.tree.map(jnp.asarray, coeffs.states)
            n_exp = coeffs.n_experiments
            rounds = int(np.asarray(indices).shape[1])
            # the scanned xs: absolute int32 round indices, (E, R) so the
            # existing chunk slicing / E-padding / E-sharding apply as-is
            coeffs = jnp.broadcast_to(
                jnp.arange(rounds, dtype=jnp.int32)[None], (n_exp, rounds))
        else:
            coeffs = jnp.asarray(coeffs, jnp.float32)
            rounds = coeffs.shape[1]
        if (self.config.mix_impl in ("sparse", "edges")
                or self.config.robust in ("trimmed", "median")):
            self._check_sparse_support(coeffs, program, states)
        if not keep_history and analytics is None:
            raise ValueError("keep_history=False without an analytics "
                             "spec would return no metrics at all")
        data_idx = jnp.asarray(data_idx, jnp.int32)
        # (E, R, n, S): per-experiment index schedule, pre-gathered host-side
        # (tiny — int32; the sample bank itself stays (D, ...)-shaped).
        idx = jnp.asarray(np.asarray(indices, np.int32)[np.asarray(data_idx)])
        bank = jax.tree.map(jnp.asarray, bank)
        opt0 = jax.vmap(jax.vmap(self.optimizer.init))(params0)
        eval_mask = np.zeros(rounds, bool)
        eval_mask[eval_round_indices(rounds, self.config.eval_every)] = True
        n_exp = jax.tree.leaves(params0)[0].shape[0]
        n_nodes = jax.tree.leaves(params0)[0].shape[1]
        acarry = (analytics.init_batch(n_exp, n_nodes)
                  if analytics is not None else {})
        if participation is None:
            if participation_rates is not None or \
                    participation_seeds is not None:
                raise ValueError("participation_rates/participation_seeds "
                                 "need a ParticipationSpec (participation=)")
            pcarry = {}
        else:
            rates = (np.ones(n_exp, np.float32)
                     if participation_rates is None
                     else np.broadcast_to(
                         np.asarray(participation_rates, np.float32),
                         (n_exp,)))
            seeds = (np.asarray(participation.seed + np.arange(n_exp),
                                np.uint32)
                     if participation_seeds is None
                     else np.broadcast_to(
                         np.asarray(participation_seeds, np.uint32),
                         (n_exp,)))
            pcarry = jax.vmap(participation_carry_init)(
                params0, jnp.asarray(rates), jnp.asarray(seeds))
        if fault is None:
            if fault_rates is not None or fault_seeds is not None:
                raise ValueError("fault_rates/fault_seeds need a "
                                 "FaultSpec (fault=)")
            fcarry = {}
        else:
            frates = (np.zeros(n_exp, np.float32)
                      if fault_rates is None
                      else np.broadcast_to(
                          np.asarray(fault_rates, np.float32), (n_exp,)))
            fseeds = (np.asarray(fault.seed + np.arange(n_exp), np.uint32)
                      if fault_seeds is None
                      else np.broadcast_to(
                          np.asarray(fault_seeds, np.uint32), (n_exp,)))
            fcarry = jax.vmap(fault_carry_init)(
                params0, jnp.asarray(frates), jnp.asarray(fseeds))
        return (params0, opt0, coeffs, idx, data_idx, eval_mask, bank,
                states, program, acarry, pcarry, fcarry, rounds, n_exp,
                n_nodes)

    def traceable(
        self,
        params0,
        coeffs,
        bank,
        indices: np.ndarray,
        data_idx: np.ndarray,
        test_iid,
        test_ood,
        batch_size: int,
        mode: str = "scanned",
        mesh=None,
        chunk_rounds: Optional[int] = None,
        analytics: Optional[AnalyticsSpec] = None,
        keep_history: bool = True,
        donate: Optional[bool] = None,
        participation: Optional[ParticipationSpec] = None,
        participation_rates=None,
        participation_seeds=None,
        fault: Optional[FaultSpec] = None,
        fault_rates=None,
        fault_seeds=None,
    ) -> Tuple[Callable, Tuple[Any, ...], Dict[str, Any]]:
        """``(fn, args, jit_kwargs)`` for static analysis — the exact
        program each execution mode runs, as a traceable closure plus
        concrete arguments, consumed by ``repro.analysis``
        (``jax.make_jaxpr(fn)(*args)`` /
        ``jax.jit(fn, **jit_kwargs).lower(*args)``).

        ``mode``: ``"scanned"`` (the one-shot jit), ``"chunked"`` (one
        donated round-chunk step — ``chunk_rounds`` bounds it),
        ``"mesh"`` (the shard_map program over ``mesh``), or
        ``"unrolled"`` (one per-round dispatch with eval).  ``donate``
        defaults to the run-time decision (:func:`donation_supported`);
        pass ``True`` to analyze donation intent on CPU, where run()
        skips it only because the backend ignores donation."""
        (params0, opt0, coeffs, idx, data_idx, eval_mask, bank, states,
         program, acarry, pcarry, fcarry, rounds, n_exp, n_nodes) = \
            self._prepare_inputs(
                params0, coeffs, bank, indices, data_idx, analytics,
                keep_history, participation, participation_rates,
                participation_seeds, fault, fault_rates, fault_seeds)
        donate = donation_supported() if donate is None else donate
        rounds_idx = jnp.arange(rounds, dtype=jnp.int32)
        eval_mask = jnp.asarray(eval_mask)
        test_iid = jax.tree.map(jnp.asarray, test_iid)
        test_ood = jax.tree.map(jnp.asarray, test_ood)

        if mode == "unrolled":
            fn = functools.partial(
                self._one_round_impl, batch_size=batch_size, do_eval=True,
                program=program, analytics=analytics,
                participation=participation, fault=fault)
            args = (params0, opt0, coeffs[:, 0], idx[:, 0], data_idx, bank,
                    test_iid, test_ood, states, acarry, pcarry, fcarry,
                    jnp.asarray(0, jnp.int32))
            return fn, args, {}

        if mode in ("scanned", "chunked"):
            fn = functools.partial(
                self._run_impl, batch_size=batch_size, program=program,
                analytics=analytics, keep_history=keep_history,
                participation=participation, fault=fault)
            c = rounds if mode == "scanned" else (chunk_rounds or rounds)
            args = (params0, opt0, coeffs[:, :c], idx[:, :c], data_idx,
                    eval_mask[:c], rounds_idx[:c], bank, test_iid,
                    test_ood, states, acarry, pcarry, fcarry)
            jit_kwargs = ({} if mode == "scanned" else
                          {"donate_argnums":
                           DONATED_CARRY_ARGNUMS if donate else ()})
            return fn, args, jit_kwargs

        if mode == "mesh":
            if mesh is None:
                from repro.launch.mesh import make_sweep_mesh

                mesh = make_sweep_mesh()
            n_dev = int(np.prod(list(mesh.shape.values())))
            pad = (-n_exp) % n_dev
            (params0, opt0, coeffs, idx, data_idx, test_iid, test_ood,
             states, acarry, pcarry, fcarry) = (
                pad_experiments(t, pad)
                for t in (params0, opt0, coeffs, idx, data_idx,
                          test_iid, test_ood, states, acarry, pcarry,
                          fcarry))
            fn = self._sharded_body(mesh, batch_size, program, analytics,
                                    keep_history, participation, fault)
            args = (params0, opt0, coeffs, idx, data_idx, eval_mask,
                    rounds_idx, bank, test_iid, test_ood, states, acarry,
                    pcarry, fcarry)
            return fn, args, {"donate_argnums":
                              DONATED_CARRY_ARGNUMS if donate else ()}

        raise KeyError(f"unknown mode {mode!r}; have 'scanned', "
                       f"'chunked', 'mesh', 'unrolled'")

    # ------------------------------------------------------------------
    def run(
        self,
        params0,                      # pytree, leaves (E, n, ...)
        coeffs,                       # (E, R, n, n) stack | ProgramCoeffs
        bank,                         # pytree, leaves (D, n, cap, ...)
        indices: np.ndarray,          # (D, R, n, S)
        data_idx: np.ndarray,         # (E,) rows into bank/indices
        test_iid,                     # pytree, leaves (E, b, ...)
        test_ood,
        batch_size: int,
        unroll_eval: Optional[bool] = None,
        mesh=None,                    # 1-D jax Mesh → shard the E axis
        chunk_rounds: Optional[int] = None,  # scan R in ⌈R/c⌉ chunks
        analytics: Optional[AnalyticsSpec] = None,
        keep_history: bool = True,
        donate: Optional[bool] = None,
        participation: Optional[ParticipationSpec] = None,
        participation_rates=None,   # (E,) or scalar; None → all 1.0
        participation_seeds=None,   # (E,) or scalar; None → seed+arange(E)
        fault: Optional[FaultSpec] = None,
        fault_rates=None,           # (E,) or scalar; None → all 0.0
        fault_seeds=None,           # (E,) or scalar; None → seed+arange(E)
        checkpoint_dir: Optional[str] = None,
        resume: bool = False,
        donate_params0: bool = False,
    ) -> SweepResult:
        """Run the whole grid.  ``unroll_eval`` overrides the config flag
        (None → use ``config.unroll_eval``).  ``mesh`` (from
        ``repro.launch.mesh.make_sweep_mesh``) shards the experiment axis
        across devices; ``chunk_rounds`` bounds device memory for long
        schedules.  ``donate`` overrides carry donation in the
        chunked/sharded paths (None → :func:`donation_supported`, i.e.
        donate wherever XLA honors it).  All modes are bit-identical.
        ``donate_params0=True`` hands ``params0`` over: the first chunk
        donates its buffers (they are deleted) instead of a copy, which
        saves one params-sized copy of device memory.

        ``coeffs`` may be a :class:`repro.core.coeffs.ProgramCoeffs`
        instead of an ``(E, R, n, n)`` stack: the per-round matrices are
        then generated device-side inside the scan (all three modes; the
        per-experiment program state shards on E like every other
        per-experiment input), the round count comes from the ``indices``
        schedule, and — for non-reactive programs — results are
        bit-identical to running the materialized stack.

        ``analytics`` (an :class:`repro.core.analytics.AnalyticsSpec`)
        threads the streaming-analytics accumulators through the scan
        (DESIGN.md §10) and populates ``SweepResult.analytics`` with
        per-experiment per-node summaries — identical values in every
        execution mode (the carry pads/shards on E and chunk boundaries
        resume it exactly).  ``keep_history=False`` (requires
        ``analytics``) drops the per-round ``(E, R, n)`` metric arrays
        entirely: the summaries are the only metrics, O(E·n) memory.

        ``participation`` (a ``repro.core.dynamic.ParticipationSpec``)
        switches every mode to partial-participation rounds (DESIGN.md
        §15): ``participation_rates`` gives the per-experiment activation
        rate (scalar broadcasts; None → 1.0, which is bit-identical to
        the synchronous path) and ``participation_seeds`` the per-
        experiment PRNG seeds (None → ``spec.seed + arange(E)``).  Rates
        and seeds are CARRIED data, not static, so one compiled program
        serves a whole rate grid.  ``SweepResult.participation`` holds
        the staleness digest.

        ``fault`` (a ``repro.core.dynamic.FaultSpec``) switches every
        mode to Byzantine-fault rounds (DESIGN.md §16):
        ``fault_rates``/``fault_seeds`` mirror the participation
        arguments (None → rate 0.0 — bit-identical to the fault-free
        path — and ``spec.seed + arange(E)``); both are CARRIED data, so
        one compiled program serves a whole fault-rate grid.
        ``SweepResult.fault`` holds the quarantine digest.

        ``checkpoint_dir`` (needs ``chunk_rounds``) persists the full
        scan state at every chunk boundary — atomic writes, outside the
        jitted scan; ``resume=True`` restarts from the latest checkpoint
        bit-identically (fresh start when none exists)."""
        with TraceAnnotation("repro.engine.prepare"):
            (params0, opt0, coeffs, idx, data_idx, eval_mask, bank, states,
             program, acarry, pcarry, fcarry, rounds, n_exp, n_nodes) = \
                self._prepare_inputs(
                    params0, coeffs, bank, indices, data_idx, analytics,
                    keep_history, participation, participation_rates,
                    participation_seeds, fault, fault_rates, fault_seeds)
        donate = donation_supported() if donate is None else donate

        if checkpoint_dir is not None and not chunk_rounds:
            raise ValueError(
                "checkpoint_dir needs chunk_rounds — checkpoints are "
                "written at chunk boundaries, outside the jitted scan")
        unroll = (self.config.unroll_eval if unroll_eval is None
                  else unroll_eval)
        if unroll:
            if mesh is not None or chunk_rounds:
                raise ValueError(
                    "mesh/chunk_rounds are scanned-mode options; they "
                    "cannot combine with unroll_eval=True")
            return self._run_unrolled(
                params0, opt0, coeffs, idx, data_idx, eval_mask, bank,
                test_iid, test_ood, batch_size, states, program,
                acarry, analytics, keep_history, pcarry, participation,
                fcarry, fault)

        if mesh is not None or chunk_rounds:
            return self._run_sharded(
                params0, opt0, coeffs, idx, data_idx, eval_mask, bank,
                test_iid, test_ood, batch_size, mesh, chunk_rounds,
                states, program, acarry, analytics, keep_history, donate,
                pcarry, participation, fcarry, fault, checkpoint_dir,
                resume, donate_params0)

        rounds_idx = jnp.arange(rounds, dtype=jnp.int32)
        with TraceAnnotation("repro.engine.chunk", first_round=0,
                             rounds=rounds, experiments=n_exp,
                             nodes=n_nodes):
            out = self._run_jit(
                params0, opt0, coeffs, idx, data_idx,
                jnp.asarray(eval_mask), rounds_idx, bank, test_iid,
                test_ood, states, acarry, pcarry, fcarry,
                batch_size=batch_size, program=program,
                analytics=analytics, keep_history=keep_history,
                participation=participation, fault=fault)
        params, _, pc_out, fc_out, ac_out, hist = _split_engine_out(
            out, participation, analytics, fault)
        if participation is not None:
            pcarry = pc_out
        if fault is not None:
            fcarry = fc_out
        if analytics is not None:
            acarry = ac_out
        if hist is not None:
            with TraceAnnotation("repro.engine.fetch"):
                losses, iid, ood = (np.asarray(h) for h in hist)
        else:
            losses = iid = ood = np.zeros((n_exp, 0, n_nodes), np.float32)
        return SweepResult(
            train_loss=losses, iid_acc=iid, ood_acc=ood, params=params,
            eval_every=self.config.eval_every,
            analytics=_finalize_analytics(analytics, acarry, n_exp),
            participation=_finalize_participation(
                participation, pcarry, n_exp, rounds),
            fault=_finalize_fault(fault, fcarry, n_exp))

    def _run_unrolled(self, params, opt, coeffs, idx, data_idx, eval_mask,
                      bank, test_iid, test_ood, batch_size, states=None,
                      program=None, acarry=None, analytics=None,
                      keep_history=True, pcarry=None,
                      participation=None, fcarry=None,
                      fault=None) -> SweepResult:
        """Escape hatch: per-round dispatch, incremental metrics (the
        analytics carry is folded one eval round at a time)."""
        if states is None:
            states = {}
        if acarry is None:
            acarry = {}
        if pcarry is None:
            pcarry = {}
        if fcarry is None:
            fcarry = {}
        n_exp = jax.tree.leaves(params)[0].shape[0]
        n_nodes = jax.tree.leaves(params)[0].shape[1]
        rounds = coeffs.shape[1]
        losses, iids, oods = [], [], []
        for r in range(rounds):
            (params, opt, l_r, iid_r, ood_r, acarry, pcarry,
             fcarry) = self._round_jit(
                params, opt, coeffs[:, r], idx[:, r], data_idx, bank,
                test_iid, test_ood, states, acarry, pcarry, fcarry,
                jnp.asarray(r, jnp.int32), batch_size=batch_size,
                do_eval=bool(eval_mask[r]), program=program,
                analytics=analytics, participation=participation,
                fault=fault)
            if keep_history:
                losses.append(np.asarray(l_r))
                iids.append(np.asarray(iid_r))
                oods.append(np.asarray(ood_r))
        if keep_history:
            l = np.stack(losses, axis=1)
            i = np.stack(iids, axis=1)
            o = np.stack(oods, axis=1)
        else:
            l = i = o = np.zeros((n_exp, 0, n_nodes), np.float32)
        return SweepResult(
            train_loss=l, iid_acc=i, ood_acc=o,
            params=params, eval_every=self.config.eval_every,
            analytics=_finalize_analytics(analytics, acarry, n_exp),
            participation=_finalize_participation(
                participation, pcarry, n_exp, rounds),
            fault=_finalize_fault(fault, fcarry, n_exp))
