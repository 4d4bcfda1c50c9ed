"""Shared harness for the paper-figure benchmarks.

Two execution paths over the same experimental grid:

* ``run_experiment`` — the legacy path: ONE cell (dataset, topology,
  strategy, OOD location) per invocation, per-round Python loop.  Kept as
  the wall-clock baseline the sweep engine is compared against.
* ``run_sweep_cells`` — the batched path: a list of :class:`SweepCell`
  grouped by program shape and evaluated by ``repro.core.sweep`` — one
  compiled vmap×scan program per (dataset, n_nodes) group.

Reduced defaults keep `python -m benchmarks.run` CPU-tractable; ``--full``
restores paper scale (33 nodes, 40 rounds, 5 datasets, 3 seeds).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.coeffs import (
    PROGRAM_KINDS,
    ProgramCoeffs,
    program_for,
    stack_states,
)
from repro.core.decentralized import (
    DecentralizedConfig,
    DecentralizedTrainer,
    coeffs_stack,
    stack_params,
)
from repro.core.analytics import (AnalyticsSpec, analytics_summary,
                                  participation_summary, quarantine_summary)
from repro.core.dynamic import FaultSpec, ParticipationSpec
from repro.core.sweep import SweepEngine
from repro.core.propagation import per_node_auc, propagation_summary
from repro.core.strategies import AggregationStrategy
from repro.core.topology import Topology
from repro.data.backdoor import backdoored_testset
from repro.data.distribution import node_datasets
from repro.data.pipeline import NodeBatcher, make_test_batch
from repro.data.synthetic import make_dataset
from repro.models.paper_models import (
    classifier_accuracy,
    classifier_loss,
    ffn_init,
    ffn_apply,
    gpt2_tinymem_config,
    lm_accuracy,
    lm_loss,
    vgg_init,
    vgg_apply,
)
from repro.models.transformer import init_params as tf_init
from repro.training.optimizer import adam, sgd

# Table 1 of the paper (model + optimizer per dataset).  VGG-16 runs at
# its Table 1 width at FULL scale and at a quarter width at QUICK and
# smoke scale (``BenchScale.vgg_width``); relative strategy comparisons
# are preserved.
DATASET_SETUP = {
    "mnist":   dict(model="ffn", opt=("sgd", 1e-2)),
    "fmnist":  dict(model="ffn", opt=("sgd", 1e-2)),
    "cifar10": dict(model="vgg", opt=("adam", 1e-4)),
    "cifar100": dict(model="vgg", opt=("adam", 1e-4)),
    "tinymem": dict(model="gpt2", opt=("adam", 1e-3)),
}


@dataclasses.dataclass
class BenchScale:
    n_train: int = 6000
    n_test: int = 600
    rounds: int = 15
    local_epochs: int = 3
    batch: int = 32
    steps_per_epoch: int = 8
    eval_every: int = 3
    eval_n: int = 256
    vgg_width: float = 0.25      # VGG-16 width multiplier (1.0 = Table 1)


# QUICK uses the paper's R≈40/E=5 regime scaled to 30 rounds — below ~20
# rounds the system is dilution-limited rather than propagation-limited and
# the topology trends invert (see EXPERIMENTS.md §Reproduction notes).
QUICK = BenchScale(rounds=30, local_epochs=5, eval_every=5)

#: accuracy level that counts as "OOD knowledge arrived" for the
#: streaming arrival-round analytics (run_sweep_cells default; the
#: BENCH_sweep.json analytics sections record whichever value ran).
DEFAULT_ARRIVAL_THRESHOLD = 0.5
FULL = BenchScale(n_train=20000, n_test=2000, rounds=40, local_epochs=5,
                  batch=32, steps_per_epoch=0, eval_every=4, eval_n=512,
                  vgg_width=1.0)


def _model_fns(dataset: str, scale: BenchScale, seed: int):
    setup = DATASET_SETUP[dataset]
    kind, (opt_name, lr) = setup["model"], setup["opt"]
    opt = sgd(lr) if opt_name == "sgd" else adam(lr)
    if kind == "ffn":
        in_dim = 28 * 28 * 1
        init = lambda k: ffn_init(k, in_dim=in_dim)
        return init, classifier_loss(ffn_apply), classifier_accuracy(ffn_apply), opt
    if kind == "vgg":
        n_classes = 100 if dataset == "cifar100" else 10
        init = lambda k: vgg_init(k, n_classes=n_classes,
                                  width_mult=scale.vgg_width)
        return init, classifier_loss(vgg_apply), classifier_accuracy(vgg_apply), opt
    cfg = gpt2_tinymem_config()
    init = lambda k: tf_init(k, cfg)
    return init, lm_loss(cfg), lm_accuracy(cfg), opt


@functools.lru_cache(maxsize=32)
def _data(dataset: str, n_train: int, n_test: int, seed: int):
    train = make_dataset(dataset, n_train, seed=seed)
    test = make_dataset(dataset, n_test, seed=seed + 9999)
    return train, test


def run_experiment(
    dataset: str,
    topo: Topology,
    strategy: str,
    ood_k: int = 1,                 # OOD on k-th highest-degree node
    tau: float = 0.1,
    seed: int = 0,
    scale: BenchScale = QUICK,
    alpha_l: float = 1000.0,        # label-Dirichlet heterogeneity (paper B.2.1)
    alpha_s: float = 1000.0,
    ood_ks: Optional[Tuple[int, ...]] = None,  # multi-source degree ranks
) -> Dict:
    """One experimental cell → AUC summary dict.  ``ood_ks`` overrides
    ``ood_k`` with a tuple of degree ranks hosting OOD data
    simultaneously (same placement scheme as ``SweepCell.ood_ks``, so
    the legacy loop stays a valid baseline for multi-source grids)."""
    t0 = time.time()
    train, test = _data(dataset, scale.n_train, scale.n_test, seed)
    ood_nodes = tuple(topo.kth_highest_degree_node(k)
                      for k in (ood_ks or (ood_k,)))
    parts = node_datasets(train, topo.n_nodes, ood_node=ood_nodes,
                          q=0.10, seed=seed, alpha_l=alpha_l, alpha_s=alpha_s)
    nb = NodeBatcher(parts, batch_size=scale.batch,
                     steps_per_epoch=scale.steps_per_epoch, seed=seed,
                     local_epochs=scale.local_epochs)
    tb = make_test_batch(test, scale.eval_n, seed=seed)
    ob = make_test_batch(backdoored_testset(test, seed=seed), scale.eval_n,
                         seed=seed, ood_mask=(test.kind == "lm"))

    init, loss_fn, acc_fn, opt = _model_fns(dataset, scale, seed)
    common = init(jax.random.key(seed))
    params = stack_params([common] * topo.n_nodes)

    trainer = DecentralizedTrainer(
        topo, AggregationStrategy(strategy, tau=tau, seed=seed), opt,
        loss_fn, acc_fn,
        # unroll_eval=True: this is the pre-sweep-engine per-round loop,
        # kept as the wall-clock baseline (benchmarks/sweep.py compares).
        DecentralizedConfig(rounds=scale.rounds,
                            local_epochs=scale.local_epochs,
                            eval_every=scale.eval_every,
                            unroll_eval=True),
        data_counts=nb.data_counts(),
    )
    _, hist = trainer.run(
        params, lambda r: jax.tree.map(jnp.asarray, nb.round_batches(r)),
        jax.tree.map(jnp.asarray, tb), jax.tree.map(jnp.asarray, ob))

    summary = propagation_summary(hist, topo.adjacency, ood_nodes)
    summary.update(
        dataset=dataset, topology=topo.name, strategy=strategy,
        ood_k=ood_k,
        ood_node=(ood_nodes[0] if len(ood_nodes) == 1
                  else list(ood_nodes)),
        seed=seed,
        secs=round(time.time() - t0, 1),
    )
    if ood_ks:
        summary["ood_ks"] = list(ood_ks)
    return summary


def csv_row(name: str, secs: float, derived: str) -> str:
    """The scaffold's ``name,us_per_call,derived`` CSV convention."""
    return f"{name},{secs * 1e6:.0f},{derived}"


# ----------------------------------------------------------------------
# batched path: declarative cells → repro.core.sweep
# ----------------------------------------------------------------------
@dataclasses.dataclass(frozen=True, eq=False)
class SweepCell:
    """One cell of a figure's grid, as data (no control flow).

    ``name`` is the CSV label; ``sweep`` is the free-form annotation the
    fig6-style verdicts group by (stored on the summary row verbatim).
    ``p_fail`` drops each edge i.i.d. per round (``repro.core.dynamic``);
    ``reactive`` recomputes centralities on the surviving subgraph
    in-scan — both realized by the cell's coefficient program
    (``repro.core.coeffs``; must agree across a compiled group).

    ``ood_ks`` opens the multi-source scenario axis: a tuple of degree
    ranks hosting OOD data simultaneously (each gets its own backdoored
    subset — ``data.distribution.place_ood``).  When set it overrides the
    single-source ``ood_k``; hop fields and arrival bins then use the
    min-over-sources distance.

    ``participation`` is the cell's node-activation rate under a
    partial-participation sweep (``run_sweep_cells(participation=...)``,
    DESIGN.md §15); ``None`` means fully synchronous — in a mixed group
    such cells run at rate 1.0, which is bit-identical.

    ``fault_rate`` is the cell's per-node-round Byzantine fault
    probability under a fault-injection sweep
    (``run_sweep_cells(fault=...)``, DESIGN.md §16); ``None`` runs at
    rate 0.0, bit-identical to the fault-free round.  ``robust`` selects
    the cell's aggregation rule (``make_mix_fn``); it is static engine
    configuration, so cells with different ``robust`` compile into
    separate groups.
    """

    dataset: str
    topo: Topology
    strategy: str
    ood_k: int = 1
    tau: float = 0.1
    seed: int = 0
    name: str = ""
    sweep: Optional[tuple] = None
    p_fail: float = 0.0
    reactive: bool = False
    ood_ks: Optional[Tuple[int, ...]] = None
    participation: Optional[float] = None
    fault_rate: Optional[float] = None
    robust: str = "mean"

    @property
    def label(self) -> str:
        return self.name or f"{self.dataset}/{self.topo.name}/{self.strategy}"

    def ood_nodes(self) -> Tuple[int, ...]:
        """The cell's OOD host node(s): ``ood_ks`` degree ranks when set,
        else the single ``ood_k``-th highest-degree node."""
        ranks = tuple(self.ood_ks) if self.ood_ks else (self.ood_k,)
        nodes = tuple(self.topo.kth_highest_degree_node(k) for k in ranks)
        if len(set(nodes)) != len(nodes):
            raise ValueError(f"ood_ks {ranks} map to duplicate nodes "
                             f"{nodes} on {self.topo.name}")
        return nodes


def linkfail_cells(
    datasets=("mnist",),
    seeds=(0,),
    n_nodes: int = 16,
    strategies=("unweighted", "degree"),
    p_fails=(0.0, 0.3, 0.6),
    reactive: bool = True,
    prefix: str = "linkfail",
) -> List[SweepCell]:
    """Link-failure grid shared by the ``benchmarks/sweep.py linkfail``
    preset and ``benchmarks/ablations.py run_link_failure``: strategies ×
    p_fail on per-seed BA graphs, coefficients generated in-scan by each
    cell's program (reactive=True recomputes centralities on the
    surviving subgraph)."""
    from repro.core.topology import barabasi_albert

    cells = []
    for ds in datasets:
        for seed in seeds:
            # one Topology per (dataset, seed) so the networkx centrality
            # cache (nominal scores, kth_highest_degree_node) is shared
            topo = barabasi_albert(n_nodes, 2, seed=seed)
            for strat in strategies:
                for pf in p_fails:
                    cells.append(SweepCell(
                        ds, topo, strat, ood_k=1, seed=seed,
                        p_fail=pf, reactive=reactive,
                        name=f"{prefix}/{ds}/{strat}/p{pf}",
                        sweep=("p_fail", strat, pf)))
    return cells


def multisource_cells(
    datasets=("mnist",),
    seeds=(0,),
    n_nodes: int = 16,
    strategies=("unweighted", "degree"),
    source_counts=(1, 2, 4),
    prefix: str = "multisource",
) -> List[SweepCell]:
    """Multi-source OOD grid (the ``benchmarks/sweep.py multisource``
    preset): k backdoor sources on the k highest-degree nodes of per-seed
    BA graphs, strategies × source counts.  Every source plants the SAME
    trigger on its own backdoored subset, so the in-scan arrival-round
    analytics measure how source multiplicity accelerates propagation
    (min-over-sources hop fields)."""
    from repro.core.topology import barabasi_albert

    cells = []
    for ds in datasets:
        for seed in seeds:
            topo = barabasi_albert(n_nodes, 2, seed=seed)
            for strat in strategies:
                for k in source_counts:
                    cells.append(SweepCell(
                        ds, topo, strat, seed=seed,
                        ood_ks=tuple(range(1, k + 1)),
                        name=f"{prefix}/{ds}/{strat}/k{k}",
                        sweep=("sources", strat, k)))
    return cells


def edges_cells(
    datasets=("mnist",),
    seeds=(0,),
    n_nodes: int = 64,
    strategies=("unweighted", "degree"),
    prefix: str = "edges",
) -> List[SweepCell]:
    """Edge-list mix smoke grid (the ``benchmarks/sweep.py edges``
    preset): strategies × hub-OOD placement on per-seed BA graphs at a
    node count (default 64) where the dense (n, n) coefficient slab is
    already the wrong representation — run with
    ``run_sweep_cells(..., mix_impl="edges")``."""
    from repro.core.topology import barabasi_albert

    cells = []
    for ds in datasets:
        for seed in seeds:
            topo = barabasi_albert(n_nodes, 2, seed=seed)
            for strat in strategies:
                cells.append(SweepCell(
                    ds, topo, strat, ood_k=1, seed=seed,
                    name=f"{prefix}/{ds}/{strat}/n{n_nodes}",
                    sweep=("edges", strat, n_nodes)))
    return cells


def participation_cells(
    datasets=("mnist",),
    seeds=(0,),
    n_nodes: int = 16,
    strategy: str = "degree",
    rates=(1.0, 0.7, 0.4),
    prefix: str = "participation",
) -> List[SweepCell]:
    """Partial-participation grid (the ``benchmarks/sweep.py
    participation`` preset): activation rate × topology (ring vs per-seed
    BA) × OOD placement (hub ``ood_k=1`` vs periphery ``ood_k=n``), run
    with ``run_sweep_cells(..., participation=ParticipationSpec())``.
    Rate 1.0 rides along as the synchronous control — bit-identical to a
    no-participation run — so every row's staleness × arrival digest has
    an in-grid baseline."""
    from repro.core.topology import barabasi_albert, ring

    cells = []
    for ds in datasets:
        for seed in seeds:
            topos = (ring(n_nodes), barabasi_albert(n_nodes, 2, seed=seed))
            for topo in topos:
                for place, k in (("hub", 1), ("leaf", n_nodes)):
                    for rate in rates:
                        cells.append(SweepCell(
                            ds, topo, strategy, ood_k=k, seed=seed,
                            participation=rate,
                            name=(f"{prefix}/{ds}/{topo.name}/{place}"
                                  f"/r{rate}"),
                            sweep=("participation", topo.name, place, rate)))
    return cells


def byzantine_cells(
    datasets=("mnist",),
    seeds=(0,),
    n_nodes: int = 16,
    strategy: str = "degree",
    rates=(0.0, 0.1, 0.3),
    robusts=("mean", "trimmed", "median"),
    prefix: str = "byzantine",
) -> List[SweepCell]:
    """Byzantine-fault grid (the ``benchmarks/sweep.py byzantine``
    preset): fault rate × topology (ring vs per-seed BA) × OOD placement
    (hub vs periphery) × aggregation rule, run with
    ``run_sweep_cells(..., fault=FaultSpec(...))``.  Rate 0.0 rides
    along as the fault-free control — bit-identical to the synchronous
    round under ``robust="mean"`` — and every (topology, placement,
    rate) cell appears under each aggregator so the robust-vs-mean
    recovery gap is read off within one artifact."""
    from repro.core.topology import barabasi_albert, ring

    cells = []
    for ds in datasets:
        for seed in seeds:
            topos = (ring(n_nodes), barabasi_albert(n_nodes, 2, seed=seed))
            for topo in topos:
                for place, k in (("hub", 1), ("leaf", n_nodes)):
                    for rate in rates:
                        for robust in robusts:
                            cells.append(SweepCell(
                                ds, topo, strategy, ood_k=k, seed=seed,
                                fault_rate=rate, robust=robust,
                                name=(f"{prefix}/{ds}/{topo.name}/{place}"
                                      f"/f{rate}/{robust}"),
                                sweep=("byzantine", topo.name, place,
                                       rate, robust)))
    return cells


def group_cells(
        cells: List[SweepCell]) -> Dict[Tuple[str, int, str], List[int]]:
    """Cells sharing one compiled program: same dataset (model + sample
    shapes), same node count (topology/coeffs shapes), and same robust
    aggregation rule (static mix-fn configuration)."""
    groups: Dict[Tuple[str, int, str], List[int]] = {}
    for i, cell in enumerate(cells):
        groups.setdefault(
            (cell.dataset, cell.topo.n_nodes, cell.robust), []).append(i)
    return groups


def _replicate_inits(inits: List, n_nodes: int):
    """(E, n, ...) initial params — each experiment's one-node init on
    all of its n nodes — built on the device in one program, so no
    per-experiment stacked copy outlives it (2 GB each for VGG-16 at
    n=33)."""
    return jax.jit(lambda xs: jax.tree.map(
        lambda *leaves: jnp.stack([jnp.broadcast_to(x, (n_nodes,) + x.shape)
                                   for x in leaves]), *xs))(inits)


def _experiment_devices(leaf, e: int) -> List[int]:
    """Ids of the devices holding experiment ``e``'s rows of an
    (E, ...) result array (one device, or several under a mesh)."""
    ids = set()
    for shard in leaf.addressable_shards:
        rows = shard.index[0]
        lo = rows.start or 0
        hi = leaf.shape[0] if rows.stop is None else rows.stop
        if lo <= e < hi:
            ids.add(shard.device.id)
    return sorted(ids)


def _pad_cap(leaves: Dict[str, np.ndarray], cap: int) -> Dict[str, np.ndarray]:
    return {
        k: np.pad(v, [(0, 0), (0, cap - v.shape[1])] + [(0, 0)] * (v.ndim - 2))
        for k, v in leaves.items()
    }


def run_sweep_cells(
    cells: List[SweepCell],
    scale: BenchScale = QUICK,
    alpha_l: float = 1000.0,
    alpha_s: float = 1000.0,
    unroll_eval: bool = False,
    mesh=None,
    chunk_rounds: Optional[int] = None,
    coeff_mode: str = "stack",
    mix_impl: str = "einsum",
    analytics: bool = True,
    arrival_threshold: float = DEFAULT_ARRIVAL_THRESHOLD,
    participation: Optional[ParticipationSpec] = None,
    fault: Optional[FaultSpec] = None,
    log=None,
) -> List[Dict]:
    """Evaluate a whole grid of cells through the sweep engine.

    One compiled program per (dataset, n_nodes) group: experiments that
    share a data configuration (seed × OOD placement) share a sample-bank
    row; per-experiment initial params, mixing-matrix stacks, and test
    batches ride the vmap axis.  Returns one ``run_experiment``-compatible
    summary dict per cell (in input order) with ``secs`` amortized over the
    group.  Host spans (``jax.profiler.TraceAnnotation``) mark each group's
    ``repro.sweep.prep`` (data split, batchers, test sets, bank, index
    schedule, coefficients, inits) and ``repro.sweep.summarize`` (the
    per-experiment summaries) around the engine's own spans.

    ``mesh`` (``repro.launch.mesh.make_sweep_mesh``) shards each group's
    experiment axis across devices; ``chunk_rounds`` scans the round
    schedule in bounded chunks — both bit-identical to the default path.

    ``coeff_mode`` picks the coefficient representation (DESIGN.md §9):
    ``"stack"`` materializes each cell's ``(R, n, n)`` slab host-side
    (link-failure cells materialize their program);  ``"program"`` ships
    only the compact per-experiment program state and generates matrices
    in-scan — required memory-wise for long reactive sweeps, bit-identical
    to the stack otherwise.

    ``mix_impl`` routes each group's aggregation through the chosen
    backend (``decentralized.make_mix_fn``): ``"edges"``/``"sparse"``
    build the group's ``mix_support`` as the union of its cells'
    neighbourhood masks (adjacency + self loops) so one static schedule
    serves every experiment in the compiled program.

    ``analytics=True`` (default) threads the streaming accumulators
    through the scan (DESIGN.md §10): each row gains an ``"analytics"``
    sub-dict with the in-scan AUCs, arrival-round stats (hop-binned
    against the cell's OOD source set at ``arrival_threshold``), and the
    max per-node deviation from the host-side ``propagation.py`` oracle
    (``stream_vs_host_max_dev`` — the equivalence the golden suite locks).

    ``participation`` (a :class:`ParticipationSpec`) switches the group
    onto the partial-participation round (DESIGN.md §15): each cell's
    ``participation`` rate rides the vmap axis (cells without one run at
    1.0, bit-identical to the synchronous round), and each row gains a
    ``"participation"`` digest (:func:`participation_summary`) — realized
    activity, staleness statistics, and the staleness × arrival-round
    interaction when analytics are on.  Cells that set a rate without a
    spec get the default ``ParticipationSpec()``.

    ``fault`` (a :class:`FaultSpec`) switches each group onto the
    Byzantine-fault round (DESIGN.md §16): each cell's ``fault_rate``
    rides the vmap axis (cells without one run at 0.0, bit-identical to
    the fault-free round), each cell's ``robust`` rule picks its
    compiled group's aggregator, and each row gains a ``"fault"`` digest
    (:func:`quarantine_summary`) — realized corruption, detection lag,
    quarantine occupancy.  Cells that set a rate without a spec get the
    default ``FaultSpec()``.
    """
    if coeff_mode not in ("stack", "program"):
        raise KeyError(f"coeff_mode {coeff_mode!r}; have 'stack', 'program'")
    if participation is None and any(c.participation is not None
                                     for c in cells):
        participation = ParticipationSpec()
    if fault is None and any(c.fault_rate is not None for c in cells):
        fault = FaultSpec()
    spec = (AnalyticsSpec(arrival_threshold=arrival_threshold)
            if analytics else None)
    rows: List[Optional[Dict]] = [None] * len(cells)
    for (ds, n_nodes, robust), idxs in group_cells(cells).items():
        t0 = time.time()
        with TraceAnnotation("repro.sweep.prep"):
            init, loss_fn, acc_fn, opt = _model_fns(ds, scale, cells[idxs[0]].seed)
            mix_support = None
            if mix_impl != "einsum" or robust in ("trimmed", "median"):
                # one static schedule per compiled program: the union of every
                # cell's neighbourhood mask (adjacency + self loops).  The
                # order-statistic aggregators need it even on the einsum impl
                # — their padded-ELL tables are static engine configuration.
                mix_support = np.eye(n_nodes)
                for i in idxs:
                    mix_support = np.maximum(
                        mix_support, np.asarray(cells[i].topo.adjacency))
            engine = SweepEngine(
                opt, loss_fn, acc_fn,
                DecentralizedConfig(rounds=scale.rounds,
                                    local_epochs=scale.local_epochs,
                                    eval_every=scale.eval_every,
                                    mix_impl=mix_impl, robust=robust),
                mix_support=mix_support)

            # distinct data configurations (seed × OOD node) → bank rows.
            # Synchronous sweep rounds need ONE step count across the group:
            # with steps_per_epoch=0 each NodeBatcher would derive its own from
            # its median node size, so the first batcher's derivation is pinned
            # for the rest (index schedules must stack to a common S).
            dconf: Dict[Tuple[int, Tuple[int, ...]], int] = {}
            batchers, tbs, obs = [], [], []
            group_steps = scale.steps_per_epoch
            for i in idxs:
                cell = cells[i]
                ood_nodes = cell.ood_nodes()
                key = (cell.seed, ood_nodes)
                if key not in dconf:
                    train, test = _data(ds, scale.n_train, scale.n_test, cell.seed)
                    parts = node_datasets(train, n_nodes, ood_node=ood_nodes,
                                          q=0.10, seed=cell.seed,
                                          alpha_l=alpha_l, alpha_s=alpha_s)
                    nb = NodeBatcher(parts, batch_size=scale.batch,
                                     steps_per_epoch=group_steps,
                                     seed=cell.seed,
                                     local_epochs=scale.local_epochs)
                    group_steps = nb.steps
                    dconf[key] = len(batchers)
                    batchers.append(nb)
                    tbs.append(make_test_batch(test, scale.eval_n, seed=cell.seed))
                    obs.append(make_test_batch(
                        backdoored_testset(test, seed=cell.seed), scale.eval_n,
                        seed=cell.seed, ood_mask=(test.kind == "lm")))

            # D-stacked bank + index schedules (pad node caps to the group max)
            raw_banks = [nb.sample_bank() for nb in batchers]
            cap = max(b[next(iter(b))].shape[1] for b in raw_banks)
            padded = [_pad_cap(b, cap) for b in raw_banks]
            bank = {k: np.stack([p[k] for p in padded]) for k in raw_banks[0]}
            indices = np.stack(
                [nb.all_round_indices(scale.rounds) for nb in batchers])

            # per-experiment axes.  Every program-supported cell (incl. all
            # link-failure / reactive cells) goes through its coefficient
            # program — materialized to a slab in "stack" mode, shipped as
            # compact state in "program" mode; both consume identical values.
            reactives = {cells[i].reactive for i in idxs}
            if coeff_mode == "program" and len(reactives) > 1:
                raise ValueError(
                    "cells compiled into one program-mode sweep group must "
                    "share the `reactive` flag (it is static program "
                    "configuration); stack mode materializes per-cell "
                    "programs and supports mixed grids")
            data_idx, coeffs, states, p0s, t_iid, t_ood, metas = (
                [], [], [], [], [], [], [])
            program = None
            init_cache: Dict[int, object] = {}
            for i in idxs:
                cell = cells[i]
                ood_nodes = cell.ood_nodes()
                d = dconf[(cell.seed, ood_nodes)]
                data_idx.append(d)
                strategy = AggregationStrategy(cell.strategy, tau=cell.tau,
                                               seed=cell.seed)
                if cell.strategy in PROGRAM_KINDS:
                    program, state = program_for(
                        cell.topo, strategy,
                        data_counts=batchers[d].data_counts(),
                        p_fail=cell.p_fail, reactive=cell.reactive)
                    if coeff_mode == "program":
                        states.append(state)
                    else:
                        coeffs.append(program.materialize(state, scale.rounds))
                else:
                    if coeff_mode == "program" or cell.p_fail or cell.reactive:
                        raise ValueError(
                            f"strategy {cell.strategy!r} has no coefficient "
                            f"program (coeff_mode='program' / link-failure "
                            f"cells need one); use coeff_mode='stack'")
                    coeffs.append(coeffs_stack(
                        cell.topo, strategy, scale.rounds,
                        data_counts=batchers[d].data_counts()))
                if cell.seed not in init_cache:
                    init_cache[cell.seed] = init(jax.random.key(cell.seed))
                p0s.append(init_cache[cell.seed])
                t_iid.append(tbs[d])
                t_ood.append(obs[d])
                metas.append((cell, ood_nodes))

            if coeff_mode == "program":
                # one shared program serves the whole group, so prune its
                # lax.switch to the UNION of the group's strategy kinds (and
                # drop the per-round edge mask when no cell churns links):
                # under vmap-over-E the batched switch computes every traced
                # branch — for reactive programs the unused 200-iteration
                # power-method branches were the measured ~1.8× overhead
                # (BENCH_sweep.json `coeff_programs`).  Bit-identical for the
                # kinds that remain.
                program = dataclasses.replace(
                    program,
                    kinds=tuple(sorted({PROGRAM_KINDS.index(cells[i].strategy)
                                        for i in idxs})),
                    link_failure=any(cells[i].p_fail > 0 for i in idxs))
                engine_coeffs = ProgramCoeffs(program, stack_states(states))
            else:
                engine_coeffs = np.stack(coeffs)
            params0 = _replicate_inits(p0s, n_nodes)
            del p0s
            stack_tests = lambda ts: {
                k: jnp.stack([jnp.asarray(t[k]) for t in ts]) for k in ts[0]}
            part_kwargs = {}
            if participation is not None:
                part_kwargs = dict(
                    participation=participation,
                    participation_rates=np.asarray(
                        [1.0 if cells[i].participation is None
                         else cells[i].participation for i in idxs], np.float32))
            if fault is not None:
                part_kwargs.update(
                    fault=fault,
                    fault_rates=np.asarray(
                        [0.0 if cells[i].fault_rate is None
                         else cells[i].fault_rate for i in idxs], np.float32))
            test_iid, test_ood = stack_tests(t_iid), stack_tests(t_ood)
        result = engine.run(
            params0, engine_coeffs, bank, indices,
            np.asarray(data_idx), test_iid, test_ood,
            batch_size=scale.batch, unroll_eval=unroll_eval,
            mesh=mesh, chunk_rounds=chunk_rounds, analytics=spec,
            donate_params0=True, **part_kwargs)

        secs = time.time() - t0
        with TraceAnnotation("repro.sweep.summarize"):
            for e, (i, (cell, ood_nodes)) in enumerate(zip(idxs, metas)):
                hist = result.history(e)
                summary = propagation_summary(
                    hist, cell.topo.adjacency, ood_nodes,
                    arrival_threshold=arrival_threshold)
                # per-node metrics of every evaluated round, and the devices
                # that held this experiment's trained params
                summary["per_node"] = [
                    {"round": int(m.round),
                     **{k: np.asarray(getattr(m, k)).tolist()
                        for k in ("train_loss", "iid_acc", "ood_acc")}}
                    for m in hist]
                summary["param_devices"] = _experiment_devices(
                    jax.tree.leaves(result.params)[0], e)
                summary.update(
                    dataset=ds, topology=cell.topo.name, strategy=cell.strategy,
                    ood_k=cell.ood_k,
                    ood_node=(ood_nodes[0] if len(ood_nodes) == 1
                              else list(ood_nodes)),
                    seed=cell.seed,
                    secs=round(secs / len(idxs), 2),
                )
                if cell.ood_ks:
                    summary["ood_ks"] = list(cell.ood_ks)
                if result.analytics is not None:
                    stream = {k: v[e] for k, v in result.analytics.items()}
                    a = analytics_summary(stream, cell.topo.adjacency,
                                          ood_nodes)
                    a["stream_vs_host_max_dev"] = float(max(
                        np.abs(stream["iid_auc"]
                               - per_node_auc(hist, "iid")).max(),
                        np.abs(stream["ood_auc"]
                               - per_node_auc(hist, "ood")).max()))
                    summary["analytics"] = a
                if result.participation is not None:
                    part_row = {k: v[e]
                                for k, v in result.participation.items()}
                    part_stream = (
                        {k: v[e] for k, v in result.analytics.items()}
                        if result.analytics is not None else None)
                    summary["participation_rate"] = (
                        1.0 if cell.participation is None
                        else cell.participation)
                    summary["participation"] = participation_summary(
                        part_row, scale.rounds, part_stream)
                if result.fault is not None:
                    summary["fault_rate"] = (0.0 if cell.fault_rate is None
                                             else cell.fault_rate)
                    summary["robust"] = cell.robust
                    summary["fault"] = quarantine_summary(
                        {k: v[e] for k, v in result.fault.items()},
                        scale.rounds)
                if cell.p_fail or cell.reactive:
                    summary.update(p_fail=cell.p_fail, reactive=cell.reactive)
                if cell.sweep is not None:
                    summary["sweep"] = cell.sweep
                rows[i] = summary
                if log is not None:
                    log(csv_row(
                        cell.label, summary["secs"],
                        f"iid_auc={summary['iid_auc']:.3f};"
                        f"ood_auc={summary['ood_auc']:.3f}"))
    return rows  # type: ignore[return-value]
