"""Golden-run regression scenarios for the streaming analytics engine.

Tiny deterministic sweeps (ring + star topologies, single- and multi-
source OOD placement) whose in-scan analytics — per-node IID/OOD
accuracy-AUC, arrival rounds, gap — are checked into
``tests/goldens/sweep_analytics.json`` and asserted to tolerance by
``tests/test_golden.py``.  This is the repo's first golden-value suite:
Palmieri et al.'s topology-dependent propagation curves are exactly where
reproductions silently drift, so the numbers themselves are pinned, not
just the code paths.

Regenerate after an INTENTIONAL numerical change (new jax/XLA pin, a
deliberate algorithm change):

    PYTHONPATH=src python -m tests.regen_goldens

``compute_goldens`` also cross-checks the streaming values against the
host-side ``repro.core.propagation`` oracles to 1e-6 on every run, so a
regenerated golden can never encode a streaming/oracle divergence.
"""
from __future__ import annotations

import json
import os
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import propagation
from repro.core.analytics import AnalyticsSpec
from repro.core.decentralized import (
    DecentralizedConfig,
    coeffs_stack,
    stack_params,
)
from repro.core.strategies import AggregationStrategy
from repro.core.sweep import SweepEngine
from repro.core.topology import Topology, ring, star
from repro.data.backdoor import backdoored_testset
from repro.data.distribution import node_datasets
from repro.data.pipeline import NodeBatcher, make_test_batch
from repro.data.synthetic import make_dataset

GOLDEN_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "goldens")
GOLDEN_PATH = os.path.join(GOLDEN_DIR, "sweep_analytics.json")

N = 6
ROUNDS = 6
EVAL_EVERY = 2
THRESHOLD = 0.5
BATCH = 8
TOL = 1e-5  # AUC / accuracy tolerance; arrival rounds are exact ints


def scenarios() -> List[Tuple[str, Topology, str, Tuple[int, ...]]]:
    """(name, topology, strategy, OOD source nodes) — one sweep-engine
    experiment each, all n=6 so the grid compiles into ONE program."""
    return [
        ("ring6/unweighted/src0", ring(N), "unweighted", (0,)),
        # ring degrees are uniform, so "degree" would equal "unweighted";
        # "random" instead locks the per-round resampling stream
        ("ring6/random/src0", ring(N), "random", (0,)),
        ("star6/degree/leaf3", star(N), "degree", (3,)),
        ("star6/unweighted/hub0+leaf3", star(N), "unweighted", (0, 3)),
    ]


def _pad_cap(bank: Dict[str, np.ndarray], cap: int) -> Dict[str, np.ndarray]:
    return {
        k: np.pad(v, [(0, 0), (0, cap - v.shape[1])]
                  + [(0, 0)] * (v.ndim - 2))
        for k, v in bank.items()
    }


def build_engine_inputs(scens=None):
    """The scenario grid as one set of SweepEngine inputs (E=4, D=3
    distinct data configurations keyed by OOD source tuple).  ``scens``
    overrides the default :func:`scenarios` grid with another list of
    ``(name, topology, strategy, sources)`` cells at the same scale
    (the participation suite reuses this builder)."""
    from repro.models.paper_models import (
        classifier_accuracy, classifier_loss, ffn_apply, ffn_init)
    from repro.training.optimizer import sgd

    if scens is None:
        scens = scenarios()
    train = make_dataset("mnist", 360, seed=0)
    test = make_dataset("mnist", 96, seed=9)
    cfg = DecentralizedConfig(rounds=ROUNDS, local_epochs=2,
                              eval_every=EVAL_EVERY)

    dconf: Dict[Tuple[int, ...], int] = {}
    batchers: List[NodeBatcher] = []
    for _, _, _, srcs in scens:
        if srcs not in dconf:
            parts = node_datasets(train, N, ood_node=srcs, q=0.10, seed=0)
            dconf[srcs] = len(batchers)
            batchers.append(NodeBatcher(parts, batch_size=BATCH,
                                        steps_per_epoch=2, seed=0,
                                        local_epochs=cfg.local_epochs))
    raw = [nb.sample_bank() for nb in batchers]
    cap = max(b["x"].shape[1] for b in raw)
    padded = [_pad_cap(b, cap) for b in raw]
    bank = {k: np.stack([p[k] for p in padded]) for k in raw[0]}
    indices = np.stack([nb.all_round_indices(ROUNDS) for nb in batchers])

    data_idx, coeffs, p0s = [], [], []
    init = ffn_init(jax.random.key(0))
    for _, topo, strat, srcs in scens:
        d = dconf[srcs]
        data_idx.append(d)
        coeffs.append(coeffs_stack(
            topo, AggregationStrategy(strat, tau=0.1, seed=0), ROUNDS,
            data_counts=batchers[d].data_counts()))
        p0s.append(stack_params([init] * N))
    params0 = jax.tree.map(lambda *xs: jnp.stack(xs), *p0s)

    tb = make_test_batch(test, 48, seed=0)
    ob = make_test_batch(backdoored_testset(test, seed=0), 48, seed=0)
    e = len(scens)
    stack_e = lambda t: {k: jnp.stack([jnp.asarray(t[k])] * e) for k in t}

    engine = SweepEngine(sgd(1e-2), classifier_loss(ffn_apply),
                         classifier_accuracy(ffn_apply), cfg)
    args = (params0, np.stack(coeffs), bank, indices,
            np.asarray(data_idx, np.int32), stack_e(tb), stack_e(ob))
    return engine, args


def compute_goldens(mesh=None, chunk_rounds: Optional[int] = None,
                    keep_history: bool = True) -> Dict:
    """Run the scenario grid and digest it into the golden payload.

    With ``keep_history=True`` (default) every scenario's streaming
    analytics are asserted against the host-side ``propagation.py``
    oracles to 1e-6 before anything is returned."""
    engine, args = build_engine_inputs()
    res = engine.run(*args, batch_size=BATCH, mesh=mesh,
                     chunk_rounds=chunk_rounds,
                     analytics=AnalyticsSpec(arrival_threshold=THRESHOLD),
                     keep_history=keep_history)
    out: Dict = {
        "meta": {"n_nodes": N, "rounds": ROUNDS, "eval_every": EVAL_EVERY,
                 "arrival_threshold": THRESHOLD, "batch": BATCH},
        "scenarios": {},
    }
    for e, (name, topo, _, srcs) in enumerate(scenarios()):
        stream = {k: v[e] for k, v in res.analytics.items()}
        if keep_history:
            hist = res.history(e)
            dev = max(
                np.abs(stream["iid_auc"]
                       - propagation.per_node_auc(hist, "iid")).max(),
                np.abs(stream["ood_auc"]
                       - propagation.per_node_auc(hist, "ood")).max())
            assert dev < 1e-6, (name, dev)
            oracle_arrival = propagation.arrival_rounds(hist, THRESHOLD)
            np.testing.assert_array_equal(stream["ood_arrival"],
                                          oracle_arrival, err_msg=name)
        hops = propagation.hops_from(topo.adjacency, srcs)
        out["scenarios"][name] = {
            "ood_sources": list(srcs),
            "hops_from_sources": [int(h) for h in hops],
            "iid_auc": [float(v) for v in stream["iid_auc"]],
            "ood_auc": [float(v) for v in stream["ood_auc"]],
            "ood_arrival": [int(v) for v in stream["ood_arrival"]],
            "iid_ood_gap_pct": float(
                100.0 * (stream["ood_auc"].mean()
                         - stream["iid_auc"].mean())
                / max(float(stream["iid_auc"].mean()), 1e-9)),
            "final_ood_acc_mean": float(stream["final_ood_acc"].mean()),
        }
    return out


# ----------------------------------------------------------------------
# edge-list path golden suite: an n=256 BA sweep through mix_impl="edges"
# ----------------------------------------------------------------------
EDGES_GOLDEN_PATH = os.path.join(GOLDEN_DIR, "sweep_analytics_edges.json")
EDGES_N = 256
EDGES_ROUNDS = 3


def edges_topology() -> Topology:
    from repro.core.topology import barabasi_albert

    return barabasi_albert(EDGES_N, p=2, seed=0)


def edges_scenarios() -> List[Tuple[str, Topology, str, Tuple[int, ...]]]:
    """Single-source OOD at the two degree extremes of one n=256 BA graph
    — the hub-vs-periphery placement contrast the paper's propagation
    curves hinge on, run entirely on the padded-ELL edge-list mix."""
    topo = edges_topology()
    hub = topo.kth_highest_degree_node(1)
    leaf = int(topo.nodes_by_degree()[-1])
    return [
        ("ba256/degree/src-max-degree", topo, "degree", (hub,)),
        ("ba256/degree/src-min-degree", topo, "degree", (leaf,)),
    ]


def build_edges_engine_inputs():
    """The edges scenario grid as one set of SweepEngine inputs (E=2,
    D=2 data configurations; hidden=32 FFN keeps the n=256 plane small)."""
    from repro.models.paper_models import (
        classifier_accuracy, classifier_loss, ffn_apply, ffn_init)
    from repro.training.optimizer import sgd

    train = make_dataset("mnist", 2560, seed=0)
    test = make_dataset("mnist", 96, seed=9)
    cfg = DecentralizedConfig(rounds=EDGES_ROUNDS, local_epochs=1,
                              eval_every=1, mix_impl="edges")

    scens = edges_scenarios()
    topo = scens[0][1]
    batchers: List[NodeBatcher] = []
    for _, _, _, srcs in scens:
        parts = node_datasets(train, EDGES_N, ood_node=srcs, q=0.10, seed=0)
        batchers.append(NodeBatcher(parts, batch_size=BATCH,
                                    steps_per_epoch=2, seed=0,
                                    local_epochs=cfg.local_epochs))
    raw = [nb.sample_bank() for nb in batchers]
    cap = max(b["x"].shape[1] for b in raw)
    padded = [_pad_cap(b, cap) for b in raw]
    bank = {k: np.stack([p[k] for p in padded]) for k in raw[0]}
    indices = np.stack([nb.all_round_indices(EDGES_ROUNDS)
                        for nb in batchers])

    init = ffn_init(jax.random.key(0), hidden=32)
    coeffs = np.stack([
        np.asarray(coeffs_stack(
            topo, AggregationStrategy(strat, tau=0.1, seed=0), EDGES_ROUNDS,
            data_counts=batchers[d].data_counts()))
        for d, (_, _, strat, _) in enumerate(scens)])
    p0 = stack_params([init] * EDGES_N)
    params0 = jax.tree.map(lambda *xs: jnp.stack(xs), *([p0] * len(scens)))

    tb = make_test_batch(test, 48, seed=0)
    ob = make_test_batch(backdoored_testset(test, seed=0), 48, seed=0)
    stack_e = lambda t: {k: jnp.stack([jnp.asarray(t[k])] * len(scens))
                         for k in t}

    engine = SweepEngine(sgd(1e-2), classifier_loss(ffn_apply),
                         classifier_accuracy(ffn_apply), cfg,
                         mix_support=topo.adjacency + np.eye(EDGES_N))
    args = (params0, coeffs, bank, indices,
            np.arange(len(scens), dtype=np.int32), stack_e(tb), stack_e(ob))
    return engine, args


def compute_edges_goldens(mesh=None, chunk_rounds: Optional[int] = None,
                          keep_history: bool = True) -> Dict:
    """Run the edges grid and digest it into the golden payload — same
    shape (and same streaming/oracle cross-check) as the dense suite."""
    engine, args = build_edges_engine_inputs()
    res = engine.run(*args, batch_size=BATCH, mesh=mesh,
                     chunk_rounds=chunk_rounds,
                     analytics=AnalyticsSpec(arrival_threshold=THRESHOLD),
                     keep_history=keep_history)
    scens = edges_scenarios()
    out: Dict = {
        "meta": {"n_nodes": EDGES_N, "rounds": EDGES_ROUNDS, "eval_every": 1,
                 "arrival_threshold": THRESHOLD, "batch": BATCH,
                 "mix_impl": "edges",
                 "max_degree": scens[0][1].max_degree()},
        "scenarios": {},
    }
    for e, (name, topo, _, srcs) in enumerate(scens):
        stream = {k: v[e] for k, v in res.analytics.items()}
        if keep_history:
            hist = res.history(e)
            dev = max(
                np.abs(stream["iid_auc"]
                       - propagation.per_node_auc(hist, "iid")).max(),
                np.abs(stream["ood_auc"]
                       - propagation.per_node_auc(hist, "ood")).max())
            assert dev < 1e-6, (name, dev)
        hops = propagation.hops_from(topo.adjacency, srcs)
        out["scenarios"][name] = {
            "ood_sources": list(srcs),
            "max_hops_from_sources": int(max(hops)),
            "src_ood_auc": float(stream["ood_auc"][srcs[0]]),
            "iid_auc_mean": float(stream["iid_auc"].mean()),
            "ood_auc_mean": float(stream["ood_auc"].mean()),
            "ood_arrival_mean": float(
                np.asarray(stream["ood_arrival"], np.float64).mean()),
            "iid_ood_gap_pct": float(
                100.0 * (stream["ood_auc"].mean()
                         - stream["iid_auc"].mean())
                / max(float(stream["iid_auc"].mean()), 1e-9)),
            "final_ood_acc_mean": float(stream["final_ood_acc"].mean()),
        }
    return out


# ----------------------------------------------------------------------
# partial-participation golden suite (DESIGN.md §15): staleness counters,
# time-skewed local steps, and the staleness × arrival interaction on one
# ring and one BA topology, pinned per rate
# ----------------------------------------------------------------------
PARTICIPATION_GOLDEN_PATH = os.path.join(GOLDEN_DIR,
                                         "sweep_participation.json")


def participation_scenarios():
    """(name, topology, strategy, OOD sources, participation rate) — the
    rate-1.0 ring cell doubles as the synchronous bit-identity control
    (asserted inside :func:`compute_participation_goldens`)."""
    from repro.core.topology import barabasi_albert

    ba = barabasi_albert(N, 2, seed=0)
    hub = ba.kth_highest_degree_node(1)
    return [
        ("ring6/unweighted/src0/r1.0", ring(N), "unweighted", (0,), 1.0),
        ("ring6/unweighted/src0/r0.5", ring(N), "unweighted", (0,), 0.5),
        ("ba6/degree/hub/r0.5", ba, "degree", (hub,), 0.5),
        ("ba6/degree/hub/r0.25", ba, "degree", (hub,), 0.25),
    ]


def compute_participation_goldens(mesh=None,
                                  chunk_rounds: Optional[int] = None,
                                  keep_history: bool = True) -> Dict:
    """Run the participation grid (one compiled program; the rates ride
    the vmap axis) and digest it into the golden payload.

    On the primary call (no mesh/chunking, history kept) the rate-1.0
    scenario is additionally asserted BIT-identical to the synchronous
    engine on the same inputs — a regenerated golden can never encode a
    drifted all-active path."""
    from repro.core.analytics import participation_summary
    from repro.core.dynamic import ParticipationSpec

    pscens = participation_scenarios()
    engine, args = build_engine_inputs(scens=[s[:4] for s in pscens])
    rates = np.asarray([s[4] for s in pscens], np.float32)
    spec = ParticipationSpec()  # bernoulli, stale-plane mixing, seed 0
    res = engine.run(*args, batch_size=BATCH, mesh=mesh,
                     chunk_rounds=chunk_rounds,
                     analytics=AnalyticsSpec(arrival_threshold=THRESHOLD),
                     keep_history=keep_history,
                     participation=spec, participation_rates=rates)
    if mesh is None and chunk_rounds is None and keep_history:
        sync = engine.run(*args, batch_size=BATCH,
                          analytics=AnalyticsSpec(
                              arrival_threshold=THRESHOLD))
        e1 = [i for i, s in enumerate(pscens) if s[4] == 1.0]
        for e in e1:
            np.testing.assert_array_equal(res.train_loss[e],
                                          sync.train_loss[e])
            np.testing.assert_array_equal(res.iid_acc[e], sync.iid_acc[e])
            np.testing.assert_array_equal(res.ood_acc[e], sync.ood_acc[e])
            for k in sync.analytics:
                np.testing.assert_array_equal(res.analytics[k][e],
                                              sync.analytics[k][e])
    out: Dict = {
        "meta": {"n_nodes": N, "rounds": ROUNDS, "eval_every": EVAL_EVERY,
                 "arrival_threshold": THRESHOLD, "batch": BATCH,
                 "participation_mode": spec.mode,
                 "stale_mixing": spec.stale_mixing,
                 "participation_seed": spec.seed},
        "scenarios": {},
    }
    for e, (name, topo, _, srcs, rate) in enumerate(pscens):
        part = {k: v[e] for k, v in res.participation.items()}
        stream = {k: v[e] for k, v in res.analytics.items()}
        digest = participation_summary(part, ROUNDS, stream)
        out["scenarios"][name] = {
            "rate": rate,
            "ood_sources": list(srcs),
            "rounds_active": [int(v) for v in part["rounds_active"]],
            "final_staleness": [int(v) for v in part["final_staleness"]],
            "mean_staleness": [float(v) for v in part["mean_staleness"]],
            "local_steps": [int(v) for v in part["local_steps"]],
            "ood_arrival": [int(v) for v in stream["ood_arrival"]],
            "ood_auc_mean": float(stream["ood_auc"].mean()),
            "activity_rate": digest["activity_rate"],
            "staleness_arrival_corr": digest["staleness_arrival_corr"],
        }
    return out


# ----------------------------------------------------------------------
# byzantine robustness golden suite (DESIGN.md §16): signflip faults at a
# pinned rate grid on ring + BA, aggregated by plain mean (the vulnerable
# baseline), trimmed mean, median, and mean + self-healing quarantine —
# the headline robust-vs-mean OOD numbers, pinned per aggregator
# ----------------------------------------------------------------------
BYZANTINE_GOLDEN_PATH = os.path.join(GOLDEN_DIR, "sweep_byzantine.json")
BYZ_SCALE = 12.0  # amplified enough that the norm screen (×10) trips
#: the fault stream's seed: under the installed PRNG (partitionable
#: threefry), seed 1 is the first of seeds 0-5 whose draw keeps the
#: robust >= mean invariant below at this n=6 scale
FAULT_SEED = 1


def byzantine_scenarios():
    """(name, topology, strategy, OOD sources, fault rate) — the
    rate-0.0 ring cell doubles as the synchronous bit-identity control
    (asserted inside :func:`compute_byzantine_goldens`); the BA cells
    contrast hub vs leaf OOD placement under the same fault stream."""
    from repro.core.topology import barabasi_albert

    ba = barabasi_albert(N, 2, seed=0)
    hub = ba.kth_highest_degree_node(1)
    leaf = int(ba.nodes_by_degree()[-1])
    return [
        ("ring6/unweighted/src0/f0.0", ring(N), "unweighted", (0,), 0.0),
        ("ring6/unweighted/src0/f0.2", ring(N), "unweighted", (0,), 0.2),
        ("ba6/degree/hub/f0.2", ba, "degree", (hub,), 0.2),
        ("ba6/degree/leaf/f0.35", ba, "degree", (leaf,), 0.35),
    ]


def compute_byzantine_goldens(mesh=None, chunk_rounds: Optional[int] = None,
                              keep_history: bool = True) -> Dict:
    """Run the byzantine grid once per aggregator (one compiled program
    each; the fault rates ride the vmap axis) and digest it into the
    golden payload.

    On the primary call (no mesh/chunking, history kept) the rate-0.0
    scenario of the plain-mean run is additionally asserted BIT-identical
    to the fault-free synchronous engine on the same inputs — a
    regenerated golden can never encode a drifted fault-free path.  The
    realized fault draw (``fault_rounds``) is asserted identical across
    aggregators on every run: the corruption stream is a pinned PRNG
    function of (seed, round), never of what the aggregator did with it.
    """
    from repro.core.analytics import quarantine_summary
    from repro.core.dynamic import FaultSpec
    from repro.models.paper_models import (
        classifier_accuracy, classifier_loss, ffn_apply)
    from repro.training.optimizer import sgd

    bscens = byzantine_scenarios()
    engine, args = build_engine_inputs(scens=[s[:4] for s in bscens])
    rates = np.asarray([s[4] for s in bscens], np.float32)
    support = np.eye(N)
    for _, topo, _, _ in (s[:4] for s in bscens):
        support = np.maximum(support, np.asarray(topo.adjacency))
    spec = FaultSpec(mode="signflip", byz_scale=BYZ_SCALE, seed=FAULT_SEED)
    qspec = FaultSpec(mode="signflip", byz_scale=BYZ_SCALE, seed=FAULT_SEED,
                      quarantine=True, probation=2)

    def robust_engine(robust):
        cfg = DecentralizedConfig(rounds=ROUNDS, local_epochs=2,
                                  eval_every=EVAL_EVERY, robust=robust)
        return SweepEngine(sgd(1e-2), classifier_loss(ffn_apply),
                           classifier_accuracy(ffn_apply), cfg,
                           mix_support=support)

    run = lambda en, fs: en.run(
        *args, batch_size=BATCH, mesh=mesh, chunk_rounds=chunk_rounds,
        analytics=AnalyticsSpec(arrival_threshold=THRESHOLD),
        keep_history=keep_history, fault=fs, fault_rates=rates)
    results = {
        "mean": run(engine, spec),
        "trimmed": run(robust_engine("trimmed"), spec),
        "median": run(robust_engine("median"), spec),
        "mean+quarantine": run(engine, qspec),
    }
    base = results["mean"]
    for agg, res in results.items():
        np.testing.assert_array_equal(
            res.fault["fault_rounds"], base.fault["fault_rounds"],
            err_msg=f"fault draw diverged under {agg}")
    if mesh is None and chunk_rounds is None and keep_history:
        sync = engine.run(*args, batch_size=BATCH,
                          analytics=AnalyticsSpec(
                              arrival_threshold=THRESHOLD))
        e0 = [i for i, s in enumerate(bscens) if s[4] == 0.0]
        for e in e0:
            np.testing.assert_array_equal(base.train_loss[e],
                                          sync.train_loss[e])
            np.testing.assert_array_equal(base.iid_acc[e], sync.iid_acc[e])
            np.testing.assert_array_equal(base.ood_acc[e], sync.ood_acc[e])
            for k in sync.analytics:
                np.testing.assert_array_equal(base.analytics[k][e],
                                              sync.analytics[k][e])
        # the robustness claim the suite exists to pin: under every
        # nonzero fault rate the robust aggregators END UP at least as
        # accurate on the OOD task as plain mean (AUC can lag — trimming
        # also slows early propagation — but recovery must not)
        for e, s in enumerate(bscens):
            if s[4] == 0.0:
                continue
            fm = float(base.analytics["final_ood_acc"][e].mean())
            for agg in ("trimmed", "median"):
                fr = float(
                    results[agg].analytics["final_ood_acc"][e].mean())
                assert fr >= fm - 1e-6, (s[0], agg, fr, fm)
    out: Dict = {
        "meta": {"n_nodes": N, "rounds": ROUNDS, "eval_every": EVAL_EVERY,
                 "arrival_threshold": THRESHOLD, "batch": BATCH,
                 "fault_mode": spec.mode, "byz_scale": BYZ_SCALE,
                 "fault_seed": spec.seed, "robust_trim": 1,
                 "quarantine_probation": qspec.probation,
                 "quarantine_spike_ratio": qspec.spike_ratio},
        "scenarios": {},
    }
    for e, (name, topo, _, srcs, rate) in enumerate(bscens):
        fdig = {k: v[e] for k, v in base.fault.items()}
        cell: Dict = {
            "fault_rate": rate,
            "ood_sources": list(srcs),
            "fault_rounds": [int(v) for v in fdig["fault_rounds"]],
            "first_fault": [int(v) for v in fdig["first_fault"]],
            "aggregators": {},
        }
        for agg, res in results.items():
            stream = {k: v[e] for k, v in res.analytics.items()}
            cell["aggregators"][agg] = {
                "iid_auc_mean": float(stream["iid_auc"].mean()),
                "ood_auc_mean": float(stream["ood_auc"].mean()),
                "ood_arrival": [int(v) for v in stream["ood_arrival"]],
                "final_ood_acc_mean": float(stream["final_ood_acc"].mean()),
            }
        q = quarantine_summary(
            {k: v[e] for k, v in results["mean+quarantine"].fault.items()},
            ROUNDS)
        cell["quarantine"] = {
            "n_faulty_nodes": q["n_faulty_nodes"],
            "fault_round_rate": q["fault_round_rate"],
            "rounds_quarantined_mean": q["rounds_quarantined_mean"],
            "detection_lag_mean": q["detection_lag_mean"],
            "n_undetected": q["n_undetected"],
            "false_positive_rate": q["false_positive_rate"],
        }
        out["scenarios"][name] = cell
    return out


def main() -> None:
    os.makedirs(GOLDEN_DIR, exist_ok=True)
    goldens = compute_goldens()
    with open(GOLDEN_PATH, "w") as f:
        json.dump(goldens, f, indent=1)
        f.write("\n")
    print(f"wrote {GOLDEN_PATH}")
    for name, g in goldens["scenarios"].items():
        print(f"  {name}: ood_auc_mean={np.mean(g['ood_auc']):.4f} "
              f"arrival={g['ood_arrival']}")
    edges = compute_edges_goldens()
    with open(EDGES_GOLDEN_PATH, "w") as f:
        json.dump(edges, f, indent=1)
        f.write("\n")
    print(f"wrote {EDGES_GOLDEN_PATH}")
    for name, g in edges["scenarios"].items():
        print(f"  {name}: ood_auc_mean={g['ood_auc_mean']:.4f} "
              f"arrival_mean={g['ood_arrival_mean']:.2f}")
    part = compute_participation_goldens()
    with open(PARTICIPATION_GOLDEN_PATH, "w") as f:
        json.dump(part, f, indent=1)
        f.write("\n")
    print(f"wrote {PARTICIPATION_GOLDEN_PATH}")
    for name, g in part["scenarios"].items():
        print(f"  {name}: ood_auc_mean={g['ood_auc_mean']:.4f} "
              f"activity={g['activity_rate']:.2f} "
              f"staleness={np.mean(g['mean_staleness']):.2f}")
    byz = compute_byzantine_goldens()
    with open(BYZANTINE_GOLDEN_PATH, "w") as f:
        json.dump(byz, f, indent=1)
        f.write("\n")
    print(f"wrote {BYZANTINE_GOLDEN_PATH}")
    for name, g in byz["scenarios"].items():
        aucs = " ".join(f"{a}={v['ood_auc_mean']:.4f}"
                        for a, v in g["aggregators"].items())
        print(f"  {name}: rate={g['fault_rate']} {aucs}")


if __name__ == "__main__":
    main()
