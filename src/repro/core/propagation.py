"""Knowledge-propagation metrics (paper §3/§5) — host-side oracles.

The paper's headline metric is **accuracy AUC**: for each node, the area
under the (round → test accuracy) curve over R rounds, averaged over all
nodes in a topology.  High OOD-AUC means the OOD source's knowledge
reached the rest of the topology quickly.  ``arrival_rounds`` reads the
complementary quantity: the first round at which each node's accuracy
crosses a threshold — "rounds until the knowledge arrived", binned by hop
distance from the OOD source(s) in the figures.

These functions consume full ``Sequence[RoundMetrics]`` histories and run
in numpy on the host.  They are the ORACLE for the in-scan streaming
accumulators in ``repro.core.analytics`` (DESIGN.md §10), which compute
the same numbers as O(n) online state inside the round scan; the two
paths are equivalence-tested to 1e-6.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Union

import numpy as np

from repro.core.decentralized import RoundMetrics

__all__ = [
    "accuracy_auc",
    "per_node_auc",
    "mean_auc",
    "iid_ood_gap",
    "arrival_rounds",
    "arrival_by_hop",
    "propagation_summary",
    "render_propagation_map",
    "hops_from",
    "UNREACHABLE",
    "NO_ARRIVAL",
]

#: ``hops_from`` sentinel for nodes with no path from any source (e.g.
#: components disconnected by ``core.dynamic`` link failures).  Consumers
#: label these ``"unreachable"`` and exclude them from hop statistics.
UNREACHABLE = -1

#: ``arrival_rounds`` sentinel for nodes whose accuracy never reaches the
#: threshold within the recorded history.
NO_ARRIVAL = -1

#: One or several OOD source nodes (multi-source scenarios place the
#: backdoor data on k nodes; hop fields and summaries take the min-over-
#: sources distance).
Sources = Union[int, Sequence[int], np.ndarray]


def _curves(history: Sequence[RoundMetrics], which: str) -> np.ndarray:
    """(rounds, n) matrix of per-node accuracies."""
    key = {"iid": "iid_acc", "ood": "ood_acc"}[which]
    return np.stack([getattr(m, key) for m in history])  # (R, n)


def per_node_auc(history: Sequence[RoundMetrics], which: str) -> np.ndarray:
    """Per-node accuracy-AUC, normalized to [0, 1] (trapezoid over rounds
    divided by the round span, i.e. mean height of the accuracy curve)."""
    acc = _curves(history, which)  # (R, n)
    if acc.shape[0] == 1:
        return acc[0]
    rounds = np.array([m.round for m in history], dtype=np.float64)
    auc = np.trapezoid(acc, x=rounds, axis=0)
    return auc / (rounds[-1] - rounds[0])


def accuracy_auc(history: Sequence[RoundMetrics], which: str) -> float:
    """Topology-mean accuracy AUC — the paper's bar-plot quantity."""
    return float(per_node_auc(history, which).mean())


def mean_auc(history: Sequence[RoundMetrics]) -> Dict[str, float]:
    return {
        "iid_auc": accuracy_auc(history, "iid"),
        "ood_auc": accuracy_auc(history, "ood"),
    }


def iid_ood_gap(history: Sequence[RoundMetrics]) -> float:
    """Percent difference between IID and OOD AUC (paper Fig. 2):
    lower (more negative) means OOD knowledge propagated worse."""
    iid = accuracy_auc(history, "iid")
    ood = accuracy_auc(history, "ood")
    return 100.0 * (ood - iid) / max(iid, 1e-9)


def arrival_rounds(
    history: Sequence[RoundMetrics],
    threshold: float = 0.5,
    which: str = "ood",
) -> np.ndarray:
    """First recorded round at which each node's accuracy reaches
    ``threshold`` — the "rounds until OOD knowledge arrived" quantity the
    paper plots against hop distance.  Nodes that never reach it keep
    :data:`NO_ARRIVAL` (-1).  Oracle for the streaming accumulator in
    ``repro.core.analytics``."""
    acc = _curves(history, which)  # (R, n)
    rounds = np.array([m.round for m in history], dtype=np.int64)
    hit = acc >= threshold
    first = np.argmax(hit, axis=0)  # first True (0 when none hit)
    return np.where(hit.any(axis=0), rounds[first], NO_ARRIVAL)


def arrival_by_hop(arrival: np.ndarray,
                   hops: np.ndarray) -> Dict[object, Optional[float]]:
    """Mean arrival round per hop-distance bin (single- or multi-source
    hop fields).  Nodes that never reached the threshold
    (:data:`NO_ARRIVAL`) are excluded from the means — ``None`` marks a
    bin with no arrivals — and BFS-unreachable nodes report under their
    own ``"unreachable"`` bin.  Shared by :func:`propagation_summary`
    and ``repro.core.analytics.analytics_summary`` so the host-oracle
    and streaming digests cannot drift apart."""
    arrival = np.asarray(arrival)
    hops = np.asarray(hops)
    arrived = arrival != NO_ARRIVAL
    out: Dict[object, Optional[float]] = {}
    for h in sorted(set(hops.tolist()) - {UNREACHABLE}):
        m = (hops == h) & arrived
        out[int(h)] = float(arrival[m].mean()) if m.any() else None
    unreachable = hops == UNREACHABLE
    if unreachable.any():
        m = unreachable & arrived
        out["unreachable"] = float(arrival[m].mean()) if m.any() else None
    return out


def _as_sources(source: Sources) -> np.ndarray:
    srcs = np.atleast_1d(np.asarray(source, dtype=np.int64))
    if srcs.ndim != 1 or srcs.size == 0:
        raise ValueError(f"need at least one source node, got {source!r}")
    return srcs


def hops_from(adjacency: np.ndarray, source: Sources) -> np.ndarray:
    """BFS hop distance of every node from the nearest OOD source.

    ``source`` may be a single node or a collection of nodes (multi-source
    OOD placement): seeding the BFS frontier with all sources yields the
    pointwise minimum over the single-source hop fields.  Nodes with no
    path from any source keep :data:`UNREACHABLE` (-1)."""
    n = adjacency.shape[0]
    dist = np.full(n, UNREACHABLE, dtype=np.int64)
    frontier = [int(s) for s in _as_sources(source)]
    for s in frontier:
        dist[s] = 0
    d = 0
    while frontier:
        d += 1
        nxt = []
        for u in frontier:
            for v in np.nonzero(adjacency[u])[0]:
                if dist[v] < 0:
                    dist[v] = d
                    nxt.append(int(v))
        frontier = nxt
    return dist


def render_propagation_map(
    history: Sequence[RoundMetrics],
    adjacency: np.ndarray,
    ood_node: Sources,
    which: str = "ood",
) -> str:
    """Text rendering of the paper's Fig. 1 heatmap: final per-node
    accuracy grouped by hop distance from the OOD source(s) (terminal-
    friendly stand-in for the graph plot)."""
    acc = _curves(history, which)[-1]
    hops = hops_from(adjacency, ood_node)
    srcs = _as_sources(ood_node)
    label = (f"node {int(srcs[0])}" if srcs.size == 1
             else "nodes " + ", ".join(str(int(s)) for s in srcs))
    lines = [f"final {which.upper()} accuracy by hop distance "
             f"from {label}:"]
    blocks = " ▁▂▃▄▅▆▇█"

    def cells_for(nodes):
        return " ".join(
            f"{i}:{blocks[min(int(acc[i] * 8), 8)]}{acc[i]:.2f}" for i in nodes
        )

    for h in sorted(set(int(x) for x in hops) - {UNREACHABLE}):
        lines.append(f"  hop {h}: {cells_for(np.flatnonzero(hops == h))}")
    unreachable = np.flatnonzero(hops == UNREACHABLE)
    if unreachable.size:
        lines.append(f"  unreachable: {cells_for(unreachable)}")
    return "\n".join(lines)


def propagation_summary(
    history: Sequence[RoundMetrics],
    adjacency: np.ndarray,
    ood_node: Sources,
    arrival_threshold: float = 0.5,
) -> Dict[str, object]:
    """Full report: AUCs, gap, arrival rounds, and OOD accuracy binned by
    hop distance from the OOD source(s) (quantifies the paper's 'knowledge
    hops between devices').  ``ood_node`` may be a single node or a
    collection (multi-source placement: hop bins use the min-over-sources
    distance).

    Nodes the BFS cannot reach (link-failure runs that disconnect the
    graph) are reported under the ``"unreachable"`` key rather than a
    bogus hop ``-1`` bin, and are excluded from the hop-distance bins;
    nodes that never cross ``arrival_threshold`` are excluded from
    arrival means (``None`` marks an all-excluded bin)."""
    ood_final = _curves(history, "ood")[-1]  # (n,)
    hops = hops_from(adjacency, ood_node)
    arrival = arrival_rounds(history, threshold=arrival_threshold)
    arrived = arrival != NO_ARRIVAL
    by_hop: Dict[object, float] = {}
    for h in sorted(set(hops.tolist()) - {UNREACHABLE}):
        by_hop[int(h)] = float(ood_final[hops == h].mean())
    unreachable = hops == UNREACHABLE
    if unreachable.any():
        by_hop["unreachable"] = float(ood_final[unreachable].mean())
    srcs = _as_sources(ood_node)
    return {
        **mean_auc(history),
        "iid_ood_gap_pct": iid_ood_gap(history),
        "final_ood_acc_by_hop": by_hop,
        "final_ood_acc_mean": float(ood_final.mean()),
        "ood_arrival_mean": (float(arrival[arrived].mean())
                             if arrived.any() else None),
        "ood_arrival_by_hop": arrival_by_hop(arrival, hops),
        "ood_sources": ([int(s) for s in srcs] if srcs.size > 1
                        else int(srcs[0])),
    }
