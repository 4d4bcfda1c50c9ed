"""Host-side propagation oracles: per-node accuracy AUC, the
arrival-round oracle, and multi-source hop fields / summaries.

Property tests ride the optional-hypothesis shim; deterministic twins
always run.
"""
import numpy as np
import pytest

from tests._hypothesis import given, settings, st  # optional dep; skips if absent

from repro.core.decentralized import RoundMetrics
from repro.core.propagation import (
    NO_ARRIVAL,
    UNREACHABLE,
    arrival_rounds,
    hops_from,
    per_node_auc,
    propagation_summary,
)
from repro.core.topology import barabasi_albert, ring, star


def _hist(ood, rounds=None, iid=None):
    ood = np.asarray(ood, np.float32)
    iid = ood if iid is None else np.asarray(iid, np.float32)
    rounds = list(range(len(ood))) if rounds is None else rounds
    return [RoundMetrics(round=r, iid_acc=iid[i], ood_acc=ood[i],
                         train_loss=np.zeros_like(ood[i]))
            for i, r in enumerate(rounds)]


# ----------------------------------------------------------------------
# per-node accuracy AUC
# ----------------------------------------------------------------------
def test_trapezoid_matches_numpy():
    # trapezoid areas [1.5, 3.0] over a span of 3 rounds
    hist = _hist([[0.0, 1.0], [1.0, 1.0], [0.0, 1.0]], rounds=[0, 1, 3])
    np.testing.assert_allclose(per_node_auc(hist, "ood"), [0.5, 1.0])


def test_per_node_auc_uses_round_positions():
    # uneven eval rounds: AUC is trapezoid over ACTUAL round numbers
    hist = _hist([[0.0], [1.0], [1.0]], rounds=[0, 1, 5])
    np.testing.assert_allclose(per_node_auc(hist, "ood"), [4.5 / 5])


# ----------------------------------------------------------------------
# arrival-round oracle
# ----------------------------------------------------------------------
def test_arrival_rounds_first_crossing_and_sentinel():
    hist = _hist([[0.1, 0.6], [0.7, 0.2], [0.2, 0.3]], rounds=[1, 3, 5])
    np.testing.assert_array_equal(arrival_rounds(hist, 0.5), [3, 1])
    np.testing.assert_array_equal(arrival_rounds(hist, 0.95),
                                  [NO_ARRIVAL, NO_ARRIVAL])


def test_arrival_rounds_respects_recorded_round_numbers():
    hist = _hist([[0.9]], rounds=[7])
    np.testing.assert_array_equal(arrival_rounds(hist, 0.5), [7])


# ----------------------------------------------------------------------
# multi-source hop fields
# ----------------------------------------------------------------------
def test_multisource_hops_is_min_over_single_source():
    topo = barabasi_albert(12, 1, seed=0)  # tree: long hop distances
    srcs = (0, 7)
    multi = hops_from(topo.adjacency, srcs)
    single = np.stack([hops_from(topo.adjacency, s) for s in srcs])
    np.testing.assert_array_equal(multi, single.min(axis=0))


def test_multisource_hops_min_includes_unreachable():
    # two components: {0,1} and {2,3}; sources in different components
    adj = np.zeros((4, 4))
    adj[0, 1] = adj[1, 0] = adj[2, 3] = adj[3, 2] = 1.0
    np.testing.assert_array_equal(hops_from(adj, 0),
                                  [0, 1, UNREACHABLE, UNREACHABLE])
    # min-over-sources semantics: UNREACHABLE (-1) means "infinite", so
    # the multi-source field reaches both components
    np.testing.assert_array_equal(hops_from(adj, (0, 2)), [0, 1, 0, 1])


def test_hops_from_rejects_empty_sources():
    with pytest.raises(ValueError):
        hops_from(np.zeros((3, 3)), ())


def test_star_topology_hops():
    topo = star(6)
    np.testing.assert_array_equal(hops_from(topo.adjacency, 0),
                                  [0, 1, 1, 1, 1, 1])
    np.testing.assert_array_equal(hops_from(topo.adjacency, 3),
                                  [1, 2, 2, 0, 2, 2])


def test_propagation_summary_multisource():
    topo = ring(6)
    acc = np.linspace(0.0, 1.0, 6, dtype=np.float32)
    hist = _hist([acc, acc], rounds=[0, 2])
    s = propagation_summary(hist, topo.adjacency, (0, 3),
                            arrival_threshold=0.5)
    assert s["ood_sources"] == [0, 3]
    hops = hops_from(topo.adjacency, (0, 3))
    assert set(s["final_ood_acc_by_hop"]) == set(int(h) for h in hops)
    # arrival stats present and consistent with the oracle
    arr = arrival_rounds(hist, 0.5)
    arrived = arr != NO_ARRIVAL
    np.testing.assert_allclose(s["ood_arrival_mean"], arr[arrived].mean())


# ----------------------------------------------------------------------
# hypothesis property: multi-source == min over single-source fields
# ----------------------------------------------------------------------
@given(seed=st.integers(min_value=0, max_value=2**31 - 1),
       n=st.integers(min_value=2, max_value=9),
       p=st.floats(min_value=0.0, max_value=0.6),
       k=st.integers(min_value=1, max_value=3))
@settings(max_examples=40, deadline=None)
def test_prop_multisource_hops_min(seed, n, p, k):
    rng = np.random.default_rng(seed)
    adj = (rng.uniform(size=(n, n)) < p).astype(float)
    adj = np.triu(adj, 1)
    adj = adj + adj.T  # symmetric 0/1, zero diagonal; may be disconnected
    srcs = rng.choice(n, size=min(k, n), replace=False)
    multi = hops_from(adj, srcs)
    single = np.stack([hops_from(adj, int(s)) for s in srcs]).astype(float)
    single[single == UNREACHABLE] = np.inf  # -1 means "no path"
    want = single.min(axis=0)
    want[np.isinf(want)] = UNREACHABLE
    np.testing.assert_array_equal(multi, want.astype(np.int64))
