"""Streaming-analytics invariants (DESIGN.md §10).

Property tests (hypothesis, optional via tests/_hypothesis.py) plus
deterministic twins that always run:

* a constant accuracy curve has AUC equal to the constant;
* AUC is monotone under pointwise accuracy dominance;
* the in-scan accumulator equals the host ``propagation.py`` oracle for
  random ``eval_every`` schedules and random histories (to 1e-6; arrival
  rounds exactly);
* the accumulator ignores non-eval rounds entirely (garbage accuracies
  at masked-out rounds cannot leak in, mirroring the gated in-scan eval).
"""
import numpy as np
import pytest

from tests._hypothesis import given, settings, st  # optional dep; skips if absent

from repro.core.analytics import NO_ARRIVAL, AnalyticsSpec, analytics_summary
from repro.core.decentralized import RoundMetrics, eval_round_indices
from repro.core.propagation import arrival_rounds, iid_ood_gap, per_node_auc


def _stream(iid, ood, eval_mask, threshold=0.5):
    """Fold an (R, n) pair of accuracy matrices through the accumulator
    exactly as the scan body does (masked rounds feed zeros, like the
    gated eval)."""
    iid, ood = np.asarray(iid, np.float32), np.asarray(ood, np.float32)
    spec = AnalyticsSpec(arrival_threshold=threshold)
    carry = spec.init(iid.shape[1])
    for r in range(iid.shape[0]):
        m = bool(eval_mask[r])
        carry = spec.update(carry, r, m,
                            iid[r] if m else np.zeros_like(iid[r]),
                            ood[r] if m else np.zeros_like(ood[r]))
    import jax

    return jax.tree.map(np.asarray, spec.finalize(carry))


def _history(iid, ood, eval_mask):
    """The host-side view: RoundMetrics at the eval rounds only."""
    n = iid.shape[1]
    return [RoundMetrics(round=r, iid_acc=np.asarray(iid[r], np.float32),
                         ood_acc=np.asarray(ood[r], np.float32),
                         train_loss=np.zeros(n))
            for r in range(iid.shape[0]) if eval_mask[r]]


def _rand(rng, rounds, n):
    return rng.uniform(0.0, 1.0, size=(rounds, n)).astype(np.float32)


# ----------------------------------------------------------------------
# deterministic invariants (always run)
# ----------------------------------------------------------------------
def test_constant_curve_auc_is_the_constant():
    for c in (0.0, 0.25, 1.0):
        acc = np.full((5, 3), c, np.float32)
        out = _stream(acc, acc, np.ones(5, bool))
        np.testing.assert_allclose(out["iid_auc"], c, atol=1e-6)
        np.testing.assert_allclose(out["ood_auc"], c, atol=1e-6)


def test_auc_monotone_under_dominance():
    rng = np.random.default_rng(0)
    lo = _rand(rng, 8, 4)
    hi = np.clip(lo + rng.uniform(0, 0.5, size=lo.shape), 0, 1)
    mask = np.ones(8, bool)
    assert (_stream(hi, hi, mask)["ood_auc"]
            >= _stream(lo, lo, mask)["ood_auc"] - 1e-6).all()


@pytest.mark.parametrize("eval_every", [1, 2, 3, 5])
def test_stream_matches_host_oracle(eval_every):
    rng = np.random.default_rng(eval_every)
    rounds, n = 9, 5
    iid, ood = _rand(rng, rounds, n), _rand(rng, rounds, n)
    mask = np.zeros(rounds, bool)
    mask[eval_round_indices(rounds, eval_every)] = True
    out = _stream(iid, ood, mask)
    hist = _history(iid, ood, mask)
    np.testing.assert_allclose(out["iid_auc"], per_node_auc(hist, "iid"),
                               atol=1e-6)
    np.testing.assert_allclose(out["ood_auc"], per_node_auc(hist, "ood"),
                               atol=1e-6)
    np.testing.assert_array_equal(out["ood_arrival"],
                                  arrival_rounds(hist, 0.5))
    np.testing.assert_array_equal(
        out["iid_arrival"], arrival_rounds(hist, 0.5, which="iid"))
    np.testing.assert_allclose(
        100.0 * (out["ood_auc"].mean() - out["iid_auc"].mean())
        / max(out["iid_auc"].mean(), 1e-9),
        iid_ood_gap(hist), atol=1e-4)


def test_single_eval_round_degenerates_to_final_accuracy():
    rng = np.random.default_rng(7)
    iid, ood = _rand(rng, 4, 3), _rand(rng, 4, 3)
    mask = np.array([False, False, False, True])
    out = _stream(iid, ood, mask)
    np.testing.assert_allclose(out["iid_auc"], iid[3], atol=1e-7)
    np.testing.assert_allclose(out["ood_auc"], ood[3], atol=1e-7)


def test_masked_rounds_cannot_leak():
    """Garbage at non-eval rounds must not move any accumulator."""
    rng = np.random.default_rng(3)
    iid, ood = _rand(rng, 6, 4), _rand(rng, 6, 4)
    mask = np.array([False, True, False, True, False, True])
    clean = _stream(iid, ood, mask)
    poisoned_iid, poisoned_ood = iid.copy(), ood.copy()
    poisoned_iid[~mask] = 999.0
    poisoned_ood[~mask] = 999.0
    spec = AnalyticsSpec()
    carry = spec.init(4)
    for r in range(6):  # feed the garbage THROUGH update, mask gating it
        carry = spec.update(carry, r, bool(mask[r]),
                            poisoned_iid[r], poisoned_ood[r])
    import jax

    poisoned = jax.tree.map(np.asarray, spec.finalize(carry))
    for k in clean:
        np.testing.assert_array_equal(clean[k], poisoned[k])


def test_never_arriving_node_keeps_sentinel():
    acc = np.full((5, 2), 0.1, np.float32)
    acc[:, 1] = 0.9
    out = _stream(acc, acc, np.ones(5, bool), threshold=0.5)
    assert out["ood_arrival"][0] == NO_ARRIVAL
    assert out["ood_arrival"][1] == 0


def test_analytics_summary_digest():
    arr = np.array([0, 2, NO_ARRIVAL, 4], np.int32)
    stream = {
        "iid_auc": np.array([0.5, 0.5, 0.5, 0.5]),
        "ood_auc": np.array([0.4, 0.6, 0.2, 0.8]),
        "ood_arrival": arr,
    }
    adj = np.zeros((4, 4))
    adj[0, 1] = adj[1, 0] = adj[1, 2] = adj[2, 1] = 1.0  # node 3 isolated
    s = analytics_summary(stream, adj, sources=0)
    np.testing.assert_allclose(s["iid_auc"], 0.5)
    np.testing.assert_allclose(s["ood_auc"], 0.5)
    np.testing.assert_allclose(s["ood_arrival_mean"], (0 + 2 + 4) / 3)
    assert s["n_no_arrival"] == 1
    by = s["ood_arrival_by_hop"]
    assert by[0] == 0.0 and by[1] == 2.0 and by[2] is None
    assert by["unreachable"] == 4.0


# ----------------------------------------------------------------------
# hypothesis properties (skip cleanly without the optional dep)
# ----------------------------------------------------------------------
@given(c=st.floats(min_value=0.0, max_value=1.0, width=32),
       rounds=st.integers(min_value=1, max_value=10),
       n=st.integers(min_value=1, max_value=6))
@settings(max_examples=30, deadline=None)
def test_prop_constant_curve(c, rounds, n):
    acc = np.full((rounds, n), c, np.float32)
    out = _stream(acc, acc, np.ones(rounds, bool))
    np.testing.assert_allclose(out["ood_auc"], np.float32(c), atol=1e-6)


@given(seed=st.integers(min_value=0, max_value=2**31 - 1),
       rounds=st.integers(min_value=2, max_value=12),
       n=st.integers(min_value=1, max_value=6))
@settings(max_examples=30, deadline=None)
def test_prop_auc_dominance(seed, rounds, n):
    rng = np.random.default_rng(seed)
    lo = _rand(rng, rounds, n)
    hi = np.clip(lo + rng.uniform(0, 1, size=lo.shape), 0, 1)
    mask = np.ones(rounds, bool)
    assert (_stream(hi, hi, mask)["ood_auc"]
            >= _stream(lo, lo, mask)["ood_auc"] - 1e-6).all()


@given(seed=st.integers(min_value=0, max_value=2**31 - 1),
       rounds=st.integers(min_value=1, max_value=12),
       eval_every=st.integers(min_value=1, max_value=6),
       # bounds given as exact float32 values, as width=32 requires
       threshold=st.floats(min_value=float(np.float32(0.1)),
                           max_value=float(np.float32(0.9)), width=32))
@settings(max_examples=40, deadline=None)
def test_prop_stream_equals_host_oracle(seed, rounds, eval_every,
                                        threshold):
    rng = np.random.default_rng(seed)
    n = 4
    iid, ood = _rand(rng, rounds, n), _rand(rng, rounds, n)
    mask = np.zeros(rounds, bool)
    mask[eval_round_indices(rounds, eval_every)] = True
    out = _stream(iid, ood, mask, threshold=threshold)
    hist = _history(iid, ood, mask)
    np.testing.assert_allclose(out["iid_auc"], per_node_auc(hist, "iid"),
                               atol=1e-6)
    np.testing.assert_allclose(out["ood_auc"], per_node_auc(hist, "ood"),
                               atol=1e-6)
    np.testing.assert_array_equal(out["ood_arrival"],
                                  arrival_rounds(hist, threshold))
