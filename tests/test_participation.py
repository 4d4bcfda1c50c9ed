"""Partial-participation gossip (DESIGN.md §15): the node-level active-set
round must collapse to the synchronous engine bit-for-bit at rate 1.0 —
in every execution mode and every mixing backend — and at partial rates
the staleness counters, stale-plane selects, and time-skewed local-step
counts must agree exactly across scanned / chunked / unrolled (the
8-device mesh lives in the subprocess test at the bottom, like
tests/test_sweep_sharded.py).
"""
import inspect
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.coeffs import participation_renormalize
from repro.core.decentralized import (
    DecentralizedConfig,
    coeffs_stack,
    stack_params,
)
from repro.core.dynamic import PARTICIPATION_MODES, ParticipationSpec
from repro.core.strategies import AggregationStrategy, renormalize_rows
from repro.core.sweep import SweepEngine
from repro.core.topology import ring
from repro.data.backdoor import backdoored_testset
from repro.data.distribution import node_datasets
from repro.data.pipeline import NodeBatcher, make_test_batch
from repro.data.synthetic import make_dataset
from repro.training.optimizer import sgd

N, ROUNDS, E = 4, 4, 3


@pytest.fixture(scope="module")
def grid():
    """E=3 experiments (unweighted / random / degree) on ring(4), shared
    data bank — the test_sweep_sharded.py setting at 1 device."""
    train = make_dataset("mnist", 400, seed=0)
    test = make_dataset("mnist", 100, seed=9)
    from repro.models.paper_models import (
        classifier_accuracy, classifier_loss, ffn_apply, ffn_init)

    topo = ring(N)
    parts = node_datasets(train, N, ood_node=0, q=0.10, seed=0)
    nb = NodeBatcher(parts, batch_size=8, steps_per_epoch=2, seed=0,
                     local_epochs=2)
    tb = make_test_batch(test, 32, seed=0)
    ob = make_test_batch(backdoored_testset(test, seed=0), 32, seed=0)
    kinds = ["unweighted", "random", "degree"]
    bank = {k: v[None] for k, v in nb.sample_bank().items()}
    indices = nb.all_round_indices(ROUNDS)[None]
    data_idx = np.zeros(E, np.int32)
    coeffs = np.stack([
        coeffs_stack(topo, AggregationStrategy(k, seed=0), ROUNDS,
                     nb.data_counts())
        for k in kinds])
    params0 = jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[stack_params([ffn_init(jax.random.key(0))] * N)] * E)
    st = lambda t: {k: jnp.stack([jnp.asarray(t[k])] * E) for k in t}
    return {
        "topo": topo,
        "loss_fn": classifier_loss(ffn_apply),
        "acc_fn": classifier_accuracy(ffn_apply),
        "args": (params0, coeffs, bank, indices, data_idx, st(tb), st(ob)),
        "params0": params0,
    }


def _engine(grid, mix_impl="einsum"):
    cfg = DecentralizedConfig(rounds=ROUNDS, local_epochs=2, eval_every=2,
                              mix_impl=mix_impl)
    support = None
    if mix_impl in ("sparse", "edges"):
        support = np.asarray(grid["topo"].adjacency) + np.eye(N)
    return SweepEngine(sgd(1e-2), grid["loss_fn"], grid["acc_fn"], cfg,
                       mix_support=support)


def _assert_results_equal(a, b):
    np.testing.assert_array_equal(a.train_loss, b.train_loss)
    np.testing.assert_array_equal(a.iid_acc, b.iid_acc)
    np.testing.assert_array_equal(a.ood_acc, b.ood_acc)
    for x, y in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ----------------------------------------------------------------------
# rate 1.0 == the synchronous engine, bit-for-bit (tentpole acceptance)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("mix_impl", ["einsum", "pallas", "edges"])
def test_rate1_bit_identical_to_synchronous(grid, mix_impl):
    """uniform(key) < 1.0 activates every node every round, the stale-
    plane selects pick the fresh branch everywhere, and the carry adds no
    arithmetic to the plane — so a rate-1.0 run must reproduce the
    no-participation program EXACTLY, per backend and per mode."""
    from repro.launch.mesh import make_sweep_mesh

    engine = _engine(grid, mix_impl)
    run = lambda **kw: engine.run(*grid["args"], batch_size=8, **kw)
    ref = run()
    spec = ParticipationSpec()
    for label, kw in [
        ("scanned", {}),
        ("chunked", {"chunk_rounds": 3}),
        ("mesh1", {"mesh": make_sweep_mesh(1)}),
        ("unrolled", {"unroll_eval": True}),
    ]:
        res = run(participation=spec,
                  participation_rates=np.ones(E, np.float32), **kw)
        _assert_results_equal(res, ref)
        part = res.participation
        assert part is not None, label
        np.testing.assert_array_equal(part["rounds_active"],
                                      np.full((E, N), ROUNDS))
        np.testing.assert_array_equal(part["final_staleness"],
                                      np.zeros((E, N), np.int32))
        np.testing.assert_array_equal(part["mean_staleness"],
                                      np.zeros((E, N)))
        steps = part["local_steps"]
        assert (steps == steps[0, 0]).all() and steps[0, 0] % ROUNDS == 0


def test_duty_cycle_rate1_bit_identical(grid):
    """The static duty-cycle schedule at rate 1.0 (k == period) is the
    all-active schedule — synchronous bit-identity holds there too."""
    engine = _engine(grid)
    ref = engine.run(*grid["args"], batch_size=8)
    res = engine.run(*grid["args"], batch_size=8,
                     participation=ParticipationSpec(mode="duty", period=3),
                     participation_rates=np.ones(E, np.float32))
    _assert_results_equal(res, ref)


# ----------------------------------------------------------------------
# degenerate active sets
# ----------------------------------------------------------------------
def test_zero_active_rounds_freeze_everything(grid):
    """rate 0.0: nobody ever publishes or mixes — params stay at their
    init, losses report zero, staleness increments everywhere, and the
    time-skewed local-step counts stay zero."""
    engine = _engine(grid)
    res = engine.run(*grid["args"], batch_size=8,
                     participation=ParticipationSpec(),
                     participation_rates=np.zeros(E, np.float32))
    for a, b in zip(jax.tree.leaves(res.params),
                    jax.tree.leaves(grid["params0"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(res.train_loss,
                                  np.zeros_like(res.train_loss))
    part = res.participation
    np.testing.assert_array_equal(part["rounds_active"], np.zeros((E, N)))
    np.testing.assert_array_equal(part["local_steps"], np.zeros((E, N)))
    np.testing.assert_array_equal(part["final_staleness"],
                                  np.full((E, N), ROUNDS))
    # Σ_{r=1..R} r / R
    np.testing.assert_allclose(part["mean_staleness"],
                               np.full((E, N), (ROUNDS + 1) / 2))


def test_duty_cycle_exactly_one_active(grid):
    """period=N at rate 1/N staggers the phases so EXACTLY one node is
    active each round — each node trains exactly R/period times."""
    engine = _engine(grid)
    res = engine.run(*grid["args"], batch_size=8,
                     participation=ParticipationSpec(mode="duty", period=N),
                     participation_rates=np.full(E, 1.0 / N, np.float32))
    part = res.participation
    # R == N == period here: every node active exactly once
    np.testing.assert_array_equal(part["rounds_active"],
                                  np.ones((E, N), np.int32))
    assert int(part["rounds_active"].sum()) == E * ROUNDS
    # per-round losses: exactly one nonzero row per (experiment, round)
    active_rows = (np.asarray(res.train_loss) != 0).sum(axis=2)
    np.testing.assert_array_equal(active_rows,
                                  np.ones((E, ROUNDS), np.int32))


def test_duty_mask_schedule():
    """The (r + i) % period phase stagger, directly."""
    spec = ParticipationSpec(mode="duty", period=4)
    masks = np.stack([
        np.asarray(spec.active_mask(0.25, 0, r, 4)) for r in range(4)])
    # one active node per round, rotating
    np.testing.assert_array_equal(masks.sum(axis=1), np.ones(4))
    np.testing.assert_array_equal(masks.sum(axis=0), np.ones(4))
    full = np.stack([
        np.asarray(spec.active_mask(1.0, 0, r, 4)) for r in range(4)])
    assert full.all()


def test_participation_spec_validation():
    with pytest.raises(ValueError, match="period"):
        ParticipationSpec(mode="duty", period=0)
    with pytest.raises(ValueError, match="mode"):
        ParticipationSpec(mode="nope")
    assert set(PARTICIPATION_MODES) == {"bernoulli", "duty"}


# ----------------------------------------------------------------------
# cross-mode equality at a genuinely partial rate
# ----------------------------------------------------------------------
def test_partial_rate_modes_bit_identical(grid):
    """rate 0.5: scanned == chunked (absolute round indices drive the
    active-set draw, so chunk boundaries cannot shift it) == unrolled,
    including every participation digest array."""
    engine = _engine(grid)
    spec = ParticipationSpec()
    run = lambda **kw: engine.run(
        *grid["args"], batch_size=8, participation=spec,
        participation_rates=np.full(E, 0.5, np.float32), **kw)
    ref = run()
    for label, other in [("chunked", run(chunk_rounds=3)),
                         ("unrolled", run(unroll_eval=True))]:
        _assert_results_equal(other, ref)
        for k in ref.participation:
            np.testing.assert_array_equal(
                ref.participation[k], other.participation[k],
                err_msg=(label, k))
    # the draw actually drops nodes at this rate
    assert (np.asarray(ref.participation["rounds_active"]) < ROUNDS).any()


def test_per_experiment_rates_ride_the_vmap_axis(grid):
    """One compiled program serves a rate grid: the rate-1.0 row of a
    mixed [1.0, 0.5, 0.0] run equals the all-ones run's row bit-for-bit
    (rates are carried data, not static config)."""
    engine = _engine(grid)
    spec = ParticipationSpec()
    run = lambda rates: engine.run(
        *grid["args"], batch_size=8, participation=spec,
        participation_rates=np.asarray(rates, np.float32))
    mixed = run([1.0, 0.5, 0.0])
    ones = run([1.0, 1.0, 1.0])
    np.testing.assert_array_equal(mixed.train_loss[0], ones.train_loss[0])
    np.testing.assert_array_equal(
        mixed.participation["rounds_active"][0],
        np.full(N, ROUNDS))
    np.testing.assert_array_equal(
        mixed.participation["rounds_active"][2], np.zeros(N))


def test_drop_mode_rate1_bit_identical(grid):
    """stale_mixing=False (drop inactive columns + renormalize) keeps
    the all-active round bit-identical: the row-level `changed` gate in
    participation_renormalize skips the divide when no mass was lost."""
    engine = _engine(grid)
    ref = engine.run(*grid["args"], batch_size=8)
    res = engine.run(*grid["args"], batch_size=8,
                     participation=ParticipationSpec(stale_mixing=False),
                     participation_rates=np.ones(E, np.float32))
    _assert_results_equal(res, ref)


def test_analytics_and_participation_compose(grid):
    """Both carries thread the same scan; the staleness × arrival digest
    (analytics.participation_summary) reads them together."""
    from repro.core.analytics import AnalyticsSpec, participation_summary

    engine = _engine(grid)
    res = engine.run(*grid["args"], batch_size=8,
                     analytics=AnalyticsSpec(arrival_threshold=0.5),
                     participation=ParticipationSpec(),
                     participation_rates=np.full(E, 0.6, np.float32))
    assert res.analytics is not None and res.participation is not None
    for e in range(E):
        part = {k: v[e] for k, v in res.participation.items()}
        stream = {k: v[e] for k, v in res.analytics.items()}
        s = participation_summary(part, ROUNDS, stream)
        assert 0.0 <= s["activity_rate"] <= 1.0
        assert s["local_steps_total"] == int(part["local_steps"].sum())
        assert "staleness_arrival_corr" in s
        assert "arrival_low_staleness" in s


def test_rates_require_spec(grid):
    engine = _engine(grid)
    with pytest.raises(ValueError, match="participation"):
        engine.run(*grid["args"], batch_size=8,
                   participation_rates=np.ones(E, np.float32))


# ----------------------------------------------------------------------
# the shared row-normalize helper + drop-mode renormalization
# ----------------------------------------------------------------------
def test_renormalize_rows_healthy_rows_divide_exact_rowsum():
    rng = np.random.default_rng(0)
    # healthy rows divide by their EXACT row sum (the old
    # np.maximum(rowsum, 1e-12) epsilon was dead there by construction)
    d = rng.uniform(0.5, 2.0, size=(4, 4))
    np.testing.assert_array_equal(renormalize_rows(d),
                                  d / d.sum(axis=-1, keepdims=True))
    # rows already summing to exactly 1.0 come back bit-identical
    c = np.array([[0.5, 0.25, 0.25], [1.0, 0.0, 0.0], [0.0, 0.5, 0.5]])
    np.testing.assert_array_equal(renormalize_rows(c), c)


def test_renormalize_rows_zero_row_falls_back_to_self():
    c = np.array([[0.5, 0.5, 0.0],
                  [0.0, 0.0, 0.0],
                  [0.0, 0.2, 0.8]])
    out = renormalize_rows(c)
    np.testing.assert_array_equal(out[1], np.array([0.0, 1.0, 0.0]))
    np.testing.assert_array_equal(out[0], c[0])


def test_renormalize_rows_asserts_on_subnormal_rowsum():
    c = np.zeros((2, 2))
    c[0, 0] = 1e-12  # positive but far below any honest coefficient
    with pytest.raises(AssertionError, match="masking bug"):
        renormalize_rows(c)


def test_renormalize_rows_jnp_path_no_assert():
    c = jnp.zeros((2, 2)).at[0, 0].set(1e-12)
    out = renormalize_rows(c, xp=jnp)  # traced path cannot assert
    assert np.isfinite(np.asarray(out)).all()


def test_participation_renormalize_semantics():
    rng = np.random.default_rng(1)
    c = rng.uniform(0.0, 1.0, size=(2, 4, 4)).astype(np.float32)
    c *= rng.uniform(size=(2, 4, 4)) > 0.4  # sparsify
    c[..., np.arange(4), np.arange(4)] += 0.2  # self mass
    c /= c.sum(axis=-1, keepdims=True)
    c = jnp.asarray(c)
    all_on = jnp.ones((4,), bool)
    np.testing.assert_array_equal(
        np.asarray(participation_renormalize(c, all_on)), np.asarray(c))
    active = jnp.asarray([True, False, True, True])
    out = np.asarray(participation_renormalize(c, active))
    np.testing.assert_allclose(out.sum(axis=-1), np.ones((2, 4)),
                               rtol=1e-6)
    # the dropped column is zeroed everywhere EXCEPT rows whose entire
    # support went inactive — those fall back to self-weight 1 (and the
    # inactive node's own row is discarded by the round select anyway)
    masked = np.asarray(c) * np.asarray(active, np.float32)[None, None, :]
    fallback = masked.sum(axis=-1) == 0
    np.testing.assert_array_equal(out[..., 1][~fallback],
                                  np.zeros_like(out[..., 1][~fallback]))
    np.testing.assert_array_equal(
        out[fallback], np.broadcast_to(np.eye(4, dtype=np.float32)[1],
                                       out[fallback].shape))
    # rows with no support on the dropped column are returned bit-exact
    untouched = np.asarray(c)[..., 1] == 0
    np.testing.assert_array_equal(out[untouched], np.asarray(c)[untouched])


# ----------------------------------------------------------------------
# satellite regressions: drop_edges dead param, reactive betweenness
# ----------------------------------------------------------------------
def test_drop_edges_dead_param_removed():
    """`keep_connected_to_self` was dead (Topology rejects nonzero
    diagonals, so a self-loop-preserving variant is unrepresentable);
    node-level dropout is ParticipationSpec's job now.  The parameter is
    gone — passing it must fail loudly instead of silently no-opping."""
    from repro.core.dynamic import drop_edges

    assert "keep_connected_to_self" not in inspect.signature(
        drop_edges).parameters
    with pytest.raises(TypeError):
        drop_edges(ring(4), 0.5, np.random.default_rng(0),
                   keep_connected_to_self=True)


def test_reactive_betweenness_rejected_with_opt_in():
    from repro.core.coeffs import program_for

    topo = ring(6)
    strat = AggregationStrategy("betweenness", tau=0.1, seed=0)
    program, state = program_for(topo, strat, p_fail=0.3, reactive=True)
    with pytest.raises(ValueError, match="betweenness"):
        program.validate_state_kinds(state)
    ok, state_ok = program_for(topo, strat, p_fail=0.3, reactive=True,
                               allow_nominal_betweenness=True)
    ok.validate_state_kinds(state_ok)  # explicit opt-in passes
    nominal, state_n = program_for(topo, strat, p_fail=0.3, reactive=False)
    nominal.validate_state_kinds(state_n)  # non-reactive never gated


# ----------------------------------------------------------------------
# 8-device mesh: participation shards on E, agreeing with the scanned
# run to f32 rounding (subprocess —
# XLA_FLAGS must be set before jax initializes; see conftest.py)
# ----------------------------------------------------------------------
SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax, jax.numpy as jnp
    assert len(jax.devices()) == 8, jax.devices()

    from repro.core.decentralized import (
        DecentralizedConfig, coeffs_stack, stack_params)
    from repro.core.dynamic import ParticipationSpec
    from repro.core.strategies import AggregationStrategy
    from repro.core.sweep import SweepEngine
    from repro.core.topology import ring
    from repro.data.backdoor import backdoored_testset
    from repro.data.distribution import node_datasets
    from repro.data.pipeline import NodeBatcher, make_test_batch
    from repro.data.synthetic import make_dataset
    from repro.launch.mesh import make_sweep_mesh
    from repro.models.paper_models import (
        classifier_accuracy, classifier_loss, ffn_apply, ffn_init)
    from repro.training.optimizer import sgd

    N, R, E = 4, 4, 3
    train = make_dataset("mnist", 400, seed=0)
    test = make_dataset("mnist", 100, seed=9)
    cfg = DecentralizedConfig(rounds=R, local_epochs=2, eval_every=2)
    topo = ring(N)
    parts = node_datasets(train, N, ood_node=0, q=0.10, seed=0)
    nb = NodeBatcher(parts, batch_size=8, steps_per_epoch=2, seed=0,
                     local_epochs=2)
    tb = make_test_batch(test, 32, seed=0)
    ob = make_test_batch(backdoored_testset(test, seed=0), 32, seed=0)
    kinds = ["unweighted", "random", "degree"]  # E=3 pads to 8 devices
    bank = {k: v[None] for k, v in nb.sample_bank().items()}
    indices = nb.all_round_indices(R)[None]
    data_idx = np.zeros(E, np.int32)
    coeffs = np.stack([
        coeffs_stack(topo, AggregationStrategy(k, seed=0), R,
                     nb.data_counts())
        for k in kinds])
    params0 = jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[stack_params([ffn_init(jax.random.key(0))] * N)] * E)
    st = lambda t: {k: jnp.stack([jnp.asarray(t[k])] * E) for k in t}
    mesh = make_sweep_mesh()  # all 8 virtual devices
    engine = SweepEngine(sgd(1e-2), classifier_loss(ffn_apply),
                         classifier_accuracy(ffn_apply), cfg)
    run = lambda **kw: engine.run(
        params0, coeffs, bank, indices, data_idx, st(tb), st(ob),
        batch_size=8, **kw)

    def close(a, b, **kw):  # f32 tolerance between compiled programs
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5, **kw)

    def check(r, ref, label):
        close(r.train_loss, ref.train_loss)
        close(r.iid_acc, ref.iid_acc)
        close(r.ood_acc, ref.ood_acc)
        for a, b in zip(jax.tree.leaves(r.params),
                        jax.tree.leaves(ref.params)):
            close(np.asarray(a), np.asarray(b))
        if ref.participation is not None:
            for k in ref.participation:
                close(r.participation[k], ref.participation[k],
                      err_msg=(label, k))
        print(label, "ok")

    # rate 1.0 sharded over 8 devices == the synchronous scanned run
    sync = run()
    spec = ParticipationSpec()
    ones = np.ones(E, np.float32)
    check(run(participation=spec, participation_rates=ones, mesh=mesh),
          sync, "mesh8/rate1-vs-sync")

    # a genuine rate grid: scanned == mesh(8) == mesh(8)+chunk, incl.
    # the participation digest (carry shards on E; padding rows dropped)
    rates = np.asarray([1.0, 0.6, 0.3], np.float32)
    ref = run(participation=spec, participation_rates=rates)
    check(run(participation=spec, participation_rates=rates, mesh=mesh),
          ref, "mesh8/rate-grid")
    check(run(participation=spec, participation_rates=rates, mesh=mesh,
              chunk_rounds=3),
          ref, "mesh8/rate-grid+chunk")
    # the grid's rate-1.0 row is the synchronous row, even sharded
    close(ref.train_loss[0], sync.train_loss[0])
    print("PARTICIPATION_SHARDED_OK")
""")


def test_participation_sharded_subprocess():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "PARTICIPATION_SHARDED_OK" in out.stdout, (out.stdout[-2000:],
                                                      out.stderr[-3000:])
