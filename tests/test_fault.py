"""Byzantine fault injection + self-healing quarantine + crash-safe
resume (DESIGN.md §16): fault rate 0.0 must collapse to the synchronous
engine bit-for-bit in every execution mode and mixing backend; at nonzero
rates the corruption draw, quarantine state machine, and fault digest
must agree exactly across scanned / chunked / unrolled; and a chunked
sweep killed mid-run must resume from its checkpoints bit-identically to
an uninterrupted one (8-device mesh subprocess at the bottom, like
tests/test_participation.py).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.analytics import quarantine_summary
from repro.core.coeffs import quarantine_renormalize
from repro.core.decentralized import (
    DecentralizedConfig,
    coeffs_stack,
    stack_params,
)
from repro.core.dynamic import FAULT_MODES, FaultSpec, ParticipationSpec
from repro.core.strategies import AggregationStrategy
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
    data bank — the tests/test_participation.py setting."""
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


def _engine(grid, mix_impl="einsum", robust="mean"):
    cfg = DecentralizedConfig(rounds=ROUNDS, local_epochs=2, eval_every=2,
                              mix_impl=mix_impl, robust=robust)
    support = None
    if mix_impl in ("sparse", "edges") or robust in ("trimmed", "median"):
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
# rate 0.0 == the synchronous engine, bit-for-bit (tentpole acceptance)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("mix_impl", ["einsum", "pallas", "edges"])
def test_rate0_bit_identical_to_synchronous(grid, mix_impl):
    """uniform(key) < 0.0 marks no node faulty, the corruption selects
    pick the clean branch everywhere, and the carry adds no arithmetic
    to the plane — so a rate-0.0 run must reproduce the no-fault program
    EXACTLY, per backend and per mode."""
    from repro.launch.mesh import make_sweep_mesh

    engine = _engine(grid, mix_impl)
    run = lambda **kw: engine.run(*grid["args"], batch_size=8, **kw)
    ref = run()
    spec = FaultSpec()
    for label, kw in [
        ("scanned", {}),
        ("chunked", {"chunk_rounds": 3}),
        ("mesh1", {"mesh": make_sweep_mesh(1)}),
        ("unrolled", {"unroll_eval": True}),
    ]:
        res = run(fault=spec, **kw)  # fault_rates default to 0.0
        _assert_results_equal(res, ref)
        f = res.fault
        assert f is not None, label
        np.testing.assert_array_equal(f["fault_rounds"],
                                      np.zeros((E, N), np.int32))
        np.testing.assert_array_equal(f["rounds_quarantined"],
                                      np.zeros((E, N), np.int32))
        np.testing.assert_array_equal(f["first_fault"],
                                      np.full((E, N), -1, np.int32))
        np.testing.assert_array_equal(f["first_quar"],
                                      np.full((E, N), -1, np.int32))


def test_rate0_with_quarantine_bit_identical(grid):
    """Quarantine screen armed at zero fault rate: the screen flags
    nothing (the norm EMA warms up on clean published norms, nonfinite
    counts stay zero) and the run reproduces the plain program exactly.
    A never-clipping norm_clip threshold is equally inert — every row of
    the clipped matrix is returned bit-identical."""
    ref = _engine(grid).run(*grid["args"], batch_size=8)
    res = _engine(grid).run(*grid["args"], batch_size=8,
                            fault=FaultSpec(quarantine=True))
    _assert_results_equal(res, ref)
    np.testing.assert_array_equal(res.fault["rounds_quarantined"],
                                  np.zeros((E, N), np.int32))
    cfg = DecentralizedConfig(rounds=ROUNDS, local_epochs=2, eval_every=2,
                              robust="norm_clip", robust_clip=1e6)
    loose_clip = SweepEngine(sgd(1e-2), grid["loss_fn"], grid["acc_fn"],
                             cfg).run(*grid["args"], batch_size=8,
                                      fault=FaultSpec(quarantine=True))
    _assert_results_equal(loose_clip, ref)


# ----------------------------------------------------------------------
# the corruption draw + modes
# ----------------------------------------------------------------------
def test_faulty_mask_rate_extremes_and_determinism():
    spec = FaultSpec()
    assert not np.asarray(spec.faulty_mask(0.0, 7, 3, 16)).any()
    assert np.asarray(spec.faulty_mask(1.0, 7, 3, 16)).all()
    a = np.asarray(spec.faulty_mask(0.5, 7, 3, 16))
    np.testing.assert_array_equal(a, np.asarray(spec.faulty_mask(0.5, 7, 3, 16)))
    assert not (a == np.asarray(spec.faulty_mask(0.5, 7, 4, 16))).all()
    # fold index 3 is disjoint from the participation draw (index 2)
    p = np.asarray(ParticipationSpec().active_mask(0.5, 7, 3, 16))
    assert not (a == p).all()


@pytest.mark.parametrize("mode", FAULT_MODES)
def test_corruption_modes(mode):
    spec = FaultSpec(mode=mode, noise_scale=0.5, byz_scale=3.0)
    p = {"w": jax.random.normal(jax.random.key(0), (6, 4, 3)) + 1.0,
         "b": jax.random.normal(jax.random.key(1), (6, 5))}
    bad = spec.corrupt(p, 0, 2)
    for k in p:
        b, o = np.asarray(bad[k]), np.asarray(p[k])
        if mode == "nan":
            assert np.isnan(b).all(), k
        elif mode == "inf":
            assert np.isinf(b).all(), k
        elif mode == "zero":
            np.testing.assert_array_equal(b, np.zeros_like(o))
        elif mode == "signflip":
            np.testing.assert_allclose(b, -3.0 * o, rtol=1e-6)
        else:  # noise: every coordinate perturbed, deterministically
            assert (b != o).all(), k
            np.testing.assert_array_equal(
                b, np.asarray(spec.corrupt(p, 0, 2)[k]))


def test_fault_spec_validation():
    with pytest.raises(ValueError, match="mode"):
        FaultSpec(mode="gremlins")
    with pytest.raises(ValueError, match="probation"):
        FaultSpec(quarantine=True, probation=0)
    assert set(FAULT_MODES) == {"nan", "inf", "noise", "signflip", "zero"}


def test_fault_rates_require_spec(grid):
    engine = _engine(grid)
    with pytest.raises(ValueError, match="[Ff]ault"):
        engine.run(*grid["args"], batch_size=8,
                   fault_rates=np.ones(E, np.float32))


# ----------------------------------------------------------------------
# cross-mode equality at a genuinely nonzero rate
# ----------------------------------------------------------------------
def test_nonzero_rate_modes_bit_identical(grid):
    """rate grid [0, .4, .4] with noise faults + quarantine: scanned ==
    chunked (absolute round indices drive the draw) == unrolled,
    including every fault digest array."""
    engine = _engine(grid)
    spec = FaultSpec(mode="noise", quarantine=True, probation=2)
    rates = np.asarray([0.0, 0.4, 0.4], np.float32)
    run = lambda **kw: engine.run(*grid["args"], batch_size=8, fault=spec,
                                  fault_rates=rates, **kw)
    ref = run()
    for label, other in [("chunked", run(chunk_rounds=3)),
                         ("unrolled", run(unroll_eval=True))]:
        _assert_results_equal(other, ref)
        for k in ref.fault:
            np.testing.assert_array_equal(ref.fault[k], other.fault[k],
                                          err_msg=(label, k))
    # the draw actually lands faults at this rate
    assert (np.asarray(ref.fault["fault_rounds"])[1:] > 0).any()


def test_per_experiment_rates_ride_the_vmap_axis(grid):
    """One compiled program serves a fault-rate grid: the rate-0.0 row
    of a mixed [0, .5, .5] run equals the fault-free run bit-for-bit
    (rates are carried data, not static config)."""
    engine = _engine(grid)
    ref = engine.run(*grid["args"], batch_size=8)
    mixed = engine.run(*grid["args"], batch_size=8,
                       fault=FaultSpec(mode="signflip"),
                       fault_rates=np.asarray([0.0, 0.5, 0.5], np.float32))
    np.testing.assert_array_equal(mixed.train_loss[0], ref.train_loss[0])
    np.testing.assert_array_equal(mixed.iid_acc[0], ref.iid_acc[0])
    np.testing.assert_array_equal(
        mixed.fault["fault_rounds"][0], np.zeros(N, np.int32))


# ----------------------------------------------------------------------
# quarantine state machine + containment
# ----------------------------------------------------------------------
def test_nan_faults_detected_immediately_and_contained(grid):
    """NaN-poisoned published rows trip the nonfinite screen the same
    round they appear (first_quar == first_fault), quarantined columns
    are excised before mixing, and every node's parameters stay finite —
    while the same faults WITHOUT quarantine poison the plane."""
    engine = _engine(grid)
    rates = np.asarray([0.0, 0.5, 0.5], np.float32)
    res = engine.run(*grid["args"], batch_size=8,
                     fault=FaultSpec(mode="nan", quarantine=True,
                                     probation=2),
                     fault_rates=rates)
    f = res.fault
    faulted = np.asarray(f["fault_rounds"]) > 0
    assert faulted[1:].any()
    ff, fq = np.asarray(f["first_fault"]), np.asarray(f["first_quar"])
    np.testing.assert_array_equal(fq[faulted], ff[faulted])
    assert (np.asarray(f["quar_fault_rounds"])[faulted] > 0).all()
    for leaf in jax.tree.leaves(res.params):
        assert np.isfinite(np.asarray(leaf)).all()
    # control: same faults, no quarantine, plain mean → contagion
    loose = engine.run(*grid["args"], batch_size=8,
                       fault=FaultSpec(mode="nan"), fault_rates=rates)
    assert not all(np.isfinite(np.asarray(l)).all()
                   for l in jax.tree.leaves(loose.params))


def test_robust_aggregation_contains_nan_without_quarantine(grid):
    """The robust rules are the OTHER containment mechanism: trimmed /
    median keep every parameter finite under NaN faults with the screen
    off (the poisoned rows are outliers the order statistics drop).

    Containment is only guaranteed while each neighbourhood sees at most
    ``trim_k`` faulty rows — on ring(4) that means at most ONE faulty
    node among each node and its two neighbours per round.  The draw is
    deterministic (``FaultSpec.seed`` + the default per-experiment
    fseeds); seed 4 at rate 0.15 realizes 4 single-node fault rounds
    across the nonzero-rate experiments without ever drawing two in one
    neighbourhood, and the test asserts exactly that precondition."""
    rates = np.asarray([0.0, 0.15, 0.15], np.float32)
    spec = FaultSpec(mode="nan", seed=4)
    adj = np.asarray(grid["topo"].adjacency) + np.eye(N)
    n_faults = 0
    for e in range(E):
        for r in range(ROUNDS):
            faulty = np.asarray(spec.faulty_mask(
                rates[e], np.uint32(spec.seed + e), r, N)).astype(int)
            assert (adj @ faulty).max() <= 1, (e, r, faulty)
            n_faults += faulty.sum()
    assert n_faults > 0
    for robust in ["trimmed", "median"]:
        res = _engine(grid, robust=robust).run(
            *grid["args"], batch_size=8, fault=spec, fault_rates=rates)
        assert (np.asarray(res.fault["fault_rounds"])[1:] > 0).any()
        for leaf in jax.tree.leaves(res.params):
            assert np.isfinite(np.asarray(leaf)).all(), robust


def test_fault_and_participation_compose(grid):
    """Both carries thread the same scan: dropout (fold 2) and faults
    (fold 3) draw independently; rate-1.0 participation + rate-0.0
    faults still collapse to the synchronous run."""
    engine = _engine(grid)
    ref = engine.run(*grid["args"], batch_size=8)
    res = engine.run(*grid["args"], batch_size=8,
                     participation=ParticipationSpec(),
                     participation_rates=np.ones(E, np.float32),
                     fault=FaultSpec(quarantine=True))
    _assert_results_equal(res, ref)
    assert res.participation is not None and res.fault is not None
    # and a genuinely mixed run completes with both digests populated
    both = engine.run(*grid["args"], batch_size=8,
                      participation=ParticipationSpec(),
                      participation_rates=np.full(E, 0.6, np.float32),
                      fault=FaultSpec(mode="signflip", quarantine=True),
                      fault_rates=np.full(E, 0.3, np.float32))
    assert (np.asarray(both.participation["rounds_active"]) < ROUNDS).any()
    assert (np.asarray(both.fault["fault_rounds"]) > 0).any()


def test_quarantine_renormalize_matches_participation_semantics():
    c = jnp.asarray([[0.5, 0.25, 0.25], [0.3, 0.4, 0.3], [0.2, 0.3, 0.5]])
    none = jnp.zeros((3,), bool)
    np.testing.assert_array_equal(
        np.asarray(quarantine_renormalize(c, none)), np.asarray(c))
    out = np.asarray(quarantine_renormalize(c, jnp.asarray([False, True,
                                                            False])))
    np.testing.assert_allclose(out.sum(-1), np.ones(3), rtol=1e-6)
    np.testing.assert_array_equal(out[[0, 2], 1], np.zeros(2))


def test_quarantine_summary_digest():
    fault = {
        "fault_rounds": np.asarray([3, 0, 1, 0]),
        "rounds_quarantined": np.asarray([4, 2, 0, 0]),
        "quar_fault_rounds": np.asarray([3, 0, 0, 0]),
        "first_fault": np.asarray([2, -1, 5, -1]),
        "first_quar": np.asarray([3, 6, -1, -1]),
    }
    s = quarantine_summary(fault, rounds=10)
    assert s["n_faulty_nodes"] == 2
    assert s["fault_round_rate"] == pytest.approx(4 / 40)
    assert s["rounds_quarantined_max"] == 4
    assert s["detection_lag_mean"] == pytest.approx(1.0)  # node 0 only
    assert s["n_undetected"] == 1                         # node 2
    # node 1 (never faulty) spent 2/10 rounds quarantined; node 3 clean
    assert s["false_positive_rate"] == pytest.approx(2 / 20)
    # all-faulted edge case: FPR undefined
    all_bad = {k: np.asarray(v)[:1] for k, v in fault.items()}
    assert quarantine_summary(all_bad, rounds=10)["false_positive_rate"] is None


# ----------------------------------------------------------------------
# crash-safe checkpointing
# ----------------------------------------------------------------------
def test_checkpoint_dir_requires_chunking(grid):
    with pytest.raises(ValueError, match="chunk_rounds"):
        _engine(grid).run(*grid["args"], batch_size=8,
                          checkpoint_dir="/tmp/nope")


def test_resume_reproduces_uninterrupted_run(grid, tmp_path):
    """Chunked run with checkpointing == plain chunked run; dropping the
    later checkpoints and resuming reproduces the uninterrupted result
    (metrics, params, fault digest) bit-for-bit."""
    engine = _engine(grid)
    spec = FaultSpec(mode="noise", quarantine=True)
    rates = np.asarray([0.0, 0.4, 0.4], np.float32)
    run = lambda **kw: engine.run(*grid["args"], batch_size=8, fault=spec,
                                  fault_rates=rates, chunk_rounds=1, **kw)
    full = run()
    d = str(tmp_path / "ckpt")
    with_ckpt = run(checkpoint_dir=d)
    _assert_results_equal(with_ckpt, full)
    cks = sorted(os.listdir(d))
    assert len(cks) == ROUNDS - 1  # boundaries only, no final-round save
    for fn in cks[1:]:
        os.remove(os.path.join(d, fn))
    resumed = run(checkpoint_dir=d, resume=True)
    _assert_results_equal(resumed, full)
    for k in full.fault:
        np.testing.assert_array_equal(full.fault[k], resumed.fault[k],
                                      err_msg=k)
    # resume with an empty directory is a fresh start, not an error
    fresh = run(checkpoint_dir=str(tmp_path / "empty"), resume=True)
    _assert_results_equal(fresh, full)


# ----------------------------------------------------------------------
# kill-mid-sweep: the crash hook exits hard after 2 saved chunks; the
# resumed run must reproduce the uninterrupted analytics exactly.
# 8 virtual devices — the mesh path's device-put/reput is what a real
# crash recovery exercises (subprocess: XLA_FLAGS before jax init).
# ----------------------------------------------------------------------
_SETUP = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax, jax.numpy as jnp
    assert len(jax.devices()) == 8, jax.devices()

    from repro.core.decentralized import (
        DecentralizedConfig, coeffs_stack, stack_params)
    from repro.core.dynamic import FaultSpec
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
    spec = FaultSpec(mode="noise", quarantine=True)
    rates = np.asarray([0.0, 0.4, 0.4], np.float32)
    ckpt_dir = os.environ["FAULT_TEST_CKPT_DIR"]
    run = lambda **kw: engine.run(
        params0, coeffs, bank, indices, data_idx, st(tb), st(ob),
        batch_size=8, fault=spec, fault_rates=rates, mesh=mesh,
        chunk_rounds=1, **kw)
""")

_SCRIPT_KILL = _SETUP + textwrap.dedent("""
    print("starting doomed run", flush=True)
    run(checkpoint_dir=ckpt_dir)
    print("SHOULD NEVER GET HERE")
""")

_SCRIPT_RESUME = _SETUP + textwrap.dedent("""
    import jax
    saved = sorted(os.listdir(ckpt_dir))
    assert len(saved) == 2, saved   # killed after exactly 2 chunk saves
    resumed = run(checkpoint_dir=ckpt_dir, resume=True)
    full = run()
    np.testing.assert_array_equal(resumed.train_loss, full.train_loss)
    np.testing.assert_array_equal(resumed.iid_acc, full.iid_acc)
    np.testing.assert_array_equal(resumed.ood_acc, full.ood_acc)
    for a, b in zip(jax.tree.leaves(resumed.params),
                    jax.tree.leaves(full.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for k in full.fault:
        np.testing.assert_array_equal(resumed.fault[k], full.fault[k],
                                      err_msg=k)
    from repro.core.analytics import quarantine_summary
    for e in range(E):
        s = quarantine_summary({k: v[e] for k, v in resumed.fault.items()},
                               R)
        assert 0.0 <= s["fault_round_rate"] <= 1.0
    print("FAULT_RESUME_OK")
""")


def test_kill_and_resume_subprocess(tmp_path):
    repo = os.path.dirname(os.path.dirname(__file__))
    env = dict(os.environ, PYTHONPATH="src",
               FAULT_TEST_CKPT_DIR=str(tmp_path))
    killed = subprocess.run(
        [sys.executable, "-c", _SCRIPT_KILL],
        env=dict(env, REPRO_SWEEP_CRASH_AFTER_CHUNKS="2"),
        capture_output=True, text=True, timeout=600, cwd=repo)
    assert killed.returncode == 17, (killed.returncode,
                                     killed.stdout[-2000:],
                                     killed.stderr[-3000:])
    assert "SHOULD NEVER GET HERE" not in killed.stdout
    resumed = subprocess.run([sys.executable, "-c", _SCRIPT_RESUME],
                             env=env, capture_output=True, text=True,
                             timeout=600, cwd=repo)
    assert "FAULT_RESUME_OK" in resumed.stdout, (resumed.stdout[-2000:],
                                                 resumed.stderr[-3000:])
