"""Sweep-engine equivalence: the scanned / vmapped paths must reproduce
the legacy per-round loop exactly (same histories, same final params),
including per-round Random resampling and dynamic link-failure schedules.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.decentralized import (
    DecentralizedConfig,
    DecentralizedTrainer,
    coeffs_stack,
    eval_round_indices,
    stack_params,
)
from repro.core.dynamic import dynamic_mixing_matrix, link_failure_schedule
from repro.core.strategies import AggregationStrategy
from repro.core.sweep import SweepEngine, gather_round_batch
from repro.core.topology import ring
from repro.data.distribution import node_datasets
from repro.data.pipeline import NodeBatcher, make_test_batch
from repro.data.synthetic import make_dataset
from repro.training.optimizer import sgd

N, ROUNDS = 4, 5
# epoch_shuffle=False: these equivalence tests drive hand-built one-epoch
# batch stacks, i.e. the legacy replay-E-times behavior the flag preserves.
CFG = DecentralizedConfig(rounds=ROUNDS, local_epochs=2, eval_every=2,
                          epoch_shuffle=False)


# ----------------------------------------------------------------------
# tiny MLP regression setting (fast; exercises multi-leaf pytrees)
# ----------------------------------------------------------------------
def _loss_fn(p, batch):
    h = jnp.tanh(batch["x"] @ p["w1"] + p["b1"][None])
    pred = h @ p["w2"] + p["b2"][None]
    return jnp.mean((pred - batch["y"]) ** 2)


def _eval_fn(p, tb):
    h = jnp.tanh(tb["x"] @ p["w1"] + p["b1"][None])
    pred = h @ p["w2"] + p["b2"][None]
    return jnp.mean((jnp.abs(pred - tb["y"]) < 0.5).astype(jnp.float32))


def _mlp_init(seed):
    r = np.random.default_rng(seed)
    return {
        "w1": jnp.asarray(r.normal(size=(5, 8)) * 0.3, jnp.float32),
        "b1": jnp.zeros((8,), jnp.float32),
        "w2": jnp.asarray(r.normal(size=(8, 2)) * 0.3, jnp.float32),
        "b2": jnp.zeros((2,), jnp.float32),
    }


def _mlp_batches_fn(r):
    g = np.random.default_rng(100 + r)
    return {
        "x": jnp.asarray(g.normal(size=(N, 3, 8, 5)), jnp.float32),
        "y": jnp.asarray(g.normal(size=(N, 3, 8, 2)), jnp.float32),
    }


def _mlp_tests():
    g = np.random.default_rng(7)
    mk = lambda: {
        "x": jnp.asarray(g.normal(size=(16, 5)), jnp.float32),
        "y": jnp.asarray(g.normal(size=(16, 2)), jnp.float32),
    }
    return mk(), mk()


def _assert_hist_equal(h1, h2):
    assert [m.round for m in h1] == [m.round for m in h2]
    for a, b in zip(h1, h2):
        np.testing.assert_array_equal(a.iid_acc, b.iid_acc)
        np.testing.assert_array_equal(a.ood_acc, b.ood_acc)
        np.testing.assert_array_equal(a.train_loss, b.train_loss)


def _assert_trees_equal(t1, t2):
    for a, b in zip(jax.tree.leaves(t1), jax.tree.leaves(t2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


#: f32 tolerance for programs that XLA compiles differently (scanned vs
#: unrolled vs the legacy trainer): each may fuse and order its float
#: operations its own way, so they agree to rounding, not bit-for-bit
F32_TOL = 1e-5


def _assert_hist_close(h1, h2):
    assert [m.round for m in h1] == [m.round for m in h2]
    for a, b in zip(h1, h2):
        for k in ("iid_acc", "ood_acc", "train_loss"):
            np.testing.assert_allclose(getattr(a, k), getattr(b, k),
                                       rtol=F32_TOL, atol=F32_TOL)


def _assert_trees_close(t1, t2):
    for a, b in zip(jax.tree.leaves(t1), jax.tree.leaves(t2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=F32_TOL, atol=F32_TOL)


def _run_mlp(strategy, cfg, coeffs_fn=None):
    trainer = DecentralizedTrainer(
        ring(N), strategy, sgd(1e-2), _loss_fn, _eval_fn, cfg,
        coeffs_fn=coeffs_fn)
    params = stack_params([_mlp_init(0)] * N)
    tb, ob = _mlp_tests()
    return trainer.run(params, _mlp_batches_fn, tb, ob)


@pytest.mark.parametrize("kind", ["unweighted", "random"])
def test_scan_matches_unrolled_bitexact(kind):
    """The single-scan path == the legacy loop, incl. the Random
    baseline's per-round mixing-matrix resampling."""
    strat = AggregationStrategy(kind, seed=3)
    p_scan, h_scan = _run_mlp(strat, CFG)
    p_unr, h_unr = _run_mlp(strat, dataclasses.replace(CFG, unroll_eval=True))
    _assert_hist_equal(h_scan, h_unr)
    _assert_trees_equal(p_scan, p_unr)


def test_scan_matches_unrolled_dynamic_link_failure():
    """A core.dynamic drop_edges coefficient schedule is pure data to the
    scanned path and host control flow to the unrolled one — same run."""
    topo = ring(N)
    strat = AggregationStrategy("degree", tau=0.1, seed=1)
    fn = lambda r: dynamic_mixing_matrix(topo, strat, r, p_fail=0.5)
    p_scan, h_scan = _run_mlp(strat, CFG, coeffs_fn=fn)
    p_unr, h_unr = _run_mlp(
        strat, dataclasses.replace(CFG, unroll_eval=True), coeffs_fn=fn)
    _assert_hist_equal(h_scan, h_unr)
    _assert_trees_equal(p_scan, p_unr)


def test_link_failure_schedule_is_the_coeffs_stack():
    topo = ring(N)
    strat = AggregationStrategy("degree", tau=0.1, seed=1)
    sched = link_failure_schedule(topo, strat, ROUNDS, p_fail=0.5)
    assert sched.shape == (ROUNDS, N, N)
    stack = coeffs_stack(
        topo, strat, ROUNDS,
        coeffs_fn=lambda r: dynamic_mixing_matrix(topo, strat, r, 0.5))
    np.testing.assert_array_equal(sched, stack)


def test_coeffs_stack_random_resamples_per_round():
    stack = coeffs_stack(ring(N), AggregationStrategy("random", seed=0),
                         ROUNDS)
    assert stack.shape == (ROUNDS, N, N)
    assert not np.array_equal(stack[0], stack[1])
    # coeffs_stack materializes the float32 device-side coefficient
    # program (core/coeffs.py) — rows are stochastic to f32 precision
    np.testing.assert_allclose(stack.sum(axis=2), 1.0, atol=1e-6)


def test_eval_round_indices_matches_legacy_rule():
    assert eval_round_indices(5, 2) == [1, 3, 4]
    assert eval_round_indices(4, 1) == [0, 1, 2, 3]
    assert eval_round_indices(6, 10) == [5]


# ----------------------------------------------------------------------
# NodeBatcher bank/indices == materialized round batches
# ----------------------------------------------------------------------
def test_bank_gather_reproduces_round_batches():
    train = make_dataset("mnist", 600, seed=0)
    parts = node_datasets(train, N, ood_node=1, q=0.10, seed=0)
    nb = NodeBatcher(parts, batch_size=8, steps_per_epoch=3, seed=0)
    bank = jax.tree.map(
        lambda x: jnp.asarray(x)[None], nb.sample_bank())  # D=1
    for r in (0, 2):
        want = nb.round_batches(r)
        got = gather_round_batch(
            bank, jnp.asarray(0), jnp.asarray(nb.round_indices(r)),
            batch_size=8)
        assert set(got) == set(want)
        for k in want:
            np.testing.assert_array_equal(np.asarray(got[k]), want[k])


# ----------------------------------------------------------------------
# vmapped grid == per-experiment legacy runs (real data pipeline)
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def mnist_setting():
    train = make_dataset("mnist", 600, seed=0)
    test = make_dataset("mnist", 120, seed=9)
    from repro.data.backdoor import backdoored_testset
    from repro.models.paper_models import (
        classifier_accuracy, classifier_loss, ffn_apply, ffn_init)

    loss_fn = classifier_loss(ffn_apply)
    acc_fn = classifier_accuracy(ffn_apply)
    configs = {}
    for seed in (0, 1):
        parts = node_datasets(train, N, ood_node=0, q=0.10, seed=seed)
        nb = NodeBatcher(parts, batch_size=8, steps_per_epoch=2, seed=seed)
        tb = make_test_batch(test, 48, seed=seed)
        ob = make_test_batch(backdoored_testset(test, seed=seed), 48,
                             seed=seed)
        configs[seed] = (nb, tb, ob)
    return loss_fn, acc_fn, ffn_init, configs


def test_sweep_grid_matches_legacy_per_experiment(mnist_setting):
    """Strategies × seeds through ONE compiled program == N independent
    legacy DecentralizedTrainer.run calls, to f32 rounding."""
    loss_fn, acc_fn, init, configs = mnist_setting
    topo = ring(N)
    cfg = DecentralizedConfig(rounds=3, local_epochs=1, eval_every=2)
    cells = [("unweighted", 0), ("random", 0), ("degree", 1), ("fl", 1)]

    seeds = sorted(configs)
    raw = [configs[s][0].sample_bank() for s in seeds]
    cap = max(b["x"].shape[1] for b in raw)
    pad = lambda a: np.pad(
        a, [(0, 0), (0, cap - a.shape[1])] + [(0, 0)] * (a.ndim - 2))
    bank = {k: np.stack([pad(b[k]) for b in raw]) for k in raw[0]}
    indices = np.stack(
        [configs[s][0].all_round_indices(cfg.rounds) for s in seeds])
    data_idx = np.array([seeds.index(s) for _, s in cells])
    coeffs = np.stack([
        coeffs_stack(topo, AggregationStrategy(k, seed=s), cfg.rounds,
                     configs[s][0].data_counts())
        for k, s in cells])
    params0 = jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[stack_params([init(jax.random.key(s))] * N) for _, s in cells])
    stack_tests = lambda which: {
        k: jnp.stack([jnp.asarray(configs[s][which][k]) for _, s in cells])
        for k in configs[0][which]}

    engine = SweepEngine(sgd(1e-2), loss_fn, acc_fn, cfg)
    res = engine.run(params0, coeffs, bank, indices, data_idx,
                     stack_tests(1), stack_tests(2), batch_size=8)
    res_unrolled = engine.run(params0, coeffs, bank, indices, data_idx,
                              stack_tests(1), stack_tests(2), batch_size=8,
                              unroll_eval=True)
    np.testing.assert_allclose(res.train_loss, res_unrolled.train_loss,
                               rtol=F32_TOL, atol=F32_TOL)
    np.testing.assert_allclose(res.iid_acc, res_unrolled.iid_acc,
                               rtol=F32_TOL, atol=F32_TOL)
    _assert_trees_close(res.params, res_unrolled.params)

    for e, (kind, seed) in enumerate(cells):
        nb, tb, ob = configs[seed]
        trainer = DecentralizedTrainer(
            topo, AggregationStrategy(kind, seed=seed), sgd(1e-2),
            loss_fn, acc_fn, cfg, data_counts=nb.data_counts())
        fp, hist = trainer.run(
            stack_params([init(jax.random.key(seed))] * N),
            lambda r: jax.tree.map(jnp.asarray, nb.round_batches(r)),
            jax.tree.map(jnp.asarray, tb), jax.tree.map(jnp.asarray, ob))
        _assert_hist_close(hist, res.history(e))
        _assert_trees_close(fp, res.experiment_params(e))


# ----------------------------------------------------------------------
# chunked-rounds + (single-device) sharded modes == scanned, bit-for-bit
# ----------------------------------------------------------------------
def _mnist_grid(mnist_setting, cfg):
    """Assemble the 4-cell grid of test_sweep_grid... as engine inputs."""
    loss_fn, acc_fn, init, configs = mnist_setting
    topo = ring(N)
    cells = [("unweighted", 0), ("random", 0), ("degree", 1), ("fl", 1)]
    seeds = sorted(configs)
    raw = [configs[s][0].sample_bank() for s in seeds]
    cap = max(b["x"].shape[1] for b in raw)
    pad = lambda a: np.pad(
        a, [(0, 0), (0, cap - a.shape[1])] + [(0, 0)] * (a.ndim - 2))
    bank = {k: np.stack([pad(b[k]) for b in raw]) for k in raw[0]}
    indices = np.stack(
        [configs[s][0].all_round_indices(cfg.rounds) for s in seeds])
    data_idx = np.array([seeds.index(s) for _, s in cells])
    coeffs = np.stack([
        coeffs_stack(topo, AggregationStrategy(k, seed=s), cfg.rounds,
                     configs[s][0].data_counts())
        for k, s in cells])
    params0 = jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[stack_params([init(jax.random.key(s))] * N) for _, s in cells])
    stack_tests = lambda which: {
        k: jnp.stack([jnp.asarray(configs[s][which][k]) for _, s in cells])
        for k in configs[0][which]}
    engine = SweepEngine(sgd(1e-2), loss_fn, acc_fn, cfg)
    args = (params0, coeffs, bank, indices, data_idx,
            stack_tests(1), stack_tests(2))
    return engine, args


def _assert_results_equal(a, b):
    np.testing.assert_array_equal(a.train_loss, b.train_loss)
    np.testing.assert_array_equal(a.iid_acc, b.iid_acc)
    np.testing.assert_array_equal(a.ood_acc, b.ood_acc)
    _assert_trees_equal(a.params, b.params)


def test_chunked_rounds_matches_scanned_bitexact(mnist_setting):
    """chunk_rounds=2 over R=3 (a full chunk + a remainder chunk) resumes
    the exact scan carry — metrics and params bit-identical."""
    cfg = DecentralizedConfig(rounds=3, local_epochs=1, eval_every=2)
    engine, args = _mnist_grid(mnist_setting, cfg)
    res = engine.run(*args, batch_size=8)
    res_chunked = engine.run(*args, batch_size=8, chunk_rounds=2)
    _assert_results_equal(res_chunked, res)


def test_chunked_donated_params0_matches_scanned(mnist_setting):
    """A donated carry copies the caller's params0 and leaves it intact;
    donate_params0=True donates those buffers instead (they are deleted),
    with bit-identical results."""
    cfg = DecentralizedConfig(rounds=3, local_epochs=1, eval_every=2)
    engine, args = _mnist_grid(mnist_setting, cfg)
    res = engine.run(*args, batch_size=8)
    fresh = lambda: jax.tree.map(jnp.copy, args[0])
    kept = fresh()
    _assert_results_equal(engine.run(kept, *args[1:], batch_size=8,
                                     chunk_rounds=2, donate=True), res)
    assert not any(x.is_deleted() for x in jax.tree.leaves(kept))
    handed = fresh()
    _assert_results_equal(engine.run(handed, *args[1:], batch_size=8,
                                     chunk_rounds=2, donate=True,
                                     donate_params0=True), res)
    assert all(x.is_deleted() for x in jax.tree.leaves(handed))


def test_sharded_single_device_mesh_matches_scanned(mnist_setting):
    """mesh=make_sweep_mesh(1) exercises the full shard_map machinery on
    the 1 CPU device the main pytest process sees (the 8-device version
    lives in tests/test_sweep_sharded.py, subprocess)."""
    from repro.launch.mesh import make_sweep_mesh

    cfg = DecentralizedConfig(rounds=3, local_epochs=1, eval_every=2)
    engine, args = _mnist_grid(mnist_setting, cfg)
    res = engine.run(*args, batch_size=8)
    res_sharded = engine.run(*args, batch_size=8, mesh=make_sweep_mesh(1))
    _assert_results_equal(res_sharded, res)
    res_both = engine.run(*args, batch_size=8, mesh=make_sweep_mesh(1),
                          chunk_rounds=2)
    _assert_results_equal(res_both, res)


def test_unroll_rejects_shard_and_chunk(mnist_setting):
    from repro.launch.mesh import make_sweep_mesh

    cfg = DecentralizedConfig(rounds=3, local_epochs=1, eval_every=2)
    engine, args = _mnist_grid(mnist_setting, cfg)
    with pytest.raises(ValueError):
        engine.run(*args, batch_size=8, unroll_eval=True, chunk_rounds=2)
    with pytest.raises(ValueError):
        engine.run(*args, batch_size=8, unroll_eval=True,
                   mesh=make_sweep_mesh(1))


def test_epoch_shuffle_distinct_passes():
    """epoch_shuffle=True + NodeBatcher(local_epochs=E) trains on E
    *different* batch orders; the legacy flag replays one order E times —
    the two runs genuinely diverge."""
    train = make_dataset("mnist", 400, seed=0)
    parts = node_datasets(train, N, ood_node=0, q=0.10, seed=0)
    from repro.models.paper_models import (
        classifier_accuracy, classifier_loss, ffn_apply, ffn_init)

    tb = make_test_batch(make_dataset("mnist", 80, seed=9), 32)
    run = lambda nb, cfg: DecentralizedTrainer(
        ring(N), AggregationStrategy("unweighted"), sgd(1e-2),
        classifier_loss(ffn_apply), classifier_accuracy(ffn_apply),
        cfg).run(
            stack_params([ffn_init(jax.random.key(0))] * N),
            lambda r: jax.tree.map(jnp.asarray, nb.round_batches(r)),
            jax.tree.map(jnp.asarray, tb), jax.tree.map(jnp.asarray, tb))

    nb_e = NodeBatcher(parts, batch_size=8, steps_per_epoch=2, seed=0,
                       local_epochs=2)
    cfg_e = DecentralizedConfig(rounds=2, local_epochs=2, eval_every=1)
    p_shuf, _ = run(nb_e, cfg_e)

    nb_l = NodeBatcher(parts, batch_size=8, steps_per_epoch=2, seed=0)
    cfg_l = dataclasses.replace(cfg_e, epoch_shuffle=False)
    p_legacy, _ = run(nb_l, cfg_l)

    leaves = zip(jax.tree.leaves(p_shuf), jax.tree.leaves(p_legacy))
    assert any(not np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in leaves)


def test_epoch_shuffle_rejects_indivisible_batch_axis():
    """A 3-step batch axis cannot be local_epochs=2 distinct passes."""
    from repro.core.decentralized import make_local_train_fn

    fn = make_local_train_fn(_loss_fn, sgd(1e-2), local_epochs=2,
                             epoch_shuffle=True)
    params = _mlp_init(0)
    opt = sgd(1e-2).init(params)
    batches = _mlp_batches_fn(0)
    one_node = jax.tree.map(lambda x: x[0], batches)  # (3, 8, ...)
    with pytest.raises(ValueError, match="not divisible"):
        fn(params, opt, one_node)


# ----------------------------------------------------------------------
# pallas aggregation routing
# ----------------------------------------------------------------------
def test_pallas_mix_impl_matches_einsum():
    """mix_impl='pallas' routes Eq. (2) through kernels/gossip_mix; the
    fused-MAC accumulation matches the einsum to f32 rounding."""
    strat = AggregationStrategy("degree", tau=0.1)
    cfg = DecentralizedConfig(rounds=2, local_epochs=1, eval_every=1)
    p_e, h_e = _run_mlp(strat, cfg)
    p_p, h_p = _run_mlp(strat, dataclasses.replace(cfg, mix_impl="pallas"))
    for a, b in zip(jax.tree.leaves(p_e), jax.tree.leaves(p_p)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
    for ma, mb in zip(h_e, h_p):
        np.testing.assert_allclose(ma.train_loss, mb.train_loss,
                                   rtol=1e-5, atol=1e-6)
