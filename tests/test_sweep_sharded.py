"""Device-sharded sweep engine vs scanned vs unrolled, on 8 forced CPU
devices — the three execution modes must agree to f32 rounding
(DESIGN.md §8; each is its own XLA program, free to order float
operations its own way), including eval_every > 1, mix_impl="pallas", a
link-failure coeffs stack, chunked rounds, E-to-mesh padding (E=3
experiments over 8 devices), in-scan coefficient programs (DESIGN.md
§9: program state sharded on E, reactive link-failure cell, program ==
materialized stack under shard_map), and in-scan streaming analytics
(DESIGN.md §10: carry sharded on E, summaries equal across scanned /
chunked / mesh modes and equal to the host-side
``propagation.py`` oracles).

Runs in a subprocess because XLA_FLAGS must be set before jax initializes
(the main pytest process must keep seeing 1 device — the device-count
override is never global; see conftest.py).
"""
import os
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import numpy as np, jax, jax.numpy as jnp
    assert len(jax.devices()) == 8, jax.devices()

    from repro.core.decentralized import (
        DecentralizedConfig, coeffs_stack, stack_params)
    from repro.core.dynamic import link_failure_schedule
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

    N = 4
    train = make_dataset("mnist", 400, seed=0)
    test = make_dataset("mnist", 100, seed=9)
    loss_fn = classifier_loss(ffn_apply)
    acc_fn = classifier_accuracy(ffn_apply)
    cfg = DecentralizedConfig(rounds=4, local_epochs=2, eval_every=2)
    topo = ring(N)
    parts = node_datasets(train, N, ood_node=0, q=0.10, seed=0)
    nb = NodeBatcher(parts, batch_size=8, steps_per_epoch=2, seed=0,
                     local_epochs=2)
    tb = make_test_batch(test, 32, seed=0)
    ob = make_test_batch(backdoored_testset(test, seed=0), 32, seed=0)

    kinds = ["unweighted", "random", "degree"]   # E=3 → pads to 8 devices
    bank = {k: v[None] for k, v in nb.sample_bank().items()}
    indices = nb.all_round_indices(cfg.rounds)[None]
    data_idx = np.zeros(len(kinds), np.int32)
    coeffs = np.stack([
        coeffs_stack(topo, AggregationStrategy(k, seed=0), cfg.rounds,
                     nb.data_counts())
        for k in kinds])
    # experiment 2 runs a core.dynamic link-failure schedule instead
    coeffs[2] = link_failure_schedule(
        topo, AggregationStrategy("degree", tau=0.1, seed=1), cfg.rounds,
        p_fail=0.5)
    params0 = jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[stack_params([ffn_init(jax.random.key(0))] * N)] * len(kinds))
    st = lambda t: {k: jnp.stack([jnp.asarray(t[k])] * len(kinds))
                    for k in t}
    mesh = make_sweep_mesh()   # all 8 virtual devices

    def close(a, b, **kw):  # f32 tolerance between compiled programs
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5, **kw)

    def check(r, ref, label):
        close(r.train_loss, ref.train_loss)
        close(r.iid_acc, ref.iid_acc)
        close(r.ood_acc, ref.ood_acc)
        for a, b in zip(jax.tree.leaves(r.params),
                        jax.tree.leaves(ref.params)):
            close(np.asarray(a), np.asarray(b))
        print(label, "ok")

    for impl in ("einsum", "pallas"):
        c = dataclasses.replace(cfg, mix_impl=impl)
        engine = SweepEngine(sgd(1e-2), loss_fn, acc_fn, c)
        run = lambda **kw: engine.run(
            params0, coeffs, bank, indices, data_idx, st(tb), st(ob),
            batch_size=8, **kw)
        ref = run()
        check(run(unroll_eval=True), ref, impl + "/unrolled")
        check(run(mesh=mesh), ref, impl + "/sharded")
        check(run(mesh=mesh, chunk_rounds=3), ref, impl + "/sharded+chunk")

    # in-scan coefficient programs (DESIGN.md §9): per-experiment state
    # shards on E exactly like a slab; program == materialized stack
    # under shard_map, incl. a reactive link-failure cell
    from repro.core.coeffs import ProgramCoeffs, program_for, stack_states

    ps = [program_for(topo, AggregationStrategy(k, tau=0.1, seed=e),
                      data_counts=nb.data_counts(), p_fail=pf,
                      reactive=True)
          for e, (k, pf) in enumerate(
              [("unweighted", 0.0), ("random", 0.0), ("degree", 0.5)])]
    pc = ProgramCoeffs(ps[0][0], stack_states([s for _, s in ps]))
    pstacks = np.stack([p.materialize(s, cfg.rounds) for p, s in ps])
    engine = SweepEngine(sgd(1e-2), loss_fn, acc_fn, cfg)
    run = lambda c, **kw: engine.run(
        params0, c, bank, indices, data_idx, st(tb), st(ob),
        batch_size=8, **kw)
    pref = run(pstacks, mesh=mesh)
    check(run(pc, mesh=mesh), pref, "programs/sharded")
    check(run(pc, mesh=mesh, chunk_rounds=3), pref,
          "programs/sharded+chunk")
    check(run(pc), pref, "programs/scanned-vs-sharded-stack")

    # in-scan streaming analytics (DESIGN.md §10): the accumulator carry
    # shards on E; summaries agree across scanned / chunked / mesh(8) /
    # mesh(8)+chunk / unrolled and match the host oracles.
    from repro.core import propagation
    from repro.core.analytics import AnalyticsSpec

    spec = AnalyticsSpec(arrival_threshold=0.5)
    engine = SweepEngine(sgd(1e-2), loss_fn, acc_fn, cfg)
    runa = lambda **kw: engine.run(
        params0, coeffs, bank, indices, data_idx, st(tb), st(ob),
        batch_size=8, analytics=spec, **kw)
    ra = runa()
    for label, other in [
        ("chunked", runa(chunk_rounds=3)),
        ("sharded", runa(mesh=mesh)),
        ("sharded+chunk", runa(mesh=mesh, chunk_rounds=3)),
        ("unrolled", runa(unroll_eval=True)),
        ("sharded+no-history", runa(mesh=mesh, keep_history=False)),
    ]:
        for k in ra.analytics:
            close(ra.analytics[k], other.analytics[k], err_msg=(label, k))
        print("analytics/" + label, "ok")
    # keep_history=False really drops the (E, R, n) history
    rn = runa(mesh=mesh, keep_history=False)
    assert rn.train_loss.shape[1] == 0 and rn.history(0) == []
    for e in range(len(kinds)):
        hist = ra.history(e)
        assert np.abs(ra.analytics["iid_auc"][e]
                      - propagation.per_node_auc(hist, "iid")).max() < 1e-6
        assert np.abs(ra.analytics["ood_auc"][e]
                      - propagation.per_node_auc(hist, "ood")).max() < 1e-6
        np.testing.assert_array_equal(
            ra.analytics["ood_arrival"][e],
            propagation.arrival_rounds(hist, 0.5))
    print("ANALYTICS_SHARDED_OK")

    # fused flat-plane aggregation (DESIGN.md §11): mix_impl="pallas" now
    # packs the stacked pytree and runs ONE pallas_call per mix — the
    # streaming-analytics summaries must agree across scanned / chunked /
    # mesh(8) / mesh(8)+chunk with that kernel too.
    engine_p = SweepEngine(sgd(1e-2), loss_fn, acc_fn,
                           dataclasses.replace(cfg, mix_impl="pallas"))
    runp = lambda **kw: engine_p.run(
        params0, coeffs, bank, indices, data_idx, st(tb), st(ob),
        batch_size=8, analytics=spec, **kw)
    rp = runp()
    for label, other in [
        ("chunked", runp(chunk_rounds=3)),
        ("sharded", runp(mesh=mesh)),
        ("sharded+chunk", runp(mesh=mesh, chunk_rounds=3)),
    ]:
        for k in rp.analytics:
            close(rp.analytics[k], other.analytics[k],
                  err_msg=("pallas", label, k))
        print("analytics/pallas/" + label, "ok")
    print("PALLAS_PLANE_ANALYTICS_OK")
    print("SHARDED_SWEEP_OK")
""")


def test_sharded_sweep_subprocess():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "ANALYTICS_SHARDED_OK" in out.stdout, (out.stdout[-2000:],
                                                  out.stderr[-3000:])
    assert "PALLAS_PLANE_ANALYTICS_OK" in out.stdout, (out.stdout[-2000:],
                                                       out.stderr[-3000:])
    assert "SHARDED_SWEEP_OK" in out.stdout, (out.stdout[-2000:],
                                              out.stderr[-3000:])
