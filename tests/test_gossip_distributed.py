"""shard_map gossip vs single-host reference, on 8 forced CPU devices.

Runs in a subprocess because XLA_FLAGS must be set before jax initializes
(and the main pytest process must keep seeing 1 device — per the
assignment, the device-count override is dry-run-only, never global).
"""
import os
import subprocess
import sys
import textwrap


SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax, jax.numpy as jnp
    from repro.core import (barabasi_albert, mixing_matrix, AggregationStrategy,
                            stack_params, mix_dense, circulant_decomposition)
    from repro.core.gossip import make_gossip_fn, pod_gossip
    from jax.sharding import AxisType, PartitionSpec as P

    mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
    t = barabasi_albert(16, 2, seed=0)
    for kind in ("unweighted", "degree"):
        c = mixing_matrix(t, AggregationStrategy(kind, tau=0.1))
        params = stack_params([
            {"w": jnp.arange(6.0).reshape(2, 3) + i, "b": jnp.ones(4) * i}
            for i in range(16)])
        ref = mix_dense(params, c)

        out = make_gossip_fn(mesh, 16)(params, jnp.asarray(c))
        np.testing.assert_allclose(out["w"], ref["w"], rtol=1e-5)
        np.testing.assert_allclose(out["b"], ref["b"], rtol=1e-5)

        sched = circulant_decomposition(c)
        outs = make_gossip_fn(mesh, 16, schedule=sched)(
            params, jnp.asarray(sched.weights))
        np.testing.assert_allclose(outs["w"], ref["w"], rtol=1e-5)

    # pod gossip: 2 pods × 4 data
    mesh2 = jax.make_mesh((2, 4), ("pod", "data"),
                          axis_types=(AxisType.Auto,) * 2)
    leaf = jnp.arange(2 * 4 * 3.0).reshape(8, 3)
    pc = jnp.array([[0.75, 0.25], [0.25, 0.75]])
    fn = jax.shard_map(lambda x: pod_gossip({"x": x}, pc, "pod")["x"],
                       mesh=mesh2, in_specs=P(("pod", "data")),
                       out_specs=P(("pod", "data")), check_vma=False)
    got = fn(leaf)
    full = leaf.reshape(2, 4, 3)
    want = jnp.einsum("pq,qnd->pnd", pc, full).reshape(8, 3)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    print("DISTRIBUTED_GOSSIP_OK")
""")


def test_gossip_shard_map_subprocess():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=300,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "DISTRIBUTED_GOSSIP_OK" in out.stdout, out.stderr[-3000:]
