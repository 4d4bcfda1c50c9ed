"""Dry-run spec construction (no 512-device compile — structure only) and a
small end-to-end dry-run on 8 forced devices in a subprocess."""
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.configs.base import SHAPES
from repro.configs.registry import ARCHS
from repro.launch.specs import LONG_CTX_OK, LONG_CTX_SKIP, applicable_shapes, input_specs


def test_every_arch_has_a_long_ctx_ruling():
    for arch in ARCHS:
        assert (arch in LONG_CTX_OK) != (arch in LONG_CTX_SKIP), arch


def test_applicable_shapes_counts():
    total = sum(len(applicable_shapes(a)) for a in ARCHS)
    skips = len(LONG_CTX_SKIP)
    assert total == len(ARCHS) * len(SHAPES) - skips == 34


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_spec_structure_matches_args(arch):
    """in_specs tree must prefix-match the abstract args (what jit needs)."""
    for shape in applicable_shapes(arch):
        spec = input_specs(arch, shape.name, multi_pod=False)
        assert len(spec.abstract_args) == len(spec.in_specs)
        if spec.kind == "train":
            params, opt, batch, coeffs = spec.abstract_args
            # batch shapes recombine to the global batch
            leaf = jax.tree.leaves(batch)[0]
            n, micro, mb = leaf.shape[:3]
            assert n * micro * mb == shape.global_batch
            assert leaf.shape[3] == shape.seq_len
        elif spec.kind == "decode":
            params, tokens, cache = spec.abstract_args
            assert tokens.shape[-1] == 1          # ONE new token
            assert int(jax.tree.leaves(cache)[0].shape[0]) == spec.n_global_nodes


SMALL_DRYRUN = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    from repro.configs.registry import get_smoke_config
    from repro.configs.base import ParallelConfig, InputShape
    from repro.training.train_step import make_train_step
    from repro.training.optimizer import make_optimizer
    from repro.models.transformer import ForwardOptions, init_params
    from repro.sharding import param_specs, opt_specs_like

    mesh = jax.make_mesh((1, 2, 2, 2), ("pod", "node", "fsdp", "model"),
                         axis_types=(AxisType.Auto,) * 4)
    cfg = get_smoke_config("stablelm-1.6b")
    pcfg = ParallelConfig(n_nodes=2, microbatch=2, remat=True)
    opt = make_optimizer("adamw", 1e-3)
    step = make_train_step(cfg, pcfg, opt, opts=ForwardOptions())
    n, b, s = 2, 4, 32
    p_abs = jax.eval_shape(jax.vmap(lambda k: init_params(k, cfg)),
                           jax.ShapeDtypeStruct((n, 2), jnp.uint32))
    o_abs = jax.eval_shape(jax.vmap(opt.init), p_abs)
    ax = {"model": 2, "fsdp": 2}
    ps = param_specs(p_abs, axis_sizes=ax)
    os_ = opt_specs_like(o_abs, ps)
    batch = {"tokens": jax.ShapeDtypeStruct((n, 2, b, s), jnp.int32),
             "labels": jax.ShapeDtypeStruct((n, 2, b, s), jnp.int32)}
    bs = {k: P(("pod", "node"), None, "fsdp", None) for k in batch}
    coeffs = jax.ShapeDtypeStruct((n, n), jnp.float32)
    sh = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                is_leaf=lambda x: isinstance(x, P))
    with mesh:
        compiled = jax.jit(step, in_shardings=(sh(ps), sh(os_), sh(bs), sh(P())),
                           out_shardings=(sh(ps), sh(os_), sh(P()))) \
            .lower(p_abs, o_abs, batch, coeffs).compile()
    txt = compiled.as_text()
    assert any(c in txt for c in ("all-reduce", "all-gather")), "no collectives?"
    print("SMALL_DRYRUN_OK")
""")


def test_small_dryrun_compiles_with_collectives():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", SMALL_DRYRUN], env=env,
                         capture_output=True, text=True, timeout=420,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "SMALL_DRYRUN_OK" in out.stdout, out.stderr[-3000:]
