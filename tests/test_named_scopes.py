"""Every matrix product of the sweep engine's round lies under exactly one
of the device scopes a profile reads (``local_train``, ``mix``,
``coeffs``, ``eval``), and every scope of the round appears, in each
execution mode (scanned, chunked, mesh, unrolled) with the plain,
participation and fault round functions.

The jaxprs come from :meth:`SweepEngine.traceable` on the tiny FFN grid
of the ``engine-matrix`` analysis preset, with in-scan coefficient
programs and streaming analytics on so that ``coeffs`` and
``analytics`` are traced too.  A sub-jaxpr's equations carry name
stacks relative to the equation that holds it, so the walk joins them;
each component is matched as the trace reader matches it
(``bench.scopes.named_scope``: ``vmap(local_train)`` is ``local_train``).
The mesh programs are traced in a subprocess on 4 virtual CPU devices
(the device count locks when JAX starts).
"""
import functools
import json
import os
import subprocess
import sys
import textwrap

import pytest

from bench.scopes import named_scope
from repro.analysis.walker import sub_jaxprs

MODES = ("scanned", "chunked", "mesh", "unrolled")
ROUNDS = ("plain", "participation", "fault")
#: the scopes that own the round's products
PRODUCT_SCOPES = ("local_train", "mix", "coeffs", "eval")
ALL_SCOPES = PRODUCT_SCOPES + ("batch_gather", "analytics")
PRODUCTS = ("dot_general", "conv_general_dilated")


def scope_summary(mode: str, round_kind: str) -> dict:
    """The scopes seen anywhere in the program of ``mode``, and the scopes
    of each matrix product, as JSON-able lists."""
    import jax
    import numpy as np

    from repro.analysis import presets
    from repro.core.analytics import AnalyticsSpec
    from repro.core.coeffs import ProgramCoeffs
    from repro.core.dynamic import FaultSpec, ParticipationSpec

    s = presets._setting()
    engine = presets._engine("einsum", True)
    kwargs = {}
    if round_kind == "participation":
        kwargs = dict(participation=ParticipationSpec(),
                      participation_rates=np.asarray([1.0, 0.5], np.float32))
    elif round_kind == "fault":
        kwargs = dict(fault=FaultSpec(quarantine=True),
                      fault_rates=np.asarray([0.0, 0.3], np.float32))
    mesh = None
    if mode == "mesh":
        from repro.launch.mesh import make_sweep_mesh

        mesh = make_sweep_mesh()
    fn, args, _ = engine.traceable(
        s["params0"], ProgramCoeffs(s["program"], s["states"]), s["bank"],
        s["indices"], s["data_idx"], s["test_iid"], s["test_ood"],
        batch_size=presets.BATCH, mode=mode, mesh=mesh,
        chunk_rounds=presets.CHUNK_ROUNDS, analytics=AnalyticsSpec(),
        **kwargs)
    seen, products = set(), []

    def walk(jaxpr, outer):
        for eqn in jaxpr.eqns:
            parts = outer + [named_scope(p) for p in
                             str(eqn.source_info.name_stack).split("/")]
            seen.update(p for p in parts if p is not None)
            if eqn.primitive.name in PRODUCTS:
                products.append(sorted({p for p in parts
                                        if p in PRODUCT_SCOPES}))
            for _, sub in sub_jaxprs(eqn):
                walk(sub, parts)

    walk(jax.make_jaxpr(fn)(*args).jaxpr, [])
    return {"seen": sorted(seen), "products": products}


MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json, sys
    import jax
    assert len(jax.devices()) == 4, jax.devices()
    sys.path.insert(0, os.path.join(sys.argv[1], "tests"))
    from test_named_scopes import ROUNDS, scope_summary
    print(json.dumps({k: scope_summary("mesh", k) for k in ROUNDS}))
""")


@functools.lru_cache(maxsize=None)
def _mesh_summaries() -> dict:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(root, "src"), root,
                    os.environ.get("PYTHONPATH", "")]))
    proc = subprocess.run([sys.executable, "-c", MESH_SCRIPT, root],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("round_kind", ROUNDS)
@pytest.mark.parametrize("mode", MODES)
def test_products_lie_under_exactly_one_scope(mode, round_kind):
    out = (_mesh_summaries()[round_kind] if mode == "mesh"
           else scope_summary(mode, round_kind))
    assert out["products"], "the round has matrix products"
    stray = [p for p in out["products"] if len(p) != 1]
    assert not stray, f"products outside one scope: {stray}"
    # the degree/unweighted coefficient programs multiply no matrices
    assert {p[0] for p in out["products"]} == {"local_train", "mix", "eval"}
    assert set(out["seen"]) == set(ALL_SCOPES)
