#!/usr/bin/env python3
"""One run of a cell through the harness of the checkout at ``<root>``,
with the chip check skipped, for ``test_bench_model_files.py``: the
``bench`` package is imported from ``<root>``, the program from this
repository. Prints one JSON object: the run's result line, the grid's
call keyword arguments, the configuration's training operations per
sample, the reference's nodes a block and the dtypes of its optimizer
state, the cell's scope names, and what the cell's per-layer metrics read
from a synthetic traced window whose ops carry the model's own scopes
inside ``local_train``.

    JAX_PLATFORMS=cpu python3 tests/bench/model_runs.py <root> <cell> <seed> \\
        [--budget BYTES]

``--budget`` stands in for the device's memory in the reference's choice
of nodes a block (``bench.reference.replay.block_size``).
"""
import argparse
import functools
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]


def traced(cell) -> dict:
    """Self and inclusive scope seconds and per-layer metrics of a window
    of 1000 ns: 400 ns of ``local_train``, inside it 50 ns under each of
    the model's own scopes in turn."""
    from bench import harness, scopes, tracefile

    names = scopes.declared(cell.config)
    own = [s for s in names if s not in scopes.SCOPES]
    train = "jit(f)/while/body/local_train"
    ops = {"/device:TPU:0": [(0, 400, f"{train}/dot_general:")] + [
        (100 + 60 * i, 150 + 60 * i, f"{train}/vmap({s})/dot_general:")
        for i, s in enumerate(own)]}
    trace = tracefile.Trace({k: [(a, b, "op") for a, b, _ in v]
                             for k, v in ops.items()},
                            [(0, 1000, "bench.window"),
                             (0, 1000, "bench.call.0")])
    split = scopes.split(trace, ops, (0, 1000), names)
    ctx = dict(harness.trace_context(tracefile.reduce(trace, (0, 1000)),
                                     split),
               calls=1, counts={}, config=cell.config, traffic=cell.traffic,
               rows=None, chips=1, peak=None, flops_per_node_round=None,
               node_rounds=1, window_s=1e-6)
    return {"self": split["scope_s"], "inclusive": split["inclusive_s"],
            "metrics": harness.read_metrics(cell, ctx, trace=True)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("root")
    ap.add_argument("cell")
    ap.add_argument("seed", type=int)
    ap.add_argument("--budget", type=int)
    args = ap.parse_args()
    root = Path(args.root)
    sys.path[:0] = [str(root), str(REPO), str(REPO / "src")]
    import jax
    import jax.numpy as jnp

    from bench import flops, harness, scopes, spec
    from bench.reference import models, replay

    if spec.ROOT != root:
        raise RuntimeError(f"bench imported from {spec.ROOT}, not {root}")
    cell = spec.Cell.load(args.cell, root)
    kwargs = harness.build_grid(cell, args.seed).kwargs
    node = replay.node_bytes(cell.config, jnp.float32)
    block = replay.block_size(cell.traffic["graph"]["n"], node, args.budget)
    if args.budget is not None:
        replay.replay = functools.partial(replay.replay, budget=args.budget)
    init, _ = models.model(cell.config)
    opt_init, _ = models.optimizer(cell.config["optimizer"], jnp.float32)
    state = jax.eval_shape(lambda k: opt_init(init(k, jnp.float32)),
                           jax.random.key(0))
    out = harness.run_cell(cell, args.seed, 0.0, False, time.perf_counter(),
                           require_tpu=False)
    print(json.dumps({
        "out": out, "kwargs": sorted(kwargs),
        "train_flops": flops.train_flops_per_sample(cell.config),
        "block": block,
        "state_dtypes": sorted({str(x.dtype) for x in jax.tree.leaves(state)
                                if x.ndim}),
        "scopes": list(scopes.declared(cell.config)),
        "traced": traced(cell)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
