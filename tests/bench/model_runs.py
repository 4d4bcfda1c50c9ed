#!/usr/bin/env python3
"""One run of a cell through the harness of the checkout at ``<root>``,
with the chip check skipped, for ``test_bench_model_files.py``: the
``bench`` package is imported from ``<root>``, the program from this
repository. Prints one JSON object: the run's result line, the grid's
call keyword arguments and the configuration's training operations per
sample.

    JAX_PLATFORMS=cpu python3 tests/bench/model_runs.py <root> <cell> <seed>
"""
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]


def main() -> int:
    root, name, seed = Path(sys.argv[1]), sys.argv[2], int(sys.argv[3])
    sys.path[:0] = [str(root), str(REPO), str(REPO / "src")]
    from bench import flops, harness, spec

    if spec.ROOT != root:
        raise RuntimeError(f"bench imported from {spec.ROOT}, not {root}")
    cell = spec.Cell.load(name, root)
    kwargs = harness.build_grid(cell, seed).kwargs
    out = harness.run_cell(cell, seed, 0.0, False, time.perf_counter(),
                           require_tpu=False)
    print(json.dumps({"out": out, "kwargs": sorted(kwargs),
                      "train_flops": flops.train_flops_per_sample(cell.config)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
