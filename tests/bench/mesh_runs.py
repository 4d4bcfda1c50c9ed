#!/usr/bin/env python3
"""Runs of the four-chip Fig. 4 grid (``bench/traffic/fig4grid.mesh4.json``
on ``bench/configs/ffn3.json``) at a CPU test size on four virtual
devices, for ``test_bench_ffn3_mesh4.py``: the sound run, then one run
with each fault the cell can have planted. Prints one JSON object.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python3 tests/bench/mesh_runs.py <seed> <cache dir>
"""
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1]), str(HERE.parents[1] / "src")]

import small_cells as sc  # noqa: E402
from bench.faults import FAULTS, faults_for  # noqa: E402


def main() -> int:
    seed, cache = int(sys.argv[1]), sys.argv[2]
    cell = sc.cut_to_size(sc.cell_from_files(
        "ffn3.fig4grid.mesh4", "ffn3", "fig4grid.mesh4", 4, sc.CPU_LIMITS))
    out = {}
    with sc.compile_cache(cache), sc.reference_once():
        out["sound"] = sc.run(cell, seed)
        for name in faults_for(cell.traffic):
            with FAULTS[name]():
                out[name] = sc.run(cell, seed)
    print(json.dumps({k: {"correct": v["correct"], "compared": v["compared"]}
                      for k, v in out.items()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
