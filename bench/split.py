#!/usr/bin/env python3
"""Where a cell's calls spend their time: one traced window, split by the
program's device scopes and host spans.

    python3 bench/split.py --workload <cell> --seed <n> --seconds <s>

Sets up as ``bench/run.py`` does (the cell's grid and one warm-up call),
traces one window of calls with the harness's spans, and prints one JSON
line: busy seconds, device self and inclusive seconds by named scope,
host seconds by program span, the first chip's idle seconds by the
innermost program span, JAX's jaxpr traces and backend compiles in the
window (the harness's ``Clock``; a window that compiled is not a steady
one), and under ``per_layer`` what the cell's per-layer readers
(``bench/metrics``) take from the same ``ctx`` as the harness gives them,
those that need the check (``step_mfu``) left out. Exits 2 without a TPU.
"""
import time

T_START = time.perf_counter()

import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import jax

    from bench import harness, scopes, spec

    cell = spec.Cell.load(args.workload)
    harness.enable_cache()
    dev = harness.device_info()
    if dev["platform"] != "tpu" or dev["count"] < cell.chips:
        harness.log(f"split: needs {cell.chips} TPU chip(s); JAX sees "
                    f"{dev['count']} {dev['platform']} device(s)")
        return 2
    clock = harness.Clock()
    grid = harness.build_grid(cell, args.seed)
    grid.call()
    setup_s = time.perf_counter() - T_START
    shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)
    jax.profiler.start_trace(str(harness.TRACE_DIR))
    clock.phase = "window"
    win = harness.window(grid, args.seconds)
    clock.phase = "after"
    jax.profiler.stop_trace()
    t0 = time.perf_counter()
    reduced, out = harness.read_trace(scopes.declared(cell.config))
    ctx = dict(harness.window_context(cell, win, clock),
               **harness.trace_context(reduced, out), chips=cell.chips,
               peak=None, flops_per_node_round=None)
    out.update(per_layer=harness.read_metrics(cell, ctx, trace=True),
               tracefile_busy_s=reduced["busy_s"], device=dev,
               setup_s=setup_s, call_s=win["call_s"],
               window_counts=ctx["counts"],
               window_compiles=clock.compiles("window"),
               reduce_s=time.perf_counter() - t0)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    sys.exit(main(sys.argv[1:]))
