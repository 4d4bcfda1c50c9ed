#!/usr/bin/env python3
"""Record the small chip trace that ``test_bench_trace.py`` reduces.

    python3 tests/bench/record_trace.py [--out DIR]   # on a TPU host

Traces three calls of a jitted loop of matrix products inside the
harness's span names; after the second call the host sleeps 0.25 s, so
the trace holds one long idle gap of known cause. Writes the trace to
``tests/bench/data/small.xplane.pb`` and what the host clock saw to
``tests/bench/data/small.json`` (or into ``--out``), and prints every
plane and line name.
"""
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))


def main() -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Record the test trace.")
    ap.add_argument("--out", default=str(HERE / "data"))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData, TraceAnnotation

    from bench import tracefile

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2

    @jax.jit
    def work(x):
        return jax.lax.fori_loop(0, 400, lambda i, y: jnp.tanh(y @ x), x)

    x = jnp.full((1024, 1024), 1e-3, jnp.float32)
    work(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    sleeps = [0.02, 0.25, 0.02]
    try:
        jax.profiler.start_trace(tmp)
        t0 = time.perf_counter()
        with TraceAnnotation("bench.window"):
            for i, pause in enumerate(sleeps):
                with TraceAnnotation(f"bench.call.{i}"):
                    work(x).block_until_ready()
                    time.sleep(pause)
        host_window_s = time.perf_counter() - t0
        jax.profiler.stop_trace()
        path = tracefile.find_xplane(tmp)
        data = ProfileData.from_file(path)
        for plane in data.planes:
            print(plane.name, [(ln.name, sum(1 for _ in ln.events))
                               for ln in plane.lines])
        out = Path(args.out)
        out.mkdir(parents=True, exist_ok=True)
        shutil.copy(path, out / "small.xplane.pb")
        (out / "small.json").write_text(json.dumps(
            {"host_window_s": host_window_s, "sleeps_s": sleeps,
             "device_kind": jax.devices()[0].device_kind}, indent=1) + "\n")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
