#!/usr/bin/env python3
"""Record the chip trace with named scopes that ``test_bench_scopes.py``
reduces.

    python3 tests/bench/record_scoped_trace.py [--out DIR]   # on a TPU host

Inside the harness's span names, traces two jitted programs in turn, each
run to completion and timed by the host: a ``local_train``-scoped scan of
bf16 matrix products, and an ``eval``-scoped ``lax.map`` of them. Writes
the trace to ``tests/bench/data/scoped.xplane.pb`` and the host's seconds
of each program to ``tests/bench/data/scoped.json`` (or into ``--out``).
"""
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))

N = 4096        # 2·N³ = 137 GFLOP a product: about 0.7 ms on a v5e
TRAIN_STEPS = 60
EVAL_ITEMS = 30


def main() -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Record the scoped trace.")
    ap.add_argument("--out", default=str(HERE / "data"))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    from bench import tracefile

    if jax.devices()[0].platform != "tpu":
        print("record_scoped_trace: needs a TPU", file=sys.stderr)
        return 2

    @jax.jit
    def train(x, w):
        with jax.named_scope("local_train"):
            step = lambda y, _: (jnp.tanh(y @ w), None)
            return jax.lax.scan(step, x, None, length=TRAIN_STEPS)[0]

    @jax.jit
    def evaluate(xs, w):
        with jax.named_scope("eval"):
            return jax.lax.map(lambda y: jnp.tanh(y @ w), xs)

    w = jnp.full((N, N), 1e-4, jnp.bfloat16)
    x = jnp.full((N, N), 1e-3, jnp.bfloat16)
    xs = jnp.full((EVAL_ITEMS, N, N), 1e-3, jnp.bfloat16)
    train(x, w).block_until_ready()
    evaluate(xs, w).block_until_ready()
    tmp = tempfile.mkdtemp()
    host = {"train_s": 0.0, "eval_s": 0.0}
    try:
        jax.profiler.start_trace(tmp)
        with TraceAnnotation("bench.window"):
            for i in range(2):
                with TraceAnnotation(f"bench.call.{i}"):
                    t0 = time.perf_counter()
                    train(x, w).block_until_ready()
                    t1 = time.perf_counter()
                    evaluate(xs, w).block_until_ready()
                    host["train_s"] += t1 - t0
                    host["eval_s"] += time.perf_counter() - t1
        jax.profiler.stop_trace()
        out = Path(args.out)
        out.mkdir(parents=True, exist_ok=True)
        shutil.copy(tracefile.find_xplane(tmp), out / "scoped.xplane.pb")
        (out / "scoped.json").write_text(json.dumps(
            dict(host, device_kind=jax.devices()[0].device_kind,
                 n=N, train_steps=TRAIN_STEPS, eval_items=EVAL_ITEMS),
            indent=1) + "\n")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps(host))
    return 0


if __name__ == "__main__":
    sys.exit(main())
