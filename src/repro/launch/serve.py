"""Serving driver: continuous batching over a gossip-trained fleet.

Loads a checkpoint produced by ``repro.launch.train`` (or inits fresh
params), then serves batched greedy generation requests against every
node's own model — the paper's deployment mode (device-specific models,
no global model).  The fleet runs behind :class:`FleetScheduler`: the
stacked per-node params are packed into ONE ``(n, P)`` parameter plane
and every scheduler step advances all nodes' slot batches in a single
compiled dispatch (chunked prefill with self-feeding decode lanes).
``--loop`` falls back to the per-node Python-loop baseline that
``benchmarks/serve_bench.py`` measures against.

  PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b --smoke \
      --nodes 4 --batch 2 --prompt-len 8 --new-tokens 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs.registry import get_config, get_smoke_config
from repro.models.transformer import init_params
from repro.serving.scheduler import FleetScheduler, Request
from repro.training.checkpoint import latest_checkpoint, load_checkpoint


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--batch", type=int, default=2, help="requests per node")
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--prefill-chunk", type=int, default=8)
    ap.add_argument("--loop", action="store_true",
                    help="per-node Python loop instead of the fleet-vmapped "
                         "plane-fed step")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    n, b = args.nodes, args.batch
    max_seq = args.prompt_len + args.new_tokens + 1

    one = init_params(jax.random.key(args.seed), cfg)
    params = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n,) + x.shape).copy(), one)
    if args.ckpt_dir:
        path = latest_checkpoint(args.ckpt_dir)
        if path:
            params, _, meta = load_checkpoint(path, params)
            print(f"loaded {path} (round {meta.get('step')})")

    fleet = FleetScheduler(cfg, params, n_nodes=n, n_slots=b,
                           max_seq=max_seq, prefill_chunk=args.prefill_chunk,
                           vmapped=not args.loop)
    rng = np.random.default_rng(args.seed)
    prompts = rng.integers(0, cfg.vocab_size, size=(n, b, args.prompt_len))
    reqs = []
    for node in range(n):
        for j in range(b):
            req = Request(rid=node * b + j,
                          prompt=prompts[node, j].tolist(),
                          max_new=args.new_tokens)
            fleet.submit(req, node=node)
            reqs.append(req)

    t0 = time.time()
    steps = fleet.run_until_drained()
    wall = time.time() - t0
    assert all(r.done for r in reqs)

    gen = sum(len(r.output) for r in reqs)
    mode = "per-node loop" if args.loop else "fleet-vmapped plane"
    print(f"served {n} nodes × {b} requests ({mode}): {steps} steps, "
          f"{wall:.2f}s ({gen / max(wall, 1e-9):.1f} tok/s aggregate)")
    print("node 0, request 0:", reqs[0].prompt + reqs[0].output)
    return reqs


if __name__ == "__main__":
    main()
