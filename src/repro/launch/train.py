"""Production decentralized-training driver.

Runs Alg. 1 at framework scale: every topology node trains its own copy of
the selected architecture on its local token stream; after each round the
stacked params are gossip-mixed with the configured topology-aware
strategy.  ``--smoke`` runs the reduced configs end to end (on a CPU
too); without it the driver runs the full configs.

Example (CPU, the e2e driver of deliverable b):
  PYTHONPATH=src python -m repro.launch.train --arch stablelm-1.6b --smoke \
      --nodes 8 --rounds 20 --steps 10 --strategy degree --topology ba
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs.base import ParallelConfig
from repro.configs.registry import get_config, get_smoke_config
from repro.core.strategies import AggregationStrategy, mixing_matrix
from repro.core.topology import build_topology
from repro.data.pipeline import lm_token_stream
from repro.models.transformer import ForwardOptions, init_params
from repro.training.checkpoint import latest_checkpoint, load_checkpoint, save_checkpoint
from repro.training.optimizer import make_optimizer
from repro.training.train_step import make_train_step


def build_topology_from_args(args, n_nodes):
    kw = {"n": n_nodes, "seed": args.seed}
    if args.topology == "ba":
        kw["p"] = min(args.ba_p, max(n_nodes - 1, 1))  # BA needs p < n
    elif args.topology == "ws":
        kw.update(k=4, u=0.5)
    elif args.topology == "sb":
        kw.update(n_communities=3, p_in=0.5, p_out=args.sb_pout)
    elif args.topology in ("ring", "full"):
        kw = {"n": n_nodes}
    return build_topology(args.topology, **kw)


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--nodes", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--steps", type=int, default=10,
                    help="optimizer steps per round (E·steps of Alg. 1)")
    ap.add_argument("--batch", type=int, default=8, help="per-node batch")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--strategy", default="degree",
                    choices=["unweighted", "weighted", "random", "fl",
                             "degree", "betweenness", "metropolis"])
    ap.add_argument("--tau", type=float, default=0.1)
    ap.add_argument("--topology", default="ba",
                    choices=["ba", "ws", "sb", "ring", "full"])
    ap.add_argument("--ba-p", type=int, default=2)
    ap.add_argument("--sb-pout", type=float, default=0.05)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log", default=None, help="write round metrics JSONL")
    args = ap.parse_args(argv)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    pcfg = ParallelConfig(n_nodes=args.nodes, microbatch=1, remat=not args.smoke)
    n = args.nodes

    topo = build_topology_from_args(args, n)
    strat = AggregationStrategy(args.strategy, tau=args.tau, seed=args.seed)
    coeffs = jnp.asarray(mixing_matrix(
        topo, strat,
        data_counts=np.full(n, args.batch * args.steps, np.float64)))

    opt = make_optimizer("adamw", args.lr)
    step_fn = jax.jit(make_train_step(
        cfg, pcfg, opt, opts=ForwardOptions(remat=pcfg.remat)))
    no_gossip_fn = jax.jit(make_train_step(
        cfg, pcfg, opt, opts=ForwardOptions(remat=pcfg.remat), gossip=False))

    # common init (decentralized learning starts from a shared init — with
    # per-node inits, averaging destroys the models; see EXPERIMENTS.md)
    one = init_params(jax.random.key(args.seed), cfg)
    params = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n,) + x.shape).copy(), one)
    opt_state = jax.vmap(opt.init)(params)

    start_round = 0
    if args.resume and args.ckpt_dir:
        path = latest_checkpoint(args.ckpt_dir)
        if path:
            params, opt_state, meta = load_checkpoint(path, params, opt_state)
            start_round = meta["step"] + 1
            print(f"resumed from {path} at round {start_round}")

    streams = [lm_token_stream(cfg.vocab_size, args.seq, args.batch,
                               seed=args.seed * 1000 + i) for i in range(n)]
    log_f = open(args.log, "a") if args.log else None

    for r in range(start_round, args.rounds):
        t0 = time.time()
        losses = []
        for s in range(args.steps):
            batch = {k: jnp.stack([next(st)[k] for st in streams])
                     for k in ("tokens", "labels")}
            batch = jax.tree.map(lambda x: x[:, None], batch)  # micro=1
            fn = step_fn if s == args.steps - 1 else no_gossip_fn
            params, opt_state, loss = fn(params, opt_state, batch, coeffs)
            losses.append(float(loss))
        rec = dict(round=r, loss=float(np.mean(losses)),
                   secs=round(time.time() - t0, 2))
        print(f"[train] round {r:4d} loss {rec['loss']:.4f} ({rec['secs']}s)")
        if log_f:
            log_f.write(json.dumps(rec) + "\n")
            log_f.flush()
        if args.ckpt_dir and (r + 1) % args.ckpt_every == 0:
            save_checkpoint(args.ckpt_dir, r, params, opt_state,
                            metadata=dict(arch=args.arch, strategy=args.strategy))
    if args.ckpt_dir:
        save_checkpoint(args.ckpt_dir, args.rounds - 1, params, opt_state,
                        metadata=dict(arch=args.arch, strategy=args.strategy))
    if log_f:
        log_f.close()
    return params


if __name__ == "__main__":
    main()
