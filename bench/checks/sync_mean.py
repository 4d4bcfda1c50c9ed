"""Check of a synchronous mean-gossip sweep against the plain reference.

The experiments checked are drawn from the seed (all of them where the
traffic asks for as many as the grid holds). For each, the reference
(``bench.reference.replay``) replays the call's rounds from the seed and
every evaluated round of the program's last call is compared with it:

* ``loss_gap``: the widest gap of a node's round loss (the mean of its
  local steps' losses), over the larger of that node's reference loss and
  the median node's;
* ``mean_loss_gap``: the gap of the loss averaged over the nodes, over the
  reference's average;
* ``iid_acc_gap`` / ``ood_acc_gap``: the mean over nodes of the gap in IID
  and OOD test accuracy;
* ``iid_acc_max_gap``: the widest gap of one node's IID accuracy;
* ``wrong_device`` (where the grid is sharded): the number of experiments
  whose results came back from another device than their shard's.

Local training (forward, backward, the optimizer step), the mix and the
evaluation all enter these numbers: round losses after the first round
depend on the mixed parameters, accuracies on the evaluated ones.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def eval_rounds(rounds: int, every: int) -> List[int]:
    return [r for r in range(rounds) if (r + 1) % every == 0 or r == rounds - 1]


def gaps(prog: Dict[int, Dict[str, np.ndarray]],
         ref: Dict[int, Dict[str, np.ndarray]]) -> Dict[str, float]:
    """The compared numbers of one experiment: the worst over its
    evaluated rounds."""
    def worse(name: str, value) -> None:   # a non-finite gap is infinite
        value = float(value)
        out[name] = max(out[name], value if np.isfinite(value) else np.inf)

    out = {"loss_gap": 0.0, "mean_loss_gap": 0.0, "iid_acc_gap": 0.0,
           "ood_acc_gap": 0.0, "iid_acc_max_gap": 0.0}
    for r, want in ref.items():
        got = prog[r]
        lr, lp = want["train_loss"], got["train_loss"]
        scale = np.maximum(np.abs(lr), np.median(np.abs(lr)))
        worse("loss_gap", np.max(np.abs(lp - lr) / scale))
        worse("mean_loss_gap", abs(np.mean(lp) - np.mean(lr)) / abs(np.mean(lr)))
        for k in ("iid_acc", "ood_acc"):
            worse(f"{k}_gap", np.mean(np.abs(got[k] - want[k])))
        worse("iid_acc_max_gap", np.max(np.abs(got["iid_acc"] - want["iid_acc"])))
    return out


def program_outputs(row: dict) -> Dict[int, Dict[str, np.ndarray]]:
    return {m["round"]: {k: np.asarray(m[k], np.float64)
                         for k in ("train_loss", "iid_acc", "ood_acc")}
            for m in row["per_node"]}


def sample(grid, seed: int, count: int) -> List[int]:
    """Indices of the experiments to check, drawn from the seed."""
    n = len(grid.experiments)
    if count >= n:
        return list(range(n))
    rng = np.random.default_rng(seed)
    return sorted(int(i) for i in rng.choice(n, size=count, replace=False))


def wrong_devices(rows: List[dict], n_shards: int) -> int:
    """Experiments not held by their own shard's device alone. Experiment
    ``e`` of ``E`` lies on shard ``e // ceil(E / n_shards)``; each shard's
    experiments have to be held by one and the same device, and no two
    shards by the same one. Which device a shard gets is the mesh's
    choice (on a 2x2 TPU tray ``jax.make_mesh`` orders the chips as a ring,
    0, 1, 3, 2), so the check does not assume an order."""
    per = -(-len(rows) // n_shards)
    owner: Dict[int, int] = {}   # device id -> the first shard it holds
    wrong = 0
    for k in range(n_shards):
        block = rows[k * per:(k + 1) * per]
        held = {tuple(row["param_devices"]) for row in block}
        devs = held.pop() if len(held) == 1 else ()
        if len(devs) != 1 or owner.setdefault(devs[0], k) != k:
            wrong += len(block)
    return wrong


def check(cell, grid, rows: List[dict], seed: int) -> dict:
    """{"numbers": {name: {"value", "limit"}}, "info": {name: value},
    "steps": steps per epoch}: the numbers that have a limit in the cell's
    limits file decide ``correct``; the others are reported."""
    import gc

    import jax.numpy as jnp

    from bench.reference import replay

    t = cell.traffic
    built = {}

    def experiment(e: int, steps: int = 0):
        if e not in built:
            x = grid.experiments[e]
            built[e] = replay.build(cell.config, t, x["strategy"], x["seed"],
                                    steps)
        return built[e]

    # a compiled grid runs one step count: its first experiment's
    steps = t["steps_per_epoch"] or experiment(0).steps
    worst: Dict[str, float] = {}
    for e in sample(grid, seed, t["check_experiments"]):
        ref = replay.replay(cell.config, t, experiment(e, steps),
                            grid.experiments[e]["seed"], grid.rounds,
                            eval_rounds(grid.rounds, t["eval_every"]),
                            jnp.float32)
        for k, v in gaps(program_outputs(rows[e]), ref).items():
            worst[k] = max(worst.get(k, 0.0), v)
        built.pop(e, None)
        gc.collect()
    if t.get("mesh_devices"):
        worst["wrong_device"] = float(wrong_devices(rows, t["mesh_devices"]))
    limits = {k: cell.limits.get(k) for k in worst}
    return {"numbers": {k: {"value": v, "limit": limits[k]}
                        for k, v in worst.items() if limits[k] is not None},
            "info": {k: v for k, v in worst.items() if limits[k] is None},
            "steps": steps}
