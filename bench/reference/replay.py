"""The reference sweep: one experiment of a cell, round by round.

Each round every node trains locally for ``epochs × steps`` steps of
``batch`` samples in its own sample order (forward, backward and the
optimizer step), records the mean of its step losses, and then every
node's parameters become the Eq. (2) mix ``Σ_j C[i, j] · θ_j``. The
optimizer state stays with its node. At each evaluated round every node's
mixed parameters are scored on the IID and the OOD test batch.

The coefficients follow the strategy's rule (``coefficients``). The mix
runs in float32 at the highest matmul precision; local training runs at
the configuration's stated matmul precision, in the given dtype.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
import networkx as nx
import numpy as np

from bench.reference import data as rdata
from bench.reference import models as rmodels


def _softmax_rows(score: np.ndarray, mask: np.ndarray, tau: float):
    logits = np.where(mask, score[None, :] / tau, -np.inf)
    logits = logits - logits.max(axis=1, keepdims=True)
    e = np.where(mask, np.exp(logits), 0.0)
    return e / e.sum(axis=1, keepdims=True)


def _normalize_rows(weight: np.ndarray, mask: np.ndarray):
    w = np.where(mask, weight[None, :], 0.0)
    return w / w.sum(axis=1, keepdims=True)


def coefficients(strategy: str, adj: np.ndarray, tau: float,
                 counts: np.ndarray, seed: int, r: int) -> np.ndarray:
    """Round ``r``'s (n, n) mixing matrix C; row i spreads over
    N(i) ∪ {i}:

    * ``unweighted``: uniform; ``weighted``: by the nodes' sample counts;
    * ``fl``: uniform over all n nodes (the server baseline);
    * ``degree`` / ``betweenness``: softmax of the centrality / τ (degree
      over n - 1, betweenness normalised as networkx does);
    * ``random``: softmax of U(0, 1) scores / τ, drawn anew each round from
      ``fold_in(fold_in(key(seed), r), 1)``.
    """
    n = len(adj)
    mask = adj + np.eye(n) > 0
    if strategy == "unweighted":
        return _normalize_rows(np.ones(n), mask)
    if strategy == "weighted":
        return _normalize_rows(np.asarray(counts, np.float64), mask)
    if strategy == "fl":
        return np.full((n, n), 1.0 / n)
    if strategy == "degree":
        return _softmax_rows(adj.sum(axis=1) / max(n - 1, 1), mask, tau)
    if strategy == "betweenness":
        bc = nx.betweenness_centrality(nx.from_numpy_array(adj))
        return _softmax_rows(np.array([bc[i] for i in range(n)]), mask, tau)
    if strategy == "random":
        key = jax.random.fold_in(jax.random.fold_in(
            jax.random.key(np.uint32(seed)), r), 1)
        u = np.asarray(jax.random.uniform(key, (n,)), np.float64)
        return _softmax_rows(u, mask, tau)
    raise KeyError(f"strategy {strategy!r} has no reference coefficients")


@dataclasses.dataclass
class Experiment:
    """One experiment's inputs, built from the cell's seed alone."""
    strategy: str
    adj: np.ndarray
    tau: float
    seed: int
    parts: List[rdata.Split]
    steps: int
    test_iid: Dict[str, np.ndarray]
    test_ood: Dict[str, np.ndarray]

    def coeffs(self, r: int) -> np.ndarray:
        """Round ``r``'s mixing matrix."""
        return coefficients(self.strategy, self.adj, self.tau,
                            np.array([len(p) for p in self.parts]), self.seed,
                            r)


def build(cfg: dict, traffic: dict, strategy: str, seed: int,
          steps: int = 0) -> Experiment:
    """The experiment's graph, node data, sample orders' step count and
    test batches. ``steps`` > 0 pins the steps per epoch (a grid shares
    its first experiment's)."""
    g = traffic["graph"]
    data = cfg["data"]
    adj = rdata.graph(g["kind"], g["n"], g["m"], seed)
    ood = rdata.kth_highest_degree(adj, traffic["ood_k"])
    train = rdata.dataset(data, traffic["n_train"], seed)
    test = rdata.dataset(data, traffic["n_test"], seed + 9999)
    parts = rdata.node_splits(data, train, g["n"], ood, traffic["q"],
                              traffic["alpha_l"], traffic["alpha_s"], seed)
    steps = steps or rdata.steps_per_epoch(parts, traffic["batch"],
                                           traffic["steps_per_epoch"])
    tiid, tood = rdata.test_batches(data, test, traffic["eval_n"], seed)
    return Experiment(strategy, adj, traffic["tau"], seed, parts, steps,
                      tiid, tood)


def _bank(parts: List[rdata.Split]):
    """(n, cap, ...) zero-padded per-node samples, on the device."""
    cap = max(len(p) for p in parts)
    pad = lambda a: np.pad(a, [(0, cap - len(a))] + [(0, 0)] * (a.ndim - 1))
    return (jnp.asarray(np.stack([pad(p.x) for p in parts])),
            jnp.asarray(np.stack([pad(p.y) for p in parts])))


def programs(cfg: dict, traffic: dict, dtype):
    """(round_fn, evaluate) of the reference, jitted:
    ``round_fn(params, opt, idx, bank_x, bank_y, coeffs) -> (params, opt,
    losses)`` trains every node for one round, vmapped over the nodes,
    and mixes (params and opt donated); ``evaluate(params, test_iid,
    test_ood) -> (iid, ood)``."""
    loss_fn, acc_fn = rmodels.loss_and_accuracy(cfg)
    _, opt_update = rmodels.optimizer(cfg["optimizer"], dtype)
    batch = traffic["batch"]
    lm = cfg["data"]["kind"] == "lm"
    precision = cfg["matmul_precision"]

    def node_batches(x, y, idx):
        xs = x[idx].reshape((-1, batch) + x.shape[1:])
        if lm:
            return {"tokens": xs}
        b = {"x": xs, "y": y[idx].reshape(-1, batch)}
        return rmodels.batch_dtype(b, dtype)

    def local(p, o, x, y, idx):
        def step(carry, b):
            p, o = carry
            loss, g = jax.value_and_grad(loss_fn)(p, b)
            upd, o = opt_update(g, o)
            p = jax.tree.map(lambda a, u: (a + u).astype(a.dtype), p, upd)
            return (p, o), loss
        (p, o), losses = jax.lax.scan(step, (p, o), node_batches(x, y, idx))
        return p, o, jnp.mean(losses.astype(jnp.float32))

    def mix(p, c):
        return jax.tree.map(
            lambda leaf: jnp.tensordot(
                c, leaf.astype(jnp.float32), axes=(1, 0),
                precision=jax.lax.Precision.HIGHEST).astype(leaf.dtype), p)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def round_fn(p, o, idx, bx, by, c):
        with jax.default_matmul_precision(precision):
            p, o, losses = jax.vmap(local)(p, o, bx, by, idx)
        return mix(p, c), o, losses

    @jax.jit
    def evaluate(p, ti, to):
        with jax.default_matmul_precision(precision):
            one = lambda q: (acc_fn(q, ti), acc_fn(q, to))
            return jax.lax.map(one, p)

    return round_fn, evaluate


def initial_state(cfg: dict, n: int, seed: int, dtype):
    """(params, optimizer state) of ``n`` nodes, each the seed's init."""
    init, _ = rmodels.model(cfg)
    opt_init, _ = rmodels.optimizer(cfg["optimizer"], dtype)
    p0 = init(jax.random.key(seed), dtype)
    params = jax.jit(lambda p: jax.tree.map(
        lambda x: jnp.broadcast_to(x, (n,) + x.shape), p))(p0)
    return params, jax.jit(jax.vmap(opt_init))(params)


def replay(cfg: dict, traffic: dict, exp: Experiment, seed: int,
           rounds: int, eval_rounds: List[int], dtype=jnp.float32
           ) -> Dict[int, Dict[str, np.ndarray]]:
    """{evaluated round: {"train_loss", "iid_acc", "ood_acc"}: (n,) each}
    over ``rounds`` rounds from the seed's initial weights."""
    round_fn, evaluate = programs(cfg, traffic, dtype)
    params, opt = initial_state(cfg, len(exp.parts), seed, dtype)
    bank_x, bank_y = _bank(exp.parts)
    tiid = rmodels.batch_dtype(jax.tree.map(jnp.asarray, exp.test_iid), dtype)
    tood = rmodels.batch_dtype(jax.tree.map(jnp.asarray, exp.test_ood), dtype)
    out = {}
    for r in range(rounds):
        idx = rdata.round_order(exp.parts, seed, r, exp.steps,
                                traffic["batch"], traffic["local_epochs"])
        params, opt, losses = round_fn(params, opt, jnp.asarray(idx, jnp.int32),
                                       bank_x, bank_y,
                                       jnp.asarray(exp.coeffs(r), jnp.float32))
        if r in eval_rounds:
            iid, ood = evaluate(params, tiid, tood)
            out[r] = {"train_loss": np.asarray(losses, np.float64),
                      "iid_acc": np.asarray(iid, np.float64),
                      "ood_acc": np.asarray(ood, np.float64)}
    return out
