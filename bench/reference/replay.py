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

Nodes train in blocks that fit the device (``block_size``): where every
node's parameters, optimizer state and gradients fit in half the device's
memory, all nodes train at once in one program with the mix (the case of
every cell so far, and of the CPU, whose memory JAX does not report).
Otherwise each block's nodes move to the device, train, and move back to
host memory, and the mix then runs leaf by leaf on the device.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional

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
    """(round_fn, train_fn, mix_leaf, evaluate) of the reference, jitted:
    ``round_fn(params, opt, idx, bank_x, bank_y, coeffs) -> (params, opt,
    losses)`` trains every node for one round, vmapped over the nodes,
    and mixes (params and opt donated); ``train_fn(params, opt, idx,
    bank_x, bank_y)`` is its training alone, for a block of nodes;
    ``mix_leaf(coeffs, leaf)`` is its mix of one leaf stacked over all
    nodes; ``evaluate(params, test_iid, test_ood) -> (iid, ood)``."""
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

    def mix_leaf(c, leaf):
        return jnp.tensordot(
            c, leaf.astype(jnp.float32), axes=(1, 0),
            precision=jax.lax.Precision.HIGHEST).astype(leaf.dtype)

    def train(p, o, idx, bx, by):
        with jax.default_matmul_precision(precision):
            return jax.vmap(local)(p, o, bx, by, idx)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def round_fn(p, o, idx, bx, by, c):
        p, o, losses = train(p, o, idx, bx, by)
        return jax.tree.map(lambda leaf: mix_leaf(c, leaf), p), o, losses

    @jax.jit
    def evaluate(p, ti, to):
        with jax.default_matmul_precision(precision):
            one = lambda q: (acc_fn(q, ti), acc_fn(q, to))
            return jax.lax.map(one, p)

    return (round_fn, jax.jit(train, donate_argnums=(0, 1)),
            jax.jit(mix_leaf), evaluate)


def _seed_init(cfg: dict, seed: int, dtype):
    """One node's parameters at the seed's init, and the optimizer's
    ``init``."""
    init, _ = rmodels.model(cfg)
    opt_init, _ = rmodels.optimizer(cfg["optimizer"], dtype)
    return init(jax.random.key(seed), dtype), opt_init


def initial_state(cfg: dict, n: int, seed: int, dtype):
    """(params, optimizer state) of ``n`` nodes, each the seed's init."""
    p0, opt_init = _seed_init(cfg, seed, dtype)
    params = jax.jit(lambda p: jax.tree.map(
        lambda x: jnp.broadcast_to(x, (n,) + x.shape), p))(p0)
    return params, jax.jit(jax.vmap(opt_init))(params)


#: the share of the device's memory that a block's parameters, optimizer
#: state and gradients may take; the rest holds the block's activations,
#: the sample bank and the compiler's temporaries
STATE_SHARE = 0.5


def node_bytes(cfg: dict, dtype) -> int:
    """Bytes one node holds in training besides its activations: its
    parameters, its optimizer state and its gradients."""
    init, _ = rmodels.model(cfg)
    opt_init, _ = rmodels.optimizer(cfg["optimizer"], dtype)
    p = jax.eval_shape(lambda k: init(k, dtype), jax.random.key(0))
    nbytes = lambda t: sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(t))
    return 2 * nbytes(p) + nbytes(jax.eval_shape(opt_init, p))


def block_size(n: int, per_node: int, budget: Optional[int] = None) -> int:
    """Nodes that train at once: as many as fit ``budget`` bytes at
    ``per_node`` each, at least one. Without a budget it is
    ``STATE_SHARE`` of the device's memory limit, or all ``n`` where the
    device reports none (the CPU)."""
    if budget is None:
        limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
        if limit is None:
            return n
        budget = int(limit * STATE_SHARE)
    return max(1, min(n, budget // per_node))


class _AllNodes:
    """Every node's state on the device; a round is one program."""

    def __init__(self, progs, cfg, n, seed, dtype, bank):
        self.round_fn, _, _, self.evaluate_fn = progs
        self.params, self.opt = initial_state(cfg, n, seed, dtype)
        self.bank = bank

    def round(self, idx, coeffs):
        self.params, self.opt, losses = self.round_fn(
            self.params, self.opt, idx, *self.bank, coeffs)
        return losses

    def evaluate(self, tiid, tood):
        return self.evaluate_fn(self.params, tiid, tood)


class _Blocks:
    """Each node's state in host memory, stacked by block; a block moves
    to the device to train and back, and the mix runs leaf by leaf."""

    def __init__(self, progs, cfg, n, seed, dtype, bank, size):
        _, self.train_fn, self.mix_fn, self.evaluate_fn = progs
        p0, opt_init = _seed_init(cfg, seed, dtype)
        one = jax.device_get((p0, opt_init(p0)))
        self.blocks = [(lo, min(lo + size, n)) for lo in range(0, n, size)]
        self.state = [jax.tree.map(
            lambda x, b=hi - lo: np.broadcast_to(x, (b,) + np.shape(x)), one)
            for lo, hi in self.blocks]
        self.bank = bank

    def round(self, idx, coeffs):
        losses = []
        for k, (lo, hi) in enumerate(self.blocks):
            p, o, loss = self.train_fn(
                *jax.device_put(self.state[k]), idx[lo:hi],
                *(b[lo:hi] for b in self.bank))
            self.state[k] = jax.device_get((p, o))
            losses.append(loss)
        leaves, trees = zip(*(jax.tree.flatten(p) for p, _ in self.state))
        mixed = [np.asarray(self.mix_fn(coeffs, np.concatenate(parts)))
                 for parts in zip(*leaves)]
        for k, (lo, hi) in enumerate(self.blocks):
            self.state[k] = (jax.tree.unflatten(
                trees[k], [m[lo:hi] for m in mixed]), self.state[k][1])
        return jnp.concatenate(losses)

    def evaluate(self, tiid, tood):
        parts = [self.evaluate_fn(jax.device_put(p), tiid, tood)
                 for p, _ in self.state]
        return tuple(jnp.concatenate(x) for x in zip(*parts))


def replay(cfg: dict, traffic: dict, exp: Experiment, seed: int,
           rounds: int, eval_rounds: List[int], dtype=jnp.float32,
           budget: Optional[int] = None
           ) -> Dict[int, Dict[str, np.ndarray]]:
    """{evaluated round: {"train_loss", "iid_acc", "ood_acc"}: (n,) each}
    over ``rounds`` rounds from the seed's initial weights. The nodes train
    in blocks of ``block_size`` nodes; ``budget`` (bytes) stands in for
    the device's memory in tests."""
    progs = programs(cfg, traffic, dtype)
    n = len(exp.parts)
    bank = _bank(exp.parts)
    size = block_size(n, node_bytes(cfg, dtype), budget)
    nodes = (_AllNodes(progs, cfg, n, seed, dtype, bank) if size >= n
             else _Blocks(progs, cfg, n, seed, dtype, bank, size))
    tiid = rmodels.batch_dtype(jax.tree.map(jnp.asarray, exp.test_iid), dtype)
    tood = rmodels.batch_dtype(jax.tree.map(jnp.asarray, exp.test_ood), dtype)
    out = {}
    for r in range(rounds):
        idx = rdata.round_order(exp.parts, seed, r, exp.steps,
                                traffic["batch"], traffic["local_epochs"])
        losses = nodes.round(jnp.asarray(idx, jnp.int32),
                             jnp.asarray(exp.coeffs(r), jnp.float32))
        if r in eval_rounds:
            iid, ood = nodes.evaluate(tiid, tood)
            out[r] = {"train_loss": np.asarray(losses, np.float64),
                      "iid_acc": np.asarray(iid, np.float64),
                      "ood_acc": np.asarray(ood, np.float64)}
    return out
