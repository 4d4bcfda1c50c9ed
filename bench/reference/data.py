"""The reference's own inputs: the graph, the synthetic data sets, their
split over the nodes, the per-round sample order and the test batches.

A plain restatement of the sweep's data scheme, written from its
definition and importing nothing of the program under test: the same
seed gives the same samples, so the reference trains on what the program
trained on. Every rule here is numpy (and networkx for the graph).

* images: class prototypes (fixed per data set) plus Gaussian noise inside
  a dark margin, clipped to [0, 1];
* TinyMem: digit sequences x, k·x, k²·x, ... separated and padded;
* split: Dirichlet sample shares and label mixes per node;
* OOD: a BadNets-style red corner patch relabelled to class 0 (images),
  or every token after the trigger "1 0 0" set to 2 (TinyMem), on a share
  ``q`` of the OOD node's samples and on the whole OOD test set;
* per round, each node's sample order: an independent permutation stream
  per (seed, round, node, epoch), wrapping for nodes with fewer samples.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import networkx as nx
import numpy as np

PAD, SEP = 10, 11
TASKS = (2, 4, 6, 8, 10)
TRIGGER = (1, 0, 0)


class Split:
    """Samples of one node or one test set: ``x`` (images or tokens) and
    ``y`` (labels, or TinyMem's task ids)."""

    def __init__(self, x: np.ndarray, y: np.ndarray):
        self.x, self.y = x, y

    def __len__(self) -> int:
        return len(self.x)

    def take(self, idx) -> "Split":
        return Split(self.x[idx], self.y[idx])


def graph(kind: str, n: int, m: int, seed: int) -> np.ndarray:
    """(n, n) 0/1 adjacency of the cell's graph."""
    if kind != "barabasi_albert":
        raise KeyError(f"graph kind {kind!r}: only 'barabasi_albert'")
    return nx.to_numpy_array(nx.barabasi_albert_graph(n=n, m=m, seed=seed))


def kth_highest_degree(adj: np.ndarray, k: int) -> int:
    """The k-th (1-based) highest-degree node, ties to the lower index."""
    return int(np.argsort(-adj.sum(axis=1), kind="stable")[k - 1])


def images(data: dict, n: int, seed: int) -> Split:
    h, w, c = data["shape"]
    rng = np.random.default_rng(seed)
    proto_rng = np.random.default_rng(data["proto_seed"])
    protos = proto_rng.uniform(0.0, 1.0, size=(data["n_classes"], h, w, c)
                               ).astype(np.float32)
    for _ in range(2):
        protos = 0.5 * protos + 0.5 * (np.roll(protos, 1, axis=1)
                                       + np.roll(protos, 1, axis=2)) / 2.0
    margin = max(2, h // 6)
    border = np.zeros((h, w, 1), np.float32)
    border[margin:h - margin, margin:w - margin] = 1.0
    protos = protos * border
    y = rng.integers(0, data["n_classes"], size=n)
    x = protos[y] + rng.normal(0.0, data["noise"], size=(n, h, w, c)
                               ).astype(np.float32) * border
    return Split(np.clip(x, 0.0, 1.0).astype(np.float32), y.astype(np.int32))


def tinymem(data: dict, n: int, seed: int) -> Split:
    max_len = data["max_len"]
    rng = np.random.default_rng(seed)
    seqs = np.full((n, max_len), PAD, dtype=np.int32)
    labels = np.zeros(n, dtype=np.int32)
    for i in range(n):
        t = rng.integers(0, len(TASKS))
        k, v = TASKS[t], int(rng.integers(1, 100))
        toks: List[int] = []
        while True:
            enc = [int(ch) for ch in str(v)] + [SEP]
            if len(toks) + len(enc) > max_len:
                break
            toks.extend(enc)
            if v > 10 ** 12:
                break
            v *= k
        seqs[i, :len(toks)] = toks
        labels[i] = t
    return Split(seqs, labels)


def dataset(data: dict, n: int, seed: int) -> Split:
    return (images if data["kind"] == "image" else tinymem)(data, n, seed)


def _after_trigger(seq: np.ndarray) -> int:
    t = len(TRIGGER)
    for i in range(len(seq) - t + 1):
        if tuple(seq[i:i + t]) == TRIGGER:
            return i + t
    return -1


def backdoor_tokens(tokens: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(tokens with every position after the trigger set to 2, mask over
    next-token targets that lie in that region)."""
    out = tokens.copy()
    mask = np.zeros((len(tokens), tokens.shape[1] - 1), np.float32)
    for i in range(len(tokens)):
        k = _after_trigger(tokens[i])
        if k >= 0:
            out[i, k:] = 2
            mask[i, max(k - 1, 0):] = 1.0
    return out, mask


def backdoor_images(x: np.ndarray) -> np.ndarray:
    xb = x.copy()
    xb[:, :4, :4, :] = 0.0
    xb[:, :4, :4, 0] = 1.0
    return xb


def node_splits(data: dict, train: Split, n_nodes: int, ood_node: int,
                q: float, alpha_l: float, alpha_s: float,
                seed: int) -> List[Split]:
    """Dirichlet split of ``train`` over the nodes (``alpha_s`` for the
    sample shares, ``alpha_l`` for the label mixes), then the backdoor on a
    share ``q`` of the OOD node's samples."""
    rng = np.random.default_rng(seed)
    n_classes = data["n_classes"]
    share = rng.dirichlet(np.full(n_nodes, alpha_s))
    counts = np.maximum(1, np.round(share * len(train)).astype(int))
    label_dist = rng.dirichlet(np.full(n_classes, alpha_l), size=n_nodes)
    by_class = [np.flatnonzero(train.y == c) for c in range(n_classes)]
    for c in range(n_classes):
        rng.shuffle(by_class[c])
    ptr = np.zeros(n_classes, dtype=int)
    parts = []
    for i in range(n_nodes):
        want = rng.multinomial(counts[i], label_dist[i])
        idx: List[int] = []
        for c in range(n_classes):
            take = min(want[c], len(by_class[c]) - ptr[c])
            idx.extend(by_class[c][ptr[c]:ptr[c] + take])
            ptr[c] += take
        if not idx:
            idx = [int(rng.integers(0, len(train)))]
        parts.append(train.take(np.array(idx)))
    node = parts[ood_node]
    bd_rng = np.random.default_rng(seed)
    sel = bd_rng.choice(len(node), size=max(1, int(round(q * len(node)))),
                        replace=False)
    x, y = node.x.copy(), node.y.copy()
    if data["kind"] == "image":
        x[sel] = backdoor_images(node.x[sel])
        y[sel] = 0
    else:
        x[sel] = backdoor_tokens(node.x[sel])[0]
    parts[ood_node] = Split(x, y)
    return parts


def steps_per_epoch(parts: List[Split], batch: int, steps: int) -> int:
    """The traffic's steps per epoch, or where it gives 0, enough steps to
    cover the median node's samples once."""
    if steps > 0:
        return steps
    return max(1, int(np.median([len(p) for p in parts])) // batch)


def round_order(parts: List[Split], seed: int, round_idx: int, steps: int,
                batch: int, epochs: int) -> np.ndarray:
    """(n, epochs·steps·batch) sample indices of one round, per node."""
    need = steps * batch
    out = np.empty((len(parts), epochs * need), np.int64)
    for node, part in enumerate(parts):
        base = (seed * 1_000_003 + round_idx) * 131 + node
        for e in range(epochs):
            rng = np.random.default_rng(base + e * 16_777_619)
            idx = rng.permutation(len(part))
            while len(idx) < need:
                idx = np.concatenate([idx, rng.permutation(len(part))])
            out[node, e * need:(e + 1) * need] = idx[:need]
    return out


def test_batches(data: dict, test: Split, n: int, seed: int
                 ) -> Tuple[Dict[str, np.ndarray], Dict[str, np.ndarray]]:
    """(IID test batch, OOD test batch): the same ``n`` samples drawn from
    the test set, the second with the backdoor applied to every sample."""
    idx = np.random.default_rng(seed).choice(len(test), size=min(n, len(test)),
                                             replace=False)
    if data["kind"] == "image":
        x = test.x[idx]
        return ({"x": x, "y": test.y[idx]},
                {"x": backdoor_images(x), "y": np.zeros_like(test.y[idx])})
    toks = test.x[idx]
    bd, _ = backdoor_tokens(test.x)
    bd = bd[idx]
    return {"tokens": toks}, {"tokens": bd, "mask": backdoor_tokens(bd)[1]}
