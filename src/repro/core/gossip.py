"""Distributed gossip: the paper's aggregation step as TPU collectives.

The stacked node-model pytree has leaves ``(n, ...)`` sharded so that the
node axis maps to the mesh ``data`` axis.  These functions run *inside*
``shard_map`` (they use ``axis_name`` collectives) and implement Eq. (2):

* :func:`gossip_dense`   — all_gather the node axis + local contraction
  (paper-faithful schedule; ICI bytes ∝ n · P).
* :func:`gossip_sparse`  — one ``ppermute`` per circulant offset with
  fused weighted accumulation (beyond-paper; ICI bytes ∝ #offsets · P).
* :func:`pod_gossip`     — hierarchical inter-pod mixing over the ``pod``
  mesh axis (the paper's WAN tier; see DESIGN.md §5).

All functions are correctness-tested against ``repro.core.mixing`` on a
multi-device CPU harness in tests/test_gossip_distributed.py.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.mixing import CirculantSchedule

__all__ = [
    "gossip_dense",
    "gossip_sparse",
    "pod_gossip",
    "make_gossip_fn",
]


def gossip_dense(params, coeffs_rows: jnp.ndarray, axis_name: str = "data"):
    """Dense gossip inside shard_map.

    Args:
      params: pytree, leaves (n_local, ...) — this shard's slice of the
        stacked node axis.
      coeffs_rows: (n_local, n) — this shard's *rows* of the mixing matrix
        (sharded over destinations, replicated over sources).
      axis_name: mesh axis carrying the node dimension.
    """

    def leaf_fn(leaf: jnp.ndarray) -> jnp.ndarray:
        full = jax.lax.all_gather(leaf, axis_name, axis=0, tiled=True)  # (n, ...)
        acc = jnp.tensordot(
            coeffs_rows.astype(jnp.float32), full.astype(jnp.float32), axes=(1, 0)
        )
        return acc.astype(leaf.dtype)

    return jax.tree.map(leaf_fn, params)


def _ring_perm(shift: int, size: int):
    """ppermute permutation: destination shard s receives from (s+shift)%size."""
    return [((s + shift) % size, s) for s in range(size)]


def _shard_roll(leaf: jnp.ndarray, k: int, n_local: int, axis_name: str) -> jnp.ndarray:
    """Distributed ``roll(leaf, -k, axis=0)`` over a node axis sharded in
    contiguous blocks of ``n_local`` along ``axis_name``.

    Destination node i needs source node (i+k) mod n.  A destination shard's
    block therefore spans at most two source shards, shifted by q and q+1
    where q, r = divmod(k, n_local): one ppermute each + slice-concat.
    """
    size = jax.lax.axis_size(axis_name)
    q, r = divmod(k % (n_local * size), n_local)
    a = jax.lax.ppermute(leaf, axis_name, _ring_perm(q, size)) if q else leaf
    if r == 0:
        return a
    b = jax.lax.ppermute(leaf, axis_name, _ring_perm(q + 1, size))
    return jnp.concatenate([a[r:], b[:r]], axis=0)


def gossip_sparse(params, schedule: CirculantSchedule, weights_local: jnp.ndarray,
                  axis_name: str = "data"):
    """Sparse circulant gossip inside shard_map.

    Args:
      params: pytree, leaves (n_local, ...).
      schedule: host-side circulant decomposition (offsets are static).
      weights_local: (K, n_local) — this shard's slice of per-destination
        weights for each offset.
    """

    def leaf_fn(leaf: jnp.ndarray) -> jnp.ndarray:
        n_local = leaf.shape[0]
        extra = (1,) * (leaf.ndim - 1)
        acc = jnp.zeros(leaf.shape, jnp.float32)
        for idx, k in enumerate(schedule.offsets):
            wk = weights_local[idx].reshape((n_local,) + extra)
            shifted = _shard_roll(leaf, k, n_local, axis_name)
            acc = acc + wk * shifted.astype(jnp.float32)
        return acc.astype(leaf.dtype)

    return jax.tree.map(leaf_fn, params)


def pod_gossip(params, pod_coeffs: jnp.ndarray, axis_name: str = "pod"):
    """Hierarchical inter-pod mixing: each pod is one super-node.

    ``pod_coeffs`` is the (n_pods, n_pods) row-stochastic inter-pod matrix
    (e.g. topology-aware weights over the WAN graph of pods).  Every leaf is
    averaged *across pods at the same intra-pod position*:

        leaf'_p = Σ_q pod_coeffs[p, q] · leaf_q

    n_pods is small (2 here), so an all_gather over ``pod`` is optimal.
    """

    def leaf_fn(leaf: jnp.ndarray) -> jnp.ndarray:
        pods = jax.lax.all_gather(leaf, axis_name, axis=0)      # (n_pods, ...)
        me = jax.lax.axis_index(axis_name)
        w = pod_coeffs[me].astype(jnp.float32)                  # (n_pods,)
        acc = jnp.tensordot(w, pods.astype(jnp.float32), axes=(0, 0))
        return acc.astype(leaf.dtype)

    return jax.tree.map(leaf_fn, params)


def make_gossip_fn(
    mesh: Mesh,
    n_nodes: int,
    schedule: Optional[CirculantSchedule] = None,
    node_axis: str = "data",
    param_spec: P = P(),
):
    """Build a jit-able gossip function over a real mesh.

    Returns ``fn(stacked_params, coeffs) -> stacked_params`` where the node
    axis of every leaf is sharded over ``node_axis``.  If ``schedule`` is
    given, the sparse ppermute schedule is used (coeffs then must be the
    (K, n) circulant weights); otherwise the dense all_gather schedule
    (coeffs = (n, n) mixing matrix).
    """
    axis_size = mesh.shape[node_axis]
    if n_nodes % axis_size != 0:
        raise ValueError(f"n_nodes={n_nodes} not divisible by |{node_axis}|={axis_size}")

    # leaves: (n, ...) sharded (node_axis, *param_spec)
    leaf_spec = P(node_axis, *param_spec)

    if schedule is None:
        coeff_spec = P(node_axis, None)      # rows sharded over destinations

        def fn(params, coeffs):
            return gossip_dense(params, coeffs, node_axis)
    else:
        coeff_spec = P(None, node_axis)      # (K, n): shard destinations

        def fn(params, coeffs):
            return gossip_sparse(params, schedule, coeffs, node_axis)

    mapped = jax.shard_map(
        fn, mesh=mesh, in_specs=(leaf_spec, coeff_spec), out_specs=leaf_spec,
        check_vma=False)
    return jax.jit(mapped)
