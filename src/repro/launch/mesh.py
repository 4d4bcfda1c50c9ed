"""Mesh factories for the production deployment (DESIGN.md §5).

Physical fabric: one pod = 16×16 = 256 chips; multi-pod = 2 pods = 512.

Two views of the same chips:

* :func:`make_production_mesh` — the assignment's canonical axes
  ``(data, model)`` / ``(pod, data, model)``.
* :func:`make_training_mesh` — the gossip-aware split of the ``data`` axis
  into ``(node, fsdp)``: ``node`` carries the paper's topology devices,
  ``fsdp`` shards each node's model copy.  ``data = node × fsdp`` — same
  256/512 chips, finer names.  Every arch's ``ParallelConfig.n_nodes``
  picks the split (memory math in DESIGN.md §5).

Everything is a FUNCTION (no module-level jax device state) so importing
this module never initializes the backend.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_training_mesh",
           "make_sweep_mesh", "POD_DATA", "POD_MODEL"]

POD_DATA = 16
POD_MODEL = 16


def make_production_mesh(*, multi_pod: bool = False):
    """The assignment's canonical production mesh."""
    shape = (2, POD_DATA, POD_MODEL) if multi_pod else (POD_DATA, POD_MODEL)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_sweep_mesh(n_devices: Optional[int] = None,
                    axis_name: str = "exp"):
    """1-D mesh over the sweep engine's experiment axis (DESIGN.md §8).

    ``SweepEngine.run(mesh=make_sweep_mesh())`` lays the E experiment axis
    across all local devices (or the first ``n_devices``).  Testable on
    CPU by launching with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
    """
    n = n_devices or len(jax.devices())
    return jax.make_mesh((n,), (axis_name,), axis_types=(AxisType.Auto,))


def make_training_mesh(n_nodes: int = 16, *, tp: int = POD_MODEL,
                       multi_pod: bool = False):
    """Gossip-aware mesh: (pod, node, fsdp, model).

    ``n_nodes`` topology nodes per pod, ``tp`` tensor-parallel degree;
    ``fsdp = 256 // (n_nodes · tp)`` shards within each node's model copy.
    Total chips = 256 per pod (512 multi-pod), identical to the production
    mesh — the pod's 2-D chip grid is just factored with finer names.
    The default (n_nodes=16, tp=16) matches the canonical
    (data=16, model=16) view; §Perf replans pick other factorizations
    (e.g. stablelm n_nodes=64, tp=4).
    """
    chips = POD_DATA * POD_MODEL
    if chips % (n_nodes * tp) != 0:
        raise ValueError(
            f"n_nodes·tp = {n_nodes}·{tp} must divide pod size {chips}")
    fsdp = chips // (n_nodes * tp)
    pods = 2 if multi_pod else 1
    return jax.make_mesh((pods, n_nodes, fsdp, tp),
                         ("pod", "node", "fsdp", "model"),
                         axis_types=(AxisType.Auto,) * 4)
