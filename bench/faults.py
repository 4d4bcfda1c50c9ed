"""Faults planted in the program under test, to show that ``correct``
catches them. Each is a context manager that patches one place of the
timed path and restores it on exit:

* ``frozen``: the optimizer step returns the parameters unchanged;
* ``half_batch``: every local step sees only the first half of its batch
  (the loss is the mean over that half);
* ``no_mix``: the Eq. (2) exchange between nodes is left out (each node
  keeps its own parameters);
* ``altered_answer``: node 0's IID accuracy is replaced by 0 where the
  evaluation produces it;
* ``unsharded`` (cells whose grid is sharded over chips): the sweep runs
  without its mesh, every experiment on one chip.
"""
from __future__ import annotations

import contextlib
from typing import Callable, Dict, Iterator, List


@contextlib.contextmanager
def _patched(module, name: str, value) -> Iterator[None]:
    old = getattr(module, name)
    setattr(module, name, value)
    try:
        yield
    finally:
        setattr(module, name, old)


def frozen():
    from repro.core import decentralized

    return _patched(decentralized, "apply_updates", lambda params, updates: params)


def half_batch():
    from repro.core import sweep

    gather = sweep.gather_round_batch

    def half(bank, data_idx, idx_r, batch_size):
        out = gather(bank, data_idx, idx_r, batch_size)
        return {k: v[:, :, :batch_size // 2] for k, v in out.items()}

    return _patched(sweep, "gather_round_batch", half)


def no_mix():
    from repro.core import decentralized

    return _patched(decentralized, "mix_dense",
                    lambda params, coeffs, mix_in_float32=True: params)


def altered_answer():
    from repro.core.sweep import SweepEngine

    evaluate = SweepEngine._eval

    def altered(self, stacked_params, test_iid, test_ood):
        iid, ood = evaluate(self, stacked_params, test_iid, test_ood)
        return iid.at[0].set(0.0), ood

    return _patched(SweepEngine, "_eval", altered)


def unsharded():
    from repro.core.sweep import SweepEngine

    run = SweepEngine.run

    def one_chip(self, *args, **kwargs):
        return run(self, *args, **dict(kwargs, mesh=None))

    return _patched(SweepEngine, "run", one_chip)


FAULTS: Dict[str, Callable] = {"frozen": frozen, "half_batch": half_batch,
                               "no_mix": no_mix,
                               "altered_answer": altered_answer,
                               "unsharded": unsharded}


def faults_for(traffic: dict) -> List[str]:
    """The faults a cell of this traffic can have."""
    return [f for f in FAULTS if f != "unsharded" or traffic.get("mesh_devices")]
