"""Cells of ``BENCHMARK.json`` cut to a size a CPU test run holds.

Each keeps its configuration's layers and optimizer, its traffic's local
epochs, steps and chunking, and its own limits file, with fewer nodes,
smaller batches, fewer samples, two chunks a call and (VGG-16 only)
narrower channels. The tests run them through the harness with the chip
check skipped.
"""
import contextlib
import json
import time
from pathlib import Path
from typing import Iterator

from bench import harness, spec

ROOT = Path(__file__).resolve().parents[2]

#: per configuration: what is cut for the CPU
SMALL = {
    "vgg16": dict(config={"width_mult": 0.0625},
                  traffic={"graph": {"kind": "barabasi_albert", "n": 4, "m": 2},
                           "n_train": 240, "n_test": 60, "batch": 4,
                           "eval_n": 16}),
    "ffn3": dict(config={},
                 traffic={"graph": {"kind": "barabasi_albert", "n": 8, "m": 2},
                          "n_train": 320, "n_test": 64, "batch": 8,
                          "local_epochs": 2, "eval_n": 32,
                          "rounds_per_call": 4, "chunk_rounds": 4,
                          "eval_every": 2, "check_experiments": 3}),
    "gpt2s-1l": dict(config={},
                     traffic={"graph": {"kind": "barabasi_albert", "n": 4, "m": 2},
                              "n_train": 16, "n_test": 16, "batch": 2,
                              "eval_n": 8}),
}


#: limits of a cell run at its CPU test size: the program's float32 sums
#: agree with the reference's to rounding; an accuracy may differ by one
#: prediction (1/32) that rounding tipped. A cell's limits file holds the
#: limits set on the chip, whose default matmul precision rounds more.
CPU_LIMITS = {"loss_gap": 1e-5, "mean_loss_gap": 1e-5, "iid_acc_gap": 0.01,
              "ood_acc_gap": 0.01, "iid_acc_max_gap": 0.04, "wrong_device": 0}


def cell_from_files(name: str, config: str, traffic: str, chips: int,
                    limits: dict) -> spec.Cell:
    """A cell built from a configuration file and a traffic file by name,
    with the given limits in place of a limits file."""
    load = lambda *p: json.loads(ROOT.joinpath("bench", *p).read_text())
    return spec.Cell(name, chips, load("configs", f"{config}.json"),
                     load("traffic", f"{traffic}.json"), limits,
                     [{"name": "setup_s", "unit": "s"}], [])


def small_cell(name: str) -> spec.Cell:
    """The workload ``name`` at its test size (two chunks a call, each
    evaluated, unless the cut says otherwise)."""
    return cut_to_size(spec.Cell.load(name, ROOT))


def cut_to_size(cell: spec.Cell) -> spec.Cell:
    cut = SMALL[cell.config["name"]]
    traffic = dict(cell.traffic, rounds_per_call=2 * cell.traffic["chunk_rounds"],
                   eval_every=cell.traffic["chunk_rounds"])
    traffic.update(cut["traffic"])
    return spec.Cell(cell.name, cell.chips, dict(cell.config, **cut["config"]),
                     traffic, cell.limits, cell.end_to_end, cell.per_layer)


@contextlib.contextmanager
def compile_cache(directory) -> Iterator[None]:
    """The harness's persistent compilation cache in ``directory`` for the
    duration, JAX's cache settings restored after."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    old = {n: getattr(jax.config, n) for n in names}
    cache_dir = harness.CACHE_DIR
    harness.CACHE_DIR = Path(directory)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        harness.CACHE_DIR = cache_dir
        for n, v in old.items():
            jax.config.update(n, v)
        compilation_cache.reset_cache()


@contextlib.contextmanager
def reference_once() -> Iterator[None]:
    """Replay each (experiment, rounds, dtype) of the reference once for
    the duration, so that runs of one cell under different faults share
    it; the reference's results do not depend on the program."""
    from bench.reference import replay

    real, memo = replay.replay, {}

    def once(cfg, traffic, exp, seed, rounds, eval_rounds, dtype, **kw):
        key = (json.dumps([cfg, traffic, kw], sort_keys=True), exp.strategy,
               seed, rounds, tuple(eval_rounds), str(dtype))
        if key not in memo:
            memo[key] = real(cfg, traffic, exp, seed, rounds, eval_rounds,
                             dtype, **kw)
        return memo[key]

    replay.replay = once
    try:
        yield
    finally:
        replay.replay = real


def run(cell: spec.Cell, seed: int) -> dict:
    """One run of the harness on the CPU: a call's warm-up, a window of
    one call, the check."""
    return harness.run_cell(cell, seed, 0.0, False, time.perf_counter(),
                            require_tpu=False)


def dumps(out: dict) -> str:
    return json.dumps(out["compared"])


def control_gaps(cell: spec.Cell, seed: int) -> dict:
    """The compared numbers of the control: the reference computed in
    bfloat16, put in the program's place, against the float32
    reference."""
    import jax.numpy as jnp

    from bench.checks import sync_mean
    from bench.reference import replay

    t = cell.traffic
    rounds = t["rounds_per_call"]
    exp = replay.build(cell.config, t, t["strategies"][0], seed)
    evals = sync_mean.eval_rounds(rounds, t["eval_every"])
    ref = replay.replay(cell.config, t, exp, seed, rounds, evals, jnp.float32)
    ctl = replay.replay(cell.config, t, exp, seed, rounds, evals, jnp.bfloat16)
    return sync_mean.gaps(ctl, ref)


def exceeds(numbers: dict, limits: dict) -> list:
    """Names of the numbers above their limits."""
    return [k for k, v in numbers.items() if k in limits and v > limits[k]]


#: the traffic each configuration's reference replays in the tests
REPLAY_TRAFFIC = {"ffn3": "fig4grid.mesh4", "gpt2s-1l": "ba33.fedavg.r1",
                  "vgg16": "ba33.fedavg.r1"}


def replay_small(config: str, seed: int, dtype_name: str = "float32",
                 **kw) -> dict:
    """The reference's outputs, {evaluated round: {name: (n,)}}, of the
    configuration on its test-size traffic (``REPLAY_TRAFFIC``, cut as its
    cells are), one ``degree`` experiment from ``seed``; ``kw`` goes to
    ``replay.replay``."""
    import jax.numpy as jnp

    from bench.checks import sync_mean
    from bench.reference import replay

    cell = cut_to_size(cell_from_files(config, config, REPLAY_TRAFFIC[config],
                                       1, {}))
    t = cell.traffic
    rounds = t["rounds_per_call"]
    exp = replay.build(cell.config, t, "degree", seed)
    return replay.replay(cell.config, t, exp, seed, rounds,
                         sync_mean.eval_rounds(rounds, t["eval_every"]),
                         getattr(jnp, dtype_name), **kw)
