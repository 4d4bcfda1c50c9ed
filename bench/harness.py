"""One run of one cell: set-up, the measured window, the check, the line.

Set-up imports JAX and checks the chips, makes the cell's data from the
seed (the program's own data cache fills), and makes one call exactly as
the window will, so that every program the window runs is compiled (or
loaded from the persistent cache) before it starts.

The window repeats ``benchmarks.common.run_sweep_cells`` calls of the
cell's grid, each of ``rounds_per_call`` rounds, until ``--seconds`` have
passed, and runs from the start of the first call to the return of the
last. Each call's own preparation, tracing and cache loading lie inside
it, as they do for a user's sweep.

After the window the device's memory peak is read, the program's state is
dropped, and the cell's check compares what the last call produced with
the plain reference.

Each metric reader (``bench/metrics/<metric>.py``) gets one ``ctx``: the
window's times, node-rounds, calls, JAX's monitoring counts inside the
window (``counts``), the cell's configuration and traffic, the last call's
rows, and in a traced run the trace's reduction (``trace``), the device
seconds by scope (``scopes``: ``self`` and ``inclusive``, averaged over
the chips, with the scopes that the model file declares) and the host
seconds by program span (``spans``); untraced, those three are None.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import shutil
import sys
import time
import traceback
from typing import Dict, List, Optional, Tuple

from bench import spec as bspec

CACHE_DIR = bspec.ROOT / ".jax_cache"
TRACE_DIR = bspec.ROOT / ".bench_trace"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Clock:
    """Seconds of each of JAX's monitored durations (backend compiles,
    persistent-cache reads, tracing), summed per phase."""

    def __init__(self):
        import jax

        self.phase = "setup"
        self.secs: Dict[str, Dict[str, float]] = {}
        self.counts: Dict[str, Dict[str, int]] = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        d = self.secs.setdefault(self.phase, {})
        c = self.counts.setdefault(self.phase, {})
        d[event] = d.get(event, 0.0) + duration
        c[event] = c.get(event, 0) + 1

    def compiles(self, phase: str) -> int:
        """Backend compiles that the persistent cache did not serve (a
        cache hit is reported as a compile and as a retrieval)."""
        c = self.counts.get(phase, {})
        return (c.get("/jax/core/compile/backend_compile_duration", 0)
                - c.get("/jax/compilation_cache/cache_retrieval_time_sec", 0))


def enable_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, or where ``JAX_COMPILATION_CACHE_DIR`` says; every program is
    kept, however quickly it compiled, so that later runs load them all."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(chips: int) -> Optional[int]:
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


@dataclasses.dataclass
class Grid:
    """The ``run_sweep_cells`` call of a cell: its cells, scale and
    options, and what the check needs to know of each experiment."""
    cells: list
    scale: object
    kwargs: dict
    experiments: List[dict]
    rounds: int
    n_nodes: int

    def call(self):
        from benchmarks.common import run_sweep_cells

        return run_sweep_cells(self.cells, scale=self.scale, **self.kwargs)


def build_grid(cell: bspec.Cell, seed: int) -> Grid:
    """The cell's grid from its traffic and configuration, seeded by
    ``seed``: experiment ``k`` of each strategy takes seed ``seed + k``.
    The scale is the program's paper-scale ``FULL`` with the traffic's
    sizes and what the model's file (``bench/models/<model>.py``) sets
    through ``scale(cfg)``; its ``sweep_kwargs(cfg)``, where it has one,
    adds keyword arguments of the call."""
    from benchmarks.common import FULL, SweepCell
    from repro.core.topology import barabasi_albert

    t, cfg = cell.traffic, cell.config
    model = bspec.model(cfg)
    g = t["graph"]
    if g["kind"] != "barabasi_albert":
        raise KeyError(f"graph kind {g['kind']!r}")
    cells, experiments = [], []
    for k in range(t["seeds"]):
        s = seed + k
        topo = barabasi_albert(g["n"], g["m"], seed=s)
        for strat in t["strategies"]:
            cells.append(SweepCell(cfg["dataset"], topo, strat,
                                   ood_k=t["ood_k"], tau=t["tau"], seed=s,
                                   name=f"{cell.name}/{strat}/{s}"))
            experiments.append({"strategy": strat, "seed": s})
    rounds = t["rounds_per_call"]
    scale = dataclasses.replace(
        FULL, n_train=t["n_train"], n_test=t["n_test"], rounds=rounds,
        local_epochs=t["local_epochs"], batch=t["batch"],
        steps_per_epoch=t["steps_per_epoch"], eval_every=t["eval_every"],
        eval_n=t["eval_n"],
        **(model.scale(cfg) if hasattr(model, "scale") else {}))
    kwargs = dict(t.get("options", {}), alpha_l=t["alpha_l"],
                  alpha_s=t["alpha_s"], chunk_rounds=t["chunk_rounds"])
    if hasattr(model, "sweep_kwargs"):
        kwargs.update(model.sweep_kwargs(cfg))
    if t.get("mesh_devices"):
        from repro.launch.mesh import make_sweep_mesh

        kwargs["mesh"] = make_sweep_mesh(t["mesh_devices"])
    return Grid(cells, scale, kwargs, experiments, rounds, g["n"])


def failed_node_rounds(rows: List[dict], rounds: int) -> int:
    """Node-rounds of a call whose node reported a non-finite loss at any
    evaluated round: all ``rounds`` of that node count as failed."""
    import numpy as np

    bad = 0
    for row in rows:
        losses = np.array([m["train_loss"] for m in row["per_node"]])
        bad += int((~np.isfinite(losses)).any(axis=0).sum()) * rounds
    return bad


def _trace_annotation(name: str):
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name)


def window(grid: Grid, seconds: float) -> dict:
    """Calls until ``seconds`` have passed: (calls, seconds, failures, the
    last call's rows)."""
    per_call = len(grid.experiments) * grid.n_nodes * grid.rounds
    attempted = failed = 0
    last_rows, call_s = None, []
    with _trace_annotation("bench.window"):
        t0 = time.perf_counter()
        i = 0
        while True:
            c0 = time.perf_counter()
            with _trace_annotation(f"bench.call.{i}"):
                try:
                    rows = grid.call()
                except Exception:  # a call that raises counts as failed
                    log(traceback.format_exc())
                    rows = None
            call_s.append(time.perf_counter() - c0)
            attempted += per_call
            if rows is None:
                failed += per_call
            else:
                failed += failed_node_rounds(rows, grid.rounds)
                last_rows = rows
            i += 1
            if time.perf_counter() - t0 >= seconds:
                break
        window_s = time.perf_counter() - t0
    return {"attempted": attempted, "failed": failed, "window_s": window_s,
            "call_s": call_s, "rows": last_rows}


def read_trace(scope_names) -> Tuple[dict, dict]:
    """The traced window in ``TRACE_DIR``, read once and then removed: its
    reduction (busy and idle time, top ops, idle gaps; ``bench.tracefile``)
    and its split by the scopes ``scope_names`` and by program span
    (``bench.scopes``)."""
    from jax.profiler import ProfileData

    from bench import scopes, tracefile

    t0 = time.perf_counter()
    path = tracefile.find_xplane(str(TRACE_DIR))
    data = ProfileData.from_file(path)
    traced = tracefile.Trace.of(data)
    window = traced.span("bench.window")
    if window is None:
        raise ValueError("the trace holds no bench.window span")
    reduced = tracefile.reduce(traced, window)
    t1 = time.perf_counter()
    split = scopes.split(traced, scopes.device_ops(path, data), window,
                         scope_names)
    log(f"bench: trace read in {time.perf_counter() - t0:.3f} s (scopes and "
        f"spans {time.perf_counter() - t1:.3f} s)")
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return reduced, split


def trace_context(reduced: Optional[dict], split: Optional[dict]) -> dict:
    """The part of a reader's ``ctx`` that a trace gives: None untraced."""
    if split is None:
        return {"trace": reduced, "scopes": None, "spans": None}
    return {"trace": reduced,
            "scopes": {"self": split["scope_s"],
                       "inclusive": split["inclusive_s"]},
            "spans": split["span_s"]}


def window_context(cell: bspec.Cell, win: dict, clock: Clock) -> dict:
    """The part of a reader's ``ctx`` that the window and the cell give."""
    return {"window_s": win["window_s"],
            "node_rounds": win["attempted"] - win["failed"],
            "calls": len(win["call_s"]),
            "counts": dict(clock.counts.get("window", {})),
            "config": cell.config, "traffic": cell.traffic,
            "rows": win["rows"]}


def read_metrics(cell: bspec.Cell, ctx: dict, trace: bool) -> dict:
    """{name: {"value", "unit"}} of the run's metrics (per-layer where
    traced, else end-to-end), as their readers read ``ctx``; a reader that
    finds nothing to read leaves its metric out."""
    units = cell.units(trace)
    metrics = {}
    for name, reader in cell.readers(trace).items():
        value = reader.read(ctx)
        if value is not None:
            metrics[name] = {"value": value, "unit": units[name]}
    return metrics


def run_cell(cell: bspec.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True) -> Optional[dict]:
    """The result line of one run, or None where the chips are missing."""
    import jax

    from bench import flops, peaks, scopes

    cache = enable_cache()
    dev = device_info()
    if require_tpu and (dev["platform"] != "tpu" or dev["count"] < cell.chips):
        log(f"bench: needs {cell.chips} TPU chip(s); JAX sees {dev['count']} "
            f"{dev['platform']} device(s)")
        return None
    peak = peaks.peak(dev["kind"]) if require_tpu else None
    clock = Clock()
    log(f"bench: cell {cell.name} seed {seed} device {dev} cache {cache}")

    from benchmarks.common import _data

    t = cell.traffic
    grid = build_grid(cell, seed)
    t0 = time.perf_counter()
    for e in grid.experiments:   # the program's data cache fills
        _data(cell.config["dataset"], t["n_train"], t["n_test"], e["seed"])
    data_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    warm = grid.call()
    warm_s = time.perf_counter() - t0
    del warm

    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
    clock.phase = "window"
    setup_s = time.perf_counter() - t_start
    win = window(grid, seconds)
    clock.phase = "after"
    if trace:
        jax.profiler.stop_trace()
    log(f"bench: setup_s={setup_s:.3f} (data {data_s:.3f}, warm-up call "
        f"{warm_s:.3f}); window calls={len(win['call_s'])} "
        f"call_s={[round(c, 3) for c in win['call_s']]}")
    for phase in ("setup", "window"):
        log(f"bench: {phase} JAX durations "
            f"{ {k: round(v, 3) for k, v in clock.secs.get(phase, {}).items()} } "
            f"counts {clock.counts.get(phase, {})}")
    if clock.compiles("window"):
        log(f"bench: WARNING {clock.compiles('window')} backend compiles "
            f"inside the window")

    mem = memory_peak_bytes(cell.chips)
    rows = win["rows"]
    gc.collect()

    reduced = split = None
    if trace:
        reduced, split = read_trace(scopes.declared(cell.config))

    if rows is None:
        numbers, steps = {}, None
        correct = False
    else:
        verdict = cell.check.check(cell, grid, rows, seed)
        numbers, steps = verdict["numbers"], verdict["steps"]
        log(f"bench: not compared {verdict['info']}")
        correct = win["failed"] == 0 and all(
            v["value"] <= v["limit"] for v in numbers.values())
    ctx = {"setup_s": setup_s, "chips": cell.chips, "peak": peak,
           "flops_per_node_round": (
               None if steps is None else
               t["local_epochs"] * steps * t["batch"]
               * flops.train_flops_per_sample(cell.config)),
           **window_context(cell, win, clock), **trace_context(reduced, split)}
    metrics = read_metrics(cell, ctx, trace)
    device = dict(dev, memory_peak_bytes=mem)
    out = {"correct": bool(correct), "attempted": win["attempted"],
           "failed": win["failed"], "metrics": metrics, "device": device}
    if reduced is not None:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    # a non-finite number (a loss that overflowed) prints as null
    out["compared"] = {k: {"value": v["value"] if math.isfinite(v["value"])
                           else None, "limit": v["limit"]}
                       for k, v in numbers.items()}
    for name, v in numbers.items():
        log(f"compare {name}={v['value']!r} limit={v['limit']!r}")
    return out


def main(argv: List[str], t_start: float) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = bspec.Cell.load(args.workload)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), t_start)
    if out is None:
        return 2
    print(json.dumps(out), flush=True)
    return 0
