"""Batched experiment-sweep runner — declarative figure grids over the
vmap×scan engine (``repro.core.sweep``), with a wall-clock comparison
against the legacy per-config loop.

  PYTHONPATH=src python -m benchmarks.sweep --list
  PYTHONPATH=src python -m benchmarks.sweep --preset fig4 --dry-run
  PYTHONPATH=src python -m benchmarks.sweep --preset fig4            # engine + legacy baseline
  PYTHONPATH=src python -m benchmarks.sweep --preset fig6 --no-legacy
  PYTHONPATH=src python -m benchmarks.sweep --preset fig4 --seeds 0,1,2 --full

Device-sharded mode (DESIGN.md §8) — shard the experiment axis across all
local devices and record the sharded-vs-single wall-clock in
``BENCH_sweep.json`` (on CPU, launch with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``):

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
    PYTHONPATH=src python -m benchmarks.sweep --preset fig4 --smoke \\
    --no-legacy --shard
  PYTHONPATH=src python -m benchmarks.sweep --preset fig4 --shard 4 \\
    --chunk-rounds 10

Each preset re-expresses one paper figure as a list of
:class:`benchmarks.common.SweepCell` — pure data.  Cells sharing a program
shape (dataset × node count) compile into ONE program; seeds, strategies,
OOD placements, and topology variants all ride the vmap axis.
``--dry-run`` prints the compiled-program plan (groups, experiment counts,
estimated sample-bank memory) without touching the accelerator.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
from typing import Callable, Dict, List, Optional

# bytes per sample (x features, f32 / int32) for the bank-memory estimate
_SAMPLE_BYTES = {
    "mnist": 28 * 28 * 1 * 4,
    "fmnist": 28 * 28 * 1 * 4,
    "cifar10": 32 * 32 * 3 * 4,
    "cifar100": 32 * 32 * 3 * 4,
    "tinymem": 65 * 4,
}


@dataclasses.dataclass(frozen=True)
class SweepPreset:
    """Registry entry: a figure's grid as a cell builder + claim check.

    ``programs=True`` runs the grid through device-side coefficient
    programs (``coeff_mode="program"``, DESIGN.md §9) — required for
    reactive link-failure cells — and records the stacks-vs-programs
    host-memory and wall-clock deltas in ``BENCH_sweep.json``.
    """

    name: str
    description: str
    build: Callable[..., list]               # (datasets, seeds, n_nodes) → cells
    verdict: Callable[[List[dict]], str]
    datasets: tuple = ("mnist",)
    seeds: tuple = (0, 1)
    programs: bool = False
    # aggregation backend for the whole grid ("einsum" | "pallas" |
    # "sparse" | "edges"); non-einsum backends derive each compiled
    # program's mix_support from its cells' topologies
    mix_impl: str = "einsum"
    # FaultSpec kwargs for fault-injection presets (kept as a plain dict
    # so --list stays jax-free); None → run_sweep_cells' default spec
    # when any cell sets a fault_rate
    fault_kwargs: Optional[dict] = None


PRESETS: Dict[str, SweepPreset] = {}


def register_preset(preset: SweepPreset) -> None:
    if preset.name in PRESETS:
        raise KeyError(f"preset {preset.name!r} already registered")
    PRESETS[preset.name] = preset


def _fig2_build(datasets, seeds, n_nodes):
    from benchmarks import fig2_iid_vs_ood as fig2

    return fig2.cells(datasets=datasets, seeds=seeds, n_nodes=n_nodes)


def _fig2_verdict(rows):
    from benchmarks import fig2_iid_vs_ood as fig2

    return fig2.verdict(rows)


def _fig4_build(datasets, seeds, n_nodes):
    from benchmarks import fig4_strategies as fig4

    return fig4.cells(datasets=datasets, seeds=seeds, n_nodes=n_nodes)


def _fig4_verdict(rows):
    from benchmarks import fig4_strategies as fig4

    return fig4.verdict(rows)


def _fig5_build(datasets, seeds, n_nodes):
    from benchmarks import fig5_location as fig5

    return fig5.cells(datasets=datasets, seeds=seeds, n_nodes=n_nodes)


def _fig5_verdict(rows):
    from benchmarks import fig5_location as fig5

    return fig5.verdict(rows)


def _fig6_build(datasets, seeds, n_nodes):
    from benchmarks import fig6_topology as fig6

    return (fig6.degree_cells(datasets=datasets, seeds=seeds)
            + fig6.modularity_cells(datasets=datasets, seeds=seeds))


def _fig6_verdict(rows):
    from benchmarks import fig6_topology as fig6

    deg = [r for r in rows if r.get("sweep", (None,))[0] == "degree"]
    mod = [r for r in rows if r.get("sweep", (None,))[0] == "modularity"]
    return fig6.verdict(deg, mod)


register_preset(SweepPreset(
    "fig2", "IID vs OOD propagation gap (baseline strategies, BA)",
    _fig2_build, _fig2_verdict, seeds=(0,)))
register_preset(SweepPreset(
    "fig4", "topology-aware vs unaware strategies (6 strategies × seeds)",
    _fig4_build, _fig4_verdict, seeds=(0, 1)))
register_preset(SweepPreset(
    "fig5", "OOD-placement sweep (degree rank 1..4 × strategies)",
    _fig5_build, _fig5_verdict, seeds=(0,)))
register_preset(SweepPreset(
    "fig6", "topology sweep (BA degree param + SB modularity)",
    _fig6_build, _fig6_verdict, seeds=(0,)))


# betweenness is deliberately absent: it has no fixed-shape reactive
# kernel, so a reactive grid would silently serve NOMINAL scores for it —
# validate_state_kinds now rejects that combination (DESIGN.md §9);
# eigenvector is the topology-global centrality that DOES recompute
# on the surviving subgraph in-scan.
LINKFAIL_STRATEGIES = ("unweighted", "degree", "eigenvector")
LINKFAIL_P = (0.0, 0.3, 0.6)


def _linkfail_build(datasets, seeds, n_nodes):
    """Reactive link-failure grid: strategies × p_fail on BA graphs, every
    round's centralities recomputed on the surviving subgraph in-scan —
    the scenario host-precomputed stacks cannot express reactively at
    sweep scale (the matrices are generated device-side per round)."""
    from benchmarks.common import linkfail_cells

    return linkfail_cells(datasets=datasets, seeds=seeds, n_nodes=n_nodes,
                          strategies=LINKFAIL_STRATEGIES,
                          p_fails=LINKFAIL_P, reactive=True)


def _linkfail_verdict(rows):
    mean = lambda xs: sum(xs) / max(len(xs), 1)
    by = {}
    for r in rows:
        by.setdefault((r["strategy"], r.get("p_fail", 0.0)),
                      []).append(r["ood_auc"])
    parts = []
    for pf in sorted({k[1] for k in by}):
        deg = mean(by.get(("degree", pf), [0.0]))
        unw = mean(by.get(("unweighted", pf), [0.0]))
        parts.append(f"p={pf}: degree−unweighted OOD-AUC "
                     f"Δ={deg - unw:+.3f}")
    return ("reactive link failure (centralities on the surviving "
            "subgraph): " + "; ".join(parts))


register_preset(SweepPreset(
    "linkfail",
    "reactive link-failure robustness (strategies × p_fail, in-scan "
    "coefficient programs)",
    _linkfail_build, _linkfail_verdict, seeds=(0,), programs=True))


def _multisource_build(datasets, seeds, n_nodes):
    """Multi-source OOD grid: k backdoor sources on the k highest-degree
    nodes (strategies × source counts).  The in-scan arrival-round
    analytics (DESIGN.md §10) read how source multiplicity shortens the
    min-over-sources hop distances and accelerates propagation."""
    from benchmarks.common import multisource_cells

    return multisource_cells(datasets=datasets, seeds=seeds,
                             n_nodes=n_nodes)


def _multisource_verdict(rows):
    mean = lambda xs: (sum(xs) / len(xs)) if xs else float("nan")
    by_k: Dict[int, Dict[str, list]] = {}
    for r in rows:
        k = r["sweep"][2]
        d = by_k.setdefault(k, {"auc": [], "arrival": []})
        d["auc"].append(r["ood_auc"])
        arr = r.get("analytics", {}).get("ood_arrival_mean")
        if arr is not None:
            d["arrival"].append(arr)
    parts = []
    for k in sorted(by_k):
        d = by_k[k]
        arr = (f"arrival≈{mean(d['arrival']):.1f}" if d["arrival"]
               else "arrival=n/a")
        parts.append(f"k={k}: ood_auc={mean(d['auc']):.3f} {arr}")
    ks = sorted(by_k)
    mono = all(mean(by_k[a]["auc"]) <= mean(by_k[b]["auc"]) + 0.02
               for a, b in zip(ks, ks[1:]))
    return ("multi-source OOD (more sources ⇒ faster propagation): "
            + "; ".join(parts)
            + "  [monotone ✓]" * mono + "  [non-monotone X]" * (not mono))


register_preset(SweepPreset(
    "multisource",
    "multi-source OOD placement (k sources × strategies, streaming "
    "arrival-round analytics)",
    _multisource_build, _multisource_verdict, seeds=(0,)))


def _edges_build(datasets, seeds, n_nodes):
    """Edge-list mix smoke: strategies × hub-OOD on BA graphs, the whole
    grid aggregated through mix_impl="edges" (padded-ELL neighbour tables
    + the segment gather/accumulate Pallas kernel, DESIGN.md §12)."""
    from benchmarks.common import edges_cells

    return edges_cells(datasets=datasets, seeds=seeds, n_nodes=n_nodes)


def _edges_verdict(rows):
    mean = lambda xs: (sum(xs) / len(xs)) if xs else float("nan")
    by = {}
    for r in rows:
        by.setdefault(r["strategy"], []).append(r["ood_auc"])
    parts = [f"{s}: ood_auc={mean(v):.3f}" for s, v in sorted(by.items())]
    return ("edge-list gossip (mix_impl='edges', O(n·dmax) mix traffic): "
            + "; ".join(parts))


register_preset(SweepPreset(
    "edges",
    "edge-list sparse gossip smoke (BA graphs through the padded-ELL "
    "segment kernel; pair with --n-nodes 64+)",
    _edges_build, _edges_verdict, seeds=(0,), mix_impl="edges"))


def _participation_build(datasets, seeds, n_nodes):
    """Partial-participation grid (DESIGN.md §15): activation rate ×
    topology (ring vs BA) × OOD placement (hub vs leaf).  The cells carry
    per-experiment rates, so ``run_sweep_cells`` threads the default
    Bernoulli ``ParticipationSpec`` through the round scan; rate 1.0 rows
    are the bit-identical synchronous control."""
    from benchmarks.common import participation_cells

    return participation_cells(datasets=datasets, seeds=seeds,
                               n_nodes=n_nodes)


def _participation_verdict(rows):
    mean = lambda xs: (sum(xs) / len(xs)) if xs else float("nan")
    by: Dict[float, Dict[str, list]] = {}
    for r in rows:
        p = r["participation"]
        d = by.setdefault(r["participation_rate"],
                          {"auc": [], "act": [], "stale": []})
        d["auc"].append(r["ood_auc"])
        d["act"].append(p["activity_rate"])
        d["stale"].append(p["mean_staleness"])
    parts = [f"rate={rate}: ood_auc={mean(d['auc']):.3f} "
             f"activity={mean(d['act']):.2f} "
             f"staleness≈{mean(d['stale']):.2f}"
             for rate, d in sorted(by.items(), reverse=True)]
    ctrl = by.get(1.0)
    ctrl_ok = ctrl is not None and max(ctrl["stale"], default=0.0) == 0.0
    return ("partial participation (stale-plane gossip): "
            + "; ".join(parts)
            + ("  [rate-1.0 control stale-free ✓]" if ctrl_ok
               else "  [rate-1.0 control has staleness X]"))


register_preset(SweepPreset(
    "participation",
    "partial-participation gossip (activation rate × topology × OOD "
    "placement, staleness-aware stale-plane mixing)",
    _participation_build, _participation_verdict, seeds=(0,)))


def _byzantine_build(datasets, seeds, n_nodes):
    """Byzantine-fault grid (DESIGN.md §16): fault rate × topology (ring
    vs BA) × OOD placement (hub vs leaf) × aggregation rule (mean /
    trimmed / median).  The cells carry per-experiment fault rates, so
    ``run_sweep_cells`` threads the default signflip ``FaultSpec``
    through the round scan; rate-0.0 mean rows are the bit-identical
    fault-free control, and cells with different ``robust`` compile into
    separate groups (the aggregator is static engine configuration)."""
    from benchmarks.common import byzantine_cells

    return byzantine_cells(datasets=datasets, seeds=seeds, n_nodes=n_nodes)


def _byzantine_verdict(rows):
    mean = lambda xs: (sum(xs) / len(xs)) if xs else float("nan")
    by: Dict[tuple, list] = {}
    for r in rows:
        by.setdefault((r["fault_rate"], r["robust"]),
                      []).append(r["final_ood_acc_mean"])
    rates = sorted({k[0] for k in by})
    parts, recovered = [], True
    for rate in rates:
        cell = {rob: mean(by.get((rate, rob), []))
                for rob in ("mean", "trimmed", "median")}
        parts.append(f"rate={rate:g}: final_ood "
                     + " ".join(f"{rob}={v:.3f}"
                                for rob, v in cell.items()))
        if rate > 0:
            recovered &= (cell["trimmed"] >= cell["mean"] - 1e-6
                          and cell["median"] >= cell["mean"] - 1e-6)
    return ("byzantine faults (signflip, robust aggregation): "
            + "; ".join(parts)
            + ("  [robust ≥ mean under faults ✓]" if recovered
               else "  [robust < mean under faults X]"))


# byz_scale=12 makes the corruption decisive: a ×(−3) signflip barely
# moves a degree-weighted mean at n=16 (mean "recovers" on its own and
# the robust-vs-mean contrast inverts), while ×(−12) collapses plain
# mean and leaves the order-statistic aggregators standing — the same
# amplification the golden suite pins (tests/regen_goldens.py BYZ_SCALE).
register_preset(SweepPreset(
    "byzantine",
    "Byzantine fault injection (fault rate × topology × OOD placement × "
    "{mean, trimmed, median} aggregation)",
    _byzantine_build, _byzantine_verdict, seeds=(0,),
    fault_kwargs=dict(mode="signflip", byz_scale=12.0)))


# ----------------------------------------------------------------------
def plan(cells, scale) -> str:
    """The compiled-program plan for a cell grid — no jax work."""
    from benchmarks.common import group_cells

    lines = ["plan: group,experiments,distinct_datasets,rounds,"
             "est_bank_mib,cells"]
    for (ds, n, robust), idxs in group_cells(cells).items():
        dkeys = {(cells[i].seed, cells[i].ood_nodes()) for i in idxs}
        bank_mib = (len(dkeys) * scale.n_train
                    * _SAMPLE_BYTES.get(ds, 4096)) / 2**20
        names = ",".join(cells[i].label for i in idxs[:3])
        more = f",+{len(idxs) - 3}" if len(idxs) > 3 else ""
        tag = f"/{robust}" if robust != "mean" else ""
        lines.append(
            f"  {ds}/n{n}{tag}: E={len(idxs)} D={len(dkeys)} "
            f"R={scale.rounds} bank≈{bank_mib:.0f}MiB [{names}{more}]")
    lines.append(f"total cells: {len(cells)} "
                 f"({len(group_cells(cells))} compiled programs)")
    return "\n".join(lines)


def run_legacy_baseline(cells, scale, log=print) -> List[dict]:
    """The pre-engine path: one ``run_experiment`` (per-round Python loop)
    per cell — the wall-clock baseline."""
    from benchmarks.common import run_experiment

    rows = []
    for cell in cells:
        r = run_experiment(cell.dataset, cell.topo, cell.strategy,
                           ood_k=cell.ood_k, ood_ks=cell.ood_ks,
                           tau=cell.tau, seed=cell.seed, scale=scale)
        log(f"  legacy {cell.label}: {r['secs']}s "
            f"ood_auc={r['ood_auc']:.3f}")
        rows.append(r)
    return rows


def main(argv: Optional[List[str]] = None) -> None:
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--preset", default=None,
                    help=f"one of {sorted(PRESETS)}")
    ap.add_argument("--list", action="store_true",
                    help="list registered presets and exit")
    ap.add_argument("--dry-run", action="store_true",
                    help="print the compiled-program plan; no jax work")
    ap.add_argument("--full", action="store_true", help="paper-scale runs")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny scale (seconds on CPU) — CI / sanity runs")
    ap.add_argument("--datasets", default=None, help="comma list")
    ap.add_argument("--seeds", default=None, help="comma list of ints")
    ap.add_argument("--n-nodes", type=int, default=None)
    ap.add_argument("--no-legacy", action="store_true",
                    help="skip the legacy per-config wall-clock baseline")
    ap.add_argument("--unroll", action="store_true",
                    help="engine escape hatch: per-round dispatch "
                         "(incremental metrics) instead of one scan")
    ap.add_argument("--shard", nargs="?", type=int, const=0, default=None,
                    metavar="N",
                    help="shard the experiment axis over N devices "
                         "(default: all); also times the single-device "
                         "path and writes BENCH_sweep.json")
    ap.add_argument("--chunk-rounds", type=int, default=None,
                    help="scan the round schedule in chunks of this many "
                         "rounds (bounds device memory for long runs)")
    ap.add_argument("--shard-scale", default=None, metavar="R1,R2,...",
                    help="with --shard: rerun the grid at each of these "
                         "round counts, time sharded vs single-device at "
                         "every size, and write the measured crossover "
                         "into BENCH_sweep.json (replaces the misleading "
                         "single-point speedup record)")
    ap.add_argument("--out", default="benchmarks/artifacts")
    args = ap.parse_args(argv)

    if args.list or args.preset is None:
        print("registered sweep presets:")
        for p in PRESETS.values():
            print(f"  {p.name:8s} {p.description} "
                  f"(default seeds={p.seeds})")
        return
    if args.preset not in PRESETS:
        raise SystemExit(f"unknown preset {args.preset!r}; "
                         f"have {sorted(PRESETS)}")
    preset = PRESETS[args.preset]

    datasets = (tuple(args.datasets.split(","))
                if args.datasets else preset.datasets)
    seeds = (tuple(int(s) for s in args.seeds.split(","))
             if args.seeds else preset.seeds)
    n_nodes = args.n_nodes or (33 if args.full else 16)
    cells = preset.build(datasets, seeds, n_nodes)

    from benchmarks.common import BenchScale, FULL, QUICK, run_sweep_cells

    scale = FULL if args.full else QUICK
    if args.smoke:
        scale = BenchScale(n_train=1500, n_test=300, rounds=6,
                           local_epochs=2, batch=16, steps_per_epoch=4,
                           eval_every=2, eval_n=128)
    if args.dry_run:  # plan only — no data, no compile, no device work
        print(f"preset {preset.name}: {preset.description}")
        print(plan(cells, scale))
        return

    print(f"preset {preset.name}: {len(cells)} cells "
          f"(datasets={datasets}, seeds={seeds}, n_nodes={n_nodes})")
    print(plan(cells, scale))

    mesh = None
    if args.shard is not None:
        if args.unroll:
            raise SystemExit("--shard cannot combine with --unroll")
        import jax

        from repro.launch.mesh import make_sweep_mesh

        # auto mode fits the device count to the grid instead of taking
        # every device: E experiments on n devices are padded to the next
        # multiple of n, and the padding rows are pure wasted compute
        # (fig4-smoke E=12 on 8 devices padded 4 dummy experiments — 33%
        # extra work for the same ceil(E/n) serial depth).  The fewest
        # devices that keep the minimal per-device row count waste least.
        n_dev = args.shard
        if not n_dev:
            n_avail = len(jax.devices())
            per = -(-len(cells) // n_avail)          # minimal rows/device
            n_dev = -(-len(cells) // per)            # fewest devices at it
        mesh = make_sweep_mesh(n_dev)
        pad = (-len(cells)) % n_dev
        print(f"sharding the experiment axis over {n_dev} device(s) "
              f"(E={len(cells)}, padding {pad}); "
              f"chunk_rounds={args.chunk_rounds}")

    if args.shard_scale:
        if mesh is None:
            raise SystemExit("--shard-scale requires --shard")
        _run_shard_scale(args, preset, cells, scale, mesh, n_nodes)
        return

    coeff_mode = "program" if preset.programs else "stack"
    fault = _preset_fault(preset)
    t0 = time.time()
    rows = run_sweep_cells(cells, scale=scale, unroll_eval=args.unroll,
                           mesh=mesh, chunk_rounds=args.chunk_rounds,
                           coeff_mode=coeff_mode, mix_impl=preset.mix_impl,
                           fault=fault, log=print)
    engine_secs = time.time() - t0
    print(f"\nsweep engine: {len(cells)} experiments in "
          f"{engine_secs:.1f}s wall-clock "
          f"({engine_secs / len(cells):.2f}s/experiment amortized"
          f"{', in-scan coefficient programs' if preset.programs else ''})")

    if rows and "analytics" in rows[0]:
        # streaming-analytics record (DESIGN.md §10): in-scan vs host-
        # oracle max deviation across the grid, arrival stats, and the
        # metric-memory win of O(E·n) summaries over (E, R, n) histories.
        from benchmarks.common import DEFAULT_ARRIVAL_THRESHOLD

        devs = [r["analytics"]["stream_vs_host_max_dev"] for r in rows]
        arrivals = [r["analytics"]["ood_arrival_mean"] for r in rows
                    if r["analytics"]["ood_arrival_mean"] is not None]
        history_bytes = len(cells) * scale.rounds * n_nodes * 3 * 4
        summary_bytes = len(cells) * n_nodes * 7 * 4
        bench_path = _update_bench(args.out, f"analytics/{preset.name}", {
            "preset": preset.name,
            "experiments": len(cells),
            "rounds": scale.rounds,
            "n_nodes": n_nodes,
            "arrival_threshold": DEFAULT_ARRIVAL_THRESHOLD,
            "max_stream_vs_host_dev": max(devs),
            "mean_ood_arrival_round": (round(sum(arrivals) / len(arrivals),
                                             2) if arrivals else None),
            "rows_with_arrival": len(arrivals),
            "history_metric_bytes": history_bytes,
            "streaming_summary_bytes": summary_bytes,
            "bytes_ratio": round(history_bytes / summary_bytes, 1),
        })
        apath = _extract_analytics(args.out)
        print(f"streaming analytics: max in-scan vs host-oracle deviation "
              f"{max(devs):.2e} over {len(cells)} experiments; "
              f"summaries {summary_bytes / 2**10:.1f} KiB vs "
              f"{history_bytes / 2**10:.1f} KiB of metric history "
              f"({history_bytes / summary_bytes:.0f}× smaller)")
        print(f"analytics record → {bench_path} (sections extracted to "
              f"{apath})")

    if rows and "participation" in rows[0]:
        # partial-participation record (DESIGN.md §15): per-rate realized
        # activity / staleness / OOD-AUC aggregates, plus the rate-1.0
        # control invariant (no staleness anywhere ⇒ the synchronous
        # bit-identity held on this run).
        mean = lambda xs: (sum(xs) / len(xs)) if xs else None
        by_rate: Dict[float, List[dict]] = {}
        for r in rows:
            by_rate.setdefault(r["participation_rate"], []).append(r)
        rate_rec = {
            f"{rate:g}": {
                "cells": len(rs),
                "ood_auc": round(mean([r["ood_auc"] for r in rs]), 4),
                "activity_rate": round(mean(
                    [r["participation"]["activity_rate"] for r in rs]), 4),
                "mean_staleness": round(mean(
                    [r["participation"]["mean_staleness"] for r in rs]), 4),
                "max_final_staleness": max(
                    r["participation"]["max_final_staleness"] for r in rs),
                "local_steps_total": sum(
                    r["participation"]["local_steps_total"] for r in rs),
            }
            for rate, rs in sorted(by_rate.items(), reverse=True)
        }
        ctrl = by_rate.get(1.0, [])
        bench_path = _update_bench(args.out, f"participation/{preset.name}", {
            "preset": preset.name,
            "experiments": len(cells),
            "rounds": scale.rounds,
            "n_nodes": n_nodes,
            "mode": "bernoulli",
            "rates": rate_rec,
            "rate1_control_stale_free": bool(ctrl) and all(
                r["participation"]["mean_staleness"] == 0.0 for r in ctrl),
        })
        print(f"participation record → {bench_path}")

    if rows and "fault" in rows[0]:
        # byzantine robustness record (DESIGN.md §16): per (rate, robust)
        # OOD aggregates + detection analytics, and the headline
        # robust-vs-mean recovery flag under nonzero fault rates.
        mean = lambda xs: (sum(xs) / len(xs)) if xs else None
        by_cell: Dict[tuple, List[dict]] = {}
        for r in rows:
            by_cell.setdefault((r["fault_rate"], r["robust"]),
                               []).append(r)
        grid_rec = {
            f"{rate:g}/{rob}": {
                "cells": len(rs),
                "ood_auc": round(mean([r["ood_auc"] for r in rs]), 4),
                "final_ood_acc": round(mean(
                    [r["final_ood_acc_mean"] for r in rs]), 4),
                "fault_round_rate": round(mean(
                    [r["fault"]["fault_round_rate"] for r in rs]), 4),
            }
            for (rate, rob), rs in sorted(by_cell.items())
        }
        nz_rates = sorted({k[0] for k in by_cell if k[0] > 0})
        final = lambda rate, rob: mean(
            [r["final_ood_acc_mean"] for r in by_cell.get((rate, rob), [])])
        recovered = bool(nz_rates) and all(
            final(rate, rob) >= final(rate, "mean") - 1e-6
            for rate in nz_rates for rob in ("trimmed", "median"))
        bench_path = _update_bench(args.out, f"byzantine/{preset.name}", {
            "preset": preset.name,
            "experiments": len(cells),
            "rounds": scale.rounds,
            "n_nodes": n_nodes,
            "fault_mode": "signflip",
            "grid": grid_rec,
            "robust_recovers_vs_mean": recovered,
        })
        print(f"byzantine record → {bench_path}")

    if mesh is not None:
        # sharded-vs-single comparison → BENCH_sweep.json (perf trajectory)
        t0 = time.time()
        single_rows = run_sweep_cells(cells, scale=scale,
                                      coeff_mode=coeff_mode,
                                      mix_impl=preset.mix_impl,
                                      fault=fault)
        single_secs = time.time() - t0
        identical = all(
            a["iid_auc"] == b["iid_auc"] and a["ood_auc"] == b["ood_auc"]
            and a["final_ood_acc_mean"] == b["final_ood_acc_mean"]
            for a, b in zip(rows, single_rows))
        print(f"single-device scanned path: {single_secs:.1f}s wall-clock "
              f"→ sharded speedup {single_secs / max(engine_secs, 1e-9):.2f}×"
              f"  (metrics bit-identical: {identical})")
        bench_path = _update_bench(args.out, f"sharded/{preset.name}", {
            "preset": preset.name,
            "experiments": len(cells),
            "rounds": scale.rounds,
            "n_nodes": n_nodes,
            "devices": int(mesh.devices.size),
            "chunk_rounds": args.chunk_rounds,
            "sharded_secs": round(engine_secs, 2),
            "single_device_secs": round(single_secs, 2),
            "speedup": round(single_secs / max(engine_secs, 1e-9), 3),
            "bit_identical_metrics": bool(identical),
        })
        print(f"sharded-vs-single wall-clock → {bench_path}")

    if preset.programs:
        # stacks-vs-programs comparison: identical grid, coefficients
        # host-materialized as (E, R, n, n) slabs instead of generated
        # in-scan — records the memory and wall-clock deltas of the
        # coefficient-program subsystem (DESIGN.md §9).
        from repro.core.coeffs import program_for, state_nbytes
        from repro.core.strategies import AggregationStrategy

        t0 = time.time()
        stack_rows = run_sweep_cells(cells, scale=scale, mesh=mesh,
                                     chunk_rounds=args.chunk_rounds,
                                     coeff_mode="stack",
                                     mix_impl=preset.mix_impl,
                                     fault=fault)
        stack_secs = time.time() - t0
        identical = all(
            a["iid_auc"] == b["iid_auc"] and a["ood_auc"] == b["ood_auc"]
            for a, b in zip(rows, stack_rows))
        c0 = cells[0]
        _, state0 = program_for(
            c0.topo, AggregationStrategy(c0.strategy, tau=c0.tau,
                                         seed=c0.seed),
            p_fail=c0.p_fail, reactive=c0.reactive)
        program_bytes = state_nbytes(state0) * len(cells)
        stack_bytes = len(cells) * scale.rounds * n_nodes * n_nodes * 4
        secs_ratio = engine_secs / max(stack_secs, 1e-9)
        print(f"coefficient stacks: {stack_secs:.1f}s wall-clock, "
              f"{stack_bytes / 2**20:.1f} MiB of host coefficients vs "
              f"{program_bytes / 2**10:.1f} KiB program state "
              f"({stack_bytes / max(program_bytes, 1):.0f}× smaller); "
              f"metrics bit-identical: {identical}")
        # the pre-pruning record was programs ≈ 1.8× stacks (24.2 s vs
        # 13.3 s): the batched lax.switch computed every reactive
        # centrality branch per round.  Static kind pruning
        # (CoeffProgram.kinds) must keep the in-scan path near parity.
        verdict = "improved ✓" if secs_ratio < 1.5 else "regressed ✗"
        print(f"programs-vs-stacks wall-clock ratio {secs_ratio:.2f}× "
              f"(pre-pruning record 1.82×) — {verdict}")
        bench_path = _update_bench(
            args.out, f"coeff_programs/{preset.name}", {
            "preset": preset.name,
            "experiments": len(cells),
            "rounds": scale.rounds,
            "n_nodes": n_nodes,
            "reactive": bool(c0.reactive),
            "program_secs": round(engine_secs, 2),
            "stack_secs": round(stack_secs, 2),
            "secs_ratio": round(secs_ratio, 3),
            "pre_pruning_secs_ratio": 1.82,
            "ratio_improved": bool(secs_ratio < 1.5),
            "stack_coeff_bytes": stack_bytes,
            "program_state_bytes": program_bytes,
            "bytes_ratio": round(stack_bytes / max(program_bytes, 1), 1),
            "bit_identical_metrics": bool(identical),
        })
        print(f"stacks-vs-programs record → {bench_path}")

    if not args.no_legacy and preset.programs:
        print("\n(legacy per-config baseline skipped: run_experiment has "
              "no link-failure path — programs presets compare against "
              "the materialized-stack engine run instead)")
    elif not args.no_legacy:
        t0 = time.time()
        run_legacy_baseline(cells, scale)
        legacy_secs = time.time() - t0
        print(f"legacy per-config loop: {len(cells)} experiments in "
              f"{legacy_secs:.1f}s wall-clock "
              f"({legacy_secs / len(cells):.2f}s/experiment)")
        print(f"speedup: {legacy_secs / max(engine_secs, 1e-9):.2f}× "
              f"(batched engine vs legacy loop)")

    print("\n=== verdict ===")
    print(" •", preset.verdict(rows))

    os.makedirs(args.out, exist_ok=True)
    path = f"{args.out}/sweep_{preset.name}.json"
    json.dump(rows, open(path, "w"), indent=1, default=_json_default)
    print(f"rows → {path}")


def _preset_fault(preset: SweepPreset):
    """Materialize a preset's ``fault_kwargs`` into a FaultSpec (lazy —
    keeps --list/--dry-run jax-free)."""
    if preset.fault_kwargs is None:
        return None
    from repro.core.dynamic import FaultSpec

    return FaultSpec(**preset.fault_kwargs)


def _linfit(xs, ys):
    """Least-squares slope/intercept of secs vs rounds."""
    import numpy as np

    b, a = np.polyfit(np.asarray(xs, float), np.asarray(ys, float), 1)
    return float(a), float(b)  # intercept (fixed secs), slope (secs/round)


def _crossover_from_entries(entries):
    """Single-vs-sharded crossover in rounds: measured interpolation when
    the speedup crosses 1.0 inside the sweep, otherwise extrapolated from
    the per-path linear fits (secs = fixed + slope·rounds); None when the
    sharded slope is not smaller (no crossover exists — e.g. more virtual
    devices than physical cores)."""
    for lo, hi in zip(entries, entries[1:]):
        s0, s1 = lo["speedup"], hi["speedup"]
        if (s0 - 1.0) * (s1 - 1.0) <= 0 and s0 != s1:
            frac = (1.0 - s0) / (s1 - s0)
            return (round(lo["rounds"]
                          + frac * (hi["rounds"] - lo["rounds"]), 1),
                    "measured")
    xs = [e["rounds"] for e in entries]
    a_sh, b_sh = _linfit(xs, [e["sharded_secs"] for e in entries])
    a_si, b_si = _linfit(xs, [e["single_device_secs"] for e in entries])
    if b_sh < b_si and a_sh > a_si:
        return round((a_sh - a_si) / (b_si - b_sh), 1), "extrapolated"
    return None, ("sharded per-round cost is not below single-device "
                  "on this host — no crossover at any scale")


def _run_shard_scale(args, preset, cells, scale, mesh, n_nodes) -> None:
    """--shard-scale: the same grid timed sharded AND single-device at
    2–3 round counts, so BENCH_sweep.json records the single-vs-sharded
    *crossover* (where amortized compute overtakes the sharded path's
    fixed compile/dispatch overhead) instead of one misleading
    single-point speedup."""
    from benchmarks.common import run_sweep_cells

    sizes = sorted({int(s) for s in args.shard_scale.split(",")})
    if len(sizes) < 2:
        raise SystemExit("--shard-scale needs ≥ 2 round counts")
    coeff_mode = "program" if preset.programs else "stack"
    fault = _preset_fault(preset)
    entries = []
    for r in sizes:
        s = dataclasses.replace(scale, rounds=r)
        t0 = time.time()
        rows_sh = run_sweep_cells(cells, scale=s, mesh=mesh,
                                  chunk_rounds=args.chunk_rounds,
                                  coeff_mode=coeff_mode,
                                  mix_impl=preset.mix_impl, fault=fault)
        sh = time.time() - t0
        t0 = time.time()
        rows_si = run_sweep_cells(cells, scale=s, coeff_mode=coeff_mode,
                                  mix_impl=preset.mix_impl, fault=fault)
        si = time.time() - t0
        identical = all(
            a["iid_auc"] == b["iid_auc"] and a["ood_auc"] == b["ood_auc"]
            for a, b in zip(rows_sh, rows_si))
        entries.append({
            "rounds": r,
            "sharded_secs": round(sh, 2),
            "single_device_secs": round(si, 2),
            "speedup": round(si / max(sh, 1e-9), 3),
            "bit_identical_metrics": bool(identical),
        })
        print(f"  R={r}: sharded {sh:.1f}s vs single {si:.1f}s "
              f"→ speedup {si / max(sh, 1e-9):.3f}× "
              f"(bit-identical: {identical})")
    crossover, how = _crossover_from_entries(entries)
    xs = [e["rounds"] for e in entries]
    a_sh, b_sh = _linfit(xs, [e["sharded_secs"] for e in entries])
    a_si, b_si = _linfit(xs, [e["single_device_secs"] for e in entries])
    payload = {
        "preset": preset.name,
        "experiments": len(cells),
        "n_nodes": n_nodes,
        "devices": int(mesh.devices.size),
        "physical_cpus": os.cpu_count(),
        "chunk_rounds": args.chunk_rounds,
        "scale_sweep": entries,
        "sharded_fixed_secs": round(a_sh, 2),
        "sharded_secs_per_round": round(b_sh, 4),
        "single_fixed_secs": round(a_si, 2),
        "single_secs_per_round": round(b_si, 4),
        "crossover_rounds": crossover,
        "crossover_kind": how,
    }
    bench_path = _update_bench(args.out, f"sharded/{preset.name}", payload)
    print("\n=== verdict ===")
    if crossover is not None:
        print(f" • single-vs-sharded crossover at R≈{crossover} ({how}); "
              f"fixed overhead {a_sh - a_si:+.1f}s, per-round "
              f"{b_sh:.3f}s vs {b_si:.3f}s")
    else:
        print(f" • no crossover: {how} (fixed {a_sh - a_si:+.1f}s, "
              f"per-round sharded {b_sh:.3f}s vs single {b_si:.3f}s)")
    print(f"sharded scale sweep → {bench_path}")


def _update_bench(out_dir: str, section: str, payload: dict) -> str:
    """Merge one section into benchmarks/artifacts/BENCH_sweep.json.
    Sections are keyed ``kind/preset`` (e.g. ``sharded/fig4``,
    ``coeff_programs/linkfail``) so the CI job's successive preset runs
    accumulate instead of overwriting each other's records."""
    os.makedirs(out_dir, exist_ok=True)
    path = f"{out_dir}/BENCH_sweep.json"
    bench = {}
    if os.path.exists(path):
        try:
            loaded = json.load(open(path))
            # pre-section records were one flat sharded dict — discard
            if isinstance(loaded, dict) and "preset" not in loaded:
                bench = loaded
        except ValueError:
            pass
    bench[section] = payload
    json.dump(bench, open(path, "w"), indent=1)
    return path


def _extract_analytics(out_dir: str) -> str:
    """Mirror the ``analytics/*`` sections of BENCH_sweep.json into a
    standalone ``BENCH_sweep_analytics.json`` — the artifact the CI golden
    job uploads."""
    path = f"{out_dir}/BENCH_sweep.json"
    bench = json.load(open(path)) if os.path.exists(path) else {}
    sections = {k: v for k, v in bench.items()
                if k.startswith("analytics/")}
    apath = f"{out_dir}/BENCH_sweep_analytics.json"
    json.dump(sections, open(apath, "w"), indent=1)
    return apath


def _json_default(o):
    try:
        return float(o)
    except (TypeError, ValueError):
        return str(o)


if __name__ == "__main__":
    main()
