#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,13 \\
        [--control] [--highest] \\
        [--faults frozen,half_batch,no_mix,altered_answer]

For each seed, in one process: one call of the cell exactly as the window
makes it, the reference of each experiment that the check draws for that
seed, and the compared numbers of the program against the reference, the
worst over those experiments as the check takes them (the lower reading;
``program_each`` holds each experiment's). ``--control`` also runs the
reference in bfloat16 in the program's place (the upper reading); ``--highest``
replays the reference at the highest matmul precision and reads the
program and the stated-precision reference against it (information: how
far the configuration's own precision lies from float32); ``--faults``
runs the call again with each fault of ``bench.faults`` planted. One JSON
line per seed goes to standard output and to ``chiprun_out/calibrate/``.
The benchmark's own runs do not run this.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--highest", action="store_true")
    ap.add_argument("--faults", default="")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from bench import faults as bfaults
    from bench import harness, spec
    from bench.checks import sync_mean
    from bench.reference import replay

    cell = spec.Cell.load(args.workload)
    harness.enable_cache()
    dev = harness.device_info()
    if dev["platform"] != "tpu" or dev["count"] < cell.chips:
        print(f"calibrate: needs {cell.chips} TPU chip(s); have {dev}",
              file=sys.stderr)
        return 2
    out_dir = ROOT / "chiprun_out" / "calibrate"
    out_dir.mkdir(parents=True, exist_ok=True)
    t = cell.traffic
    for seed in (int(s) for s in args.seeds.split(",")):
        grid = harness.build_grid(cell, seed)
        rec = {"workload": cell.name, "seed": seed, "device": dev}
        t0 = time.perf_counter()
        rows = grid.call()
        rec["call_s"] = time.perf_counter() - t0
        rec["memory_peak_bytes"] = harness.memory_peak_bytes(cell.chips)
        # a compiled grid runs one step count: its first experiment's
        x0 = grid.experiments[0]
        steps = t["steps_per_epoch"] or replay.build(
            cell.config, t, x0["strategy"], x0["seed"]).steps
        rounds = sync_mean.eval_rounds(grid.rounds, t["eval_every"])
        exps = sync_mean.sample(grid, seed, t["check_experiments"])
        rec["experiments"] = exps
        built, ref = {}, {}
        t0 = time.perf_counter()
        for e in exps:
            x = grid.experiments[e]
            built[e] = replay.build(cell.config, t, x["strategy"], x["seed"],
                                    steps)
            ref[e] = replay.replay(cell.config, t, built[e], x["seed"],
                                   grid.rounds, rounds, jnp.float32)
        rec["reference_s"] = time.perf_counter() - t0

        def compared(outputs, rows=None) -> dict:
            """The check's numbers: the worst over the sampled experiments,
            and where the grid is sharded the experiments on a wrong
            device."""
            worst = {}
            for e in exps:
                for k, v in sync_mean.gaps(outputs(e), ref[e]).items():
                    worst[k] = max(worst.get(k, 0.0), v)
            if rows is not None and t.get("mesh_devices"):
                worst["wrong_device"] = float(
                    sync_mean.wrong_devices(rows, t["mesh_devices"]))
            return worst

        def of_rows(rows):
            return lambda e: sync_mean.program_outputs(rows[e])

        rec["program"] = compared(of_rows(rows), rows)
        rec["program_each"] = [sync_mean.gaps(of_rows(rows)(e), ref[e])
                               for e in exps]
        e0 = exps[0]
        rec["loss_mean"] = {r: float(v["train_loss"].mean())
                            for r, v in ref[e0].items()}
        rec["iid_acc_mean"] = {r: float(v["iid_acc"].mean())
                               for r, v in ref[e0].items()}
        rec["ood_acc_mean"] = {r: float(v["ood_acc"].mean())
                               for r, v in ref[e0].items()}
        if args.control:
            t0 = time.perf_counter()
            ctl = {e: replay.replay(cell.config, t, built[e],
                                    grid.experiments[e]["seed"], grid.rounds,
                                    rounds, jnp.bfloat16) for e in exps}
            rec["control_s"] = time.perf_counter() - t0
            rec["control"] = compared(ctl.get)
        if args.highest:
            t0 = time.perf_counter()
            hi = {e: replay.replay(dict(cell.config, matmul_precision="highest"),
                                   t, built[e], grid.experiments[e]["seed"],
                                   grid.rounds, rounds, jnp.float32)
                  for e in exps}
            rec["highest_s"] = time.perf_counter() - t0
            rec["program_vs_highest"] = {}
            rec["reference_vs_highest"] = {}
            for e in exps:
                for key, got in (("program_vs_highest", of_rows(rows)(e)),
                                 ("reference_vs_highest", ref[e])):
                    for k, v in sync_mean.gaps(got, hi[e]).items():
                        rec[key][k] = max(rec[key].get(k, 0.0), v)
        for name in filter(None, args.faults.split(",")):
            t0 = time.perf_counter()
            with bfaults.FAULTS[name]():
                try:
                    frows = grid.call()
                    rec[f"fault.{name}"] = compared(of_rows(frows), frows)
                except Exception as exc:  # a fault that crashes is caught
                    rec[f"fault.{name}"] = {"raised": repr(exc)[:300]}
            rec[f"fault.{name}_s"] = time.perf_counter() - t0
            jax.clear_caches()
        line = json.dumps(rec)
        print(line, flush=True)
        with open(out_dir / f"{cell.name}.jsonl", "a") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
