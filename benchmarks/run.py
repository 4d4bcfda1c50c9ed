"""Benchmark entry point — one section per paper table/figure + the
roofline and gossip-cost tables.  ``name,us_per_call,derived`` CSV rows.

  PYTHONPATH=src python -m benchmarks.run            # reduced (CPU) scale
  PYTHONPATH=src python -m benchmarks.run --full     # paper scale
  PYTHONPATH=src python -m benchmarks.run --only fig4,roofline
"""
from __future__ import annotations

import argparse
import json
import os
import time


def main() -> None:
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale runs")
    ap.add_argument("--only", default=None,
                    help="comma list: fig2,fig4,fig5,fig6,gossip,mix,"
                         "serve,roofline")
    ap.add_argument("--out", default="benchmarks/artifacts")
    args = ap.parse_args()

    from benchmarks.common import FULL, QUICK

    scale = FULL if args.full else QUICK
    datasets = (("mnist", "fmnist", "tinymem", "cifar10", "cifar100")
                if args.full else ("mnist", "fmnist"))
    seeds = (0, 1, 2) if args.full else (0,)
    n_nodes = 33 if args.full else 16
    sections = (args.only.split(",") if args.only
                else ["fig2", "fig4", "fig5", "fig6", "ablations",
                      "gossip", "mix", "serve", "roofline"])
    os.makedirs(args.out, exist_ok=True)
    verdicts = []
    t_start = time.time()

    print("name,us_per_call,derived")

    if "fig2" in sections:
        from benchmarks import fig2_iid_vs_ood as fig2

        rows = fig2.run(datasets=datasets[:2], ba_p=(2,), n_nodes=n_nodes,
                        seeds=seeds, scale=scale)
        verdicts.append(fig2.verdict(rows))
        json.dump(rows, open(f"{args.out}/fig2.json", "w"), indent=1,
                  default=float)

    if "fig4" in sections:
        from benchmarks import fig4_strategies as fig4

        rows = fig4.run(datasets=datasets[:2], ba_p=(1, 2) if args.full else (2,),
                        n_nodes=n_nodes, seeds=seeds, scale=scale)
        verdicts.append(fig4.verdict(rows))
        json.dump(rows, open(f"{args.out}/fig4.json", "w"), indent=1,
                  default=float)

    if "fig5" in sections:
        from benchmarks import fig5_location as fig5

        rows = fig5.run(datasets=datasets[:1], n_nodes=n_nodes, seeds=seeds,
                        scale=scale)
        verdicts.append(fig5.verdict(rows))
        json.dump(rows, open(f"{args.out}/fig5.json", "w"), indent=1,
                  default=float)

    if "fig6" in sections:
        from benchmarks import fig6_topology as fig6

        d = fig6.run_degree(datasets=datasets[:1], seeds=seeds, scale=scale)
        m = fig6.run_modularity(datasets=datasets[:1], seeds=seeds, scale=scale)
        if args.full:
            fig6.run_nodecount(datasets=datasets[:1], seeds=seeds, scale=scale)
        verdicts.append(fig6.verdict(d, m))
        json.dump(d + m, open(f"{args.out}/fig6.json", "w"), indent=1,
                  default=float)

    if "ablations" in sections:
        from benchmarks import ablations

        z = ablations.run_centrality_zoo(seeds=seeds, scale=scale)
        t = ablations.run_tau_sweep(seeds=seeds, scale=scale)
        f = ablations.run_link_failure(seeds=seeds, scale=scale)
        h = ablations.run_heterogeneity(seeds=seeds, scale=scale)
        import numpy as _np
        aware = [r for r in z if r["strategy"] != "unweighted"]
        verdicts.append(
            "ablations: all %d centrality metrics beat unweighted on OOD "
            "(%.3f–%.3f vs %.3f); τ≤0.1 plateau; degree OOD at 60%% link "
            "failure: %.3f" % (
                len(aware),
                min(r["ood_auc"] for r in aware),
                max(r["ood_auc"] for r in aware),
                next(r["ood_auc"] for r in z if r["strategy"] == "unweighted"),
                next((r["ood_auc"] for r in f
                      if r["strategy"] == "degree" and r["p_fail"] == 0.6), -1)))
        json.dump(dict(centrality=z, tau=t, linkfail=f, heterogeneity=h),
                  open(f"{args.out}/ablations.json", "w"), indent=1,
                  default=float)

    if "gossip" in sections:
        from benchmarks import gossip_cost

        rows = gossip_cost.run()
        json.dump(rows, open(f"{args.out}/gossip_cost.json", "w"), indent=1,
                  default=float)

    if "mix" in sections:
        from benchmarks import gossip_cost

        rec = gossip_cost.run_mix(smoke=not args.full,
                                  out_path=f"{args.out}/BENCH_mix.json")
        verdicts.append(
            "mix kernel: fused plane %s the legacy per-row path "
            "(wall %.1fx, modeled HBM bytes %.1fx; 1 pallas_call vs %d "
            "programs per mix)" % (
                "dominates" if rec["fused_vs_rows"]["dominates"]
                else "DOES NOT dominate",
                rec["fused_vs_rows"]["wall_speedup"],
                rec["fused_vs_rows"]["hbm_bytes_ratio"],
                rec["impls"]["pallas_rows"]["kernel_programs_per_mix"]))

    if "serve" in sections:
        from benchmarks import serve_bench

        code = serve_bench.main(
            ["--smoke", "--out", args.out] if not args.full
            else ["--fleets", "2,4,8", "--out", args.out])
        rec = json.load(open(f"{args.out}/BENCH_serve.json"))
        best = max(rec["fleets"], key=lambda f: f["vmapped_speedup"])
        verdicts.append(
            "serving: fleet-vmapped continuous batching %s the per-node "
            "loop (best %.2fx at n=%d; %.0f tok/s; outputs identical and "
            "post-gossip swap without re-jit: %s)" % (
                "beats" if code == 0 and all(
                    f["vmapped_speedup"] > 1 for f in rec["fleets"])
                else "DOES NOT beat",
                best["vmapped_speedup"], best["n_nodes"],
                best["fleet_vmapped"]["tokens_per_sec"],
                rec["all_checks_passed"]))

    if "roofline" in sections:
        from benchmarks import roofline

        rows = roofline.full_table(multi_pod=False)
        print("\n" + roofline.format_table(rows))
        json.dump(rows, open(f"{args.out}/roofline_1pod.json", "w"),
                  indent=1, default=float)

    print("\n=== verdicts (paper-claim checks) ===")
    for v in verdicts:
        print(" •", v)
    print(f"total bench time: {time.time() - t_start:.0f}s")


if __name__ == "__main__":
    main()
