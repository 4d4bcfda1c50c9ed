import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("REPRO_EXTRA_XLA_FLAGS", "")
)
# the forced devices are host (CPU) devices: select that platform, so an
# analytic dry run never claims an attached accelerator
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST precede every other import (jax locks device count on first init).

"""Multi-pod dry-run: lower + compile every (architecture × input shape ×
mesh) combination against the production mesh, and extract the roofline
terms from the compiled artifact.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch stablelm-1.6b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both --out benchmarks/artifacts

Per pair this records (EXPERIMENTS.md §Dry-run / §Roofline):
  * compiled.memory_analysis()  — bytes/device: proves the config fits;
  * compiled.cost_analysis()    — HLO FLOPs & bytes accessed;
  * collective bytes parsed from the compiled HLO (all-gather, all-reduce,
    reduce-scatter, all-to-all, collective-permute operand sizes);
  * the three roofline terms vs TPU v5e constants.
"""
import argparse
import json
import re
import time
import traceback
from typing import Any, Dict

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import SHAPES
from repro.configs.registry import ARCHS, get_config, get_parallel
from repro.launch.mesh import make_training_mesh
from repro.launch.specs import (
    DryRunSpec,
    LONG_CTX_SKIP,
    applicable_shapes,
    input_specs,
)
from repro.models.transformer import ForwardOptions
from repro.serving.serve_step import make_forward_prefill, make_serve_step
from repro.training.optimizer import make_optimizer
from repro.training.train_step import make_train_step

# TPU v5e-class constants (per chip)
PEAK_FLOPS = 197e12          # bf16 FLOP/s
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s per link
HBM_PER_CHIP = 16 * 1024**3

_DTYPE_BYTES = {
    "f32": 4, "bf16": 2, "f16": 2, "f64": 8,
    "s32": 4, "u32": 4, "s8": 1, "u8": 1, "pred": 1, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "s16": 2, "u16": 2,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def _parse_shape_bytes(shape_str: str) -> int:
    """'bf16[16,1024,512]{...}' → bytes.  Tuples handled by the caller."""
    m = re.match(r"([a-z0-9]+)\[([0-9,]*)\]", shape_str)
    if not m:
        return 0
    dt, dims = m.groups()
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dt, 4)


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum output-shape bytes of every collective op in the compiled HLO.

    Parsed from lines like:
      %ag = bf16[16,...] all-gather(...), replica_groups=...
    (tuple-shaped collectives contribute each element).
    """
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        s = line.strip()
        for coll in _COLLECTIVES:
            # match '= <shape> collective-name(' — covers -start variants
            m = re.search(
                r"=\s+(\(?[a-z0-9]+\[[^=]*?)\s+" + coll + r"(-start|-done)?\(", s
            )
            if not m:
                continue
            if m.group(2) == "-done":   # avoid double counting start/done
                continue
            shapes = re.findall(r"[a-z0-9]+\[[0-9,]*\]", m.group(1))
            nbytes = sum(_parse_shape_bytes(x) for x in shapes)
            out[coll] += nbytes
            out["count"] += 1
            break
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


def _mem_stats(mem) -> Dict[str, float]:
    """CompiledMemoryStats → per-device byte counts (arguments = resident
    params/opt/cache; temp = activation workspace; peak = high-water)."""
    out = {}
    for name in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes",
                 "peak_memory_in_bytes", "generated_code_size_in_bytes"):
        out[name.replace("_size_in_bytes", "_bytes")
                .replace("_in_bytes", "_bytes")] = int(getattr(mem, name, 0))
    return out


def build_step(spec: DryRunSpec, cfg, pcfg):
    opts = ForwardOptions(remat=pcfg.remat, use_scan=pcfg.scan_layers,
                          attn_impl="chunked")
    if spec.kind == "train":
        opt = make_optimizer("adamw", 3e-4)
        return make_train_step(cfg, pcfg, opt, opts=opts)
    if spec.kind == "prefill":
        return make_forward_prefill(cfg, opts=opts, last_only=True)
    return make_serve_step(cfg, opts=ForwardOptions(remat=False,
                                                    use_scan=pcfg.scan_layers))


def dry_run_pair(arch: str, shape_name: str, multi_pod: bool,
                 verbose: bool = True, pcfg=None) -> Dict[str, Any]:
    cfg = get_config(arch)
    pcfg = pcfg or get_parallel(arch)
    t0 = time.time()
    spec = input_specs(arch, shape_name, multi_pod=multi_pod, cfg=cfg, pcfg=pcfg)
    mesh = make_training_mesh(pcfg.n_nodes, tp=pcfg.tp_degree,
                              multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))
    step = build_step(spec, cfg, pcfg)

    def shardify(spec_tree):
        return jax.tree.map(
            lambda s: NamedSharding(mesh, s), spec_tree,
            is_leaf=lambda x: isinstance(x, P))

    in_sh = tuple(shardify(s) for s in spec.in_specs)
    out_sh = shardify(spec.out_specs)
    with mesh:
        lowered = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh) \
            .lower(*spec.abstract_args)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis() or {}
        hlo = compiled.as_text()

    coll = collective_bytes(hlo)
    flops = float(cost.get("flops", 0.0))
    bytes_accessed = float(cost.get("bytes accessed", 0.0))

    # roofline terms (per chip; cost_analysis reports per-partition HLO)
    t_compute = flops / PEAK_FLOPS
    t_memory = bytes_accessed / HBM_BW
    t_collective = (coll["total"]) / ICI_BW
    dominant = max(
        [("compute", t_compute), ("memory", t_memory),
         ("collective", t_collective)], key=lambda kv: kv[1])[0]

    n_total = cfg.param_count()
    n_active = cfg.active_param_count()
    tokens = (SHAPES[shape_name].global_batch * SHAPES[shape_name].seq_len
              if spec.kind == "train" else
              SHAPES[shape_name].global_batch * SHAPES[shape_name].seq_len
              if spec.kind == "prefill" else SHAPES[shape_name].global_batch)
    mult = 6 if spec.kind == "train" else 2
    model_flops = mult * n_active * tokens
    # per-chip useful flops for the ratio against per-partition HLO flops
    model_flops_per_chip = model_flops / n_chips

    mem_stats = _mem_stats(mem)
    result = dict(
        arch=arch, shape=shape_name, kind=spec.kind,
        mesh="pod2x16x16" if multi_pod else "pod16x16",
        n_chips=n_chips, n_nodes=spec.n_global_nodes,
        compile_s=round(time.time() - t0, 1),
        flops_per_chip=flops, bytes_per_chip=bytes_accessed,
        collective_bytes=coll["total"], collective_ops=coll["count"],
        collective_breakdown={k: coll[k] for k in _COLLECTIVES},
        t_compute_s=t_compute, t_memory_s=t_memory,
        t_collective_s=t_collective, dominant=dominant,
        model_flops=model_flops, model_flops_per_chip=model_flops_per_chip,
        useful_flops_ratio=(model_flops_per_chip / flops) if flops else 0.0,
        params_total=n_total, params_active=n_active,
        memory=mem_stats,
        meta=spec.meta,
    )
    if verbose:
        fit = (mem_stats.get("argument_bytes", 0)
               + mem_stats.get("temp_bytes", 0)) / max(n_chips, 1)
        print(f"[dryrun] {arch:24s} {shape_name:12s} "
              f"{'2pod' if multi_pod else '1pod'}  "
              f"compile={result['compile_s']:6.1f}s  "
              f"flops/chip={flops:.3e}  bytes/chip={bytes_accessed:.3e}  "
              f"coll={coll['total']:.3e}B  dom={dominant}  "
              f"mem/chip(arg+tmp)={fit/1e9:.2f}GB")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="one arch id (default: all)")
    ap.add_argument("--shape", default=None, help="one shape (default: all)")
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="benchmarks/artifacts/dryrun")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(ARCHS)
    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    results, failures = [], []
    for arch in archs:
        shapes = ([SHAPES[args.shape]] if args.shape
                  else applicable_shapes(arch))
        for shape in shapes:
            if shape.name == "long_500k" and arch in LONG_CTX_SKIP:
                results.append(dict(arch=arch, shape=shape.name,
                                    skipped=LONG_CTX_SKIP[arch]))
                continue
            for mp in meshes:
                tag = f"{arch}__{shape.name}__{'2pod' if mp else '1pod'}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path):
                    print(f"[dryrun] {tag} cached")
                    results.append(json.load(open(path)))
                    continue
                try:
                    r = dry_run_pair(arch, shape.name, mp)
                    results.append(r)
                    json.dump(r, open(path, "w"), indent=1)
                except Exception as e:  # noqa: BLE001 — report, keep going
                    traceback.print_exc()
                    failures.append((tag, repr(e)))
    summary = os.path.join(args.out, "summary.json")
    json.dump(results, open(summary, "w"), indent=1)
    print(f"\n{len(results)} results → {summary}; {len(failures)} failures")
    for tag, err in failures:
        print("FAIL", tag, err[:200])
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
