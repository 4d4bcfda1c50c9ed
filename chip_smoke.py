#!/usr/bin/env python3
"""Prove that the gossip sweep engine runs on a TPU chip, at the paper's
Table 1 widths, through the entry points a user calls.

One process, phases in order:

1. Device check: a TPU must be attached (there is no CPU fallback).
2. Gossip-mix kernels at n=33 and VGG-16's plane width: the plane kernel
   (f32 and bf16 planes), the edge-list kernel and the robust kernel
   (trimmed mean and median), each compiled for the chip (its program
   holds a ``tpu_custom_call``) and checked against its jnp reference;
   then the sweep's Pallas mix function against its einsum mix on 33
   nodes' VGG-16 params.
3. The sweep engine via ``benchmarks.common.run_sweep_cells`` on a
   33-node Barabási–Albert graph, ``degree`` strategy, OOD data on the
   hub, at FULL batch and local-epoch settings: the FFN on mnist, VGG-16
   (``width_mult=1.0``) on cifar10 and the 1-layer GPT-2 on tinymem with
   the einsum mix, and VGG-16 with the Pallas plane mix, compared with
   its einsum run.

Usage::

    python chip_smoke.py             # one chip: phases 1-3
    python chip_smoke.py --chips 4   # four chips: only the experiment
                                     # axis sharded over a 4-device mesh
                                     # (FFN fig4 grid) vs one device

Lines before the last are information (compile seconds, seconds per
round, device memory) from one run on the host clock, not a benchmark.
The last line is ``{"ok": true, "device": {...}}``; any failed check
raises, and the script exits non-zero without that line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
N_NODES = 33
ROUNDS = 3
#: columns of the plane the robust jnp reference recomputes (its slot
#: gather holds dmax copies of the plane, too many at full width)
ROBUST_WINDOW = 131072


def check(ok, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


class CompileClock:
    """Seconds JAX spent in backend compiles (or in loading them from the
    persistent cache), accumulated from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += duration


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _peak_bytes(device=None) -> int:
    import jax

    return int((device or jax.devices()[0]).memory_stats()
               ["peak_bytes_in_use"])


def _close(out, ref, rtol: float, atol: float):
    """(all within tolerance, max |out − ref|, bit-equal) over two arrays
    or two pytrees of one structure, on the device."""
    import jax
    import jax.numpy as jnp

    def leaf(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        err = jnp.abs(a - b)
        return (jnp.all(err <= atol + rtol * jnp.abs(b)), jnp.max(err),
                jnp.all(a == b))

    @jax.jit
    def stats(a, b):
        ok, err, same = zip(*map(leaf, jax.tree.leaves(a),
                                 jax.tree.leaves(b)))
        return (jnp.all(jnp.stack(ok)), jnp.max(jnp.stack(err)),
                jnp.all(jnp.stack(same)))

    ok, err, same = stats(out, ref)
    return bool(ok), float(err), bool(same)


def _run_kernel(name, fn, *args):
    """Compile ``fn`` for the chip, check that it holds a Mosaic kernel,
    run it and return its output."""
    import jax

    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    compile_s = time.perf_counter() - t0
    check("tpu_custom_call" in compiled.as_text(),
          f"{name}: no tpu_custom_call in the compiled program")
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    print(f"kernel {name}: compile_s={compile_s:.2f} "
          f"first_run_s={time.perf_counter() - t0:.3f}", flush=True)
    return out


def _check_mean_kernels(dtype, tol, width, c, w, idx) -> None:
    """The plane and edges kernels on one random plane vs ``mix_dense``;
    every device array made here is freed on return."""
    import jax
    import jax.numpy as jnp

    from repro.core.mixing import mix_dense
    from repro.kernels.gossip_mix import gossip_edges_pallas, gossip_plane_pallas

    name = jnp.dtype(dtype).name
    plane = (jax.random.normal(jax.random.key(0), (N_NODES, width))
             * 2).astype(dtype)
    ref = mix_dense({"x": plane}, c)["x"]
    for kname, fn, args in (
            ("plane", lambda p, c_: gossip_plane_pallas(p, c_), (plane, c)),
            ("edges", lambda p, w_, i: gossip_edges_pallas(p, w_, i),
             (plane, w, idx))):
        ok, err, _ = _close(_run_kernel(f"{kname}/{name}", fn, *args), ref,
                            tol, tol)
        print(f"kernel {kname}/{name} vs mix_dense: max_abs_err={err:.3e} "
              f"tol={tol}", flush=True)
        check(ok, f"{kname}/{name} kernel disagrees with mix_dense")


def _check_robust_kernel(op, trim_k, width, c, w, idx, mask) -> None:
    """The robust kernel on a random f32 plane vs ``mix_robust_tables``
    on its first and last column windows."""
    import jax

    from repro.core.mixing import mix_robust_tables
    from repro.kernels.gossip_mix import gossip_robust_pallas

    plane = jax.random.normal(jax.random.key(1), (N_NODES, width)) * 2
    out = _run_kernel(f"robust/{op}",
                      lambda p, w_, i: gossip_robust_pallas(
                          p, w_, i, op=op, trim_k=trim_k), plane, w, idx)
    for lo in (0, width - ROBUST_WINDOW):
        cols = slice(lo, lo + ROBUST_WINDOW)
        ref = mix_robust_tables({"x": plane[:, cols]}, c, idx, mask, op,
                                trim_k=trim_k)["x"]
        ok, err, same = _close(out[:, cols], ref, 2e-5, 1e-5)
        print(f"kernel robust/{op} vs mix_robust_tables, columns "
              f"{lo}..{lo + ROBUST_WINDOW}: max_abs_err={err:.3e} "
              f"bit_equal={same}", flush=True)
        check(ok, f"robust/{op} kernel disagrees with mix_robust_tables")


def _check_engine_mix(init, c) -> None:
    """The sweep's Pallas mix (``make_mix_fn("pallas")``: VGG-16's params
    packed into one plane, one kernel, unpacked) vs its einsum mix, on 33
    nodes' params, each node's from its own key."""
    import jax

    from repro.core.decentralized import make_mix_fn

    params = jax.jit(jax.vmap(init))(
        jax.random.split(jax.random.key(2), N_NODES))
    out = _run_kernel("engine mix/pallas", make_mix_fn("pallas"), params, c)
    ref = jax.jit(make_mix_fn("einsum"))(params, c)
    ok, err, _ = _close(out, ref, 1e-6, 1e-6)
    print(f"engine mix pallas vs einsum on VGG-16 params: "
          f"max_abs_err={err:.3e} tol=1e-06", flush=True)
    check(ok, "the sweep's Pallas mix disagrees with its einsum mix")


def kernel_phase(init) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.decentralized import edges_schedule
    from repro.core.mixing import edge_weights
    from repro.core.strategies import AggregationStrategy, mixing_matrix
    from repro.core.topology import barabasi_albert

    topo = barabasi_albert(N_NODES, 2, seed=0)
    c = jnp.asarray(mixing_matrix(topo, AggregationStrategy("degree",
                                                            tau=0.1)),
                    jnp.float32)
    idx, mask = (jnp.asarray(t) for t in edges_schedule(topo.adjacency))
    w = edge_weights(c, idx, mask)
    width = sum(x.size for x in jax.tree.leaves(
        jax.eval_shape(init, jax.random.key(0))))
    print(f"kernels: n={N_NODES} plane_width={width} dmax={idx.shape[1]}",
          flush=True)
    for dtype, tol in ((jnp.float32, 1e-6), (jnp.bfloat16, 2e-2)):
        _check_mean_kernels(dtype, tol, width, c, w, idx)
    for op, trim_k in (("trimmed", 1), ("median", 0)):
        _check_robust_kernel(op, trim_k, width, c, w, idx, mask)
    _check_engine_mix(init, c)


def _smoke_scale():
    """FULL scale for ``ROUNDS`` rounds, evaluated every round."""
    from benchmarks.common import FULL

    return dataclasses.replace(FULL, rounds=ROUNDS, eval_every=1)


def _run_program(label, cells, clock, **kwargs):
    """One ``run_sweep_cells`` call, with its compile and run seconds."""
    from benchmarks.common import run_sweep_cells

    scale = _smoke_scale()
    c0, t0 = clock.secs, time.perf_counter()
    rows = run_sweep_cells(cells, scale=scale, **kwargs)
    wall = time.perf_counter() - t0
    compile_s = clock.secs - c0
    print(f"program {label}: experiments={len(cells)} "
          f"compile_s={compile_s:.1f} "
          f"run_s_per_round={(wall - compile_s) / scale.rounds:.2f} "
          f"(wall minus compile, host data set-up included) "
          f"peak_bytes_in_use={_peak_bytes()}", flush=True)
    return rows


def _max_diffs(a: dict, b: dict, i: int) -> dict:
    """Max |a − b| of each per-node metric at evaluated round ``i``."""
    import numpy as np

    pa, pb = a["per_node"][i], b["per_node"][i]
    check(pa["round"] == pb["round"], "rows evaluated different rounds")
    return {k: float(np.max(np.abs(np.subtract(pa[k], pb[k]))))
            for k in ("train_loss", "iid_acc", "ood_acc")}


def _agree(a: dict, b: dict, what: str, tol: float = 1e-5) -> None:
    """Per-node metrics at the last evaluated round, and the AUCs over
    all rounds, agree to f32 tolerance (the sweep equivalence tests'
    rtol = atol = 1e-5)."""
    import numpy as np

    pa, pb = a["per_node"][-1], b["per_node"][-1]
    print(f"  {what} round {pa['round']}: max_abs_diff "
          f"{_max_diffs(a, b, -1)}", flush=True)
    for k in ("train_loss", "iid_acc", "ood_acc"):
        check(np.allclose(pa[k], pb[k], rtol=tol, atol=tol),
              f"{what}: per-node {k} differ at round {pa['round']}")
    for k in ("iid_auc", "ood_auc"):
        check(np.isclose(a[k], b[k], rtol=tol, atol=tol),
              f"{what}: {k} differ")


#: (dataset, mix_impl) of each sweep program: the three Table 1 models
#: with the einsum mix, and VGG-16 with the Pallas plane mix
SWEEP_RUNS = (("mnist", "einsum"), ("cifar10", "einsum"),
              ("cifar10", "pallas"), ("tinymem", "einsum"))
#: bound on |mean train loss (pallas) − mean train loss (einsum)| of
#: VGG-16 at each evaluated round. Not an f32 tolerance: the two programs
#: compile local training differently, and Adam turns the rounding
#: differences into ~1e-2 of per-node loss within round 0, before any mix
#: (see sweep_phase). A mix that lost the params would send the next
#: round's loss back towards chance (ln 10 = 2.30), far past this bound.
PAIR_MEAN_LOSS_TOL = 0.05


def sweep_phase(clock) -> None:
    import numpy as np

    from benchmarks.common import SweepCell
    from repro.core.topology import barabasi_albert

    topo = barabasi_albert(N_NODES, 2, seed=0)
    rows = {}
    for ds, mix in SWEEP_RUNS:
        label = f"{ds}/{mix}"
        cell = SweepCell(ds, topo, "degree", ood_k=1, seed=0,
                         name=f"smoke/{label}")
        # chunk_rounds: the donated-carry path — without donation the
        # initial and final (params, Adam) state of VGG-16 at n=33 do not
        # both fit one chip
        row, = _run_program(label, [cell], clock, mix_impl=mix,
                            chunk_rounds=ROUNDS)
        fp = row["per_node"][-1]
        print(f"  {label} round {fp['round']}: "
              f"mean train_loss={np.mean(fp['train_loss']):.4f} "
              f"mean iid_acc={np.mean(fp['iid_acc']):.4f} "
              f"mean ood_acc={np.mean(fp['ood_acc']):.4f} "
              f"iid_auc={row['iid_auc']:.4f} ood_auc={row['ood_auc']:.4f}",
              flush=True)
        check(all(np.isfinite(m["train_loss"]).all()
                  for m in row["per_node"]),
              f"{label}: non-finite training loss")
        rows[ds, mix] = row
    ffn_acc = float(np.mean(rows["mnist", "einsum"]["per_node"][-1]
                            ["iid_acc"]))
    check(ffn_acc > 0.2, f"FFN in-distribution accuracy {ffn_acc:.3f} is "
          f"not above chance (0.1)")
    # Round 0's training loss comes from local training alone, before the
    # first mix, yet it differs between the two programs: XLA compiles
    # local training differently around each mix, and Adam's normalised
    # steps turn rounding-level differences into visible ones. So the two
    # mixes are compared exactly in the kernel phase (the sweep's own
    # mix function on VGG-16's params), and here only coarsely.
    pallas, einsum = rows["cifar10", "pallas"], rows["cifar10", "einsum"]
    for i, (pa, pb) in enumerate(zip(pallas["per_node"],
                                     einsum["per_node"])):
        gap = abs(float(np.mean(pa["train_loss"])
                        - np.mean(pb["train_loss"])))
        print(f"  VGG-16 pallas vs einsum round {pb['round']}: "
              f"mean train_loss gap={gap:.3e} max_abs_diff "
              f"{_max_diffs(pallas, einsum, i)}", flush=True)
        check(gap <= PAIR_MEAN_LOSS_TOL,
              f"VGG-16 pallas vs einsum: mean train loss differs by "
              f"{gap:.3f} at round {pb['round']}")


def sharded_phase(clock) -> None:
    import jax

    from benchmarks.fig4_strategies import cells as fig4_cells
    from repro.launch.mesh import make_sweep_mesh

    devices = jax.devices()
    check(len(devices) == 4, f"--chips 4 needs 4 devices, have {len(devices)}")
    grid = fig4_cells(datasets=("mnist",), n_nodes=N_NODES, seeds=(0, 1))
    mesh = make_sweep_mesh(4)
    # both at full f32 matmul precision (the FFN's scratch is small): the
    # two programs may order their float sums differently, and the
    # default precision would round those differences to bf16 steps.
    # Sharded first, so devices 1-3 have held nothing else before it.
    with jax.default_matmul_precision("highest"):
        sharded = _run_program("fig4/mnist mesh(4)", grid, clock, mesh=mesh)
    peaks = [_peak_bytes(d) for d in devices]
    print(f"peak_bytes_in_use per device after the sharded run: {peaks}",
          flush=True)
    held = sorted({d for r in sharded for d in r["param_devices"]})
    print(f"devices holding experiment params: {held} "
          f"(per experiment: {[r['param_devices'] for r in sharded]})",
          flush=True)
    check(held == sorted(d.id for d in devices),
          "sharded params did not land on all four devices")
    # on one device, a shard's worth of experiments per program: the
    # whole grid's batches, gathered up front each round, do not fit one
    # chip's HBM at once
    per_shard = len(grid) // len(devices)
    single = []
    with jax.default_matmul_precision("highest"):
        for i in range(0, len(grid), per_shard):
            single += _run_program(
                f"fig4/mnist one device, experiments {i}..{i + per_shard - 1}",
                grid[i:i + per_shard], clock)
    check({d for r in single for d in r["param_devices"]} == {devices[0].id},
          "the one-device run left device 0")
    key = lambda r: (r["strategy"], r["seed"])
    check(sorted(map(key, sharded)) == sorted(map(key, single)),
          "the two runs hold different experiments")
    one = {key(r): r for r in single}
    for r4 in sharded:
        _agree(r4, one[key(r4)],
               f"{r4['strategy']}/seed{r4['seed']} mesh vs one")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded sweep and its one-device "
                         "comparison")
    args = ap.parse_args()
    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke: run this from a checkout of the repository "
              "(src/repro not found beside it)", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    from repro.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    device = device_info()
    print(f"devices: {device}", flush=True)
    if device["platform"] != "tpu":
        print(f"chip_smoke: no TPU attached (JAX sees {device['platform']})",
              file=sys.stderr)
        return 1
    check(device["count"] == args.chips,
          f"--chips {args.chips} but JAX sees {device['count']} devices")
    clock = CompileClock()
    if args.chips == 4:
        sharded_phase(clock)
    else:
        from benchmarks.common import _model_fns

        kernel_phase(_model_fns("cifar10", _smoke_scale(), 0)[0])
        sweep_phase(clock)
    print(f"total compile_s={clock.secs:.1f}", flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
