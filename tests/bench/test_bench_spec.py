"""The benchmark's definition loads and is well formed, on the CPU.

Every cell, configuration and metric of ``BENCHMARK.json`` is found by
name, each cell's grid builds, the peak table refuses a device it does not
know, and the harness refuses to run without a TPU.
"""
import dataclasses
import json
import math
import os
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import flops, harness, peaks, spec

ROOT = Path(__file__).resolve().parents[2]
BENCH = spec.benchmark(ROOT)
WORKLOADS = [w["name"] for w in BENCH["workloads"]]
ENTRIES = {e["name"]: e for e in BENCH["configs"]}
CONFIG_FILES = sorted(p.stem for p in (ROOT / "bench" / "configs").glob("*.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_json_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir()
    entries = (BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"]
               + BENCH["per_layer"])
    names = [e["name"] for e in entries]
    assert len(names) == len(set(names))
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
    with open(ROOT / "BENCHMARK.json", "rb") as f:
        assert len(f.read()) <= 64 * 1024


@pytest.mark.parametrize("name", WORKLOADS)
def test_cell_loads_by_name(name):
    cell = spec.Cell.load(name, ROOT)
    assert cell.chips in (1, 4)
    assert cell.limits, "every cell has limits for correct"
    e2e = cell.readers(trace=False)
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.readers(trace=True), "every cell reports a per-layer metric"
    for m in cell.end_to_end + cell.per_layer:
        assert callable(spec.load_module(
            spec.BENCH / "metrics" / f"{m['name']}.py").read)


def _program_and_reference_params(cfg: dict):
    """Shapes of the program's node parameters, as the harness's call makes
    them, and of the reference's."""
    import jax
    import jax.numpy as jnp

    from benchmarks.common import _model_fns
    from bench.reference import models

    traffic = json.loads(
        (spec.BENCH / "traffic" / "ba33.fedavg.r1.json").read_text())
    cell = spec.Cell(cfg["name"], 1, cfg, traffic, {}, [], [])
    scale = harness.build_grid(cell, 2_147_483_000).scale
    init = _model_fns(cfg["dataset"], scale, 0)[0]
    ref_init, _ = models.model(cfg)
    key = jax.random.key(0)
    return (jax.tree.leaves(jax.eval_shape(init, key)),
            jax.tree.leaves(jax.eval_shape(
                lambda k: ref_init(k, jnp.float32), key)))


@pytest.mark.parametrize("name", CONFIG_FILES)
def test_config_file_is_the_run_configuration(name):
    path = ROOT / "bench" / "configs" / f"{name}.json"
    cfg = json.loads(path.read_text())
    assert cfg["name"] == name
    assert set(cfg["reduced"]) == set(cfg.get("source_values", {})) \
        == set(cfg.get("reduced_why", {}))
    if name in ENTRIES:
        assert ROOT / ENTRIES[name]["file"] == path
        assert set(ENTRIES[name]["reduced"]) == set(cfg["reduced"])
    prog, ref = _program_and_reference_params(cfg)
    count = lambda leaves: sum(math.prod(x.shape) for x in leaves)
    if cfg["model"] == "gpt2":
        from repro.models.paper_models import gpt2_tinymem_config

        m = gpt2_tinymem_config()
        assert (m.n_layers, m.d_model, m.n_heads, m.n_kv_heads, m.d_ff,
                m.vocab_size, m.max_seq_len, m.norm_eps, m.rope_theta) == (
            cfg["n_layer"], cfg["n_embd"], cfg["n_head"], cfg["n_head"],
            cfg["n_inner"], cfg["vocab_size"], cfg["n_positions"],
            cfg["layer_norm_epsilon"], cfg["rope_theta"])
        assert count(prog) == count(ref)
    else:
        # the program's VGG-16 keeps a scalar marker leaf per pool
        shapes = lambda leaves: sorted(x.shape for x in leaves if x.shape)
        assert shapes(prog) == shapes(ref)
    if "floats_per_node" in cfg:
        assert count(prog) == cfg["floats_per_node"]
    assert flops.train_flops_per_sample(cfg) > 0


def test_gpt2_cell_calls_the_program_as_before():
    """The call of ``gpt2s-1l.ba33.fedavg``, written out: the grid passes
    ``run_sweep_cells`` exactly these arguments."""
    from benchmarks.common import BenchScale, SweepCell

    cell = spec.Cell.load("gpt2s-1l.ba33.fedavg", ROOT)
    seed = 2_147_483_000
    grid = harness.build_grid(cell, seed)
    assert grid.scale == BenchScale(
        n_train=20000, n_test=2000, rounds=1, local_epochs=5, batch=32,
        steps_per_epoch=0, eval_every=4, eval_n=512, vgg_width=1.0)
    assert grid.kwargs == {"mix_impl": "einsum", "coeff_mode": "stack",
                           "alpha_l": 1000.0, "alpha_s": 1e9,
                           "chunk_rounds": 1}
    from repro.core.topology import barabasi_albert

    [got] = grid.cells
    want = SweepCell("tinymem", got.topo, "degree", ood_k=1, tau=0.1,
                     seed=seed, name=f"gpt2s-1l.ba33.fedavg/degree/{seed}")
    fields = lambda c: [getattr(c, f.name) for f in dataclasses.fields(c)
                        if f.name != "topo"]
    assert fields(got) == fields(want)
    np.testing.assert_array_equal(
        got.topo.adjacency, barabasi_albert(33, 2, seed=seed).adjacency)


def test_unknown_model_names_its_missing_file():
    cfg = {"name": "x", "model": "no_such_model", "data": {"kind": "lm"}}
    with pytest.raises(FileNotFoundError,
                       match=r"no_such_model.*models/no_such_model\.py"):
        flops.train_flops_per_sample(cfg)


@pytest.mark.parametrize("name", WORKLOADS)
def test_cell_grid_builds(name, monkeypatch):
    from repro.launch import mesh

    # a sharded grid's mesh needs its chips: stand in for it here
    monkeypatch.setattr(mesh, "make_sweep_mesh", lambda n: ("mesh", n))
    cell = spec.Cell.load(name, ROOT)
    grid = harness.build_grid(cell, 2_147_483_000)
    t = cell.traffic
    if t.get("mesh_devices"):
        assert grid.kwargs["mesh"] == ("mesh", cell.chips)
    assert len(grid.cells) == len(grid.experiments) == \
        t["seeds"] * len(t["strategies"])
    assert grid.rounds % t["chunk_rounds"] == 0
    assert grid.scale.rounds == t["rounds_per_call"]
    assert grid.n_nodes == grid.cells[0].topo.n_nodes == t["graph"]["n"]


def test_peak_table_refuses_unknown_device():
    assert peaks.peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peak("cpu")


def test_harness_exits_nonzero_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable] + BENCH["command"][1:] + [
        "--workload", WORKLOADS[0], "--seed", "1", "--seconds", "1",
        "--trace", "0"]
    proc = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr
