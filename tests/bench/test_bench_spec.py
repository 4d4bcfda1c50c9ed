"""The benchmark's definition loads and is well formed, on the CPU.

Every cell, configuration and metric of ``BENCHMARK.json`` is found by
name, each cell's grid builds, the peak table refuses a device it does not
know, and the harness refuses to run without a TPU.
"""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

from bench import flops, harness, peaks, spec

ROOT = Path(__file__).resolve().parents[2]
BENCH = spec.benchmark(ROOT)
WORKLOADS = [w["name"] for w in BENCH["workloads"]]
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


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_config_file_is_the_run_configuration(entry):
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert cfg["name"] == entry["name"]
    assert set(entry["reduced"]) <= set(cfg.get("reduced", []))
    assert flops.train_flops_per_sample(cfg) > 0


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
