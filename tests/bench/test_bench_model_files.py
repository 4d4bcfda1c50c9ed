"""A model enters the benchmark as new files alone, on the CPU.

A temporary copy of ``bench/`` gains a model file, a configuration, a
traffic and a limits file, and the copy's ``BENCHMARK.json`` names the
new configuration and cell; no file of the harness is edited. The model,
``mlp``, describes its layers by a list of widths, a schema none of the
benchmark's own models uses; on the ``mnist`` data set the program trains
the same 784-128-128-10 net, so the copy's harness runs the cell and its
plain reference agrees with the program.
"""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent
CELL = "mlp784.tiny"

MODEL = '''"""A feed-forward net given by its widths, ReLU between layers."""
import math

import jax
import jax.numpy as jnp

from bench.reference.models import trunc_normal


def macs(cfg):
    w = cfg["widths"]
    layers = [a * b for a, b in zip(w[:-1], w[1:])]
    return sum(layers), layers[0]


def sweep_kwargs(cfg):
    return {"analytics": False}


def init(cfg, key, dtype):
    w = cfg["widths"]
    ks = jax.random.split(key, len(w) - 1)
    return [{"w": trunc_normal(k, (a, b), 1.0 / math.sqrt(a), dtype),
             "b": jnp.zeros((b,), dtype)}
            for k, a, b in zip(ks, w[:-1], w[1:])]


def apply(cfg, params, x):
    h = x.reshape(x.shape[0], -1)
    for i, layer in enumerate(params):
        h = h @ layer["w"] + layer["b"][None]
        if i < len(params) - 1:
            h = jax.nn.relu(h)
    return h
'''

CONFIG = {
    "name": "mlp784", "model": "mlp", "dataset": "mnist",
    "widths": [784, 128, 128, 10],
    "optimizer": {"name": "sgd", "lr": 0.01}, "dtype": "float32",
    "matmul_precision": "default",
    "data": {"kind": "image", "shape": [28, 28, 1], "n_classes": 10,
             "noise": 0.35, "proto_seed": 8332},
    "reduced": []}

TRAFFIC = {
    "graph": {"kind": "barabasi_albert", "n": 5, "m": 2},
    "strategies": ["degree"], "seeds": 1, "tau": 0.1, "ood_k": 1, "q": 0.1,
    "alpha_l": 1000.0, "alpha_s": 1e9, "n_train": 200, "n_test": 64,
    "batch": 8, "local_epochs": 1, "steps_per_epoch": 0, "eval_every": 1,
    "eval_n": 16, "rounds_per_call": 2, "chunk_rounds": 1,
    "options": {"mix_impl": "einsum", "coeff_mode": "stack"},
    "check": "sync_mean", "check_experiments": 1}

#: float32 on both sides: round losses agree to rounding, an accuracy may
#: differ by a prediction that rounding tipped (1/16)
LIMITS = {"loss_gap": 1e-5, "mean_loss_gap": 1e-5, "iid_acc_gap": 0.07,
          "ood_acc_gap": 0.07, "iid_acc_max_gap": 0.07}


def _checkout(tmp: Path) -> Path:
    """A copy of the benchmark with the new model's files added."""
    root = tmp / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": CONFIG["name"], "source": "test",
                             "file": "bench/configs/mlp784.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": CONFIG["name"],
                               "traffic": "tiny", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append(CELL)
    files = {"BENCHMARK.json": bench, "bench/configs/mlp784.json": CONFIG,
             "bench/traffic/tiny.json": TRAFFIC,
             f"bench/limits/{CELL}.json": LIMITS}
    for rel, data in files.items():
        (root / rel).write_text(json.dumps(data))
    (root / "bench" / "models" / "mlp.py").write_text(MODEL)
    return root


def _harness_files(root: Path) -> dict:
    return {p.relative_to(root).as_posix(): p.read_bytes()
            for p in (root / "bench").rglob("*.py")
            if "models" not in p.parts}


def _run(root: Path) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, str(HERE / "model_runs.py"), str(root), CELL,
         "2147483601"], env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return _checkout(tmp_path_factory.mktemp("model_files"))


def test_new_model_runs_by_files_alone(checkout):
    assert _harness_files(checkout) == _harness_files(ROOT)
    run = _run(checkout)
    out = run["out"]
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"node_rounds_per_s", "setup_s"}
    # 784·128 + 128·128 + 128·10 multiply-adds, the first layer's input
    # gradient left out
    assert run["train_flops"] == 2 * (3 * 118016 - 100352)
    assert "analytics" in run["kwargs"]


def test_missing_model_file_is_named(checkout, tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(checkout, root)
    (root / "bench" / "models" / "mlp.py").unlink()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(HERE / "model_runs.py"), str(root), CELL, "1"],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode != 0
    assert "models/mlp.py" in proc.stderr and "FileNotFoundError" in proc.stderr
