"""A model enters the benchmark as new files alone, on the CPU.

A temporary copy of ``bench/`` gains model files, configurations,
traffics, limits files and a metric reader, and the copy's
``BENCHMARK.json`` names the new configurations, cells and metric; no file
of the harness is edited. The model ``mlp`` describes its layers by a
list of widths, a schema none of the benchmark's own models uses; on the
``mnist`` data set the program trains the same 784-128-128-10 net, so the
copy's harness runs the cell and its plain reference agrees with the
program. The model ``convnet`` takes the path of a model whose nodes fill
the chip: it declares a device scope of its own (``scopes(cfg)``), its
configuration states Adam's moment dtype (``"state_dtype"``), its
reference trains node by node under a memory budget smaller than one
node's state, and a per-layer metric of its own reads the configuration
and the declared scope. On ``cifar10`` the program trains VGG-16 with
Adam, so ``convnet`` is VGG-16 at a sixteenth of its width, with float32
moments as the program keeps them.
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


CONVNET_CELL = "convnet.tiny"

CONVNET = '''"""VGG-16 as bench/models/vgg16.py has it, with a device scope of
its own."""
from bench import spec

_vgg = spec.load_module(spec.BENCH / "models" / "vgg16.py")
macs, scale, init, apply = _vgg.macs, _vgg.scale, _vgg.init, _vgg.apply


def scopes(cfg):
    return ("features",)
'''

#: the share of local training that ops under the model's own scope took,
#: per convolution of the configuration's plan
CONVNET_METRIC = '''from bench import scopes


def read(ctx):
    share = scopes.share(ctx, "features")
    if share is None:
        return None
    convs = [c for c in ctx["config"]["plan"] if c != "M"]
    return share / len(convs)
'''

CONVNET_CONFIG = dict(
    json.loads((ROOT / "bench" / "configs" / "vgg16.json").read_text()),
    name="convnet", model="convnet", width_mult=0.0625,
    optimizer={"name": "adam", "lr": 0.0001, "b1": 0.9, "b2": 0.999,
               "eps": 1e-08, "state_dtype": "float32"})
del CONVNET_CONFIG["floats_per_node"]

CONVNET_TRAFFIC = dict(
    TRAFFIC, graph={"kind": "barabasi_albert", "n": 4, "m": 2},
    n_train=120, n_test=60, batch=4, eval_n=16, chunk_rounds=1,
    rounds_per_call=2, options={"mix_impl": "einsum", "coeff_mode": "stack"})

#: float32 on both sides, as ``test_bench_vgg16_fedavg.py`` has it: Adam's
#: normalised steps grow rounding to a few 1e-4 of a node's round loss
CONVNET_LIMITS = {"loss_gap": 0.01, "mean_loss_gap": 0.01,
                  "iid_acc_gap": 0.05, "ood_acc_gap": 0.05}


def _checkout(tmp: Path) -> Path:
    """A copy of the benchmark with the new model's files added."""
    root = tmp / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": CONFIG["name"], "source": "test",
                             "file": "bench/configs/mlp784.json",
                             "reduced": [], "why": "test"})
    bench["configs"].append({"name": "convnet", "source": "test",
                             "file": "bench/configs/convnet.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": CONFIG["name"],
                               "traffic": "tiny", "chips": 1, "why": "test"})
    bench["workloads"].append({"name": CONVNET_CELL, "config": "convnet",
                               "traffic": "tiny.adam", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"] += [CELL, CONVNET_CELL]
    bench["per_layer"].append({
        "name": "features_share_per_conv", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "round step",
        "moves": "node_rounds_per_s", "workloads": [CONVNET_CELL]})
    files = {"BENCHMARK.json": bench, "bench/configs/mlp784.json": CONFIG,
             "bench/traffic/tiny.json": TRAFFIC,
             f"bench/limits/{CELL}.json": LIMITS,
             "bench/configs/convnet.json": CONVNET_CONFIG,
             "bench/traffic/tiny.adam.json": CONVNET_TRAFFIC,
             f"bench/limits/{CONVNET_CELL}.json": CONVNET_LIMITS}
    for rel, data in files.items():
        (root / rel).write_text(json.dumps(data))
    (root / "bench" / "models" / "mlp.py").write_text(MODEL)
    (root / "bench" / "models" / "convnet.py").write_text(CONVNET)
    (root / "bench" / "metrics" / "features_share_per_conv.py").write_text(
        CONVNET_METRIC)
    return root


def _assert_only_added(root: Path) -> None:
    """Every code file of this repository's benchmark is in the copy at
    ``root`` as it is here: the copy only adds files."""
    files = lambda r: {p.relative_to(r).as_posix(): p.read_bytes()
                       for p in (r / "bench").rglob("*.py")}
    mine, theirs = files(ROOT), files(root)
    assert {k: theirs.get(k) for k in mine} == mine


def _run(root: Path, cell: str = CELL, *args: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, str(HERE / "model_runs.py"), str(root), cell,
         "2147483601", *args], env=env, capture_output=True, text=True,
        timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return _checkout(tmp_path_factory.mktemp("model_files"))


def test_new_model_runs_by_files_alone(checkout):
    _assert_only_added(checkout)
    run = _run(checkout)
    out = run["out"]
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"node_rounds_per_s", "setup_s"}
    # 784·128 + 128·128 + 128·10 multiply-adds, the first layer's input
    # gradient left out
    assert run["train_flops"] == 2 * (3 * 118016 - 100352)
    assert "analytics" in run["kwargs"]


def test_model_filling_the_chip_runs_by_files_alone(checkout):
    """The path of a model whose nodes fill the chip: a budget of one byte
    leaves the reference one node a block, and the cell is still
    correct; its own metric reads its configuration and declared scope."""
    _assert_only_added(checkout)
    metrics = {p.name for p in (checkout / "bench" / "metrics").iterdir()}
    assert metrics - {p.name for p in (ROOT / "bench" / "metrics").iterdir()} \
        == {"features_share_per_conv.py"}
    run = _run(checkout, CONVNET_CELL, "--budget", "1")
    assert run["block"] == 1
    assert run["state_dtypes"] == ["float32"]
    out = run["out"]
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert run["scopes"] == ["local_train", "mix", "coeffs", "eval",
                             "batch_gather", "analytics", "features"]
    traced = run["traced"]
    # the model's scope owns its op's self time; local_train's inclusive
    # time holds it
    assert traced["self"]["features"] == pytest.approx(50e-9)
    assert traced["self"]["local_train"] == pytest.approx(350e-9)
    assert traced["inclusive"]["local_train"] == pytest.approx(400e-9)
    # 50 of 400 busy ns, over the plan's 13 convolutions
    assert traced["metrics"] == {"features_share_per_conv": {
        "value": pytest.approx(12.5 / 13), "unit": "%"}}


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
