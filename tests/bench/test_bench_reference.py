"""The plain reference trains nodes in blocks that fit the device, and
keeps Adam's moments in a configuration's stated dtype, on the CPU.

``data/replay_digest.json`` holds each configuration's reference outputs
at its test size, in float32 and in the control's bfloat16, recorded by
``record_replay_digest.py`` from the reference as it was before it trained
in blocks, when it always trained every node at once.
"""
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import small_cells as sc
from bench import peaks, spec
from bench.checks import sync_mean
from bench.reference import models, replay

DATA = Path(__file__).resolve().parent / "data"
DIGEST = json.loads((DATA / "replay_digest.json").read_text())
ADAM = {"name": "adam", "lr": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8}


def _config(name: str) -> dict:
    return json.loads((spec.BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("config", sorted(sc.REPLAY_TRAFFIC))
def test_replay_as_recorded(config, dtype):
    """With no budget the CPU reports no memory limit, so every node
    trains at once, in the same program as before: the same code on the
    same backend, equal bit for bit."""
    got = sc.replay_small(config, DIGEST["seed"], dtype)
    want = DIGEST[f"{config}/{dtype}"]
    assert {str(r): {k: [float(x) for x in v] for k, v in m.items()}
            for r, m in got.items()} == want


@pytest.mark.parametrize("config", ["ffn3", "gpt2s-1l"])
def test_blocks_of_one_node_agree_with_all_at_once(config):
    """A budget of one byte trains one node a block, state in host memory
    between blocks and the mix leaf by leaf. Float32 sums taken in another
    order (a block's products batch one node, not all) agree as the
    program and the reference do on the CPU (``CPU_LIMITS``: round losses
    to 1e-5 of a node's loss, an accuracy to the predictions that rounding
    tipped)."""
    seed = DIGEST["seed"] + 1
    whole = sc.replay_small(config, seed)
    blocks = sc.replay_small(config, seed, budget=1)
    assert sorted(blocks) == sorted(whole)
    gaps = sync_mean.gaps(blocks, whole)
    assert not sc.exceeds(gaps, sc.CPU_LIMITS), gaps


def test_block_size_follows_the_budget():
    assert replay.block_size(33, 100, 450) == 4
    assert replay.block_size(33, 100, 1) == 1
    assert replay.block_size(3, 100, 10 ** 9) == 3
    # the CPU reports no memory limit: every node at once
    assert jax.devices()[0].memory_stats() is None
    assert replay.block_size(33, 100) == 33


@pytest.mark.parametrize("workload", [w["name"] for w in
                                      spec.benchmark()["workloads"]])
def test_cells_train_every_node_at_once_on_their_chip(workload):
    """The cells' reference runs as one block on a v5e, so it is the
    program it was before blocks."""
    cell = spec.Cell.load(workload)
    n = cell.traffic["graph"]["n"]
    budget = int(peaks.peak("TPU v5 lite")["hbm_bytes"] * replay.STATE_SHARE
                 * 0.95)   # the runtime keeps a little of the 16 GB
    for dtype in (jnp.float32, jnp.bfloat16):
        per_node = replay.node_bytes(cell.config, dtype)
        assert replay.block_size(n, per_node, budget) == n


def _floats(cfg: dict) -> int:
    init, _ = models.model(cfg)
    p = jax.eval_shape(lambda k: init(k, jnp.float32), jax.random.key(0))
    return sum(math.prod(x.shape) for x in jax.tree.leaves(p))


def test_node_bytes_count_parameters_state_and_gradients():
    cfg = _config("gpt2s-1l")
    p = _floats(cfg)
    # float32 parameters, gradients and both moments, and the step count
    assert replay.node_bytes(cfg, jnp.float32) == 16 * p + 4
    stated = dict(cfg, optimizer=dict(cfg["optimizer"],
                                      state_dtype="bfloat16"))
    assert replay.node_bytes(stated, jnp.float32) == 12 * p + 4


def _moment_dtypes(state) -> set:
    _, mu, nu = state
    return {str(x.dtype) for x in jax.tree.leaves((mu, nu))}


@pytest.mark.parametrize("compute", ["float32", "bfloat16"])
def test_moments_in_the_compute_dtype_unless_stated(compute):
    dtype = getattr(jnp, compute)
    params = {"w": jnp.ones((3, 4), dtype), "b": jnp.zeros((4,), dtype)}
    grads = jax.tree.map(lambda x: jnp.full(x.shape, 0.5, dtype), params)
    for opt, want in ((ADAM, compute),
                      (dict(ADAM, state_dtype="bfloat16"), "bfloat16"),
                      (dict(ADAM, state_dtype="float32"), "float32")):
        init, update = models.optimizer(opt, dtype)
        state = init(params)
        assert _moment_dtypes(state) == {want}
        upd, state = update(grads, state)
        assert _moment_dtypes(state) == {want}
        assert {str(x.dtype) for x in jax.tree.leaves(upd)} == {compute}


def test_stated_moments_are_kept_rounded_and_the_step_computed_in_float32():
    """One Adam step from bfloat16 moments: the moments advance and the
    update is taken in float32, and the moments are then rounded to
    bfloat16 to be kept."""
    rng = np.random.default_rng(3)
    g = rng.normal(size=(64,)).astype(np.float32)
    mu0 = jnp.asarray(rng.normal(size=(64,)) * 0.1, jnp.bfloat16)
    nu0 = jnp.asarray(rng.random(64) * 0.01, jnp.bfloat16)
    _, update = models.optimizer(dict(ADAM, state_dtype="bfloat16"),
                                 jnp.float32)
    upd, (step, mu, nu) = update(jnp.asarray(g),
                                 (jnp.asarray(4, jnp.int32), mu0, nu0))
    b1, b2, lr, eps = ADAM["b1"], ADAM["b2"], ADAM["lr"], ADAM["eps"]
    m = b1 * np.asarray(mu0, np.float32) + (1 - b1) * g
    v = b2 * np.asarray(nu0, np.float32) + (1 - b2) * g * g
    c1, c2 = 1 / (1 - b1 ** 5), 1 / (1 - b2 ** 5)
    want = -lr * (m * c1) / (np.sqrt(v * c2) + eps)
    assert int(step) == 5
    np.testing.assert_allclose(np.asarray(upd), want, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(mu, np.float32),
                                  np.asarray(jnp.asarray(m, jnp.bfloat16),
                                             np.float32))
    np.testing.assert_array_equal(np.asarray(nu, np.float32),
                                  np.asarray(jnp.asarray(v, jnp.bfloat16),
                                             np.float32))
