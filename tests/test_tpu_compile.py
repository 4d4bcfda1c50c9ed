"""Every Pallas kernel compiles for a TPU v5e chip.

The TPU compiler ships with jaxlib and compiles for a chip that is
described rather than attached, so these tests run on a CPU-only host:
each lowers a kernel at the shapes the system runs it at, compiles it for
one chip of a described ``v5e:2x2`` topology, and checks that the kernel
reached the compiled program as a Mosaic custom call (no interpret-mode
fallback).  Nothing runs, so nothing here says anything about results or
time.

The topology is described inside a fixture, never at import time: only
one process may load the TPU library, and every test worker imports this
module.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.gossip_mix import (
    gossip_edges_pallas,
    gossip_plane_pallas,
    gossip_robust_pallas,
)
from repro.kernels.mla_attention import mla_attention_pallas
from repro.kernels.ssm_scan import rwkv_scan_pallas

#: floats per node of VGG-16 at Table 1 width (the widest paper plane)
VGG16_PLANE = 14_982_479
#: floats per node at n=1024: a 1 GiB f32 plane, what one chip holds
#: beside its optimizer state (the scale of the n=1024 deployments)
PLANE_1024 = 262_144
#: 33-node BA(p=2) graphs of the paper's FULL scale have hubs of degree
#: about 15: a 16-slot neighbour table (self included)
DMAX_33 = 16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_kernel(text):
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n,width", [(33, VGG16_PLANE), (1024, PLANE_1024)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_plane_kernel_compiles(one_chip, n, width, dtype):
    text = _compiled_text(
        lambda p, c: gossip_plane_pallas(p, c, interpret=False),
        ((n, width), dtype), ((n, n), jnp.float32), sharding=one_chip)
    _assert_kernel(text)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_edges_kernel_compiles(one_chip, dtype):
    text = _compiled_text(
        lambda p, w, i: gossip_edges_pallas(p, w, i, interpret=False),
        ((33, VGG16_PLANE), dtype), ((33, DMAX_33), jnp.float32),
        ((33, DMAX_33), jnp.int32), sharding=one_chip)
    _assert_kernel(text)


@pytest.mark.parametrize("op,trim_k", [("trimmed", 1), ("median", 0)])
def test_robust_kernel_compiles(one_chip, op, trim_k):
    text = _compiled_text(
        lambda p, w, i: gossip_robust_pallas(p, w, i, op=op, trim_k=trim_k,
                                             interpret=False),
        ((33, VGG16_PLANE), jnp.float32), ((33, DMAX_33), jnp.float32),
        ((33, DMAX_33), jnp.int32), sharding=one_chip)
    _assert_kernel(text)


@pytest.mark.parametrize("s,dtype", [(1024, jnp.float32),
                                     (2048, jnp.bfloat16)])
def test_flash_attention_compiles(one_chip, s, dtype):
    # GPT-2-small heads: 12 × 64
    qkv = ((8, s, 12, 64), dtype)
    text = _compiled_text(
        lambda q, k, v: flash_attention_pallas(q, k, v, interpret=False),
        qkv, qkv, qkv, sharding=one_chip)
    _assert_kernel(text)


def test_mla_attention_compiles(one_chip):
    # deepseek-v2 latent rank 512, rope dim 64
    text = _compiled_text(
        lambda ql, qr, ck, kr: mla_attention_pallas(ql, qr, ck, kr,
                                                    interpret=False),
        ((1, 1024, 16, 512), jnp.bfloat16), ((1, 1024, 16, 64), jnp.bfloat16),
        ((1, 1024, 512), jnp.bfloat16), ((1, 1024, 64), jnp.bfloat16),
        sharding=one_chip)
    _assert_kernel(text)


def test_rwkv_scan_compiles(one_chip):
    # rwkv6-3b: d=2560 as 40 heads of 64
    x = ((1, 1024, 40, 64), jnp.float32)
    text = _compiled_text(
        lambda r, k, v, w, u, st: rwkv_scan_pallas(r, k, v, w, u, st,
                                                   interpret=False),
        x, x, x, x, ((40, 64), jnp.float32), ((1, 40, 64, 64), jnp.float32),
        sharding=one_chip)
    _assert_kernel(text)
