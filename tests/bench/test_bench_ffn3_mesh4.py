"""The four-chip Fig. 4 grid at a CPU test size on four virtual devices:
the sharded program agrees with the reference in the experiments the
check draws, every experiment's results come back from its own shard, and
every planted fault, the unsharded run among them, fails ``correct``.
The runs need four devices, so they run in a child process. (The cell is
not in ``BENCHMARK.json`` yet: no four-chip machine was free to measure
it; see PERF.md.)"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench.faults import FAULTS

HERE = Path(__file__).resolve().parent


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, str(HERE / "mesh_runs.py"), "2147483501",
         str(tmp_path_factory.mktemp("jax_cache"))],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sharded_program_matches_reference(runs):
    assert runs["sound"]["correct"], runs["sound"]["compared"]
    assert runs["sound"]["compared"]["wrong_device"]["value"] == 0


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_fails(runs, fault):
    assert not runs[fault]["correct"], runs[fault]["compared"]
