"""The four-chip Fig. 4 grid at a CPU test size on four virtual devices:
the sharded program agrees with the reference in the experiments the
check draws, every experiment's results come back from its own shard, and
every planted fault, the unsharded run among them, fails ``correct``.
The runs need four devices, so they run in a child process. The cell
``ffn3.fig4grid.mesh4`` of ``BENCHMARK.json`` runs at this size against
``small_cells.CPU_LIMITS``, the limits of float32 on the CPU; its own
limits file holds those set on four chips, for the chip's rounding."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench.checks.sync_mean import wrong_devices
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


#: 12 experiments on 4 shards: the devices each experiment's rows came
#: back from, and how many experiments ``wrong_device`` counts
PLACEMENTS = {
    "in_order": ([[0]] * 3 + [[1]] * 3 + [[2]] * 3 + [[3]] * 3, 0),
    # jax.make_mesh's ring order on a 2x2 TPU tray
    "ring": ([[0]] * 3 + [[1]] * 3 + [[3]] * 3 + [[2]] * 3, 0),
    "unsharded": ([[0]] * 12, 9),
    "shard_split": ([[0]] * 3 + [[1], [1], [2]] + [[3]] * 3 + [[2]] * 3, 3),
    "replicated": ([[0, 1, 2, 3]] * 12, 12),
    "two_shards_one_device": ([[0]] * 3 + [[1]] * 3 + [[1]] * 3
                              + [[2]] * 3, 3),
}


@pytest.mark.parametrize("name", sorted(PLACEMENTS))
def test_wrong_devices_counts_misplaced_shards(name):
    devices, want = PLACEMENTS[name]
    rows = [{"param_devices": d} for d in devices]
    assert wrong_devices(rows, 4) == want
