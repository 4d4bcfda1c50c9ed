"""``gpt2s-1l.ba33.fedavg`` at a CPU test size: the program agrees with the
reference, and the control and every planted fault fail ``correct``."""
import pytest

import small_cells as sc
from bench.faults import FAULTS, faults_for
from bench.spec import Cell

NAME = "gpt2s-1l.ba33.fedavg"
SEED = 2_147_483_201


@pytest.fixture(scope="module")
def cell():
    return sc.small_cell(NAME)


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    with sc.compile_cache(tmp_path_factory.mktemp("jax_cache")), \
            sc.reference_once():
        yield


def test_program_matches_reference(cell, cache):
    out = sc.run(cell, SEED)
    assert out["correct"], sc.dumps(out)
    assert out["attempted"] > 0 and out["failed"] == 0


def test_control_fails(cell):
    gaps = sc.control_gaps(cell, SEED)
    assert sc.exceeds(gaps, cell.limits), gaps


@pytest.mark.parametrize("fault", faults_for(Cell.load(NAME).traffic))
def test_fault_fails(cell, cache, fault):
    with FAULTS[fault]():
        out = sc.run(cell, SEED)
    assert not out["correct"], sc.dumps(out)
