"""``correct`` comes out true for a sound run and false for the control
and for each fault a one-chip cell can have, driven through the harness
on the CPU at a tiny grid (the look for a chip skipped)."""

import pytest

from chipbench_testutil import fault_cases

SIDE = 10
CASES = ("sound", "control", "unchanged", "half", "altered")
CELLS = ("poisson7_weak.1chip", "poisson27_weak.1chip")


@pytest.fixture(scope="module")
def outcomes(tmp_path_factory):
    cache = str(tmp_path_factory.mktemp("jax_cache"))
    return fault_cases(CELLS, SIDE, CASES, cache)


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("case", CASES)
def test_correct_decides(outcomes, workload, case):
    got = outcomes[workload][case]
    assert got["correct"] is (case == "sound"), got
