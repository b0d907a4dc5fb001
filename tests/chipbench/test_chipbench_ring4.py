"""The four-chip ring cell ``poisson7_weak.ring4``: its matrix is the
7-point grid of ``poisson7_weak`` extruded over four z-slabs, its byte
count per chip follows the slabs, and the harness runs it through the
program's ring path on four CPU devices to a correct answer."""

import json
import subprocess
import sys

from chipbench_testutil import ROOT, cpu_env

from chipbench import catalog
from chipbench.metrics.spmv_roofline import chip_bytes
from chipbench.problems import stencil

CELL = "poisson7_weak.ring4"


def test_ring_cell_is_the_one_chip_config_on_four_chips():
    ring = catalog.cell(CELL)
    one = catalog.cell("poisson7_weak.1chip")
    assert ring.chips == 4 and ring.config_name == "poisson7_weak_ring4"
    differ = {k for k in one.config.keys() | ring.config.keys()
              if one.config.get(k) != ring.config.get(k)}
    assert differ <= {"name", "source", "chips", "layout", "reduced", "assumed"}
    assert ring.traffic_name == one.traffic_name == "rhs_normal"


def test_ring_matrix_and_bytes_per_chip():
    cell = catalog.cell(CELL)
    assert stencil.global_grid(cell.config, cell.chips) == (64, 64, 256)
    problem = cell.problem.build(cell.config, cell.chips)
    assert (problem.n, problem.nnz) == (1_048_576, 7_266_304)
    # a slab at either end of z lacks one 64x64 face of neighbours more
    # than the two in the middle: 7*64^3 - 4*64^2 - 64^2 and 7*64^3 - 4*64^2
    end, mid = 1_814_528, 1_818_624
    assert problem.row_blocks() == [(262_144, end), (262_144, mid),
                                    (262_144, mid), (262_144, end)]
    rows = 262_144
    want = [nnz * (8 + 4) + 2 * rows * 8 + (rows + 1) * 4
            for nnz in (end, mid, mid, end)]
    assert chip_bytes(problem) == want == [27_017_220, 27_066_372,
                                           27_066_372, 27_017_220]


# The cell through the harness at a 4^3 grid per device, the look for a
# chip skipped.
_CHILD = """
import json, sys, time
sys.path[:0] = [sys.argv[1], sys.argv[2]]
import jax
from chipbench import catalog, harness
cell = catalog.cell("poisson7_weak.ring4")
res = harness.run_cell(cell, seed=2**31 + 41, seconds=0.0, trace=False,
                       t0=time.perf_counter(), devices=jax.devices()[:4],
                       overrides={"local_grid": [4, 4, 4]})
print(json.dumps(res))
"""


def test_ring_cell_runs_correct_on_four_devices(tmp_path):
    r = subprocess.run(
        [sys.executable, "-c", _CHILD, ROOT, f"{ROOT}/src"],
        capture_output=True, text=True, timeout=300,
        env=cpu_env(4, cache_dir=str(tmp_path / "jax_cache")),
    )
    assert r.returncode == 0, r.stderr[-3000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["correct"] and res["attempted"] == 1 and res["failed"] == 0
    assert res["device"]["count"] == 4
    assert set(res["metrics"]) == {"solve_s", "setup_s"}
