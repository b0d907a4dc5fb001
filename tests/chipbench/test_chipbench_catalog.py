"""The benchmark's files: every cell resolves by name, new files are found
without editing old ones, the command refuses to run without a TPU, and
the generators and byte counts are what the cells say."""

import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from chipbench_testutil import ROOT, cpu_env

from chipbench import catalog, reference
from chipbench.generators import closed_loop
from chipbench.metrics.spmv_roofline import chip_bytes
from chipbench.problems import stencil

BENCH = catalog.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
WIDTH_WORDS = re.compile(r"hidden|intermediate|latent|state|projection|_dim$|_rank$|head")


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_by_name(name):
    cell = catalog.cell(name)
    assert cell.config["chips"] == cell.chips
    assert callable(cell.entry.open) and callable(cell.problem.build)
    assert callable(cell.generator.window)
    assert {m.name for m in cell.end_to_end} == {"solve_s", "setup_s"}
    assert cell.per_layer and all(callable(m.reader.read) for m in cell.per_layer)


def test_benchmark_json_is_well_formed():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p)) and not p.startswith("/")
    assert 1 <= BENCH["run_seconds"] <= 51
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [c["name"] for c in BENCH["configs"]] + CELLS + [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    assert all(NAME.match(n) for n in names)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("chipbench/configs/")
        assert not any(WIDTH_WORDS.search(k) for k in c["reduced"])
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["source"] == c["source"] and len(c["source"]) <= 200
        assert set(c["reduced"]) == set(cfg["reduced"])
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, len(CELLS) // 2)
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    layers = {}
    for m in BENCH["per_layer"]:
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
        assert os.path.isfile(os.path.join(ROOT, "chipbench", "metrics", m["name"] + ".py"))
        layers.setdefault(m["layer"], m["layer"])


# A mix with a generator of its own: a fixed burst of solves of constant
# right-hand sides, whatever the clock says.
_BURST = """
import time

import numpy as np

from chipbench.generators import Window


def window(entry, problem, traffic, seed, seconds):
    t0 = time.perf_counter()
    rhss = [np.full(problem.n, float(seed % 7 + i + 1))
            for i in range(traffic["solves"])]
    answers = [entry.solve(b) for b in rhss]
    return Window(answers, rhss, time.perf_counter() - t0)
"""

ADDITIONS = {
    "metric": ("chipbench/metrics/extra_metric.py",
               "def read(ctx):\n    return 42.0\n"),
    "traffic": ("chipbench/traffic/extra_mix.json",
                json.dumps({"generator": "extra_burst", "solves": 2})),
    "generator": ("chipbench/generators/extra_burst.py", _BURST),
    "config": ("chipbench/configs/extra_config.json", None),
    "entry": ("chipbench/entries/extra_entry.py",
              "from chipbench.entries.session_cg import open  # noqa: F401\n"),
}

# Resolves the new cell, then runs it through the harness on the CPU at a
# tiny grid (the look for a chip skipped): the new mix drives the window.
_CHILD = """
import json, sys, time
sys.path[:0] = [".", sys.argv[1]]
import jax
from chipbench import catalog, harness
cell = catalog.cell("extra_config.cell")
res = harness.run_cell(cell, seed=2**31 + 3, seconds=0.0, trace=False,
                       t0=time.perf_counter(), devices=jax.devices()[:1],
                       overrides={"local_grid": [6, 6, 6]})
print(json.dumps({
    "metric": [m.name for m in cell.per_layer if m.name == "extra_metric"]
              and cell.per_layer[-1].reader.read(None) == 42.0,
    "traffic": cell.traffic["solves"] == 2,
    "generator": res["attempted"] == 2 and res["correct"],
    "config": cell.config["name"] == "extra_config",
    "entry": cell.entry.__name__ == "chipbench.entries.extra_entry",
}))
"""


@pytest.fixture(scope="module")
def added(tmp_path_factory):
    """A copy of the benchmark with one new file of each kind, found by
    the names a new BENCHMARK.json entry gives them; no file that was
    there is edited."""
    tmp = tmp_path_factory.mktemp("bench")
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: open(p, "rb").read() for p in map(str, (tmp / "chipbench").rglob("*"))
              if os.path.isfile(p)}
    cfg = json.load(open(os.path.join(ROOT, "chipbench/configs/poisson7_weak.json")))
    cfg.update(name="extra_config", entry="extra_entry")
    ADDITIONS["config"] = (ADDITIONS["config"][0], json.dumps(cfg))
    for path, text in ADDITIONS.values():
        (tmp / path).write_text(text)
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append(dict(bench["configs"][0], name="extra_config",
                                 file="chipbench/configs/extra_config.json"))
    bench["workloads"].append({"name": "extra_config.cell", "config": "extra_config",
                               "traffic": "extra_mix", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "extra_metric", "unit": "s", "better": "lower",
                               "source": "host_clock", "layer": "entry",
                               "moves": "solve_s", "workloads": ["extra_config.cell"]})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    r = subprocess.run([sys.executable, "-c", _CHILD, os.path.join(ROOT, "src")],
                       cwd=tmp, capture_output=True, text=True, timeout=300,
                       env=cpu_env(cache_dir=str(tmp / "jax_cache")))
    assert r.returncode == 0, r.stderr[-3000:]
    for p, data in before.items():
        assert open(p, "rb").read() == data, p
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("kind", sorted(ADDITIONS))
def test_new_file_found_by_name(added, kind):
    assert added[kind] is True


def _only_benchmark_files(tmp):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp / "BENCHMARK.json")
    for p in BENCH["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    return tmp


@pytest.mark.parametrize("where", ["repository", "benchmark_files_only"])
def test_run_fails_without_tpu_or_program(tmp_path, where):
    cwd = ROOT if where == "repository" else _only_benchmark_files(tmp_path)
    r = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELLS[0],
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, timeout=300, env=cpu_env(),
    )
    assert r.returncode == 2, (r.stdout[-2000:], r.stderr[-2000:])
    assert "{" not in r.stdout
    assert ("no TPU" if where == "repository" else "program under test") in r.stderr


def test_rhs_same_seed_same_vector():
    cell = catalog.cell(CELLS[0])
    problem = cell.problem.build(dict(cell.config, local_grid=[6, 5, 4]), 1)
    draw = closed_loop.draw
    b = draw(cell.traffic, problem, 2**31 + 9, 0)
    assert b.shape == (problem.n,) and b.dtype == np.float64
    assert np.array_equal(b, draw(cell.traffic, problem, 2**31 + 9, 0))
    assert not np.allclose(b, draw(cell.traffic, problem, 2**31 + 9, 1))
    assert not np.allclose(b, draw(cell.traffic, problem, 2**31 + 10, 0))
    assert not np.allclose(b, draw(cell.traffic, problem, -(2**31 + 9), 0))


def test_traffic_without_generator_is_refused(tmp_path):
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "chipbench/traffic/rhs_normal.json").write_text('{"rhs": "normal"}')
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    with pytest.raises(catalog.CatalogError, match="names no generator"):
        catalog.cell(CELLS[0], root=str(tmp_path))


def test_reference_residual():
    import scipy.sparse.linalg as spla

    cell = catalog.cell(CELLS[0])
    problem = cell.problem.build(dict(cell.config, local_grid=[6, 5, 4]), 1)
    b = closed_loop.draw(cell.traffic, problem, 3, 0)
    x = spla.spsolve(problem.a.tocsc(), b)
    assert reference.true_relres(problem.a, x, b) < 1e-14
    assert reference.true_relres(problem.a, np.zeros_like(b), b) == 1.0
    assert reference.true_relres(problem.a, np.full_like(b, np.nan), b) == float("inf")
    assert reference.true_relres(problem.a, x[:-1], b) == float("inf")


# cell -> (stencil, grid, chips, rows, nnz, CSR bytes per SpMV and chip in
# f64), as worked out by hand when these cells were defined
SIZES = {
    "poisson7_weak.1chip": ("7pt", (64, 64, 64), 1, 262_144, 1_810_432, [26.97e6]),
    "poisson27_weak.1chip": ("27pt", (64, 64, 64), 1, 262_144, 6_859_000, [87.55e6]),
}


@pytest.mark.parametrize("name", sorted(SIZES))
def test_matrix_and_spmv_bytes(name):
    st, grid, chips, n, nnz, mb = SIZES[name]
    cell = catalog.cell(name)
    assert stencil.global_grid(cell.config, cell.chips) == grid
    problem = cell.problem.build(cell.config, cell.chips)
    assert (problem.n, problem.nnz, problem.chips) == (n, nnz, chips)
    got = chip_bytes(problem)
    assert len(got) == chips
    np.testing.assert_allclose(got, mb, rtol=5e-4)
    w = 8
    assert sum(got) == nnz * (w + 4) + sum(
        2 * r * w + (r + 1) * 4 for r, _ in problem.row_blocks())
