"""The solver's layer names, from the device ops to the set-up counters.

``energy/trace.region`` opens a ``jax.named_scope`` of its name, so every
op of the compiled solve carries its layer (``spmv``, ``reductions``,
``halo``, ...) in its ``op_name``; ``core/spmv.spmv_shard`` adds
``interior``, ``boundary`` and ``halo`` inside the SpMV. The energy
ledger's counts do not depend on the scopes. ``SolverSession.matrix`` and
``SolverHandle.warm`` add their host seconds and compile work to the
process registry ``repro.obs.metrics.REGISTRY``. Tiny grids on the CPU.
"""

import contextlib
import json
import re

import jax
import numpy as np
import pytest

from repro.core.cg import make_solver, solver_handle
from repro.core.partition import pad_vector, partition_csr
from repro.core.spmv import shard_matrix, shard_vector
from repro.energy import trace
from repro.matrices.poisson import cube, poisson_scipy
from repro.obs import metrics

from tests.conftest import run_multidevice

LAYERS = {"spmv", "halo", "overlap", "reductions", "precond", "vcycle"}
# instruction, its result type, the JAX op it came from
_INST = re.compile(
    r'^\s*(?:ROOT\s+)?%([\w.\-]+) = (\S+) .*?op_name="([^"]*)"', re.M)


def _ops(compiled_text: str) -> list[tuple[str, str, str]]:
    return _INST.findall(compiled_text)


def _steps(op: str) -> set[str]:
    return set(re.split(r"[/;]", op))


@pytest.fixture(scope="module")
def system(single_mesh):
    a = poisson_scipy(cube(6, "7pt"), dtype=np.float32)
    mat = shard_matrix(single_mesh, partition_csr(a, 1))
    b = shard_vector(single_mesh, pad_vector(np.ones(a.shape[0], np.float32), mat))
    x0 = shard_vector(single_mesh, pad_vector(np.zeros(a.shape[0], np.float32), mat))
    return mat, b, x0


def test_hs_while_body_ops_carry_layer_scopes(single_mesh, system):
    mat, b, x0 = system
    solve = make_solver(single_mesh, mat, variant="hs", maxiter=50)
    ops = _ops(solve.lower(b, x0).compile().as_text())
    body = [(i, t, o) for i, t, o in ops if "/while/body/" in o]
    assert any("spmv" in _steps(o) for _, _, o in body)
    assert any("reductions" in _steps(o) for _, _, o in body)
    assert any({"spmv", "interior"} <= _steps(o) for _, _, o in body)
    # the iteration counter (an s32 scalar add) is the loop's own work
    bare = [(i, t, o) for i, t, o in body if not _steps(o) & LAYERS]
    assert all(o.endswith("/while/body/add") and t.startswith("s32[]")
               for _, t, o in bare), bare


_RING = """
import json
import numpy as np
from repro import api
from repro.matrices.poisson import cube, poisson_scipy
from repro.core.partition import pad_vector
from repro.core.spmv import shard_vector

a = poisson_scipy(cube(6, "7pt"), dtype=np.float64)
session = api.SolverSession(a, 4)
mat = session.matrix()
assert mat.plan.mode == "ring" and len(mat.plan.shifts) > 0
v = shard_vector(session.mesh_for(mat), pad_vector(np.zeros(a.shape[0]), mat))
out = {}
for overlap in (True, False):
    h = session.solver(mat, variant="hs", maxiter=20, overlap=overlap)
    out[str(overlap)] = h.fn.lower(v, v).compile().as_text()
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def ring_programs():
    """The hs solve on a ring of 4 CPU devices, overlapped and not."""
    out = json.loads(run_multidevice(_RING, n_devices=4).strip().splitlines()[-1])
    return {k == "True": _ops(v) for k, v in out.items()}


@pytest.mark.parametrize("overlap", [True, False], ids=["overlap", "serial"])
def test_ring_halo_interior_boundary_scopes(ring_programs, overlap):
    ops = ring_programs[overlap]
    permutes = [o for i, t, o in ops if "ppermute" in _steps(o)]
    assert permutes and all("halo" in _steps(o) for o in permutes)
    assert all(("overlap" in _steps(o)) is overlap for o in permutes)
    steps = [_steps(o) for _, _, o in ops if "spmv" in _steps(o)]
    assert any({"interior", "gather"} <= s for s in steps)
    assert any({"boundary", "scatter-add"} <= s for s in steps)
    psums = [o for i, t, o in ops if "psum" in _steps(o) and "/while/body/" in o]
    assert psums and all("reductions" in _steps(o) for o in psums)


def _energy_counts(mesh, mat, b, x0):
    with trace.capture() as tr:
        make_solver(mesh, mat, variant="hs", maxiter=50).lower(b, x0)
    return ({sec: {r: (t.counts, dict(t.calls)) for r, t in regs.items()}
             for sec, regs in tr.sections.items()}, dict(tr.entries))


def test_scopes_leave_energy_counts_unchanged(single_mesh, system, monkeypatch):
    mat, b, x0 = system
    scoped = _energy_counts(single_mesh, mat, b, x0)
    assert {"spmv", "reductions"} <= set(scoped[0][trace.ITERATION])
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert _energy_counts(single_mesh, mat, b, x0) == scoped


def _counter(name: str) -> float:
    return metrics.REGISTRY.snapshot().get(name, {"value": 0.0})["value"]


def test_session_matrix_counts_partition_seconds():
    from repro import api

    names = ("setup_partition_csr_seconds", "setup_shard_matrix_seconds")
    before = [_counter(n) for n in names]
    session = api.SolverSession(poisson_scipy(cube(5, "7pt")), 1)
    session.matrix()
    after = [_counter(n) for n in names]
    assert all(a > b for a, b in zip(after, before))
    session.matrix()  # partitioned already: adds nothing
    assert [_counter(n) for n in names] == after


WARM = ("warm_trace_lower_seconds", "warm_backend_compile_seconds",
        "warm_cache_hits_total", "warm_cache_misses_total")


@pytest.fixture
def compile_cache(tmp_path):
    """JAX's persistent compilation cache in a fresh directory, every
    program written to it."""
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_compilation_cache_include_metadata_in_key")
    prev = {k: getattr(jax.config, k) for k in keys}
    for k, v in zip(keys, (str(tmp_path), 0, 0)):
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    try:
        yield tmp_path
    finally:
        for k, v in prev.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def test_warm_counts_only_its_own_compile(single_mesh, system, compile_cache):
    mat, b, x0 = system

    def warm_counters():
        return {n: _counter(n) for n in WARM}

    def handle():
        # the session-style cache keeps each handle apart: a fresh jitted
        # program, compiled once, then found in the persistent cache
        return solver_handle(single_mesh, mat, variant="hs", maxiter=7,
                             cache={})

    c0 = warm_counters()
    first = handle()
    first.warm(b, x0)
    c1 = warm_counters()
    assert c1["warm_trace_lower_seconds"] > c0["warm_trace_lower_seconds"]
    assert c1["warm_backend_compile_seconds"] > c0["warm_backend_compile_seconds"]
    assert c1["warm_cache_misses_total"] == c0["warm_cache_misses_total"] + 1
    assert c1["warm_cache_hits_total"] == c0["warm_cache_hits_total"]
    assert first.warm(b, x0) is None  # warm already: adds nothing
    first.fn.lower(b, x0).compile()  # compiled outside warm: not counted
    assert warm_counters() == c1
    handle().warm(b, x0)  # the same program, from the persistent cache
    c2 = warm_counters()
    assert c2["warm_cache_hits_total"] == c1["warm_cache_hits_total"] + 1
    assert c2["warm_cache_misses_total"] == c1["warm_cache_misses_total"]


def test_serving_metrics_out_carries_setup_counters():
    from repro.autotune.pool import SessionPool
    from repro.launch.serve_solver import ServeEngine

    a = poisson_scipy(cube(5, "7pt"))
    eng = ServeEngine(1, slots=1, tol=1e-5, maxiter=100, pool=SessionPool())
    eng.serve(a, np.ones((1, a.shape[0])))
    text = eng.metrics_prometheus()
    for name in ("setup_partition_csr_seconds", "setup_shard_matrix_seconds",
                 *WARM):
        assert re.search(rf"^{name} \S+$", text, re.M), name


def test_compile_cache_keeps_scoped_programs_apart(compile_cache, monkeypatch):
    """Two programs that differ only in a scope name get entries of their
    own, so neither loads the other's executable and its op names."""
    from repro.launch import runtime

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(compile_cache))
    assert runtime.enable_compile_cache() == str(compile_cache)

    def program(scope):
        def f(x):
            with jax.named_scope(scope):
                return x * 2.0 + 1.0
        return jax.jit(f)

    x = np.ones(8, np.float32)
    before = metrics.compile_totals()
    for scope in ("spmv", "reductions", "spmv"):
        text = program(scope).lower(x).compile().as_text()
        assert f"/{scope}/" in text
    after = metrics.compile_totals()
    assert after["cache_misses_total"] - before["cache_misses_total"] == 2
    assert after["cache_hits_total"] - before["cache_hits_total"] == 1


_CACHED = """
import json, os
import jax, numpy as np
from repro.launch import runtime
from repro.obs import metrics
assert runtime.enable_compile_cache() == os.environ["JAX_COMPILATION_CACHE_DIR"]
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
def f(x):
    with jax.named_scope("spmv"):
        return x * 2.0 + 1.0
before = metrics.compile_totals()
jax.jit(f).lower(np.ones(8, np.float32)).compile()
after = metrics.compile_totals()
print(json.dumps({k: after[k] - before[k]
                  for k in ("cache_hits_total", "cache_misses_total")}))
"""


def test_compile_cache_entry_found_by_next_process(tmp_path, monkeypatch):
    """A process finds the entry an earlier one wrote under the metadata
    key; a program traced from another source line (an edit that moves it)
    does not, and compiles anew."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))

    def run(code):
        out = run_multidevice(code, n_devices=1, timeout=300, x64=False)
        return json.loads(out.strip().splitlines()[-1])

    miss = {"cache_hits_total": 0, "cache_misses_total": 1}
    assert run(_CACHED) == miss
    assert run(_CACHED) == {"cache_hits_total": 1, "cache_misses_total": 0}
    assert run("\n" + _CACHED) == miss
