"""Shared benchmark machinery.

Two result kinds, mirroring what this container can and cannot measure:

* **modeled** — paper-scale configurations (405^3/GPU etc.) evaluated through
  the calibrated roofline cost/energy model (energy/accounting.py). Matrices
  are never materialized: the DistMat ShapeDtypeStruct builder supplies the
  exact shapes/halo plans the counts need. These are the scaling curves.
* **executed** — small-scale real runs (subprocess with N host devices)
  giving true iteration counts / convergence and wall times. Wall times on
  CPU are NOT TPU-representative; they validate correctness of the compared
  implementations, while the modeled numbers carry the performance story —
  the same separation the paper makes between time measurements and
  energy-model-derived quantities.
"""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")
OUT = os.path.join(REPO, "runs", "bench")
LEDGERS = os.path.join(REPO, "runs", "ledgers")

SHARD_COUNTS = (1, 2, 4, 8, 16, 32, 64)  # the paper's GPU counts

_SMOKE = False

# Row keys that carry measured wall-clock time (machine-dependent): they go
# to the ledger's "info" side, never the gated side. Everything else numeric
# (modeled energy/time, executed iteration counts, op counts) is
# deterministic for a given code version and is gated by CI against the
# checked-in baselines (benchmarks/baselines/*.json, 5% tolerance).
NONDETERMINISTIC_KEYS = (
    "wall_s", "setup_s", "solve_s", "relres", "agree_relerr",
)


def _is_gated(key: str) -> bool:
    return key not in NONDETERMINISTIC_KEYS and "wall" not in key


def set_smoke(on: bool):
    """Smoke-mode runs write '<name>_smoke.csv' so toy-size rows never
    overwrite the canonical full-size result ledger."""
    global _SMOKE
    _SMOKE = bool(on)


def ensure_out():
    os.makedirs(OUT, exist_ok=True)
    return OUT


def abstract_poisson_mat(side: int, stencil: str, n_shards: int, weak: bool,
                         layout: str = "ring"):
    """ShapeDtypeStruct DistMat (ELL interior) at paper scale (no allocation)."""
    from repro.core.cg import abstract_stencil_dist
    from repro.matrices.poisson import PoissonProblem

    nz = side * n_shards if weak else side
    p = PoissonProblem(side, side, nz, stencil)
    mat = abstract_stencil_dist(p, n_shards)
    if layout == "allgather":
        mat = dataclasses.replace(
            mat,
            plan=dataclasses.replace(
                mat.plan, mode="allgather", shifts=(), widths=()
            ),
        )
    return p, mat


def run_solver_subprocess(
    args: list[str], n_devices: int, timeout=1800,
    module: str = "repro.launch.solve",
) -> str:
    """Run a driver module on ``n_devices`` emulated host devices.

    The child is pinned to the CPU: these runs are count gates, and a
    parent that already holds a TPU would make a TPU child hang."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [sys.executable, "-m", module, "--devices", str(n_devices)] + args
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=timeout, env=env)
    if r.returncode != 0:
        raise RuntimeError(f"solve failed:\n{r.stdout[-2000:]}\n{r.stderr[-2000:]}")
    return r.stdout


def run_solver_with_ledger(
    args: list[str], n_devices: int, timeout=1800,
    module: str = "repro.launch.solve",
) -> tuple[str, dict]:
    """Run a driver module with ``--ledger``; returns (stdout, ledger dict).

    The ledger is the driver's executed-energy JSON (per-region counts and
    energies integrated from the region trace — see energy/trace.py).
    """
    import tempfile

    fd, path = tempfile.mkstemp(suffix=".json", prefix="solve_ledger_")
    os.close(fd)
    try:
        out = run_solver_subprocess(
            args + ["--ledger", path], n_devices, timeout=timeout,
            module=module,
        )
        with open(path) as f:
            return out, json.load(f)
    finally:
        os.unlink(path)


def run_api_solve(spec, config, n_devices=None, timeout=1800, ledger=True):
    """Run :func:`repro.api.solve` in an ``n``-device subprocess.

    The typed benchmark entry point: build a ``ProblemSpec`` + a
    ``SolverConfig`` (validated at construction — a config that exists is a
    config that runs) and get ``(stdout, ledger)`` back. A subprocess is
    unavoidable because the device count must be fixed before jax
    initializes; ``to_argv()`` is the round-trip-tested bridge onto the
    ``launch.solve`` CLI adapter (tests/test_api.py), so the flags mean
    exactly what the dataclasses say.
    """
    argv = spec.to_argv() + config.to_argv()
    n = n_devices or spec.shards or 1
    if ledger:
        return run_solver_with_ledger(argv, n, timeout=timeout)
    return run_solver_subprocess(argv, n, timeout=timeout), None


def run_serve_with_ledger(
    args: list[str], n_devices: int, timeout=1800
) -> tuple[str, dict]:
    """Run the serving engine (``launch.serve_solver``) with ``--ledger``."""
    return run_solver_with_ledger(
        args, n_devices, timeout=timeout, module="repro.launch.serve_solver"
    )


def parse_solver_output(out: str) -> dict:
    """Extract per-library lines from launch.solve output."""
    res = {}
    for line in out.splitlines():
        for lib in ("BCMGX-analog", "Ginkgo-analog", "AmgX-analog"):
            if line.startswith(lib):
                parts = dict(
                    kv.split("=") for kv in line.split() if "=" in kv
                )
                res[lib] = {
                    "iters": int(parts["iters"]),
                    "relres": float(parts["relres"]),
                    "wall_s": float(parts["wall"].rstrip("s")),
                    "modeled_s": float(parts["modeled"].rstrip("s")),
                    "de_total": float(parts["DE"].rstrip("J")),
                    "peak_w": float(parts["peak"].rstrip("W")),
                    "de_gpu": float(parts.get("DEgpu", "0J").rstrip("J")),
                    "de_cpu": float(parts.get("DEcpu", "0J").rstrip("J")),
                    "setup_s": float(parts.get("setup", "0s").rstrip("s")),
                    "solve_s": float(parts.get("solve", "0s").rstrip("s")),
                }
    return res


def write_results(name: str, rows: list[dict]):
    """Write the CSV result table AND the machine-readable JSON ledger.

    The ledger splits each row into gated fields (deterministic: modeled
    energy/time, iteration counts — numbers compared against baselines with
    a 5% tolerance, strings exactly) and info fields (measured wall times).
    CI's energy-ledger job regresses the gated side; see
    benchmarks/check_ledgers.py.
    """
    from repro.energy.report import write_csv

    ensure_out()
    if _SMOKE:
        name = f"{name}_smoke"
    path = os.path.join(OUT, f"{name}.csv")
    write_csv(path, rows)
    gate_rows = [
        {k: v for k, v in r.items() if _is_gated(k)} for r in rows
    ]
    info_rows = [
        {k: v for k, v in r.items() if not _is_gated(k)} for r in rows
    ]
    write_ledger(name, gate={"rows": gate_rows}, info={"rows": info_rows})
    return path


def ledger_path(name: str) -> str:
    return os.path.join(LEDGERS, f"{name}.json")


def write_ledger(name: str, gate: dict, info: dict | None = None) -> str:
    """Emit ``runs/ledgers/<name>[_smoke].json``.

    ``gate``: deterministic quantities CI regresses against the checked-in
    baseline (>5% drift fails the energy-ledger job). ``info``: contextual
    data (wall times, environment) that is recorded but never gated.
    """
    from repro.obs.provenance import ledger_meta

    os.makedirs(LEDGERS, exist_ok=True)
    if _SMOKE and not name.endswith("_smoke"):
        name = f"{name}_smoke"
    path = ledger_path(name)
    payload = dict(schema=1, benchmark=name, smoke=_SMOKE, gate=gate,
                   info=info or {}, meta=ledger_meta())
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
    return path
