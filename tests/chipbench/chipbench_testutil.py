"""Helpers of the chipbench tests: paths and CPU child processes."""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def cpu_env(devices: int = 1, cache_dir: str | None = None) -> dict:
    """Environment of a CPU child with ``devices`` host devices, on one
    compute thread: the suite's other workers share the cores."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices}"
                        " --xla_cpu_multi_thread_eigen=false"
                        " intra_op_parallelism_threads=1")
    if cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    return env


def fault_cases(workloads, side: int, cases, cache_dir: str) -> dict:
    """``{workload: {case: {"correct", "worst"}}}`` from one
    ``_faults_child.py`` process."""
    pairs = [f"{w}:{c}" for w in workloads for c in cases]
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "_faults_child.py"), str(side),
         *pairs],
        capture_output=True, text=True, timeout=600,
        env=cpu_env(1, cache_dir),
    )
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])
