"""Shared test helpers.

NOTE: no XLA_FLAGS here — the main pytest process sees ONE device (smoke
tests / kernels). Multi-device distributed tests run in subprocesses via
``run_multidevice`` with the device-count env set only there.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def run_multidevice(code: str, n_devices: int = 8, timeout: int = 900, x64: bool = True):
    """Run a python snippet in a subprocess with N host devices; returns stdout.

    The child is pinned to the CPU: its N devices are emulated host devices,
    and a parent that already holds a TPU would make a TPU child hang."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    if x64:
        env["JAX_ENABLE_X64"] = "1"
    r = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
    )
    if r.returncode != 0:
        raise AssertionError(
            f"subprocess failed\nstdout:\n{r.stdout[-4000:]}\nstderr:\n{r.stderr[-4000:]}"
        )
    return r.stdout


@pytest.fixture(scope="session")
def single_mesh():
    import jax
    import numpy as np

    return jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("shards",))
