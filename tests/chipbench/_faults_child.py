"""Child process of the ``correct`` tests: drives the harness on the CPU
(tiny grid, the look for a chip skipped) with the timed path broken
underneath, and prints ``{workload: {case: {"correct", "worst"}}}`` as JSON.

    python _faults_child.py <side> <workload>:<case> [<workload>:<case> ...]

Run with ``JAX_PLATFORMS=cpu``. Cases: ``sound``; ``control`` (the program's float32 path, the
benchmark's control); ``unchanged`` (the solve returns its start state);
``half`` (half of the answer's rows left out); ``altered`` (one entry of
the answer altered where it is produced).
"""

import contextlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import catalog, harness  # noqa: E402


@contextlib.contextmanager
def patched(obj, name, value):
    """``obj.name`` (or ``obj[name]`` for a dict) set to ``value`` inside."""
    get, put = ((obj.__getitem__, obj.__setitem__) if isinstance(obj, dict)
                else (lambda k: getattr(obj, k),
                      lambda k, v: setattr(obj, k, v)))
    old = get(name)
    put(name, value)
    try:
        yield
    finally:
        put(name, old)


def _body_fault(change):
    """Wrap the ``hs`` loop body so its answer passes through ``change``."""
    from repro.core import cg

    real = cg._BODIES["hs"]

    def body(A, pre, pdata, b, x0, **kw):
        x, iters, rr, bb = real(A, pre, pdata, b, x0, **kw)
        return change(x, x0), iters, rr, bb

    return patched(cg._BODIES, "hs", body)


def _half(x, x0):
    n = x.shape[0]
    return jnp.where(jnp.arange(n) < n // 2, x, 0.0)


def fault(case):
    if case == "unchanged":
        return _body_fault(lambda x, x0: x0)
    if case == "half":
        return _body_fault(_half)
    if case == "altered":
        return _body_fault(lambda x, x0: x.at[0].add(1e-3))
    return contextlib.nullcontext()


def main(argv):
    side, out = int(argv[0]), {}
    for pair in argv[1:]:
        name, case = pair.split(":")
        cell = catalog.cell(name)
        devices = jax.devices()[: cell.chips]
        assert len(devices) == cell.chips, "set the host device count"
        over = {"local_grid": [side] * 3}
        if case == "control":
            over["dtype"] = "float32"
        with fault(case):
            res = harness.run_cell(cell, seed=2**31 + 17, seconds=0.0,
                                   trace=False, t0=time.perf_counter(),
                                   devices=devices, overrides=over)
        out.setdefault(name, {})[case] = {
            "correct": res["correct"],
            "worst": res["compared"]["worst_true_relres"]["value"],
        }
    print(json.dumps(out))


if __name__ == "__main__":
    main(sys.argv[1:])
