"""Entry ``session_cg``: ``api.SolverSession`` driven as its users drive it.

Set-up: ``SolverSession(a, chips)``, ``.matrix()`` (``partition_csr`` then
``shard_matrix``), ``.solver()`` (``core/cg.solver_handle``), and one call
with ``b = 0``, which compiles (or loads from the cache) the very program
the window runs; its CG loop exits before the first iteration. Each solve:
``shard_vector(pad_vector(b))``, the handle, ``unpad_vector`` to the host.
What the configuration does not fix (format, overlap, block) comes from
``api.SolverConfig``'s defaults.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from chipbench.entries import Answer


class SessionCG:
    def __init__(self, problem, config: dict):
        from repro import api
        from repro.core import partition, spmv

        self._pad, self._unpad = partition.pad_vector, partition.unpad_vector
        self._shard = spmv.shard_vector
        defaults = api.SolverConfig()
        self.spans = {}
        t0 = time.perf_counter()
        self.session = api.SolverSession(problem.a, problem.chips)
        self.mat = self.session.matrix(defaults.fmt, defaults.block)
        self.spans["partition_s"] = time.perf_counter() - t0
        self.mesh = self.session.mesh_for(self.mat)
        self.handle = self.session.solver(
            self.mat, variant=config["variant"], tol=float(config["tol"]),
            maxiter=int(config["maxiter"]), overlap=defaults.overlap,
        )
        t0 = time.perf_counter()
        self.x0 = self._put(np.zeros(problem.n))
        self.handle.warm(self.x0, self.x0)
        self.spans["compile_s"] = time.perf_counter() - t0

    def _put(self, v: np.ndarray):
        return self._shard(self.mesh, self._pad(v, self.mat))

    def solve(self, b: np.ndarray) -> Answer:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("chipbench.put"):
            bp = self._put(b)
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("chipbench.solve"):
            res = self.handle(bp, self.x0)
            jax.block_until_ready(res)
        t2 = time.perf_counter()
        with jax.profiler.TraceAnnotation("chipbench.get"):
            x = self._unpad(np.asarray(res.x), self.mat)
            iters = int(res.iters)
            # from the reported squares on the host: ``res.rel_residual``
            # would dispatch (and the first time compile) a device op
            relres = float(np.sqrt(np.asarray(res.rr) / np.asarray(res.bb)))
        t3 = time.perf_counter()
        return Answer(x=np.asarray(x, np.float64), iters=iters, relres=relres,
                      transfer_s=(t1 - t0) + (t3 - t2))

    def program_text(self) -> str:
        """The compiled solve's HLO text, from the compile cache: it names
        the JAX op behind each instruction of the device trace."""
        return self.handle.fn.lower(self.x0, self.x0).compile().as_text()

    def close(self):
        self.session.close()
        del self.handle, self.mat, self.x0, self.session


def open(problem, config: dict) -> SessionCG:
    return SessionCG(problem, config)
