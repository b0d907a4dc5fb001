"""One caller in a closed loop: each right-hand side is sent when the
previous answer is back on the host, and the window ends with the first
solve that finishes after ``seconds``.

Solve ``i`` of a run with seed ``s`` gets its vector from ``(s, i)``.
Parameters: ``"rhs"``, the distribution of its entries; ``normal``,
standard normal, is the one kind.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from chipbench.generators import Window

_KINDS = {"normal": lambda rng, n: rng.standard_normal(n)}


def draw(traffic: dict, problem, seed: int, i: int) -> np.ndarray:
    kind = _KINDS[traffic["rhs"]]
    return kind(np.random.default_rng([int(seed) % 2**63, int(i)]), problem.n)


def window(entry, problem, traffic: dict, seed: int, seconds: float) -> Window:
    answers, rhss = [], []
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("chipbench.window"):
        while True:
            with jax.profiler.TraceAnnotation("chipbench.rhs"):
                b = draw(traffic, problem, seed, len(answers))
            answers.append(entry.solve(b))
            rhss.append(b)
            if time.perf_counter() - t0 >= seconds:
                break
    return Window(answers, rhss, time.perf_counter() - t0)
