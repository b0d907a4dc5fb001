"""Entries: each ``entries/<entry>.py`` builds and drives one normal path
of the program. ``open(problem, config)`` returns an object with
``spans`` (host seconds of its set-up steps), ``solve(b) -> Answer``,
``program_text()`` (the compiled program's HLO text, for naming trace
events) and ``close()``."""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Answer:
    x: np.ndarray  # the solution on the host, float64
    iters: int  # iterations the program reports
    relres: float  # the relative residual the program reports
    transfer_s: float  # host seconds in pad + shard + unpad
