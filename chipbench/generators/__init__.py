"""Traffic generators: each ``generators/<generator>.py`` turns a mix's
parameters (``traffic/<mix>.json``, whose ``"generator"`` key names the
module) into the window's calls.

A generator exposes ``window(entry, problem, traffic, seed, seconds)``: it
drives ``entry.solve`` from the seed, in its own calling pattern, for
``seconds`` and returns a :class:`Window`. The same seed gives the same
inputs. A mix that only changes parameters is a new data file for an
existing generator; one that needs a new pattern brings its own module.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class Window:
    answers: list  # chipbench.entries.Answer, in the order they completed
    rhss: list  # the right-hand side of each answer, float64 on the host
    seconds: float  # wall time of the window on the host clock
