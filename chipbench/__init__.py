"""Chip benchmark of the sparse solver: one cell per run, found by name.

``BENCHMARK.json`` at the repository root names the cells. Everything that
belongs to one configuration, traffic mix, entry or per-layer metric sits
in a file of its own under this directory, found by its name:

- ``configs/<config>.json``: the deployment (problem, sizes, solver);
- ``problems/<problem>.py``: builds the host CSR a configuration names;
- ``traffic/<mix>.json``: a mix's parameters, read by the generator its
  ``"generator"`` key names;
- ``generators/<generator>.py``: draws the right-hand sides and drives
  the window's calls;
- ``entries/<entry>.py``: builds and drives the program's normal path;
- ``metrics/<metric>.py``: one reader per per-layer metric.

``run.py`` is the command; ``harness.py`` runs one cell; ``trace.py``
reduces a profiler trace; ``reference.py`` decides ``correct``;
``peaks.json`` holds the chip peaks.
"""
