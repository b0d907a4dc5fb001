"""The program's own counters, read from ``repro.obs.metrics.REGISTRY``
in the benchmark's process after the window."""

from __future__ import annotations


def value(name: str) -> float | None:
    """The counter ``name``; None where the program has no registry or
    has not made that counter."""
    try:
        from repro.obs.metrics import REGISTRY
    except ImportError:
        return None
    got = REGISTRY.snapshot().get(name)
    return None if got is None else float(got["value"])
