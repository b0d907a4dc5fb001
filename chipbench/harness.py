"""Run one cell once: set up, measure a window, check, reduce metrics.

The cell's traffic generator (``generators/<generator>.py``) drives the
window and ends it once ``seconds`` have passed. Nothing compiles inside
it: set-up has already called the program with the window's shapes.
After the window, once the device state is freed, every answer is checked
against the plain reference (``reference.py``).
"""

from __future__ import annotations

import dataclasses
import os
import tempfile
import time

from chipbench import reference
from chipbench import trace as tracemod


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader may read."""

    problem: object  # chipbench.problems.Problem
    config: dict
    answers: list  # chipbench.entries.Answer, in window order
    spans: dict  # host seconds of set-up steps
    window_s: float
    trace: object | None  # chipbench.trace.Trace of the window
    peaks: dict  # chipbench/peaks.json entry of this device kind

    @property
    def solves(self) -> int:
        return len(self.answers)

    @property
    def iterations(self) -> int:
        return sum(a.iters for a in self.answers)

    @property
    def spmv_calls(self) -> int:
        """SpMVs the window ran: one per iteration plus the initial
        residual of each solve."""
        return self.iterations + self.solves


def _enable_compile_cache(jax):
    from repro.launch import runtime

    runtime.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(cell, *, seed: int, seconds: float, trace: bool, t0: float,
             devices, jax_start_s: float | None = None,
             overrides: dict | None = None,
             peaks: dict | None = None) -> dict:
    """One run of ``cell``; returns the result line as a dict.

    ``t0`` is the process's start on the host clock, ``devices`` the
    devices the cell uses, ``jax_start_s`` the host seconds from ``t0``
    until JAX had found them. ``overrides`` replaces configuration keys
    (the tests run tiny grids on the CPU); ``peaks`` is the device kind's
    row of ``peaks.json``."""
    import jax

    config = dict(cell.config, **(overrides or {}))
    jax.config.update("jax_enable_x64", config["dtype"] == "float64")
    _enable_compile_cache(jax)
    problem = cell.problem.build(config, cell.chips)
    entry = cell.entry.open(problem, config)
    spans = dict(entry.spans, jax_start_s=jax_start_s)
    spans["setup_s"] = time.perf_counter() - t0

    tdir = tempfile.TemporaryDirectory(prefix="chipbench-trace-") if trace else None
    try:
        if tdir is not None:
            jax.profiler.start_trace(tdir.name)
        try:
            win = cell.generator.window(entry, problem, cell.traffic, seed,
                                        seconds)
        finally:
            if tdir is not None:
                jax.profiler.stop_trace()
        memory_peak = _memory_peak(devices)
        ops = tracemod.op_names(entry.program_text()) if trace else None
        entry.close()
        del entry
        reduced = (tracemod.reduce_dir(tdir.name, len(devices), ops,
                                       host_cpu=devices[0].platform == "cpu")
                   if tdir else None)
    finally:
        if tdir is not None:
            tdir.cleanup()
    if reduced is not None:
        tracemod.check_attributed(reduced)

    answers, rhss, window_s = win.answers, win.rhss, win.seconds
    limit = float(config["check"]["worst_true_relres"])
    relres = [reference.true_relres(problem.a, ans.x, b)
              for ans, b in zip(answers, rhss)]
    worst = max(relres)
    failed = sum(1 for r in relres if not r <= limit)

    ctx = Context(problem=problem, config=config, answers=answers,
                  spans=spans, window_s=window_s, trace=reduced,
                  peaks=peaks or {})
    metrics = {}
    if trace:
        for m in cell.per_layer:
            value = m.reader.read(ctx)
            if value is not None:
                metrics[m.name] = {"value": value, "unit": m.unit}
    else:
        e2e = {"solve_s": window_s / len(answers), "setup_s": spans["setup_s"]}
        for m in cell.end_to_end:
            metrics[m.name] = {"value": e2e[m.name], "unit": m.unit}

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": failed == 0, "attempted": len(answers),
              "failed": failed, "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s_mean()
        device["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown()
    # how far the reference's residual lies above the program's own, the
    # room that the stated tolerance as a limit has to leave
    result["relres_gap"] = max(r - a.relres for r, a in zip(relres, answers))
    result["compared"] = {"worst_true_relres": {"value": worst, "limit": limit}}
    return result
