"""Run one benchmark cell once on the chips of this machine.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

(``python3 -m chipbench.run`` from the repository root works alike.)
Loads and warms up (``setup_s``), solves back to back for ``--seconds``,
checks every answer against the float64 reference, and prints the result
as one JSON line, the last line of standard output. ``--trace 0`` reports
the cell's end-to-end metrics, ``--trace 1`` its per-layer metrics from a
profiler trace of the window. Exits 2, printing no result, when JAX finds
no TPU or fewer chips than the cell asks for, or when the program under
test (``src/repro``) is missing; it never falls back to the CPU.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def _fail(msg: str) -> int:
    print(f"chipbench: {msg}", file=sys.stderr)
    return 2


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def report(result: dict):
    """The compared numbers as the last lines of stderr, then the result
    as the last line of stdout."""
    for name, c in result["compared"].items():
        print(f"chipbench: compared {name}={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        import repro  # noqa: F401  the program under test
    except ImportError as e:
        return _fail(f"the program under test is missing: {e}")
    from chipbench import catalog, harness, peaks

    try:
        cell = catalog.cell(args.workload)
    except catalog.CatalogError as e:
        return _fail(str(e))
    import jax

    devices = jax.devices()
    jax_start_s = time.perf_counter() - T0
    if devices[0].platform != "tpu":
        return _fail(f"no TPU found (JAX platform {devices[0].platform!r})")
    if len(devices) < cell.chips:
        return _fail(f"{args.workload} needs {cell.chips} chips, found "
                     f"{len(devices)}")
    try:
        row = peaks.for_kind(devices[0].device_kind)
    except KeyError as e:
        return _fail(str(e))
    result = harness.run_cell(
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        t0=T0, devices=devices[: cell.chips], jax_start_s=jax_start_s,
        peaks=row,
    )
    report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
