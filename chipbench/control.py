"""Readings that set a cell's limit: the program's, and its control's.

    python3 chipbench/control.py --workload <name> --seeds 12 --control-seeds 3

The control is the program's own lower-precision path: the same entry with
the configuration's float64 switched to float32 (JAX's x64 off), the step
that would tempt a later change. For each seed it runs one solve of the
cell through the harness at the cell's own size, program first, then
control, in one process, and prints ``worst_true_relres`` per run as JSON
lines, then the largest program reading (the lower reading) and the
smallest control reading (the upper one). The benchmark's own runs never
run this. Exits 2 with no TPU, like ``run.py``.
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

LOWER = {"float64": "float32"}


def readings(cell, seeds, *, control: bool, devices, overrides=None,
             peaks=None):
    """``(seed, worst_true_relres, iterations per solve)`` per seed."""
    from chipbench import harness

    over = dict(overrides or {})
    if control:
        over["dtype"] = LOWER[cell.config["dtype"]]
    out = []
    for seed in seeds:
        res = harness.run_cell(cell, seed=seed, seconds=0.0, trace=False,
                               t0=time.perf_counter(), devices=devices,
                               overrides=over, peaks=peaks)
        out.append((seed, res["compared"]["worst_true_relres"]["value"],
                    res["attempted"]))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    args = ap.parse_args(argv)
    from chipbench import catalog, peaks

    cell = catalog.cell(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("chipbench: control needs the cell's TPU chips", file=sys.stderr)
        return 2
    row = peaks.for_kind(devices[0].device_kind)
    devices = devices[: cell.chips]
    seeds = [args.first_seed + i
             for i in range(max(args.seeds, args.control_seeds))]
    runs = {
        "program": readings(cell, seeds[: args.seeds], control=False,
                            devices=devices, peaks=row),
        "control": readings(cell, seeds[: args.control_seeds], control=True,
                            devices=devices, peaks=row),
    }
    for kind, rows in runs.items():
        for seed, worst, solves in rows:
            print(json.dumps({"workload": args.workload, "kind": kind,
                              "seed": seed, "worst_true_relres": worst,
                              "solves": solves}), flush=True)
    print(json.dumps({
        "workload": args.workload,
        "lower": max((w for _, w, _ in runs["program"]), default=None),
        "upper": min((w for _, w, _ in runs["control"]), default=None),
        "limit": cell.config["check"]["worst_true_relres"],
        "seconds": time.perf_counter() - T0,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
