"""Sparse-solver driver: the paper's workload end-to-end.

    python -m repro.launch.solve --problem poisson7 --side 32 --shards 4 \\
        --variant fcg --devices 4
    python -m repro.launch.solve --problem g3_circuit --scale 0.01 --amg

Prints runtime + iteration counts + the full energy report, for both the
BCMGX-analog and the Ginkgo-analog paths.

Energy accounting is *executed*, not declared: the solver is compiled under
the region trace (energy/trace.py), which records the OpCounts of every
dispatched op into the component region that ran it (spmv / reductions /
halo / vcycle — plus ``overlap``, the merged interior-SpMV + in-flight-halo
phase, when the default communication-hiding schedule is on; pass
``--no-overlap`` for the serialized A/B reference). The PowerMonitor then
integrates those counts — scaled by the executed iteration count — into the
per-region energy ledger printed below the summary line and written as JSON
via ``--ledger``; ``totals.comm_exposed_s`` vs ``totals.comm_hidden_s``
quantify the hiding (schema: docs/ledger_schema.md).

``--autotune`` delegates the configuration choice (interior format, CG
variant, overlap schedule, BCSR block, DVFS frequency) to the two-stage
autotuner (``repro.autotune``, docs/autotune.md), minimizing
``--objective``; the decision lands in the ledger's ``autotune`` section
and repeat solves are served from ``runs/autotune/cache.json``.

This module is the *CLI adapter* over :mod:`repro.api`: ``parse_args``
keeps every historical flag spelling (the deprecation shim — benchmarks
and docs drive it unchanged), builds :class:`repro.api.ProblemSpec` +
:class:`repro.api.SolverConfig`, and ``main`` delegates to
:func:`repro.api.solve`, converting typed :class:`repro.api.ConfigError`
back into the historical ``SystemExit`` messages. The driver body —
partition/tune/compile through a warm ``SolverSession``, run under the
energy trace, print, write the ledger — lives in ``api.solve``; repeat
solves in one process (``--repeats``, or any caller holding the session)
reuse one compiled solver instead of re-partitioning and re-tracing.
"""

from __future__ import annotations

import argparse

from repro.launch import runtime


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--problem", default="poisson7",
                    help="poisson7 | poisson27 | <suitesparse name>")
    ap.add_argument("--side", type=int, default=24)
    ap.add_argument("--scale", type=float, default=0.01)
    ap.add_argument("--shards", type=int, default=0, help="0 = all devices")
    ap.add_argument("--variant", default="hs",
                    choices=["hs", "fcg", "pipecg", "sstep"])
    ap.add_argument("--op", default="cg", choices=["cg", "spmv"])
    ap.add_argument("--no-overlap", dest="overlap", action="store_false",
                    help="serialize the halo exchange before the SpMV (and "
                         "the pipecg all-reduce before its matvec) instead "
                         "of the default communication-hiding schedule")
    ap.add_argument("--format", dest="fmt", default="ell",
                    choices=["auto", "ell", "hyb", "bcsr"],
                    help="interior storage format of the distributed matrix "
                         "(auto = stored-bytes cost model; see "
                         "docs/formats.md)")
    ap.add_argument("--block", type=int, default=4,
                    help="BCSR tile side (br = bc)")
    ap.add_argument("--autotune", action="store_true",
                    help="pick (format x variant x overlap x block x "
                         "frequency) via the two-stage autotuner "
                         "(docs/autotune.md) instead of the flags above; "
                         "repeat solves are served from the tuning cache")
    ap.add_argument("--objective", default="energy",
                    choices=["energy", "edp", "time"],
                    help="what --autotune minimizes (docs/autotune.md)")
    ap.add_argument("--tune-budget", type=int, default=6,
                    help="max executions the trial stage may budget for "
                         "(the default config always rides along, so up to "
                         "budget+1 trial solves run; candidates differing "
                         "only in frequency share one execution)")
    ap.add_argument("--tune-cache", default=None,
                    help="tuning-cache path (default runs/autotune/cache.json)")
    ap.add_argument("--nrhs", type=int, default=1,
                    help="right-hand sides per solve; > 1 runs the batched "
                         "block-CG (core/cg.make_block_solver): the matrix "
                         "is streamed once per iteration for all RHS "
                         "columns (docs/solvers.md). Requires --op cg, "
                         "--variant hs, no AMG")
    ap.add_argument("--s", type=int, default=None,
                    help="s-step block size (requires --variant sstep; "
                         "default 2): partitions with halo_depth=s ghost "
                         "zones so the matrix-powers basis pays ONE "
                         "widened halo exchange and one fused Gram "
                         "reduction per s iterations (docs/solvers.md)")
    ap.add_argument("--grid", default=None,
                    help="RxC process grid for the 2-D partitioned CG path "
                         "(R*C must equal the shard count; 1xN reproduces "
                         "the 1-D layout exactly). Poisson problems are "
                         "pencil-reordered so the halo scales with the "
                         "pencil surface (docs/scaling.md)")
    ap.add_argument("--amg", action="store_true", help="PCG with AMG")
    ap.add_argument("--amgx-analog", action="store_true",
                    help="PCG with the plain-aggregation (AmgX-analog) AMG")
    ap.add_argument("--tol", type=float, default=1e-8)
    ap.add_argument("--maxiter", type=int, default=200)
    ap.add_argument("--devices", type=int, default=0)
    ap.add_argument("--repeats", type=int, default=1)
    ap.add_argument("--ledger", default=None,
                    help="write the executed energy/time ledger JSON here")
    ap.add_argument("--telemetry", action="store_true",
                    help="record per-iteration convergence telemetry "
                         "(residual history via host callback) into the "
                         "ledger's 'telemetry' block "
                         "(docs/observability.md)")
    ap.add_argument("--profile", default=None, metavar="TRACE_JSON",
                    help="write a Chrome trace-event JSON of the executed "
                         "legs' power timelines (open in chrome://tracing "
                         "or ui.perfetto.dev; validate with "
                         "tools/check_trace.py)")
    ap.add_argument("--log-level", default=None,
                    choices=["debug", "info", "warning", "error"],
                    help="progress-output verbosity (default info, or "
                         "$REPRO_LOG); 'debug' prefixes each line with its "
                         "source logger")
    return ap.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    runtime.force_host_devices(args.devices)
    from repro.obs import log as olog

    olog.setup(args.log_level)
    # import AFTER the device-count env var is set (api.solve imports jax)
    from repro import api

    try:
        spec = api.ProblemSpec.from_args(args)
        config = api.SolverConfig.from_args(args)
        runtime.check_devices(args.devices)
        runtime.enable_compile_cache()
        api.solve(spec, config, ledger=args.ledger, profile=args.profile)
    except api.ConfigError as e:
        # the historical argparse-era behavior: message on stderr, exit 1
        raise SystemExit(str(e)) from e


if __name__ == "__main__":
    main()
