"""Typed public API: problem + config dataclasses, warm solver sessions.

The driver surface of this repo used to be ``launch/solve.py``'s ~20-flag
argparse soup; every benchmark re-derived the same wiring (partition →
shard → build solver → trace → ledger) from raw flag lists. This module is
the typed replacement:

* :class:`ProblemSpec` — *what* to solve (problem/side/scale/shards);
* :class:`SolverConfig` — *how* to solve it (variant/format/overlap/nrhs/
  tolerances/AMG/autotune knobs), with :class:`ConfigError` validation
  instead of argparse deaths;
* :func:`solve` — the full driver (the body ``launch.solve:main`` used to
  inline), returning a :class:`SolveReport`;
* :class:`SolverSession` — the warm per-matrix state behind it: partition
  once, autotune-or-cache-hit once, keep every compiled shard_map solver
  alive (``core.cg.solver_handle``). Repeat solves against the same matrix
  skip repartition and re-trace entirely — this is what
  ``launch/serve_solver.py`` serves requests from;
* :data:`SESSIONS` — the process-wide fingerprint-keyed session pool
  (:class:`repro.autotune.pool.SessionPool`).

``launch.solve`` remains a thin CLI adapter over this module (flag
spellings and ledger output unchanged — the deprecation shim contract,
tested in ``tests/test_api.py``).

Import order note: this module must not import jax at module scope — the
CLI adapters set ``XLA_FLAGS`` (device count) before the first jax import.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Any

from repro.obs.log import get_logger

LOG = get_logger("api")

VARIANTS = ("hs", "fcg", "pipecg", "sstep")
OPS = ("cg", "spmv")
FORMATS = ("auto", "ell", "hyb", "bcsr")
OBJECTIVES = ("energy", "edp", "time")


class ConfigError(ValueError):
    """A :class:`SolverConfig` combination that cannot run.

    Raised at dataclass construction time (typed, catchable) instead of an
    argparse ``SystemExit`` deep inside the driver. The CLI adapter
    (``launch.solve``) converts it to the historical ``SystemExit`` text.
    """


@dataclasses.dataclass(frozen=True)
class ProblemSpec:
    """What to solve: the matrix source and its partitioning width.

    ``problem`` is ``poisson7`` / ``poisson27`` (side³ cube stencils) or a
    SuiteSparse name (``scale`` subsamples it — see
    ``matrices/suitesparse.py``). ``shards == 0`` means "all visible
    devices" (resolved at :func:`solve` time, not here).
    """

    problem: str = "poisson7"
    side: int = 24
    scale: float = 0.01
    shards: int = 0

    @classmethod
    def from_args(cls, args) -> "ProblemSpec":
        """Build from a ``launch.solve``-style argparse namespace."""
        return cls(
            problem=str(args.problem), side=int(args.side),
            scale=float(args.scale), shards=int(args.shards),
        )

    def to_argv(self) -> list[str]:
        """The equivalent ``launch.solve`` CLI flags (round-trip tested)."""
        return [
            "--problem", self.problem, "--side", str(self.side),
            "--scale", str(self.scale), "--shards", str(self.shards),
        ]

    def load(self):
        """Materialize the host matrix: ``(scipy CSR, display name)``."""
        from repro.matrices import poisson
        from repro.matrices.suitesparse import load_or_generate

        if self.problem.startswith("poisson"):
            stencil = "7pt" if self.problem == "poisson7" else "27pt"
            p = poisson.cube(self.side, stencil)
            return poisson.poisson_scipy(p), f"{stencil}-{self.side}^3"
        return load_or_generate(self.problem, scale=self.scale), self.problem


# the historical launch.solve validation messages, byte-for-byte — the CLI
# shim re-raises ConfigError as SystemExit(str(e)), so these strings ARE
# the CLI contract (tests/test_api.py pins them)
_NRHS_MSG = (
    "--nrhs > 1 runs the batched block-HS CG: requires --op cg, "
    "--variant hs, and no --amg/--amgx-analog"
)
_AUTOTUNE_MSG = (
    "--autotune tunes the unpreconditioned CG path "
    "(--op cg without --amg/--amgx-analog)"
)
_GRID_MSG = (
    "--grid RxC runs the 2-D partitioned CG path: requires --op cg and "
    "no --amg/--amgx-analog/--autotune"
)
_SSTEP_MSG = (
    "--s sets the s-step block size: requires --variant sstep"
)


def parse_grid(text: str) -> tuple[int, int]:
    """``"RxC"`` -> ``(R, C)`` with positive integers (ConfigError on junk)."""
    parts = str(text).lower().split("x")
    try:
        r, c = (int(p) for p in parts)
    except ValueError:
        raise ConfigError(
            f"grid must look like RxC (e.g. 4x4): {text!r}"
        ) from None
    if r < 1 or c < 1:
        raise ConfigError(f"grid dimensions must be >= 1: {text!r}")
    return r, c


@dataclasses.dataclass(frozen=True)
class SolverConfig:
    """How to solve: every knob of the distributed solver stack.

    Invalid combinations raise :class:`ConfigError` at construction
    (``__post_init__`` → :meth:`validate`), so a config that exists is a
    config that runs.
    """

    op: str = "cg"
    variant: str = "hs"
    fmt: str = "ell"
    block: int = 4
    overlap: bool = True
    nrhs: int = 1
    tol: float = 1e-8
    maxiter: int = 200
    amg: bool = False
    amgx_analog: bool = False
    autotune: bool = False
    objective: str = "energy"
    tune_budget: int = 6
    tune_cache: str | None = None
    repeats: int = 1
    grid: str | None = None  # "RxC" process grid; None = 1-D row layout
    # s-step block size (variant == "sstep" only). None = the solver
    # default (s=2); setting it partitions with halo_depth=s ghost zones
    # so the matrix-powers basis pays one widened exchange per block.
    s: int | None = None
    # per-iteration convergence telemetry (repro.obs.convergence): bakes a
    # host callback into the compiled loop body and records the residual
    # history into the ledger's "telemetry" block. Off by default — the
    # callback changes the compiled program, so it is part of the
    # solver-handle cache key.
    telemetry: bool = False

    def __post_init__(self):
        self.validate()

    @property
    def grid_shape(self) -> tuple[int, int] | None:
        """``(rows, cols)`` of the requested process grid, or ``None``."""
        return parse_grid(self.grid) if self.grid else None

    def validate(self):
        if self.op not in OPS:
            raise ConfigError(f"op must be one of {OPS}: {self.op!r}")
        if self.variant not in VARIANTS:
            raise ConfigError(
                f"variant must be one of {VARIANTS}: {self.variant!r}"
            )
        if self.fmt not in FORMATS:
            raise ConfigError(
                f"format must be one of {FORMATS}: {self.fmt!r}"
            )
        if self.objective not in OBJECTIVES:
            raise ConfigError(
                f"objective must be one of {OBJECTIVES}: {self.objective!r}"
            )
        if self.block < 1:
            raise ConfigError(f"block must be >= 1: {self.block}")
        if self.nrhs < 1:
            raise ConfigError(f"nrhs must be >= 1: {self.nrhs}")
        if self.repeats < 1:
            raise ConfigError(f"repeats must be >= 1: {self.repeats}")
        if self.maxiter < 1:
            raise ConfigError(f"maxiter must be >= 1: {self.maxiter}")
        if not self.tol > 0.0:
            raise ConfigError(f"tol must be > 0: {self.tol}")
        if self.tune_budget < 1:
            raise ConfigError(
                f"tune-budget must be >= 1: {self.tune_budget}"
            )
        if self.s is not None:
            if self.s < 1:
                raise ConfigError(f"s must be >= 1: {self.s}")
            if self.variant != "sstep":
                raise ConfigError(_SSTEP_MSG)
        if self.nrhs > 1 and (
            self.op != "cg" or self.amg or self.amgx_analog
            or self.variant != "hs"
        ):
            raise ConfigError(_NRHS_MSG)
        if self.autotune and (
            self.op != "cg" or self.amg or self.amgx_analog
        ):
            raise ConfigError(_AUTOTUNE_MSG)
        if self.grid:
            parse_grid(self.grid)  # shape errors surface at construction
            if (
                self.op != "cg" or self.amg or self.amgx_analog
                or self.autotune
            ):
                raise ConfigError(_GRID_MSG)

    @classmethod
    def from_args(cls, args) -> "SolverConfig":
        """Build from a ``launch.solve``-style argparse namespace.

        Preserves the historical ``--nrhs 0`` clamp-to-1 behavior."""
        return cls(
            op=str(args.op), variant=str(args.variant), fmt=str(args.fmt),
            block=int(args.block), overlap=bool(args.overlap),
            nrhs=max(int(args.nrhs), 1), tol=float(args.tol),
            maxiter=int(args.maxiter), amg=bool(args.amg),
            amgx_analog=bool(args.amgx_analog),
            autotune=bool(args.autotune), objective=str(args.objective),
            tune_budget=int(args.tune_budget), tune_cache=args.tune_cache,
            repeats=int(args.repeats),
            grid=getattr(args, "grid", None),
            s=(
                int(args.s)
                if getattr(args, "s", None) is not None else None
            ),
            telemetry=bool(getattr(args, "telemetry", False)),
        )

    def to_argv(self) -> list[str]:
        """The equivalent ``launch.solve`` CLI flags (round-trip tested)."""
        argv = [
            "--op", self.op, "--variant", self.variant,
            "--format", self.fmt, "--block", str(self.block),
            "--nrhs", str(self.nrhs), "--tol", str(self.tol),
            "--maxiter", str(self.maxiter),
            "--repeats", str(self.repeats),
            "--objective", self.objective,
            "--tune-budget", str(self.tune_budget),
        ]
        if not self.overlap:
            argv.append("--no-overlap")
        if self.amg:
            argv.append("--amg")
        if self.amgx_analog:
            argv.append("--amgx-analog")
        if self.autotune:
            argv.append("--autotune")
        if self.tune_cache:
            argv += ["--tune-cache", self.tune_cache]
        if self.grid:
            argv += ["--grid", self.grid]
        if self.s is not None:
            argv += ["--s", str(self.s)]
        if self.telemetry:
            argv.append("--telemetry")
        return argv


@dataclasses.dataclass(frozen=True)
class SolveReport:
    """What one :func:`solve` produced: identity, summary, full ledger.

    ``summary`` holds one compact dict per executed leg (label →
    iters/relres/wall/modeled/energy); ``ledger`` is the complete JSON
    payload ``--ledger`` writes (docs/ledger_schema.md)."""

    problem: str
    n: int
    nnz: int
    shards: int
    config: SolverConfig
    summary: dict
    ledger: dict

    @property
    def solvers(self) -> dict:
        return self.ledger["solvers"]


class SolverSession:
    """Warm per-matrix solver state: the unit the serving engine keeps.

    One session owns one host CSR matrix pinned to one shard count, and
    accumulates everything expensive derived from it:

    * ``mats`` — ``(fmt, block) -> sharded DistMat`` partitions (the
      all-gather Ginkgo-analog partition lives under ``("allgather", 0)``);
    * the autotune decision (the PR 5 fingerprint cache is the cross-
      process warm path; this is the in-process one);
    * compiled solver handles (``core.cg.solver_handle``), each carrying
      the energy trace captured at first warmup.

    ``partitions`` / ``tune_trials`` / ``solves`` count the *work actually
    performed* through this session — the serving tests assert a warm
    session serves repeat requests with zero new partitions and zero new
    tuning trials.
    """

    def __init__(self, a_csr, n_shards: int, *, mesh=None, key=None):
        from repro.launch.mesh import make_solver_mesh

        self.a = a_csr.tocsr()
        self.n = int(self.a.shape[0])
        self.n_shards = int(n_shards)
        self.mesh = mesh if mesh is not None else make_solver_mesh(
            self.n_shards
        )
        self.key = key
        self.mats: dict[tuple, Any] = {}
        # (rows, cols) -> 2-D jax Mesh over the same devices, built lazily
        self.grid_meshes: dict[tuple, Any] = {}
        # session-owned solver handles (core.cg.solver_handle cache=):
        # dropping the session frees its compiled executables with it,
        # instead of pinning them in the process-global handle LRU
        self.handles: dict[tuple, Any] = {}
        self.tune = None  # last TuneResult routed through this session
        self.partitions = 0
        self.tune_trials = 0
        self.solves = 0

    # -- partitions ---------------------------------------------------------

    def grid_mesh(self, grid):
        """The 2-D ``(rows, cols)`` mesh over this session's devices."""
        from repro.launch.mesh import make_grid_mesh

        g = (int(grid[0]), int(grid[1]))
        if g not in self.grid_meshes:
            self.grid_meshes[g] = make_grid_mesh(*g)
        return self.grid_meshes[g]

    def mesh_for(self, mat):
        """The mesh ``mat`` runs on: its grid mesh for a GridPlan matrix,
        else the session's 1-D ``shards`` mesh."""
        if getattr(mat.plan, "mode", None) == "grid":
            return self.grid_mesh(mat.plan.grid)
        return self.mesh

    def matrix(self, fmt: str = "ell", block: int = 4, *, grid=None,
               partition=None, halo_depth: int = 1):
        """The sharded DistMat for (fmt, block[, grid]); partitions on
        first use. ``grid=(R, C)`` plans per-dimension halos and shards
        onto the matching 2-D mesh (1-D keys stay 2-tuples, so pre-grid
        callers and the autotune trial cache share unchanged keys);
        ``partition`` optionally fixes the row blocks (e.g. the
        ``pencil_partition`` layout of a permuted Poisson system);
        ``halo_depth > 1`` builds the s-step ghost zones under a
        depth-tagged key — the same key shape the autotune trial stage
        uses, so a tuned sstep winner's partition is reused here.

        A new partition adds its host seconds to the
        ``setup_partition_csr_seconds`` and ``setup_shard_matrix_seconds``
        counters of :data:`repro.obs.metrics.REGISTRY`."""
        import jax

        from repro.core.partition import partition_csr
        from repro.core.spmv import shard_matrix
        from repro.obs.metrics import REGISTRY

        if grid is not None:
            grid = (int(grid[0]), int(grid[1]))
            k = (fmt, int(block), grid)
        else:
            k = (fmt, int(block))
        depth = max(int(halo_depth), 1)
        if depth > 1:
            k = k + (("halo", depth),)
        if k not in self.mats:
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("repro.partition_csr"):
                mat = partition_csr(
                    self.a, self.n_shards, fmt=fmt, block=(block, block),
                    grid=grid, partition=partition, halo_depth=depth,
                )
            t1 = time.perf_counter()
            with jax.profiler.TraceAnnotation("repro.shard_matrix"):
                self.mats[k] = shard_matrix(self.mesh_for(mat), mat)
            t2 = time.perf_counter()
            REGISTRY.counter("setup_partition_csr_seconds").inc(t1 - t0)
            # the upload may still be in flight when shard_matrix returns
            REGISTRY.counter("setup_shard_matrix_seconds").inc(t2 - t1)
            self.partitions += 1
        return self.mats[k]

    def naive_matrix(self):
        """The padded-global (all-gather) partition of the naive baseline."""
        from repro.core.partition import partition_csr
        from repro.core.spmv import shard_matrix

        k = ("allgather", 0)
        if k not in self.mats:
            self.mats[k] = shard_matrix(
                self.mesh,
                partition_csr(self.a, self.n_shards, force_allgather=True),
            )
            self.partitions += 1
        return self.mats[k]

    # -- tuning -------------------------------------------------------------

    def autotune(self, *, objective: str = "energy", budget: int = 6,
                 cache_path: str | None = None, tol: float = 1e-8,
                 nrhs: int = 1):
        """Run (or cache-hit) the two-stage autotuner through this session.

        Trial partitions land in ``self.mats`` so the winning format is
        reused by the final solve; executed trials and new partitions are
        charged to the session counters."""
        from repro.autotune import DEFAULT_PATH
        from repro.autotune import autotune as run_autotune

        before = len(self.mats)
        tune = run_autotune(
            self.a, self.mesh, self.n_shards, objective=objective,
            budget=budget, cache_path=cache_path or DEFAULT_PATH, tol=tol,
            mats=self.mats, nrhs=nrhs,
        )
        self.partitions += len(self.mats) - before
        self.tune_trials += tune.candidates_trialed
        self.tune = tune
        return tune

    # -- compiled solvers ---------------------------------------------------

    def solver(self, mat, *, op: str = "cg", nrhs: int = 1,
               variant: str = "hs", precond=None, tol: float = 1e-8,
               maxiter: int = 100, overlap: bool = True, s: int = 2,
               telemetry: bool = False):
        """Cached :class:`~repro.core.cg.SolverHandle` for (mat, config).

        Handles live in the session's own cache (``self.handles``), so
        their compiled executables are released with the session (e.g. on
        :class:`~repro.autotune.pool.SessionPool` LRU eviction). A
        GridPlan matrix is routed onto its 2-D mesh with the
        ``("rows", "cols")`` collective axes automatically."""
        from repro.core.cg import solver_handle
        from repro.core.spmv import matrix_axis

        axis = matrix_axis(mat)
        return solver_handle(
            self.mesh_for(mat), mat, op=op, nrhs=nrhs, variant=variant,
            precond=precond, tol=tol, maxiter=maxiter, overlap=overlap,
            axis=axis, s=s, telemetry=telemetry, cache=self.handles,
        )

    def close(self):
        """Release everything expensive: partitions + compiled handles.

        Called on pool eviction; the session object stays usable but the
        next solve through it pays the cold path again."""
        self.mats.clear()
        self.handles.clear()
        self.tune = None

    def stats(self) -> dict:
        """JSON-ready counters (the serving ledger's ``sessions`` rows)."""
        return dict(
            n=self.n, shards=self.n_shards, partitions=self.partitions,
            tune_trials=self.tune_trials, solves=self.solves,
            mats=len(self.mats),
        )


def _session_pool():
    from repro.autotune.pool import SessionPool

    return SessionPool(factory=SolverSession)


#: Process-wide session pool: ``solve()`` calls against the same matrix
#: fingerprint + shard count share one warm :class:`SolverSession`.
SESSIONS = None


def default_pool():
    """The lazily-created process-wide session pool."""
    global SESSIONS
    if SESSIONS is None:
        SESSIONS = _session_pool()
    return SESSIONS


def _print_regions(label: str, ledger: dict):
    for name, r in sorted(ledger["regions"].items()):
        LOG.info(
            "  [%s] region %-12s t=%.4es DE=%.4fJ flops=%.3e hbm=%.3eB "
            "ici=%.3eB",
            label, name, r["time_s"], r["de_j"], r["flops"],
            r["hbm_bytes"], r["ici_bytes"],
        )


def _plan_dim_bytes(plan) -> tuple[float, float]:
    """Per-shard halo bytes per exchange, split by grid dimension.

    GridPlan: the per-dimension widths (a corner buffer crosses both links,
    so it counts in both entries and the two sum to the hop-weighted
    collective total). 1-D plans: all traffic rides the single flat axis —
    the ``cols`` axis of the equivalent ``1 x N`` grid."""
    if getattr(plan, "mode", None) == "grid":
        rows_b, cols_b = plan.dim_bytes_per_shard(8)
        return float(rows_b), float(cols_b)
    return 0.0, float(plan.collective_bytes_per_shard(8))


def _write_profile(path: str | None, timelines, payload: dict, log):
    """Write the Chrome-trace profile of the executed legs (``--profile``)."""
    if not path or not timelines:
        return
    from repro.obs.trace_export import write_chrome_trace

    write_chrome_trace(
        path, timelines,
        meta=dict(
            problem=payload.get("problem"), n=payload.get("n"),
            shards=payload.get("shards"), op=payload.get("op"),
        ),
    )
    log(f"profile written: {path}")


def write_ledger_json(path: str | None, payload: dict):
    """Atomically write a ledger JSON (a reader never sees a half-write)."""
    if not path:
        return
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
    os.replace(tmp, path)
    LOG.info("ledger written: %s", path)


def solve(
    spec: ProblemSpec,
    config: SolverConfig | None = None,
    *,
    ledger: str | None = None,
    profile: str | None = None,
    session: SolverSession | None = None,
    pool=None,
    x64: bool = True,
    verbose: bool = True,
) -> SolveReport:
    """The full solver driver: the body ``launch.solve:main`` used to be.

    Loads (or reuses) the problem, partitions/tunes/compiles through a
    warm :class:`SolverSession` (``session``, else one from ``pool``, else
    the process-wide :data:`SESSIONS` pool — repeat calls for the same
    matrix skip repartition and re-compile), runs the requested legs under
    the energy trace, prints the historical driver report (``verbose``),
    optionally writes the ledger JSON, and returns a :class:`SolveReport`.

    ``profile`` writes a Chrome trace-event JSON of every executed leg's
    power timeline (repro.obs.trace_export; load in chrome://tracing or
    Perfetto — docs/observability.md). With ``config.telemetry`` the
    BCMGX-analog leg additionally records its per-iteration residual
    history into the ledger's ``telemetry`` block.

    ``x64=False`` leaves the caller's JAX precision untouched (in-process
    tests run f32); the CLI always enables x64.
    """
    config = config or SolverConfig()
    config.validate()

    import contextlib

    import jax

    if x64:
        jax.config.update("jax_enable_x64", True)
    import numpy as np

    from repro.core.partition import pad_block, pad_vector
    from repro.core.spmv import shard_vector
    from repro.energy import trace
    from repro.energy.accounting import CostModel
    from repro.obs.provenance import ledger_meta
    from repro.obs.timeline import build_timeline

    def log(msg):
        if verbose:
            LOG.info("%s", msg)

    a, name = spec.load()
    n = a.shape[0]
    n_shards = spec.shards or len(jax.devices())
    b = np.ones(n)
    grid_cfg = config.grid_shape
    grid = None
    grid_part = None
    if grid_cfg is not None:
        if grid_cfg[0] * grid_cfg[1] != n_shards:
            raise ConfigError(
                f"--grid {config.grid} covers "
                f"{grid_cfg[0] * grid_cfg[1]} shards; running with "
                f"{n_shards}"
            )
        if grid_cfg[0] > 1:  # 1 x N *is* the 1-D layout; build it identically
            grid = grid_cfg
    if grid is not None and spec.problem.startswith("poisson"):
        # Pencil reordering: solve the symmetrically permuted system (same
        # spectrum, CG iterates identical up to the permutation) so each
        # shard owns a z x y pencil and the halo scales with its surface,
        # not the full slab cross-section.
        from repro.core.partition import pencil_partition
        from repro.matrices import poisson as _poisson

        stencil = "7pt" if spec.problem == "poisson7" else "27pt"
        perm, grid_part = pencil_partition(
            _poisson.cube(spec.side, stencil), grid
        )
        a = a[perm][:, perm].tocsr()
        b = b[perm]
    if session is None:
        if pool is None:
            pool = default_pool()
        session = pool.session(a, n_shards)
    mesh = session.mesh
    nrhs = config.nrhs
    log(f"problem={name} n={n} nnz={a.nnz} shards={n_shards} nrhs={nrhs}")

    cost = CostModel()
    tune = None
    fmt, block = config.fmt, config.block
    variant, overlap = config.variant, config.overlap
    sstep_s = config.s or 2  # s-step block size (used iff variant == sstep)
    if config.autotune:
        tune = session.autotune(
            objective=config.objective, budget=config.tune_budget,
            cache_path=config.tune_cache, tol=config.tol, nrhs=nrhs,
        )
        ch = tune.chosen
        fmt, block = ch.fmt, ch.block
        variant, overlap = ch.variant, ch.overlap
        if ch.variant == "sstep":
            sstep_s = ch.s
        grid = ch.grid  # --grid and --autotune are mutually exclusive
        cost = cost.at_freq(ch.freq)
        log(
            f"autotune: objective={tune.objective} chosen={ch.label} "
            f"cached={tune.cached} trialed={tune.candidates_trialed} "
            f"(space {tune.candidates_total})"
        )

    if grid is not None:
        from repro.roofline.analysis import reduce_hops

        # grid collectives stage over the sub-axes: no launch is deeper
        # than the longer one (the extra stage launches are in the trace)
        cost = dataclasses.replace(
            cost, coll_hops=float(reduce_hops(n_shards, grid))
        )

    payload = dict(
        schema=1, problem=name, n=int(n), nnz=int(a.nnz),
        shards=int(n_shards), op=config.op, overlap=bool(overlap),
        format=fmt, nrhs=nrhs, solvers={}, meta=ledger_meta(),
    )
    timelines = []  # (label, Timeline) per executed leg when profiling
    if tune is not None:
        payload["autotune"] = tune.ledger_section()

    precond = None
    amg_info = None
    setup_time = 0.0
    if config.amg or config.amgx_analog:
        from repro.core.amg import make_amg_preconditioner

        t0 = time.perf_counter()
        precond, amg_info = make_amg_preconditioner(
            a, n_shards, amgx_analog=config.amgx_analog
        )
        setup_time = time.perf_counter() - t0
        log(
            f"AMG: {amg_info.n_levels} levels rows={amg_info.level_rows} "
            f"opcx={amg_info.operator_complexity:.2f} setup={setup_time:.4f}s"
        )
        payload["amg"] = dict(
            n_levels=amg_info.n_levels,
            level_rows=list(amg_info.level_rows),
            level_nnz=list(amg_info.level_nnz),
            operator_complexity=amg_info.operator_complexity,
        )

    # the session's partition cache already holds the autotune trials'
    # formats — the winner (and any repeat solve) reuses them; an s-step
    # solve partitions with halo_depth=s so the matrix-powers basis pays
    # one widened exchange per s-iteration block
    depth = sstep_s if (variant == "sstep" and config.op == "cg") else 1
    mat = session.matrix(
        fmt, block, grid=grid, partition=grid_part, halo_depth=depth
    )
    # The Ginkgo-analog baseline keeps the flat ELL layout by definition;
    # only build its (expensive) padded-global partition when a naive leg
    # will actually run — the format sweep (--format != ell), the AMG
    # comparisons, the 2-D grid path (its comparison leg is the 1-D run of
    # the same problem), and the tuned path (whose comparison legs are the
    # autotune trials themselves) never consume it.
    need_naive = (
        mat.fmt == "ell"  # resolved format: --format auto may pick ELL
        if config.op == "spmv"
        # the naive baseline is single-RHS by definition: the batched
        # path's comparison legs are sequential nrhs=1 runs of this driver
        # (benchmarks/multirhs_scaling.py)
        else not (
            config.amg or config.amgx_analog or config.autotune or nrhs > 1
            or grid is not None
        )
    )
    matg = session.naive_matrix() if need_naive else None
    log(
        f"format={mat.fmt} (requested {fmt}) "
        f"interior_bytes={mat.interior_stored_bytes()} "
        f"stored_bytes={mat.stored_bytes()}"
    )
    payload["resolved_format"] = mat.fmt
    payload["interior_stored_bytes"] = int(mat.interior_stored_bytes())
    payload["stored_bytes"] = int(mat.stored_bytes())
    if depth > 1:
        # s-step run: record the ghost-zone depth actually built (allgather
        # fallbacks report 1 — the matrix-powers path did not engage)
        payload["halo_depth"] = int(mat.halo_depth)
        payload["s"] = int(sstep_s)
    if grid is not None or grid_cfg is not None:
        from repro.core.spmv import matrix_axis

        g = grid or grid_cfg
        rows_b, cols_b = _plan_dim_bytes(mat.plan)
        payload["grid"] = [int(g[0]), int(g[1])]
        payload["halo_bytes_rows"] = float(rows_b)
        payload["halo_bytes_cols"] = float(cols_b)
        mesh = session.mesh_for(mat)
        vec_axis = matrix_axis(mat)
    else:
        vec_axis = "shards"

    if nrhs > 1:
        from repro.core.cg import default_rhs_block

        Bpad = pad_block(default_rhs_block(n, nrhs), mat)
        bp = shard_vector(mesh, Bpad, vec_axis)
        x0 = shard_vector(mesh, np.zeros_like(Bpad), vec_axis)
    else:
        bp = shard_vector(mesh, pad_vector(b, mat), vec_axis)
        x0 = shard_vector(mesh, np.zeros_like(pad_vector(b, mat)), vec_axis)

    if config.op == "spmv":
        legs = [
            ("BCMGX-analog", mat,
             session.solver(mat, op="spmv", overlap=overlap)),
        ]
        if need_naive:
            legs.append(
                ("Ginkgo-analog", matg,
                 session.solver(matg, op="spmv", variant="naive"))
            )
        for label, m, h in legs:
            h.warm(m, bp)  # compile: executed counts recorded
            tr = h.trace
            fn = h.fn
            t0 = time.perf_counter()
            for _ in range(100):
                # sync every launch: keeps exactly one execution in flight,
                # so the per-run collective rendezvous can't interleave with
                # the next launch's (XLA CPU spin-waits; on a starved host
                # two in-flight ppermute rounds can livelock each other)
                jax.block_until_ready(fn(m, bp))
            wall = (time.perf_counter() - t0) / 100
            leg_overlap = overlap and label == "BCMGX-analog"
            led = trace.ledger_from_trace(
                tr, iters=0, n_shards=n_shards, cost=cost,
                overlap=leg_overlap, idle_s=0.01, setup_repeats=100,
            )
            if profile:
                timelines.append((label, build_timeline(
                    trace.monitor_from_trace(
                        tr, iters=0, n_shards=n_shards, cost=cost,
                        overlap=leg_overlap, idle_s=0.01, setup_repeats=100,
                    )
                )))
            e = led["totals"]
            t_model = sum(r["time_s"] for r in led["regions"].values())
            log(
                f"{label:14s} iters=100 relres=0.0e+00 "
                f"wall={wall:.6f}s modeled={t_model/100:.4e}s "
                f"DE={e['de_total']:.4f}J peak={e['gpu_power_peak']:.0f}W "
                f"DEgpu={e['de_gpu']:.4f}J DEcpu={e['de_cpu']:.4f}J"
            )
            if verbose:
                _print_regions(label, led)
            payload["solvers"][label] = dict(
                led, wall_s=wall, modeled_s=t_model / 100
            )
        _write_profile(profile, timelines, payload, log)
        write_ledger_json(ledger, payload)
        summary = {
            label: dict(
                wall_s=entry["wall_s"], modeled_s=entry["modeled_s"],
                de_total=entry["totals"]["de_total"],
            )
            for label, entry in payload["solvers"].items()
        }
        return SolveReport(
            problem=name, n=int(n), nnz=int(a.nnz), shards=int(n_shards),
            config=config, summary=summary, ledger=payload,
        )

    h = session.solver(
        mat, nrhs=nrhs, variant=variant, precond=precond,
        tol=config.tol, maxiter=config.maxiter, overlap=overlap,
        s=sstep_s, telemetry=config.telemetry,
    )
    legs = [
        ("BCMGX-analog" if not config.amgx_analog else "AmgX-analog", h)
    ]
    if need_naive:  # paper compares PCG against AmgX, not Ginkgo
        legs.append(
            ("Ginkgo-analog",
             session.solver(matg, variant="naive", tol=config.tol,
                            maxiter=config.maxiter))
        )
    bcmgx_label = legs[0][0]
    summary = {}
    for label, hdl in legs:
        rec = None
        with contextlib.ExitStack() as stack:
            if config.telemetry and label == bcmgx_label:
                from repro.obs import convergence

                # collect the baked-in per-iteration callbacks; the last
                # recorded run (= the final repeat) becomes the history
                rec = stack.enter_context(convergence.record())
            res = hdl.warm(bp, x0)  # warmup/compile: counts recorded
            tr = hdl.trace
            fn = hdl.fn
            walls = []
            for _ in range(config.repeats):
                t0 = time.perf_counter()
                res = fn(bp, x0)
                jax.block_until_ready(res.x)
                walls.append(time.perf_counter() - t0)
            if rec is not None:
                # debug callbacks run on a side thread; drain them before
                # the recorder closes
                jax.effects_barrier()
        wall = sum(walls) / len(walls)
        iters = int(res.iters)
        # the batched leg converges each column independently: report the
        # slowest column's residual (convergence of the whole batch)
        relres = float(np.max(np.asarray(res.rel_residual)))
        # energy ledger: executed per-region counts x executed iterations
        led = trace.ledger_from_trace(
            tr, iters=iters, n_shards=n_shards, cost=cost,
            overlap=(overlap and label != "Ginkgo-analog"), idle_s=0.01,
        )
        if profile:
            timelines.append((label, build_timeline(
                trace.monitor_from_trace(
                    tr, iters=iters, n_shards=n_shards, cost=cost,
                    overlap=(overlap and label != "Ginkgo-analog"),
                    idle_s=0.01,
                )
            )))
        e = led["totals"]
        t_model = sum(r["time_s"] for r in led["regions"].values())
        matrix_bytes = sum(
            r.get("hbm_matrix_bytes", 0.0) for r in led["regions"].values()
        )
        log(
            f"{label:14s} iters={iters} relres={relres:.2e} "
            f"wall={wall:.4f}s modeled={t_model:.4e}s "
            f"DE={e['de_total']:.4f}J peak={e['gpu_power_peak']:.0f}W "
            f"DEgpu={e['de_gpu']:.4f}J DEcpu={e['de_cpu']:.4f}J "
            f"setup={setup_time:.4f}s solve={wall:.4f}s"
        )
        if verbose:
            _print_regions(label, led)
        entry = dict(
            led, wall_s=wall, modeled_s=t_model,
            relres=relres, setup_s=setup_time,
            variant=variant if label == bcmgx_label else "naive",
            # per-solve amortization view: a batched run is nrhs solves
            nrhs=nrhs,
            per_solve_modeled_s=t_model / nrhs,
            per_solve_de_j=e["de_total"] / nrhs,
            per_solve_spmv_matrix_bytes=matrix_bytes / nrhs,
            wall_repeats_s=walls,
            per_solve_wall_s=wall / nrhs,
        )
        if nrhs > 1:
            entry["iters_cols"] = [
                int(v) for v in np.asarray(res.iters_cols)
            ]
        if tr.kernels:
            entry["kernels"] = trace.kernels_by_backend(tr)
        if rec is not None:
            entry["telemetry"] = rec.ledger()
        payload["solvers"][label] = entry
        summary[label] = dict(
            iters=iters, relres=relres, wall_s=wall, modeled_s=t_model,
            de_total=e["de_total"],
        )
        if label == bcmgx_label:
            session.solves += nrhs * config.repeats
    _write_profile(profile, timelines, payload, log)
    write_ledger_json(ledger, payload)
    return SolveReport(
        problem=name, n=int(n), nnz=int(a.nnz), shards=int(n_shards),
        config=config, summary=summary, ledger=payload,
    )
