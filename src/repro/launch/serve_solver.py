"""Solve-as-a-service: a serving engine over warm ``SolverSession``s.

    python -m repro.launch.serve_solver --problem poisson7 --side 12 \\
        --shards 2 --devices 2 --requests 16 --slots 8 \\
        --ledger runs/serve.json

The paper's thesis — minimizing data movement cuts both time-to-solution
and energy — pays off most when one partitioned, format-packed, autotuned
matrix is reused across many incoming solves. This engine is that reuse
loop:

* **Sessions** — every request's matrix is fingerprinted into a
  :class:`repro.autotune.pool.SessionPool`; the warm
  :class:`repro.api.SolverSession` holds the partition(s), the autotune
  decision (``--autotune``: first request for a fingerprint tunes — or
  hits ``runs/autotune/cache.json`` — later requests are served with zero
  trials) and the compiled shard_map solver. Repeat requests therefore do
  **zero** partitions and **zero** tuning trials.
* **Slot admission** — requests queue into ``--slots`` RHS slots per
  session; a full queue flushes through the batched block-HS CG
  (``core.cg.make_block_solver``) as one width-``r`` batch: the matrix is
  streamed from HBM once per iteration for all columns. A ragged final
  batch is padded with zero RHS columns, which the deflation mask retires
  at iteration 0. ``--slots 1`` serves sequentially (the single-RHS
  comparison leg).
* **Per-request energy** — the batch's executed-energy ledger is split
  back into per-request shares via the per-column convergence iterations
  (``energy.attribution.split_block_energy``): a request pays its part of
  the setup plus its share of every iteration its column was still
  unconverged in. The shares sum to the engine total exactly.

The engine ledger (``--ledger``) records per-request rows (iters, energy,
wall latency), per-batch rows (cold/warm, new partitions, new tuning
trials), per-session counters, and throughput totals (solves/sec, p50/p99
latency, J/solve) — see docs/serving.md.

Observability (docs/observability.md): the engine keeps a
:class:`repro.obs.metrics.MetricsRegistry` — request/batch/eviction
counters, queue-depth gauge, batch-width / J-per-request / latency
histograms — snapshotted into the ledger's ``metrics`` block and written
as Prometheus text via ``--metrics-out``, followed by the process's set-up
counters (``repro.obs.metrics.REGISTRY``); ``--profile`` exports every
flushed batch's power timeline as one sequential Chrome trace.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import time
from typing import Any, Callable

from repro.launch import runtime  # stdlib-only: safe before jax
from repro.obs.log import get_logger  # stdlib-only: safe before jax

LOG = get_logger("serve")


@dataclasses.dataclass
class Request:
    """One admitted solve request (RHS vector against a session matrix)."""

    rid: int
    b: Any  # (n,) host RHS
    t_submit: float


@dataclasses.dataclass
class RequestResult:
    """One served request: solution + its slice of the batch accounting."""

    rid: int
    batch: int
    iters: int
    relres: float
    energy_j: float
    latency_s: float
    cold: bool  # True = this request paid the session's compile/tune cost
    x: Any = None  # (n,) solution (not serialized into the ledger)

    def to_ledger(self) -> dict:
        return dict(
            rid=self.rid, batch=self.batch, iters=self.iters,
            relres=self.relres, energy_j=self.energy_j,
            wall_latency_s=self.latency_s, cold=self.cold,
        )


class ServeEngine:
    """Admit solve requests, flush them through warm batched solvers.

    ``clock`` is injectable (a zero-argument callable) so the latency
    statistics are deterministic under test; defaults to
    ``time.perf_counter``. ``pool`` is injectable so engines can share
    warm sessions; defaults to a fresh :class:`SessionPool`.
    """

    def __init__(
        self,
        n_shards: int,
        *,
        slots: int = 8,
        fmt: str = "ell",
        block: int = 4,
        variant: str = "hs",
        overlap: bool = True,
        s: int | None = None,
        tol: float = 1e-8,
        maxiter: int = 200,
        autotune: bool = False,
        objective: str = "energy",
        tune_budget: int = 4,
        tune_cache: str | None = None,
        grid: tuple | None = None,
        grid_partition=None,
        pool=None,
        clock: Callable[[], float] | None = None,
        verbose: bool = False,
        collect_timelines: bool = False,
    ):
        from repro.autotune.pool import SessionPool
        from repro.obs.metrics import MetricsRegistry

        if grid is not None and autotune:
            raise ValueError(
                "--grid with --autotune is not supported: the tuner owns "
                "the layout axis (it searches grids itself at >= 8 shards)"
            )
        # 1 x N *is* the 1-D layout; normalize so the engine takes the
        # plain path (same normalization as partition_csr / api.solve)
        if grid is not None and int(grid[0]) <= 1:
            grid = None
        self.grid = (
            (int(grid[0]), int(grid[1])) if grid is not None else None
        )
        self.grid_partition = grid_partition
        self.n_shards = int(n_shards)
        self.slots = max(int(slots), 1)
        self.fmt, self.block = fmt, int(block)
        self.variant, self.overlap = variant, bool(overlap)
        if s is not None:
            from repro.api import _SSTEP_MSG, ConfigError

            if int(s) < 1:
                raise ConfigError(f"s must be >= 1: {s}")
            if variant != "sstep":
                raise ConfigError(_SSTEP_MSG)
        self.s = int(s) if s is not None else None
        self.tol, self.maxiter = float(tol), int(maxiter)
        self.autotune = bool(autotune)
        self.objective = objective
        self.tune_budget = int(tune_budget)
        self.tune_cache = tune_cache
        self.pool = pool if pool is not None else SessionPool()
        self.clock = clock if clock is not None else time.perf_counter
        self.verbose = bool(verbose)
        self.pending: dict[str, list[Request]] = {}
        # session ref per pending queue: queued requests must survive a
        # pool LRU eviction of their session, so the engine (not the pool)
        # owns the session until its queue flushes
        self._queued_sessions: dict[str, Any] = {}
        self.results: list[RequestResult] = []
        self.batches: list[dict] = []
        self._configs: dict[str, dict] = {}
        self._next_rid = 0
        # per-flush power timelines (obs.timeline), collected only when the
        # caller asked for a --profile export: building one costs a monitor
        # replay per batch
        self.collect_timelines = bool(collect_timelines)
        self.timelines: list = []
        self.metrics = MetricsRegistry()
        self._evictions_seen = 0
        self._m_requests = self.metrics.counter(
            "serve_requests_total", "solve requests admitted"
        )
        self._m_batches = self.metrics.counter(
            "serve_batches_total", "batches flushed"
        )
        self._m_cold = self.metrics.counter(
            "serve_cold_batches_total",
            "flushes that paid a compile/tune (cold) cost",
        )
        self._m_warm = self.metrics.counter(
            "serve_warm_batches_total", "flushes served fully warm"
        )
        self._m_iters = self.metrics.counter(
            "serve_iterations_total", "CG iterations executed across batches"
        )
        self._m_evict = self.metrics.counter(
            "serve_session_evictions_total", "sessions evicted by the pool LRU"
        )
        self._m_queue = self.metrics.gauge(
            "serve_queue_depth", "requests waiting across all session queues"
        )
        self._m_width = self.metrics.histogram(
            "serve_batch_width", "real (non-padding) requests per flush"
        )
        self._m_req_e = self.metrics.histogram(
            "serve_request_energy_j", "attributed dynamic energy per request"
        )
        self._m_req_lat = self.metrics.histogram(
            "serve_request_latency_s", "submit-to-solution wall latency"
        )

    # -- admission ----------------------------------------------------------

    def submit(self, a_csr, b) -> int:
        """Admit one request; flushes its session's queue when slots fill.

        Raises ``ValueError`` before admission when the RHS length does
        not match the session matrix. Returns the request id (results
        carry it)."""
        import numpy as np

        sess = self.pool.session(a_csr, self.n_shards)
        b = np.asarray(b, dtype=np.float64)
        if b.shape != (sess.n,):
            raise ValueError(
                f"RHS shape {b.shape} does not match the session matrix: "
                f"expected ({sess.n},)"
            )
        req = Request(
            rid=self._next_rid, b=b, t_submit=self.clock(),
        )
        self._next_rid += 1
        self._queued_sessions[sess.key] = sess
        q = self.pending.setdefault(sess.key, [])
        q.append(req)
        self._m_requests.inc()
        self._m_queue.set(self._queue_depth())
        if len(q) >= self.slots:
            self._flush(sess)
        return req.rid

    def _queue_depth(self) -> int:
        return sum(len(q) for q in self.pending.values())

    def drain(self):
        """Flush every partially-filled queue (ragged final batches)."""
        for key in list(self.pending):
            if self.pending[key]:
                self._flush(self._queued_sessions[key])

    def serve(self, a_csr, rhs_columns) -> list[RequestResult]:
        """Submit a request per RHS column, drain, return results by rid."""
        for b in rhs_columns:
            self.submit(a_csr, b)
        self.drain()
        return sorted(self.results, key=lambda r: r.rid)

    # -- session configuration (once per fingerprint) -----------------------

    def _session_config(self, sess) -> dict:
        """Resolve (fmt/variant/overlap/cost) for a session, tuning once.

        With ``--autotune`` the first flush for a fingerprint runs the
        two-stage autotuner at the engine's batch width (``nrhs=slots``) —
        or hits the persistent tuning cache with zero trials — and every
        later flush reuses the decision."""
        cfg = self._configs.get(sess.key)
        if cfg is not None:
            return cfg
        from repro.energy.accounting import CostModel

        cost = CostModel()
        if self.grid is not None:
            from repro.roofline.analysis import reduce_hops

            # grid collectives stage over the sub-axes (same pricing as
            # api.solve): no launch is deeper than the longer sub-axis
            cost = dataclasses.replace(
                cost,
                coll_hops=float(reduce_hops(self.n_shards, self.grid)),
            )
        fmt, block = self.fmt, self.block
        variant, overlap = self.variant, self.overlap
        sstep_s = self.s or 2  # s-step block size (used iff variant == sstep)
        tuned_label = None
        cached = None
        if self.autotune:
            tune = sess.autotune(
                objective=self.objective, budget=self.tune_budget,
                cache_path=self.tune_cache, tol=self.tol, nrhs=self.slots,
            )
            ch = tune.chosen
            fmt, block, overlap = ch.fmt, ch.block, ch.overlap
            # the batched flush path is block-HS; the variant axis only
            # matters for sequential (slots=1) serving
            variant = ch.variant if self.slots == 1 else "hs"
            if variant == "sstep":
                sstep_s = ch.s
            cost = cost.at_freq(ch.freq)
            tuned_label = ch.label
            cached = tune.cached
        cfg = dict(
            fmt=fmt, block=block, variant=variant, overlap=overlap,
            s=sstep_s, cost=cost, tuned_label=tuned_label,
            tune_cached=cached,
        )
        self._configs[sess.key] = cfg
        return cfg

    # -- flushing -----------------------------------------------------------

    def _flush(self, sess):
        import jax
        import numpy as np

        from repro.core.partition import pad_block, pad_vector, unpad_block, \
            unpad_vector
        from repro.core.spmv import matrix_axis, shard_vector
        from repro.energy import trace
        from repro.energy.attribution import split_block_energy

        reqs = self.pending.pop(sess.key, [])
        self._queued_sessions.pop(sess.key, None)
        if not reqs:
            return
        bi = len(self.batches)
        t_start = self.clock()
        p0, t0 = sess.partitions, sess.tune_trials
        cfg = self._session_config(sess)
        # a sequential sstep config solves on a halo_depth=s partition
        # (matrix-powers ghost zones); batched flushes are block-HS
        depth = cfg["s"] if (cfg["variant"] == "sstep" and
                             self.slots == 1) else 1
        mat = sess.matrix(
            cfg["fmt"], cfg["block"], grid=self.grid,
            partition=self.grid_partition, halo_depth=depth,
        )
        mesh = sess.mesh_for(mat)
        axis = matrix_axis(mat)
        r, k = self.slots, len(reqs)
        h = sess.solver(
            mat, nrhs=r, variant=cfg["variant"], tol=self.tol,
            maxiter=self.maxiter, overlap=cfg["overlap"], s=cfg["s"],
        )
        cold = not h.warmed
        led_kw = dict(
            n_shards=sess.n_shards, cost=cfg["cost"],
            overlap=cfg["overlap"], idle_s=0.01,
        )

        if r == 1:
            # sequential serving: each request is its own "batch of one"
            req = reqs[0]
            bp = shard_vector(mesh, pad_vector(req.b, mat), axis)
            x0 = shard_vector(
                mesh, np.zeros_like(pad_vector(req.b, mat)), axis
            )
            res = h.warm(bp, x0)
            if res is None:
                res = h.fn(bp, x0)
                jax.block_until_ready(res.x)
            t_done = self.clock()
            iters = int(res.iters)
            led = trace.ledger_from_trace(h.trace, iters=iters, **led_kw)
            energies = [led["totals"]["de_total"]]
            iters_out = [iters]
            rel = [float(res.rel_residual)]
            X = np.asarray(unpad_vector(np.asarray(res.x), mat))[:, None]
            batch_energy = energies[0]
            hbm_bytes = sum(
                rg["hbm_bytes"] for rg in led["regions"].values()
            )
        else:
            B = np.zeros((sess.n, r), dtype=np.float64)
            for j, req in enumerate(reqs):
                B[:, j] = req.b
            Bp = pad_block(B, mat)
            bp = shard_vector(mesh, Bp, axis)
            x0 = shard_vector(mesh, np.zeros_like(Bp), axis)
            res = h.warm(bp, x0)
            if res is None:
                res = h.fn(bp, x0)
                jax.block_until_ready(res.x)
            t_done = self.clock()
            iters = int(res.iters)
            led = trace.ledger_from_trace(h.trace, iters=iters, **led_kw)
            led0 = trace.ledger_from_trace(h.trace, iters=0, **led_kw)
            batch_energy = led["totals"]["de_total"]
            it_cols = np.asarray(res.iters_cols)
            real = np.arange(r) < k
            shares = split_block_energy(
                batch_energy, led0["totals"]["de_total"], iters, it_cols,
                real,
            )
            energies = [float(shares[j]) for j in range(k)]
            iters_out = [int(it_cols[j]) for j in range(k)]
            rel = [float(v) for v in np.asarray(res.rel_residual)[:k]]
            X = unpad_block(np.asarray(res.x), mat)
            hbm_bytes = sum(
                rg["hbm_bytes"] for rg in led["regions"].values()
            )

        for j, req in enumerate(reqs):
            self.results.append(
                RequestResult(
                    rid=req.rid, batch=bi, iters=iters_out[j], relres=rel[j],
                    energy_j=energies[j], latency_s=t_done - req.t_submit,
                    cold=cold, x=X[:, j],
                )
            )
            self._m_req_e.observe(energies[j])
            self._m_req_lat.observe(t_done - req.t_submit)
        sess.solves += k
        self._m_batches.inc()
        (self._m_cold if cold else self._m_warm).inc()
        self._m_iters.inc(iters)
        self._m_width.observe(k)
        self._m_queue.set(self._queue_depth())
        if self.collect_timelines:
            from repro.obs.timeline import build_timeline

            self.timelines.append(
                (
                    f"batch {bi}",
                    build_timeline(
                        trace.monitor_from_trace(h.trace, iters=iters, **led_kw)
                    ),
                )
            )
        self.batches.append(
            dict(
                batch=bi, size=k, slots=r, cold=cold, iters=iters,
                energy_j=batch_energy, hbm_bytes=float(hbm_bytes),
                new_partitions=sess.partitions - p0,
                new_tune_trials=sess.tune_trials - t0,
                wall_s=t_done - t_start,
            )
        )
        if self.verbose:
            b = self.batches[-1]
            LOG.info(
                "batch %d: size=%d cold=%s iters=%d DE=%.4fJ wall=%.4fs "
                "new_partitions=%d new_trials=%d",
                bi, k, cold, iters, batch_energy, b["wall_s"],
                b["new_partitions"], b["new_tune_trials"],
            )

    # -- reporting ----------------------------------------------------------

    def _sync_pool_metrics(self):
        # counters are monotonic; the pool owns the eviction count, so fold
        # in only the delta since the last snapshot
        ev = int(self.pool.stats().get("evictions", 0))
        if ev > self._evictions_seen:
            self._m_evict.inc(ev - self._evictions_seen)
            self._evictions_seen = ev

    def metrics_snapshot(self) -> dict:
        """JSON metrics snapshot (counters/gauges/histograms), pool-synced."""
        self._sync_pool_metrics()
        return self.metrics.snapshot()

    def metrics_prometheus(self) -> str:
        """Prometheus text-exposition snapshot (``--metrics-out``): the
        engine's instruments, then the process's set-up counters
        (:data:`repro.obs.metrics.REGISTRY`: partition and compile time,
        compile-cache hits and misses)."""
        from repro.obs.metrics import REGISTRY

        self._sync_pool_metrics()
        return self.metrics.to_prometheus() + REGISTRY.to_prometheus()

    def ledger(self) -> dict:
        """JSON-ready engine ledger; field reference in docs/serving.md."""
        import numpy as np

        from repro.obs.provenance import ledger_meta

        results = sorted(self.results, key=lambda r: r.rid)
        lat = np.array([r.latency_s for r in results], dtype=np.float64)
        total_e = float(sum(b["energy_j"] for b in self.batches))
        req_e = float(sum(r.energy_j for r in results))
        warm_b = [b for b in self.batches if not b["cold"]]
        cold_b = [b for b in self.batches if b["cold"]]

        def rate(batches):
            wall = sum(b["wall_s"] for b in batches)
            n = sum(b["size"] for b in batches)
            return (n / wall) if wall > 0 else 0.0

        wall_total = float(sum(b["wall_s"] for b in self.batches))
        n_req = len(results)
        totals = dict(
            energy_j=total_e,
            energy_requests_j=req_e,
            energy_per_solve_j=total_e / n_req if n_req else 0.0,
            iters=int(sum(b["iters"] for b in self.batches)),
            hbm_bytes=float(sum(b["hbm_bytes"] for b in self.batches)),
            wall_s=wall_total,
            solves_per_wall_sec=(n_req / wall_total) if wall_total else 0.0,
            warm_solves_per_wall_sec=rate(warm_b),
            cold_solves_per_wall_sec=rate(cold_b),
            wall_latency_p50_s=(
                float(np.percentile(lat, 50)) if n_req else 0.0
            ),
            wall_latency_p99_s=(
                float(np.percentile(lat, 99)) if n_req else 0.0
            ),
        )
        sessions = [
            dict(index=i, **s.stats())
            for i, s in enumerate(self.pool.sessions.values())
        ]
        engine = dict(
            slots=self.slots, shards=self.n_shards, format=self.fmt,
            block=self.block, variant=self.variant,
            overlap=self.overlap, tol=self.tol, maxiter=self.maxiter,
            autotune=self.autotune, objective=self.objective,
            tune_budget=self.tune_budget,
        )
        if self.grid is not None:  # absent on the 1-D path: ledgers stay
            engine["grid"] = [self.grid[0], self.grid[1]]  # byte-identical
        if self.s is not None:  # absent unless --s was given: same contract
            engine["s"] = self.s
        return dict(
            schema=1,
            meta=ledger_meta(),
            engine=engine,
            metrics=self.metrics_snapshot(),
            n_requests=n_req,
            n_batches=len(self.batches),
            cold_batches=len(cold_b),
            warm_batches=len(warm_b),
            requests=[r.to_ledger() for r in results],
            batches=list(self.batches),
            sessions=sessions,
            tuned=[
                dict(
                    index=i, tuned_label=c["tuned_label"],
                    tune_cached=c["tune_cached"],
                )
                for i, c in enumerate(self._configs.values())
            ],
            pool=self.pool.stats(),
            totals=totals,
        )


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--problem", default="poisson7",
                    help="poisson7 | poisson27 | <suitesparse name>")
    ap.add_argument("--side", type=int, default=12)
    ap.add_argument("--scale", type=float, default=0.01)
    ap.add_argument("--shards", type=int, default=0, help="0 = all devices")
    ap.add_argument("--devices", type=int, default=0)
    ap.add_argument("--requests", type=int, default=16,
                    help="solve requests to stream through the engine "
                         "(deterministic RHS columns: "
                         "core.cg.default_rhs_block)")
    ap.add_argument("--slots", type=int, default=8,
                    help="RHS slots per batch: a full queue flushes as one "
                         "width-r block solve; 1 = sequential serving")
    ap.add_argument("--format", dest="fmt", default="ell",
                    choices=["auto", "ell", "hyb", "bcsr"])
    ap.add_argument("--block", type=int, default=4)
    ap.add_argument("--variant", default="hs",
                    choices=["hs", "fcg", "pipecg", "sstep"],
                    help="sequential-serving variant (batched flushes are "
                         "block-HS)")
    ap.add_argument("--s", type=int, default=None,
                    help="s-step block size (requires --variant sstep; "
                         "default 2): sequential serving solves on a "
                         "halo_depth=s matrix-powers partition")
    ap.add_argument("--no-overlap", dest="overlap", action="store_false")
    ap.add_argument("--tol", type=float, default=1e-8)
    ap.add_argument("--maxiter", type=int, default=200)
    ap.add_argument("--autotune", action="store_true",
                    help="first request per fingerprint tunes at the "
                         "engine's batch width (or hits the tuning cache); "
                         "later requests are served with zero trials")
    ap.add_argument("--objective", default="energy",
                    choices=["energy", "edp", "time"])
    ap.add_argument("--tune-budget", type=int, default=4)
    ap.add_argument("--tune-cache", default=None)
    ap.add_argument("--grid", default=None,
                    help="RxC process grid for the 2-D partitioned path "
                         "(R*C must equal the shard count; 1xN is the 1-D "
                         "identity; incompatible with --autotune). Poisson "
                         "problems are pencil-reordered as in launch.solve "
                         "(docs/scaling.md)")
    ap.add_argument("--ledger", default=None,
                    help="write the engine ledger JSON here")
    ap.add_argument("--profile", default=None, metavar="TRACE_JSON",
                    help="write a Chrome trace-event JSON of every flushed "
                         "batch's power timeline, laid end-to-end (open in "
                         "chrome://tracing or ui.perfetto.dev; validate "
                         "with tools/check_trace.py)")
    ap.add_argument("--metrics-out", default=None, metavar="PROM_TXT",
                    help="write the engine metrics snapshot in Prometheus "
                         "text exposition format (docs/observability.md)")
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
    import jax

    runtime.check_devices(args.devices)
    runtime.enable_compile_cache()
    jax.config.update("jax_enable_x64", True)

    from repro.api import ProblemSpec, parse_grid, write_ledger_json
    from repro.core.cg import default_rhs_block

    spec = ProblemSpec(
        problem=args.problem, side=args.side, scale=args.scale,
        shards=args.shards,
    )
    a, name = spec.load()
    n = a.shape[0]
    n_shards = args.shards or len(jax.devices())
    grid = parse_grid(args.grid) if args.grid else None
    grid_part = None
    perm = None
    if grid is not None:
        if grid[0] * grid[1] != n_shards:
            raise SystemExit(
                f"--grid {args.grid} covers {grid[0] * grid[1]} shards; "
                f"serving with {n_shards}"
            )
        if grid[0] > 1 and args.problem.startswith("poisson"):
            # pencil reordering, exactly as api.solve (docs/scaling.md)
            from repro.core.partition import pencil_partition
            from repro.matrices import poisson as _poisson

            stencil = "7pt" if args.problem == "poisson7" else "27pt"
            perm, grid_part = pencil_partition(
                _poisson.cube(args.side, stencil), grid
            )
            a = a[perm][:, perm].tocsr()
    LOG.info(
        "serve: problem=%s n=%d nnz=%d shards=%d slots=%d requests=%d%s",
        name, n, a.nnz, n_shards, args.slots, args.requests,
        f" grid={args.grid}" if args.grid else "",
    )
    engine = ServeEngine(
        n_shards, slots=args.slots, fmt=args.fmt, block=args.block,
        variant=args.variant, overlap=args.overlap, s=args.s, tol=args.tol,
        maxiter=args.maxiter, autotune=args.autotune,
        objective=args.objective, tune_budget=args.tune_budget,
        tune_cache=args.tune_cache, grid=grid, grid_partition=grid_part,
        verbose=True, collect_timelines=bool(args.profile),
    )
    B = default_rhs_block(n, max(int(args.requests), 1))
    if perm is not None:
        # permute the RHS rows with the system so each request solves the
        # same problem as its 1-D counterpart (up to the permutation)
        B = B[perm]
    engine.serve(a, (B[:, j] for j in range(B.shape[1])))
    led = engine.ledger()
    tot = led["totals"]
    LOG.info(
        "served %d requests in %.4fs: %.2f solves/s (warm %.2f, cold %.2f) "
        "p50=%.4fs p99=%.4fs",
        led["n_requests"], tot["wall_s"], tot["solves_per_wall_sec"],
        tot["warm_solves_per_wall_sec"], tot["cold_solves_per_wall_sec"],
        tot["wall_latency_p50_s"], tot["wall_latency_p99_s"],
    )
    LOG.info(
        "energy: total=%.4fJ per-solve=%.4fJ requests-sum=%.4fJ",
        tot["energy_j"], tot["energy_per_solve_j"],
        tot["energy_requests_j"],
    )
    if args.profile and engine.timelines:
        from repro.obs.trace_export import write_chrome_trace

        write_chrome_trace(
            args.profile, engine.timelines,
            meta=dict(
                problem=name, n=n, shards=n_shards, slots=args.slots,
                requests=args.requests,
            ),
            sequential=True,
        )
        LOG.info("profile written: %s", args.profile)
    if args.metrics_out:
        d = os.path.dirname(args.metrics_out)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(args.metrics_out, "w") as f:
            f.write(engine.metrics_prometheus())
        LOG.info("metrics written: %s", args.metrics_out)
    write_ledger_json(args.ledger, led)


if __name__ == "__main__":
    main()
