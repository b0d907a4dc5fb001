"""s-step CG at 4-32 emulated shards: matrix-powers SpMV vs per-iteration
halo exchanges (§CommAvoid, docs/solvers.md).

The communication-avoiding claim is about LAUNCHES, not volume: the
depth-s widened exchange of the matrix-powers basis moves exactly the
same total halo bytes per iteration as s depth-1 exchanges (the 1-D slab
ghost zones nest, so widening conserves volume), but pays the per-launch
collective latency 1/s as often — and replaces s all-reduces with ONE
fused Gram reduction per block. This benchmark pins that physics down
both modeled and executed, and checks the end of the pipeline (the
autotuner's ``s`` axis) never regresses the untuned default.

* **modeled** — the smoke cube is partitioned host-side at depth 1 (hs)
  and depth s (sstep) at every shard count (real ``partition_csr`` ghost
  plans), and the per-iteration *exposed* communication of each body is
  priced through the CostModel (``cg_iteration_counts`` with the
  matrix-powers pricing).
* **executed** — real ``--no-overlap`` solves through ``api.solve`` (all
  communication exposed by construction); exposed comm per iteration from
  the executed ledger, halo bytes from the traced ``halo`` region.
* **agreement** — x64 subprocess solves of the same system with hs and
  sstep, comparing the returned solutions directly.
* **autotune** — ``--autotune`` at 8 shards (where the ``s`` axis opens)
  on a fresh cache; the default config always rides along as a trial.

HARD-ASSERTS (the ISSUE 9 acceptance gate):

1. modeled: the widened depth-s exchange moves exactly ``s *`` the
   depth-1 bytes per shard (volume conservation), and sstep's
   per-iteration exposed comm is strictly below hs at >= 16 shards;
2. executed: same exposed-comm win at >= 16 shards, and the traced halo
   bytes equal the modeled plan bytes EXACTLY — total halo ici ==
   ``widened + widened / s * iters`` (one setup exchange plus the
   per-iteration average the 1/s-normalized trace records);
3. sstep solutions agree with hs to <= 1e-10 (x64, relative max-norm) on
   1 and 4 shards, for s in {2, 4};
4. the autotuner with the ``s`` axis enumerated trials at least one
   sstep candidate and its chosen config scores <= the untuned default's
   trial (the axis can only win, never lose).

The s-step basis pays for its cheaper communication with a modest
iteration penalty (the monomial basis conditions worse than the coupled
two-term recurrence; the A-norm column scaling keeps it bounded), so the
smoke-size autotuner legitimately picks hs — the gate is that the
*search* never loses, not that sstep always wins. The modeled win factors
(~2.6x exposed comm at s=2) are what pay at paper scale where the
latency term dominates strong scaling.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

from benchmarks.common import SRC, run_api_solve, write_results
from repro.api import ProblemSpec, SolverConfig

SIDE = 40  # same smoke cube as strong_scaling (2.5 z-planes at 16 shards)
MODELED_SHARDS = (4, 8, 16, 32)
SSTEP_S = (2, 4)
SMOKE_EXECUTED_SHARDS = (16,)
FULL_EXECUTED_SHARDS = (8, 16, 32)
AGREE_SIDE = 16
AGREE_TOL = 1e-10
AGREE_CASES = ((1, (2,)), (4, (2, 4)))  # (n_shards, s values)


def _exposed_iter_s(cost, counts, s: int) -> float:
    _, (_, _, t_coll) = cost.times(counts, s, overlap=False)
    return t_coll


def modeled(shard_counts=MODELED_SHARDS, side: int = SIDE):
    """Real host-side partitions at depth 1 vs depth s, priced per
    iteration. Returns (rows, {n_shards: depth-1 plan bytes per shard}).
    """
    from repro.core.partition import partition_csr
    from repro.energy.accounting import CostModel, cg_iteration_counts
    from repro.matrices import poisson

    p = poisson.cube(side, "7pt")
    a = poisson.poisson_scipy(p)
    cost = CostModel()
    rows, plan_bytes = [], {}
    for s in shard_counts:
        mat1 = partition_csr(a, s)
        b1 = mat1.plan.collective_bytes_per_shard(8)
        plan_bytes[s] = b1
        th = _exposed_iter_s(cost, cg_iteration_counts(mat1, "hs"), s)
        rows.append(
            dict(
                figure="sstep_modeled", variant="hs", s_step=1,
                n_shards=s, side=side, dofs=side**3,
                halo_bytes_iter=b1, comm_exposed_iter_s=th,
            )
        )
        for sv in SSTEP_S:
            mats = partition_csr(a, s, halo_depth=sv)
            widened = mats.plan.collective_bytes_per_shard(8)
            # volume conservation: the nested slab ghost zones widen to
            # exactly s times the depth-1 exchange — same bytes per
            # iteration, 1/s the launches
            assert widened == sv * b1, (
                f"widened exchange is not volume-conserving at {s} "
                f"shards, s={sv}: {widened} != {sv} * {b1}"
            )
            ts = _exposed_iter_s(
                cost, cg_iteration_counts(mats, "sstep", s=sv), s
            )
            rows.append(
                dict(
                    figure="sstep_modeled", variant="sstep", s_step=sv,
                    n_shards=s, side=side, dofs=side**3,
                    halo_bytes_iter=widened / sv, comm_exposed_iter_s=ts,
                    comm_win_vs_hs=th / ts,
                )
            )
            if s >= 16:
                # tentpole gate: fewer launches beat equal volume
                assert ts < th, (
                    f"modeled sstep exposed comm not below hs at {s} "
                    f"shards, s={sv}: {ts} !< {th}"
                )
    return rows, plan_bytes


def _halo_ici(sol: dict) -> float:
    regions = sol["regions"]
    return sum(
        regions[r]["ici_bytes"] for r in ("halo", "overlap") if r in regions
    )


def executed(
    plan_bytes: dict,
    shards=SMOKE_EXECUTED_SHARDS,
    side: int = SIDE,
    maxiter: int = 300,
    tol: float = 1e-8,
):
    """Real --no-overlap solves, hs vs sstep s=2, halo bytes gated exact.

    ``plan_bytes``: the modeled leg's depth-1 exchange bytes per shard at
    each shard count (the executed solves run the same cube, so the
    traced halo region must integrate to exactly ``widened + widened / s
    * iters`` — one setup exchange plus the normalized per-iteration
    average).
    """
    rows = []
    for s in shards:
        spec = ProblemSpec(problem="poisson7", side=side, shards=s)
        got = {}
        for variant, sv in (("hs", None), ("sstep", 2)):
            cfg = SolverConfig(
                variant=variant, s=sv, overlap=False, tol=tol,
                maxiter=maxiter,
            )
            _, led = run_api_solve(spec, cfg)
            sol = led["solvers"]["BCMGX-analog"]
            iters = int(sol["iters"])
            assert iters < maxiter, (
                f"{variant} leg did not converge at {s} shards"
            )
            depth = sv or 1
            if depth > 1:
                # the s knob must surface in the ledger (schema gate)
                assert led["halo_depth"] == depth, led.get("halo_depth")
                assert led["s"] == sv, led.get("s")
            else:
                assert "halo_depth" not in led and "s" not in led
            widened = depth * plan_bytes[s]
            traced = _halo_ici(sol)
            expect = widened + widened / depth * iters
            # the traced exchange must equal the plan EXACTLY — the
            # 1/s-normalized while-body counts are the model, measured
            assert traced == expect, (
                f"traced halo bytes diverge from the plan at {s} shards "
                f"({variant}, s={depth}): {traced} != {expect}"
            )
            exposed_iter = sol["totals"]["comm_exposed_s"] / iters
            got[variant] = exposed_iter
            rows.append(
                dict(
                    figure="sstep_executed", variant=variant,
                    s_step=depth, n_shards=s, side=side, iters=iters,
                    relres=sol["relres"],
                    halo_bytes_iter=widened / depth,
                    comm_exposed_s=sol["totals"]["comm_exposed_s"],
                    comm_exposed_iter_s=exposed_iter,
                    de_total=sol["totals"]["de_total"],
                    wall_s=sol["wall_s"],
                )
            )
        if s >= 16:
            assert got["sstep"] < got["hs"], (
                f"executed sstep exposed comm not below hs at {s} "
                f"shards: {got['sstep']} !< {got['hs']}"
            )
    return rows


_AGREE_SCRIPT = """
import json, sys
import numpy as np
import jax
jax.config.update("jax_enable_x64", True)
from repro.core.cg import make_solver
from repro.core.partition import pad_vector, partition_csr
from repro.core.spmv import shard_matrix, shard_vector
from repro.launch.mesh import make_solver_mesh
from repro.matrices.poisson import PoissonProblem, poisson_scipy

S = int(sys.argv[1])
svals = [int(v) for v in sys.argv[2].split(",")]
side = int(sys.argv[3])
a = poisson_scipy(PoissonProblem(side, side, side, "7pt"))
n = a.shape[0]
b = np.ones(n)
mesh = make_solver_mesh(S)


def solve(variant, s):
    kw = {"s": s} if variant == "sstep" else {}
    mat = shard_matrix(mesh, partition_csr(a, S, halo_depth=s))
    solver = make_solver(
        mesh, mat, variant=variant, tol=1e-11, maxiter=600, **kw
    )
    bp = shard_vector(mesh, pad_vector(b, mat), "shards")
    x0 = shard_vector(mesh, np.zeros_like(pad_vector(b, mat)), "shards")
    res = solver(bp, x0)
    return np.asarray(res.x)[:n], int(res.iters)


xh, iters_hs = solve("hs", 1)
out = []
for s in svals:
    xs, iters_s = solve("sstep", s)
    err = float(np.max(np.abs(xs - xh)) / np.max(np.abs(xh)))
    out.append(dict(s=s, iters_hs=iters_hs, iters_sstep=iters_s, err=err))
print(json.dumps(out))
"""


def agreement(cases=AGREE_CASES, side: int = AGREE_SIDE):
    """x64 subprocess per shard count: sstep vs hs solution max-norm."""
    rows = []
    for n_shards, svals in cases:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"  # a CPU count gate; the parent holds JAX
        env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n_shards}"
        )
        r = subprocess.run(
            [
                sys.executable, "-c", _AGREE_SCRIPT, str(n_shards),
                ",".join(str(s) for s in svals), str(side),
            ],
            capture_output=True, text=True, timeout=1800, env=env,
        )
        if r.returncode != 0:
            raise RuntimeError(
                f"agreement leg failed at {n_shards} shards:\n"
                f"{r.stdout[-2000:]}\n{r.stderr[-2000:]}"
            )
        for rec in json.loads(r.stdout.splitlines()[-1]):
            assert rec["err"] <= AGREE_TOL, (
                f"sstep diverged from hs at {n_shards} shards, "
                f"s={rec['s']}: {rec['err']} > {AGREE_TOL}"
            )
            rows.append(
                dict(
                    figure="sstep_agreement", n_shards=n_shards,
                    s_step=rec["s"], side=side,
                    iters_hs=rec["iters_hs"],
                    iters_sstep=rec["iters_sstep"],
                    agree_tol=f"{AGREE_TOL:g}", agree_ok=True,
                    agree_relerr=rec["err"],
                )
            )
    return rows


def autotuned(side: int = 12, shards: int = 8, budget: int = 6):
    """--autotune where the s axis opens: the search may only ever win."""
    import shutil

    from repro.autotune import DEFAULT

    cache_dir = tempfile.mkdtemp(prefix="sstep_autotune_")
    try:
        spec = ProblemSpec(problem="poisson7", side=side, shards=shards)
        cfg = SolverConfig(
            autotune=True, objective="energy", tune_budget=budget,
            tune_cache=os.path.join(cache_dir, "cache.json"), maxiter=200,
        )
        _, led = run_api_solve(spec, cfg)
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)
    at = led["autotune"]
    trials = at["trials"]
    sstep_trials = [t for t in trials if t.get("variant") == "sstep"]
    assert sstep_trials, (
        f"the s axis enumerated no sstep trials at {shards} shards"
    )
    assert any(t["executed"] for t in sstep_trials), (
        "no sstep candidate was actually executed by the trial stage"
    )
    default = next(
        (t for t in trials if t["label"] == DEFAULT.label), None
    )
    assert default is not None, (
        f"the untuned default {DEFAULT.label} did not ride along: "
        f"{[t['label'] for t in trials]}"
    )
    chosen_score = trials[0]["score"]  # sorted best-first
    assert chosen_score <= default["score"], (
        f"autotune with the s axis lost to the untuned default: "
        f"{at['chosen_label']} scores {chosen_score} > "
        f"{default['score']}"
    )
    best_sstep = min(sstep_trials, key=lambda t: t["score"])
    return [
        dict(
            figure="sstep_autotune", n_shards=shards, side=side,
            chosen=at["chosen_label"], chosen_score=chosen_score,
            candidates_total=at["candidates_total"],
            candidates_pruned=at["candidates_pruned"],
            candidates_trialed=at["candidates_trialed"],
            sstep_trials=len(sstep_trials),
            best_sstep=best_sstep["label"],
            best_sstep_score=best_sstep["score"],
            default_score=default["score"],
        )
    ]


def main(smoke: bool = False):
    from benchmarks.common import set_smoke

    set_smoke(smoke)
    from repro.energy.report import fmt_table

    mo, plan_bytes = modeled()
    ex = executed(
        plan_bytes,
        shards=SMOKE_EXECUTED_SHARDS if smoke else FULL_EXECUTED_SHARDS,
    )
    ag = agreement()
    au = autotuned()
    rows = mo + ex + ag + au

    print(fmt_table(
        mo,
        [("n_shards", "#GPUs"), ("variant", "variant"), ("s_step", "s"),
         ("halo_bytes_iter", "halo B/iter"),
         ("comm_exposed_iter_s", "exposed/iter (s)")],
        f"Modeled s-step exposed comm ({SIDE}^3, 7pt, no overlap)",
    ))
    print(fmt_table(
        ex,
        [("n_shards", "#GPUs"), ("variant", "variant"), ("s_step", "s"),
         ("iters", "iters"), ("halo_bytes_iter", "halo B/iter"),
         ("comm_exposed_iter_s", "exposed/iter (s)"),
         ("wall_s", "wall (s)")],
        "Executed s-step exposed comm (--no-overlap)",
    ))
    print(fmt_table(
        ag,
        [("n_shards", "#GPUs"), ("s_step", "s"), ("iters_hs", "hs iters"),
         ("iters_sstep", "sstep iters"), ("agree_relerr", "max rel err")],
        f"sstep vs hs solution agreement (x64, tol {AGREE_TOL:g})",
    ))
    a = au[0]
    print(
        f"autotune @{a['n_shards']} shards: chose {a['chosen']} "
        f"(score {a['chosen_score']:.3e}) vs default "
        f"{a['default_score']:.3e}; {a['sstep_trials']} sstep trials, "
        f"best {a['best_sstep']} at {a['best_sstep_score']:.3e}"
    )
    write_results("sstep_scaling", rows)


if __name__ == "__main__":
    main()
