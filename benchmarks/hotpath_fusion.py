"""Hot-path fusion accounting: fused vs unfused CG vector work (§Perf).

Three views of the same claim — routing the CG hot loop through the fused
Pallas kernel family (kernels/dispatch.py) removes roughly half the
full-vector HBM sweeps per iteration outside the SpMV:

* **measured sweeps** — trace the dispatch-routed hs/fcg solvers under the
  sweep ledger (``lax.while_loop`` traces its body exactly once, so op
  calls per trace == op calls per iteration). HARD-ASSERTS the acceptance
  bound: <= 3 full-vector sweeps/iteration outside the SpMV.
* **modeled traffic** — the roofline memory term per iteration at the
  paper's sizes (405^3/device 7pt, 260^3 27pt), fused vs unfused, ELL vs
  matrix-free SpMV (roofline/analysis.py CG_HOTPATH model).
* **executed** — real solves at a CPU-tractable size, fused dispatch body
  vs an op-by-op unfused body over the IDENTICAL matrix-free SpMV:
  convergence must match exactly; wall time on CPU is reported but not
  TPU-representative (the modeled numbers carry the perf story — see
  benchmarks/common.py).
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks.common import write_results

PAPER_CASES = [("7pt", 405, 7), ("27pt", 260, 27)]


def measured_sweeps() -> list[dict]:
    import jax

    from repro.core.stencil_solver import make_stencil_solver_fn
    from repro.kernels import dispatch as kd
    from repro.matrices.poisson import PoissonProblem

    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("shards",))
    p = PoissonProblem(8, 8, 8, "7pt")
    vec = jax.ShapeDtypeStruct((1, p.n), "float64")
    rows = []
    # pipecg's extra z recurrence buys the hidden all-reduce: bound 4, not 3
    bounds = {"hs": 3, "fcg": 3, "pipecg": 4}
    for variant, bound in bounds.items():
        with kd.record_sweeps() as led:
            solve = make_stencil_solver_fn(mesh, p, 1, variant=variant)
            solve.lower(vec, vec)
        sweeps = led.vector_sweeps("iteration")
        rows.append(dict(variant=variant, vector_sweeps_per_iter=sweeps,
                         spmv_per_iter=led.spmv_calls("iteration")))
        assert sweeps <= bound, (
            f"{variant}: {sweeps} full-vector sweeps/iter > {bound} — "
            "hot-path fusion regressed (acceptance bound)"
        )
    return rows


def modeled_table() -> list[dict]:
    from repro.roofline.analysis import (
        CG_HOTPATH,
        cg_iteration_memory_s,
        cg_vector_traffic,
    )

    rows = []
    for stencil, side, k in PAPER_CASES:
        n = side**3
        for variant in ("hs", "fcg", "pipecg"):
            for matfree in (False, True):
                row = dict(
                    stencil=stencil, variant=variant,
                    spmv="matfree" if matfree else "ell", dofs=n,
                )
                for mode in ("unfused", "fused"):
                    fused = mode == "fused"
                    row[f"{mode}_sweeps"] = CG_HOTPATH[variant][mode][1]
                    row[f"{mode}_vec_gb"] = (
                        cg_vector_traffic(n, variant=variant, fused=fused) / 1e9
                    )
                    row[f"{mode}_mem_s"] = cg_iteration_memory_s(
                        n, k, variant=variant, fused=fused, matfree=matfree
                    )
                row["mem_term_speedup"] = row["unfused_mem_s"] / row["fused_mem_s"]
                rows.append(row)
    return rows


def _unfused_hs_stencil_solver(mesh, p, n_shards, *, tol, maxiter):
    """Seed-style op-by-op hs body over the same matrix-free SpMV."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from repro.core.cg import SolveResult
    from repro.core.stencil_solver import make_matvec
    from repro.core.vectors import fused_dots, pdot

    A = make_matvec(p, n_shards, "shards", kernels="jnp")

    def body_fn(b, x0):
        r = b - A(x0)
        d0 = fused_dots([(r, r), (b, b)], "shards")
        rr, bb = d0[0], d0[1]
        tol2 = tol * tol * bb

        def cond(c):
            i, x, r, p_, rz, rr = c
            return (i < maxiter) & (rr > tol2)

        def body(c):
            i, x, r, p_, rz, rr = c
            w = A(p_)
            pw = pdot(p_, w, "shards")
            alpha = rz / pw
            x = x + alpha * p_
            r = r - alpha * w
            rz_new = pdot(r, r, "shards")
            rr = pdot(r, r, "shards")
            beta = rz_new / rz
            p_ = r + beta * p_
            return (i + 1, x, r, p_, rz_new, rr)

        i0 = jnp.asarray(0, jnp.int32)
        c = lax.while_loop(cond, body, (i0, x0, r, r, rr, rr))
        return c[1][None], c[0], c[5], bb

    mapped = jax.shard_map(
        lambda b, x0: body_fn(b[0], x0[0]),
        mesh=mesh,
        in_specs=(P("shards", None), P("shards", None)),
        out_specs=(P("shards", None), P(), P(), P()),
        check_vma=False,
    )

    @jax.jit
    def solve(b, x0):
        x, iters, rr, bb = mapped(b, x0)
        return SolveResult(x=x, iters=iters, rr=rr, bb=bb)

    return solve


def executed(side: int = 24, maxiter: int = 200) -> list[dict]:
    """Run the f64 solves in a subprocess: enabling x64 is process-global
    and must not leak into the other benchmarks (or skew the f32 traces
    already made in this process)."""
    import json
    import os
    import subprocess
    import sys

    from benchmarks.common import REPO, SRC

    env = dict(os.environ)
    env["PYTHONPATH"] = (
        REPO + os.pathsep + SRC + os.pathsep + env.get("PYTHONPATH", "")
    )
    env["JAX_ENABLE_X64"] = "1"
    env["JAX_PLATFORMS"] = "cpu"  # a CPU count gate; the parent holds JAX
    code = (
        "import json, benchmarks.hotpath_fusion as h; "
        f"print('ROWS=' + json.dumps(h._executed_body({side}, {maxiter})))"
    )
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=1800, env=env, cwd=REPO)
    if r.returncode != 0:
        raise RuntimeError(f"executed solves failed:\n{r.stdout[-2000:]}\n"
                           f"{r.stderr[-2000:]}")
    line = next(l for l in r.stdout.splitlines() if l.startswith("ROWS="))
    return json.loads(line[len("ROWS="):])


def _executed_body(side: int, maxiter: int) -> list[dict]:
    import jax
    import jax.numpy as jnp

    from repro.core.stencil_solver import make_stencil_solver_fn
    from repro.matrices.poisson import PoissonProblem

    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("shards",))
    p = PoissonProblem(side, side, side, "7pt")
    rng = np.random.default_rng(0)
    b = jnp.asarray(rng.standard_normal(p.n)).reshape(1, p.n)
    x0 = jnp.zeros_like(b)
    rows = []

    def timed(solve):
        res = solve(b, x0)  # compile + run
        jax.block_until_ready(res.x)
        t0 = time.perf_counter()
        res = solve(b, x0)
        jax.block_until_ready(res.x)
        return res, time.perf_counter() - t0

    res_u, t_u = timed(
        _unfused_hs_stencil_solver(mesh, p, 1, tol=1e-8, maxiter=maxiter)
    )
    rows.append(dict(body="hs-unfused", iters=int(res_u.iters),
                     relres=float(res_u.rel_residual), wall_s=t_u))
    for variant in ("hs", "fcg"):
        res, t = timed(make_stencil_solver_fn(
            mesh, p, 1, variant=variant, tol=1e-8, maxiter=maxiter
        ))
        rows.append(dict(body=f"{variant}-fused", iters=int(res.iters),
                         relres=float(res.rel_residual), wall_s=t))
    # identical convergence: fused hs must match the unfused reference
    hs = next(r for r in rows if r["body"] == "hs-fused")
    assert hs["iters"] == rows[0]["iters"], (hs, rows[0])
    assert abs(hs["relres"] - rows[0]["relres"]) < 1e-10 * max(rows[0]["relres"], 1e-30)
    return rows


def main(smoke: bool = False):
    from benchmarks.common import set_smoke

    set_smoke(smoke)
    from repro.energy.report import fmt_table

    sw = measured_sweeps()
    print(fmt_table(sw, [("variant", "variant"),
                         ("vector_sweeps_per_iter", "vec sweeps/iter"),
                         ("spmv_per_iter", "SpMV/iter")],
                    "Measured (traced) HBM sweeps per CG iteration"))
    mo = modeled_table()
    cols = [
        ("stencil", "stencil"), ("variant", "variant"), ("spmv", "SpMV"),
        ("unfused_sweeps", "sweeps unfused"), ("fused_sweeps", "fused"),
        ("unfused_mem_s", "mem term unfused (s)"),
        ("fused_mem_s", "fused (s)"), ("mem_term_speedup", "speedup"),
    ]
    print(fmt_table(mo, cols, "Modeled memory term per iteration (paper sizes)"))
    ex = executed(side=10 if smoke else 24, maxiter=50 if smoke else 200)
    print(fmt_table(ex, [("body", "body"), ("iters", "iters"),
                         ("relres", "relres"), ("wall_s", "wall (s)")],
                    "Executed toy-size solves (CPU wall time, not TPU-representative)"))
    write_results("hotpath_fusion", sw + mo + ex)


if __name__ == "__main__":
    main()
