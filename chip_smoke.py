"""Smoke run of the sparse solver on a TPU, checked against scipy.

    python chip_smoke.py                # one chip: phases a, b, c
    python chip_smoke.py --four-chips   # four chips: ring vs 2x2 grid only

One chip:

  a. the default solve, f64 with auto kernels, ``hs``, ELL, tol 1e-8: once
     through ``api.solve`` as the CLI runs it, then once at side 128
     through a ``SolverSession`` (one solve: the TPU emulates f64);
  b. the same problem in f32 through one ``SolverSession``, with the
     Pallas kernels forced on and with the jnp reference kernels: ``hs`` to
     tol 1e-5, ``pipecg`` for a fixed 60 iterations (f32 ``pipecg`` does
     not converge to 1e-5 at this size), the two answers compared;
  c. a ``ServeEngine`` answering 16 right-hand sides in two batches of 8
     (block-HS) from one warm session, f32 with the Pallas block kernels
     (f64 batches are refused on the TPU, see ``phase_c``).

Four chips: ``hs`` on 4 shards, as a 1x4 ring and as a 2x2 process grid;
every sharded matrix leaf and vector must span all four devices. The
sides of each phase are set below.

Every answer is checked on the host: the true residual ||b - Ax|| / ||b||
is recomputed in f64 with scipy. Times printed here are smoke timings
(host clock around ``block_until_ready``), not benchmark numbers. The last
line of stdout is ``{"ok": true, "device": {...}}``; any failed check, a
missing TPU, or a missing repository exits non-zero without it. Runs in
one process and starts no other.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
# The TPU emulates f64: at side 128 one f64 `hs` solve took 189 s (0.59 s
# per iteration) against 26 s in f32, on a v5e. `api.solve` runs four
# solves (two legs, each warmed and repeated), so it runs at DRIVER_SIDE
# and the single f64 solve at the full SIDE goes through the session.
SIDE = 128  # phase a, session: 2,097,152 rows, one f64 solve
DRIVER_SIDE = 64  # phase a, api.solve: 262,144 rows, 5 f64 solves
PALLAS_SIDE = 128  # phase b: 8 f32 solves
SERVE_SIDE = 64  # phase c: 262,144 rows, 2 f32 batches of 8
FOUR_SIDE = 64  # four chips: 262,144 rows on 4 shards, 8 f64 solves
MAXITER = 2000
# |iters(pallas) - iters(jnp)| allowed in f32: the two sum in other orders
ITER_SLACK = 5
# f32 pipecg cannot reach tol 1e-5 here: on a v5e at side 96 it ran 2000
# iterations to a true residual of 3.98 (a numpy f32 pipecg diverges the
# same way), so phase b runs it for a fixed 60 iterations, where f32 still
# tracks f64 (numpy: 2e-4 apart), and compares the Pallas answer with the
# jnp one
PIPECG_ITERS = 60
PIPECG_DIFF = 1e-2
# f32 true residual may exceed 10 * tol by the f32 rounding floor of the
# answer itself: FLOOR_C * eps32 * || |A| |x| || / ||b||
FLOOR_C = 8.0


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str):
    if not cond:
        raise SmokeFailure(what)


def log(phase: str, **kv):
    print(f"smoke[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def true_relres(a, x, b) -> float:
    import numpy as np

    return float(np.linalg.norm(b - a @ x) / np.linalg.norm(b))


def f32_bound(a, x, b, tol: float) -> float:
    import numpy as np

    floor = np.finfo(np.float32).eps * np.linalg.norm(abs(a) @ np.abs(x))
    return 10.0 * tol + FLOOR_C * float(floor / np.linalg.norm(b))


def peak_bytes() -> int | str:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


def timed_solves(h, bp, x0, reps: int = 1):
    """Per-solve wall seconds of a warm handle; returns (result, walls)."""
    import jax

    walls = []
    for _ in range(reps):
        t0 = time.perf_counter()
        res = h.fn(bp, x0)
        jax.block_until_ready(res.x)
        walls.append(time.perf_counter() - t0)
    return res, walls


def solve_phase(api, spec, cfg, session, *, x64: bool):
    """``api.solve`` through ``session``, then the answer from the same
    (already compiled) handle. Returns (report, handle, matrix, (b, x0) as
    sharded, x on the host, per-solve walls, api.solve's own seconds)."""
    import numpy as np

    from repro.core.partition import pad_vector, unpad_vector
    from repro.core.spmv import matrix_axis, shard_vector

    t0 = time.perf_counter()
    rep = api.solve(spec, cfg, session=session, x64=x64, verbose=False)
    first_s = time.perf_counter() - t0
    grid = cfg.grid_shape if cfg.grid_shape and cfg.grid_shape[0] > 1 else None
    part = None
    if grid is not None:
        from repro.core.partition import pencil_partition
        from repro.matrices.poisson import cube

        _, part = pencil_partition(cube(spec.side, "7pt"), grid)
    mat = session.matrix(cfg.fmt, cfg.block, grid=grid, partition=part)
    h = session.solver(mat, variant=cfg.variant, tol=cfg.tol,
                       maxiter=cfg.maxiter, overlap=cfg.overlap)
    check(h.warmed, "api.solve's compiled handle was not reused")
    mesh, axis = session.mesh_for(mat), matrix_axis(mat)
    b = np.ones(session.n)
    bp = shard_vector(mesh, pad_vector(b, mat), axis)
    x0 = shard_vector(mesh, np.zeros_like(pad_vector(b, mat)), axis)
    res, walls = timed_solves(h, bp, x0)
    x = unpad_vector(np.asarray(res.x), mat).astype(np.float64)
    return rep, h, mat, (bp, x0), x, walls, first_s


def phase_a(api):
    """The default solve, twice: ``api.solve`` as the CLI runs it (both of
    its legs, each solved twice), at DRIVER_SIDE; then one solve of the same
    configuration at SIDE through a ``SolverSession``, the layer under
    ``api.solve``."""
    import jax
    import numpy as np

    from repro.core.partition import pad_vector, unpad_vector
    from repro.core.spmv import shard_vector

    spec = api.ProblemSpec(problem="poisson7", side=DRIVER_SIDE, shards=1)
    a, _ = spec.load()
    cfg = api.SolverConfig(variant="hs", fmt="ell", tol=1e-8,
                           maxiter=MAXITER)
    sess = api.SolverSession(a, 1)
    rep, h, _, _, x, walls, first_s = solve_phase(api, spec, cfg, sess,
                                                  x64=True)
    entry = rep.solvers["BCMGX-analog"]
    rr = true_relres(a, x, np.ones(a.shape[0]))
    log("a:api.solve", n=rep.n, nnz=rep.nnz, dtype=x.dtype,
        iters=entry["iters"], true_relres=f"{rr:.3e}",
        first_call_s=f"{first_s:.3f}",
        solve_s=[f"{w:.4f}" for w in walls], peak_bytes=peak_bytes(),
        kernels=json.dumps(entry.get("kernels", {})))
    check(entry["iters"] < MAXITER, "phase a (api.solve) did not converge")
    check(rr <= 1e-7, f"phase a (api.solve) true residual {rr:.3e} > 1e-7")
    del sess, rep, h
    gc.collect()

    a, _ = api.ProblemSpec(problem="poisson7", side=SIDE).load()
    sess = api.SolverSession(a, 1)
    mat = sess.matrix("ell")
    h = sess.solver(mat, variant="hs", tol=1e-8, maxiter=MAXITER)
    b = np.ones(a.shape[0])
    args = (shard_vector(sess.mesh, pad_vector(b, mat)),
            shard_vector(sess.mesh, np.zeros_like(pad_vector(b, mat))))
    t0 = time.perf_counter()
    compiled = h.fn.lower(*args).compile()
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = compiled(*args)
    jax.block_until_ready(res.x)
    wall = time.perf_counter() - t0
    x = unpad_vector(np.asarray(res.x), mat).astype(np.float64)
    it, rr = int(res.iters), true_relres(a, x, b)
    log("a:session", n=a.shape[0], nnz=a.nnz, dtype=x.dtype, iters=it,
        true_relres=f"{rr:.3e}", compile_s=f"{compile_s:.3f}",
        solve_s=[f"{wall:.4f}"], peak_bytes=peak_bytes())
    check(it < MAXITER, "phase a (session) did not converge")
    check(rr <= 1e-7, f"phase a (session) true residual {rr:.3e} > 1e-7")


def phase_b(api):
    """f32 through one warm session: the Pallas kernels against the jnp
    reference kernels, per variant, same partition and inputs."""
    import jax
    import numpy as np

    from repro.core.cg import solver_handle
    from repro.core.partition import pad_vector, unpad_vector
    from repro.core.spmv import shard_vector
    from repro.energy import trace

    jax.config.update("jax_enable_x64", False)
    a, _ = api.ProblemSpec(problem="poisson7", side=PALLAS_SIDE).load()
    tol = 1e-5
    sess = api.SolverSession(a, 1)
    mat = sess.matrix("ell")
    b = np.ones(a.shape[0])
    args = (shard_vector(sess.mesh, pad_vector(b, mat)),
            shard_vector(sess.mesh, np.zeros_like(pad_vector(b, mat))))
    for variant, maxiter in (("hs", MAXITER), ("pipecg", PIPECG_ITERS)):
        out = {}
        for kernels in ("pallas", "jnp"):
            h = solver_handle(sess.mesh, mat, variant=variant, tol=tol,
                              maxiter=maxiter, kernels=kernels,
                              cache=sess.handles)
            t0 = time.perf_counter()
            h.warm(*args)
            first_s = time.perf_counter() - t0
            res, walls = timed_solves(h, *args)
            x = unpad_vector(np.asarray(res.x), mat).astype(np.float64)
            it, rr = int(res.iters), true_relres(a, x, b)
            bound = f32_bound(a, x, b, tol)
            n_calls = h.fn.lower(*args).compile().as_text().count(
                "tpu_custom_call"
            )
            log(f"b:{variant}:{kernels}", n=a.shape[0], nnz=a.nnz,
                dtype=str(res.x.dtype), iters=it, true_relres=f"{rr:.3e}",
                bound=f"{bound:.3e}", first_call_s=f"{first_s:.3f}",
                compile_s=f"{first_s - min(walls):.3f}",
                solve_s=[f"{w:.4f}" for w in walls], tpu_custom_call=n_calls,
                peak_bytes=peak_bytes(),
                kernels=json.dumps(trace.kernels_by_backend(h.trace)))
            if variant == "hs":
                check(it < maxiter, f"hs/{kernels}: did not converge")
                check(rr <= bound, f"hs/{kernels}: {rr:.3e} > {bound:.3e}")
            if kernels == "pallas":
                check(n_calls > 0, f"{variant}: no tpu_custom_call compiled")
            out[kernels] = (it, x)
        (it_p, x_p), (it_j, x_j) = out["pallas"], out["jnp"]
        diff = float(np.linalg.norm(x_p - x_j) / np.linalg.norm(x_j))
        log(f"b:{variant}:compare", rel_diff=f"{diff:.3e}")
        check(abs(it_p - it_j) <= ITER_SLACK,
              f"{variant}: iterations pallas {it_p} vs jnp {it_j}")
        if variant == "pipecg":
            check(diff <= PIPECG_DIFF,
                  f"pipecg: pallas and jnp answers differ by {diff:.3e}")


def phase_c(api):
    """Batched serving: 16 requests in two batches of 8 (block-HS) through
    one warm session, in f32 with the Pallas block kernels. f64 batches
    are refused on the TPU (``core/cg._refuse_on_tpu``): with the jnp
    block ops a v5e returned NaN in f32 and did not converge in f64."""
    import jax
    import numpy as np

    from repro.autotune.pool import SessionPool
    from repro.core.cg import default_rhs_block
    from repro.energy import trace
    from repro.launch.serve_solver import ServeEngine

    jax.config.update("jax_enable_x64", False)
    a, _ = api.ProblemSpec(problem="poisson7", side=SERVE_SIDE).load()
    n, tol = a.shape[0], 1e-5
    B = default_rhs_block(n, 16)
    engine = ServeEngine(1, slots=8, tol=tol, maxiter=MAXITER,
                         pool=SessionPool())
    t0 = time.perf_counter()
    results = engine.serve(a, (B[:, j] for j in range(16)))
    wall = time.perf_counter() - t0
    rrs, bounds = [], []
    for r in results:
        x, b = r.x.astype(np.float64), B[:, r.rid]
        rrs.append(true_relres(a, x, b))
        bounds.append(f32_bound(a, x, b, tol))
    (sess,) = engine.pool.sessions.values()
    kernels = {}
    for h in sess.handles.values():
        kernels.update(trace.kernels_by_backend(h.trace))
    walls = [bt["wall_s"] for bt in engine.batches]
    log("c", n=n, nnz=a.nnz, dtype=str(results[0].x.dtype),
        requests=len(results),
        batches=[bt["size"] for bt in engine.batches],
        iters=[bt["iters"] for bt in engine.batches],
        max_true_relres=f"{max(rrs):.3e}", min_bound=f"{min(bounds):.3e}",
        serve_s=f"{wall:.3f}", batch_s=[f"{w:.4f}" for w in walls],
        peak_bytes=peak_bytes(), kernels=json.dumps(kernels))
    check(len(results) == 16, f"served {len(results)} of 16 requests")
    check([bt["size"] for bt in engine.batches] == [8, 8]
          and engine.batches[0]["cold"],
          "16 requests did not run as 2 batches of 8 from one session")
    check(not engine.batches[1]["cold"]
          and engine.batches[1]["new_partitions"] == 0,
          "the second batch did not reuse the warm session")
    check(all(it < MAXITER for it in (bt["iters"] for bt in engine.batches)),
          "a batch did not converge")
    bad = [(rr, bd) for rr, bd in zip(rrs, bounds) if rr > bd]
    check(not bad, f"served true residual above its f32 bound: {bad[:2]}")
    check("pallas" in kernels, "no block op ran a Pallas kernel")


def check_spread(tree, what: str):
    """Every leaf is a NamedSharding array with one shard on each of the
    4 devices."""
    import jax

    for leaf in jax.tree.leaves(tree):
        sh = leaf.sharding
        check(isinstance(sh, jax.sharding.NamedSharding),
              f"{what}: leaf {leaf.shape} is not NamedSharding-placed")
        devs = {s.device for s in leaf.addressable_shards}
        check(len(devs) == 4 and len(sh.device_set) == 4,
              f"{what}: leaf {leaf.shape} spans {len(devs)} device(s)")
        check(all(s.data.shape[0] == leaf.shape[0] // 4
                  for s in leaf.addressable_shards),
              f"{what}: leaf {leaf.shape} is not split over the shards")


def phase_four(api):
    import numpy as np

    from repro.core.partition import pencil_partition
    from repro.matrices.poisson import cube

    spec = api.ProblemSpec(problem="poisson7", side=FOUR_SIDE, shards=4)
    a, _ = spec.load()
    b = np.ones(a.shape[0])
    xs, iters = {}, {}
    for label, grid in (("ring1x4", None), ("grid2x2", "2x2")):
        cfg = api.SolverConfig(variant="hs", fmt="ell", tol=1e-8,
                               maxiter=MAXITER, grid=grid)
        perm = None
        am = a
        if grid is not None:
            perm, _ = pencil_partition(cube(FOUR_SIDE, "7pt"), (2, 2))
            am = a[perm][:, perm].tocsr()
        sess = api.SolverSession(am, 4)
        rep, _, mat, args, x, walls, first_s = solve_phase(
            api, spec, cfg, sess, x64=True
        )
        check_spread(mat, f"{label} matrix")
        check_spread(args, f"{label} vectors")
        if perm is not None:
            xo = np.empty_like(x)
            xo[perm] = x
            x = xo
        entry = rep.solvers["BCMGX-analog"]
        rr = true_relres(a, x, b)
        xs[label], iters[label] = x, int(entry["iters"])
        log(f"four:{label}", n=rep.n, nnz=rep.nnz, shards=4,
            iters=entry["iters"], true_relres=f"{rr:.3e}",
            first_call_s=f"{first_s:.3f}",
            compile_s=f"{first_s - min(walls):.3f}",
            solve_s=[f"{w:.4f}" for w in walls], sharded="4/4 devices",
            peak_bytes=peak_bytes(),
            kernels=json.dumps(entry.get("kernels", {})))
        check(rr <= 1e-7, f"{label}: true residual {rr:.3e} > 1e-7")
        del sess, rep, mat, args
        gc.collect()
    diff = float(np.linalg.norm(xs["ring1x4"] - xs["grid2x2"])
                 / np.linalg.norm(xs["ring1x4"]))
    log("four:compare", rel_diff=f"{diff:.3e}",
        iters=f"{iters['ring1x4']}/{iters['grid2x2']}")
    check(diff <= 1e-6, f"ring and 2x2 answers differ by {diff:.3e}")
    check(abs(iters["ring1x4"] - iters["grid2x2"]) <= 2,
          "ring and 2x2 iteration counts differ")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-shard ring vs 2x2 phase")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(HERE, "src"))
    try:
        from repro import api
        from repro.launch import runtime
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is missing: {e}",
              file=sys.stderr)
        return 2

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(jax.devices()) < want:
        print(f"chip_smoke: need {want} TPU devices, found "
              f"{len(jax.devices())}", file=sys.stderr)
        return 1
    log("device", platform=dev.platform, kind=repr(dev.device_kind),
        count=len(jax.devices()), jax=jax.__version__,
        compile_cache=runtime.enable_compile_cache())

    jax.config.update("jax_enable_x64", True)
    try:
        if args.four_chips:
            phase_four(api)
        else:
            phase_a(api)
            gc.collect()
            phase_b(api)
            gc.collect()
            phase_c(api)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
