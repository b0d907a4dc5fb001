"""Ginkgo-analog baseline: the *algorithmically naive* distributed path.

The paper compares BootCMatchGX against Ginkgo; the binaries are not
available here, so the comparison is reproduced as an in-framework analog
that removes exactly the design choices the paper credits for the gap:

* SpMV gathers the **full global vector** (``all_gather``) before any local
  work starts — no halo minimization, no compute/communication overlap
  (the local part depends on the gathered vector by construction);
* CG performs **three separate all-reduces** per iteration (p·Ap, r·z,
  ||r||²) — no reduction fusion.

Both paths share the exact same local ELL arithmetic, so the measured /
modeled difference isolates the communication-reduction strategies (C1+C2).
Use ``partition_csr(..., force_allgather=True)`` or
``partition_stencil(..., mode="allgather")`` to build the matching layout.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.cg import Preconditioner, SolveResult, identity_precond
from repro.core.partition import DistMat
from repro.core.spmv import (
    boundary_matvec,
    dist_specs,
    ell_matvec,
    gather_ext,
    local_block,
)
from repro.core.vectors import pdot
from repro.energy import trace
from repro.kernels import dispatch as kd


def _rec_updates(x: jax.Array, n_updates: int):
    """Unfused axpy-class updates: 3 streamed vectors each (trace-time)."""
    trace.record_op(
        "axpy_unfused",
        trace.streamed_axpy_counts(x.size, x.dtype.itemsize, n_updates),
    )


def spmv_naive_shard(mat: DistMat, x_own: jax.Array, axis: str) -> jax.Array:
    """Ginkgo-analog SpMV: gather the whole vector first, then multiply.

    Requires an allgather-mode, ELL-interior DistMat (external columns in
    padded-global layout). The local part reads its slice *from the gathered
    copy*, which serializes communication before compute — deliberately.
    """
    assert mat.plan.mode == "allgather", "naive SpMV needs allgather layout"
    R = mat.n_own_pad
    # gather_ext provides the instrumented allgather (region "halo" + counts)
    x_full = gather_ext(mat, x_own, axis)
    idx = lax.axis_index(axis)
    x_own_from_full = lax.dynamic_slice_in_dim(x_full, idx * R, R)
    y = ell_matvec(mat.data_loc, mat.col_loc, x_own_from_full)
    yb = boundary_matvec(mat.data_ext, mat.col_ext, x_full)
    return y.at[mat.bnd_rows].add(yb)


def _cg_unfused_body(mat, pre: Preconditioner, pdata, b, x0, *, tol, maxiter, axis):
    """HS PCG with 3 *separate* all-reduces per iteration (no fusion)."""
    with trace.region("spmv"):
        r = b - spmv_naive_shard(mat, x0, axis)
    with trace.region("precond"):
        z = pre.apply(pdata, r, axis)
    with trace.region("reductions"):
        rz = pdot(r, z, axis)  # separate
        rr = pdot(r, r, axis)  # separate
        bb = pdot(b, b, axis)  # separate
    tol2 = tol * tol * bb

    def cond(c):
        i, x, r, z, p, rz, rr = c
        return (i < maxiter) & (rr > tol2)

    def body(c):
        i, x, r, z, p, rz, rr = c
        with kd.ledger_section("iteration"):
            with trace.region("spmv"):
                w = spmv_naive_shard(mat, p, axis)
            with trace.region("reductions"):
                pw = pdot(p, w, axis)  # all-reduce 1
                alpha = rz / pw
                _rec_updates(x, 2)  # two unfused axpy-class updates
                x = x + alpha * p
                r = r - alpha * w
            with trace.region("precond"):
                z = pre.apply(pdata, r, axis)
            with trace.region("reductions"):
                rz_new = pdot(r, z, axis)  # all-reduce 2
                rr = pdot(r, r, axis)  # all-reduce 3
                beta = rz_new / rz
                _rec_updates(x, 1)
                p = z + beta * p
        return (i + 1, x, r, z, p, rz_new, rr)

    i0 = jnp.asarray(0, jnp.int32)
    c = lax.while_loop(cond, body, (i0, x0, r, z, z, rz, rr))
    return c[1], c[0], c[6], bb


def make_naive_solver(
    mesh,
    mat: DistMat,
    *,
    precond: Preconditioner | None = None,
    tol: float = 1e-8,
    maxiter: int = 100,
    axis: str = "shards",
):
    """Jitted Ginkgo-analog CG solver: (b, x0) -> SolveResult."""
    pre = precond or identity_precond()
    mat_specs = dist_specs(mat)

    from repro.core.cg import _default_localize

    localize = pre.localize or _default_localize

    def fn(m, pdata, b, x0):
        mb = local_block(m)
        pl = localize(pdata)
        x, iters, rr, bb = _cg_unfused_body(
            mb, pre, pl, b[0], x0[0], tol=tol, maxiter=maxiter, axis=axis
        )
        return x[None], iters, rr, bb

    mapped = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(mat_specs, pre.specs, P("shards", None), P("shards", None)),
        out_specs=(P("shards", None), P(), P(), P()),
        check_vma=False,  # loop carries are not annotated as shard-varying
    )

    @jax.jit
    def solve(b, x0):
        x, iters, rr, bb = mapped(mat, pre.data, b, x0)
        return SolveResult(x=x, iters=iters, rr=rr, bb=bb)

    return solve


def make_naive_spmv(mesh, mat: DistMat, axis: str = "shards"):
    """Jitted Ginkgo-analog distributed SpMV."""
    specs = dist_specs(mat)

    def fn(m, x):
        mb = local_block(m)
        return spmv_naive_shard(mb, x[0], axis)[None]

    mapped = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(specs, P("shards", None)),
        out_specs=P("shards", None),
    )
    return jax.jit(mapped)
