"""Matrix-free distributed stencil CG (beyond-paper optimization).

The paper's benchmarks are structured 7/27-point Poisson stencils stored in
CSR; on TPU the roofline-optimal formulation drops the matrix entirely:
y = A x becomes shift-and-add on the local (nz_loc, ny, nx) grid, and the
halo exchange shrinks to ONE boundary plane per neighbor. Per SpMV this
removes ALL matrix-value and column-index HBM traffic:

    format        matrix B/row   vector B/row   total B/row   vs matfree
    ELL 7pt       7*(8+4) = 84   ~16            ~100          ~6x
    ELL 27pt      27*(8+4)= 324  ~16            ~340          ~21x
    matrix-free   0              ~16            ~16           1x

(f32 halves the matrix-free number again.) The single-node kernel-level
version of this operator is kernels/spmv_stencil.py (Pallas, VMEM-tiled);
this module is the shard_map-distributed form used by the production-mesh
dry-run and solvers.

The local slab SpMV dispatches through ``kernels/dispatch.py``: on TPU the
VMEM-tiled ``stencil_spmv_halo`` Pallas kernel runs the whole local
operator in one call (halo planes received via ``ppermute`` feed the
kernel's prev/next boundary inputs); on CPU the jnp reference executes the
identical math, and tests force ``kernels='interpret'`` to validate the
kernel code path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.cg import (
    SolveResult,
    _BODIES,
    identity_precond,
)
from repro.energy import trace
from repro.energy.accounting import OpCounts
from repro.kernels import dispatch as kd


def make_matvec(p, n_shards: int, axis: str = "shards",
                kernels: str | None = None, overlap: bool = True):
    """Per-shard matrix-free stencil operator (inside shard_map).

    v is the local flattened slab (nz_loc * ny * nx,). Requires a uniform
    slab partition (p.nz % n_shards == 0). ``kernels`` selects the SpMV
    backend (None = auto; see kernels/dispatch.py).

    ``overlap=True`` (and nz_loc >= 2, n_shards > 1): communication-hiding
    schedule — the boundary-plane ppermutes are issued first, the full slab
    is computed with zero halos while they fly (every interior output plane
    is already final), and the two slab-edge planes are patched with the
    fused boundary kernel on arrival; all attributed to the ``"overlap"``
    energy region. Otherwise: serialized exchange-then-multiply (regions
    ``"halo"`` + caller's ``"spmv"``). The split kernels are bitwise equal
    to the single-call planes per backend; end-to-end under jit the two
    schedules agree to XLA elementwise-fusion reassociation (~1 ulp).
    """
    assert p.nz % n_shards == 0, "matrix-free path needs uniform slabs"
    nz_loc = p.nz // n_shards
    ops = kd.ops_for(kernels)
    split = overlap and n_shards > 1 and nz_loc >= 2

    fwd = tuple((j, j + 1) for j in range(n_shards - 1))
    bwd = tuple((j, j - 1) for j in range(1, n_shards))

    def _exchange(x3):
        # one boundary plane to each neighbor (trace-time counts)
        trace.record_op(
            "halo_exchange",
            OpCounts(
                ici_bytes=2.0 * p.ny * p.nx * x3.dtype.itemsize,
                n_collectives=2.0,
            ),
        )
        prev = lax.ppermute(x3[-1], axis, fwd)  # from left neighbor
        nxt = lax.ppermute(x3[0], axis, bwd)  # from right neighbor
        return prev, nxt

    def A(v: jax.Array) -> jax.Array:
        x3 = v.reshape(nz_loc, p.ny, p.nx)
        if split:
            with trace.region(trace.OVERLAP):
                prev, nxt = _exchange(x3)
                zero = jnp.zeros_like(x3[0])
                # full slab with zero halos: interior planes final, no
                # dependence on the in-flight exchange
                y = ops.stencil_matvec(
                    x3, zero, zero, stencil=p.stencil, aniso=tuple(p.aniso)
                )
                # on arrival: patch the two slab-edge planes
                yb = ops.stencil_boundary(
                    x3, prev, nxt, stencil=p.stencil, aniso=tuple(p.aniso)
                )
                y = y.at[0].set(yb[0]).at[nz_loc - 1].set(yb[1])
            return y.reshape(-1)
        if n_shards > 1:
            with trace.region("halo"):
                prev, nxt = _exchange(x3)
        else:
            prev = jnp.zeros_like(x3[0])
            nxt = jnp.zeros_like(x3[0])
        y = ops.stencil_matvec(
            x3, prev, nxt, stencil=p.stencil, aniso=tuple(p.aniso)
        )
        return y.reshape(-1)

    return A


def make_stencil_solver_fn(
    mesh,
    p,
    n_shards: int,
    *,
    variant: str = "hs",
    tol: float = 1e-8,
    maxiter: int = 100,
    s: int = 2,
    axis: str = "shards",
    kernels: str | None = None,
    overlap: bool = True,
):
    """Jitted matrix-free distributed CG: (b, x0) -> SolveResult.

    b/x0: (n_shards, R) with R = (nz/n_shards) * ny * nx. Accepts
    ShapeDtypeStructs (dry-run) or real arrays (execution). ``kernels``
    selects the hot-path backend for both the slab SpMV and the fused
    vector ops (None = auto); ``overlap`` the communication-hiding schedule
    (see :func:`make_matvec` and ``core/cg.make_solver``).
    """
    pre = identity_precond()
    body = _BODIES[variant]
    kw = dict(tol=tol, maxiter=maxiter, axis=axis)
    if variant == "sstep":
        kw["s"] = s
    else:
        kw["ops"] = kd.ops_for(kernels)
    if variant == "pipecg":
        kw["overlap"] = overlap
    A = make_matvec(p, n_shards, axis, kernels=kernels, overlap=overlap)

    def fn(b, x0):
        x, iters, rr, bb = body(A, pre, (), b[0], x0[0], **kw)
        return x[None], iters, rr, bb

    mapped = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(P("shards", None), P("shards", None)),
        out_specs=(P("shards", None), P(), P(), P()),
        check_vma=False,  # loop carries are not annotated as shard-varying
    )

    @jax.jit
    def solve(b, x0):
        x, iters, rr, bb = mapped(b, x0)
        return SolveResult(x=x, iters=iters, rr=rr, bb=bb)

    return solve
