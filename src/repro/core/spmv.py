"""Device-side distributed SpMV (shard_map interior) + halo exchange.

The functions in this module run *inside* ``shard_map`` over a 1-D ``shards``
mesh axis: every argument is the local block (leading shard axis already
squeezed), collectives are explicit (``lax.ppermute`` / ``lax.all_gather`` /
``lax.psum``).

Key design point reproduced from the paper: each shard's rows are split at
partition time into an **interior block** (entries with locally-owned
columns) and a compact **boundary block** (the ghost-touching rows' external
entries only — see ``DistMat``). ``spmv_shard`` issues the halo ``ppermute``
first, multiplies the interior block while the exchange is in flight, and
scatter-adds the boundary block on arrival — the JAX analog of overlapping
CUDA kernels with MPI progress. The whole overlapped phase is attributed to
the ``"overlap"`` energy region (energy/trace.py), whose modeled time is
``max(compute, memory, collective)`` — i.e. halo communication hidden behind
the interior matvec; ``overlap=False`` restores the serialized
gather-then-multiply order (regions ``"spmv"`` + ``"halo"``, communication
fully exposed).
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.partition import (
    BCSRBlock,
    DistMat,
    ELLBlock,
    HaloPlan,
    HYBBlock,
    InteriorBlock,
)
from repro.energy import trace
from repro.energy.accounting import OpCounts


# ---------------------------------------------------------------------------
# Interior matvec primitives (local, per storage format)
# ---------------------------------------------------------------------------


def _nrhs(x: jax.Array) -> int:
    """RHS count of a vector (n,) or column block (n, r) operand."""
    return 1 if x.ndim == 1 else x.shape[1]


def ell_matvec(data: jax.Array, col: jax.Array, x: jax.Array) -> jax.Array:
    """y[r] = sum_k data[r,k] * x[col[r,k]].  Padding (data=0,col=0) is free.

    ``x`` may be an (n, r) column block: the SpMM form reuses the gathered
    ``x[col]`` tiles against the SAME streamed matrix pass, so matrix bytes
    are paid once while vector bytes scale with ``r`` — recorded as such.
    """
    # Executed-counts entry (trace-time only): matrix values + 4B indices
    # streamed once, source vector(s) read once, result(s) written once.
    b = data.dtype.itemsize
    r = _nrhs(x)
    mat_bytes = float(data.size * (b + col.dtype.itemsize))
    trace.record_op(
        "ell_matvec" if r == 1 else "ell_spmm",
        OpCounts(
            flops=2.0 * data.size * r,
            hbm_bytes=mat_bytes
            + float(x.shape[0] + data.shape[0]) * r * b,
            hbm_matrix_bytes=mat_bytes,
        ),
    )
    if x.ndim == 2:
        return jnp.einsum("rk,rkc->rc", data, x[col])
    return jnp.einsum("rk,rk->r", data, x[col])


def hyb_matvec(block: HYBBlock, x: jax.Array) -> jax.Array:
    """HYB interior matvec: ELL-prefix einsum + COO-tail scatter-add.

    ``block`` is the *local* (shard-axis-squeezed) HYBBlock. Tail padding
    (data 0, col 0, row 0) scatter-adds exact zeros. Accounted with the
    bytes this layout actually moves: ``k_typ`` slots/row with one 4 B
    index each, plus value + (col, row) index pairs for the tail — the
    stored-bytes saving vs ELL shows up directly in the SpMV region of the
    executed-energy ledger.
    """
    data, col = block.data, block.col
    b = data.dtype.itemsize
    r = _nrhs(x)
    mat_bytes = float(
        data.size * (b + col.dtype.itemsize)
        + block.tail_data.size * (b + 2 * block.tail_col.dtype.itemsize)
    )
    trace.record_op(
        "hyb_matvec" if r == 1 else "hyb_spmm",
        OpCounts(
            flops=2.0 * (data.size + block.tail_data.size) * r,
            hbm_bytes=mat_bytes
            + float(x.shape[0] + data.shape[0]) * r * b,
            hbm_matrix_bytes=mat_bytes,
        ),
    )
    if x.ndim == 2:
        y = jnp.einsum("rk,rkc->rc", data, x[col])
        tail = block.tail_data[:, None] * x[block.tail_col]
    else:
        y = jnp.einsum("rk,rk->r", data, x[col])
        tail = block.tail_data * x[block.tail_col]
    return y.at[block.tail_row].add(tail)


def interior_matvec(interior: InteriorBlock, x_own: jax.Array) -> jax.Array:
    """y_own = A_interior @ x_own for the local (squeezed) interior block.

    Dispatches on the storage format: ELL/HYB run their dense-gather jnp
    forms here; BCSR routes through the kernel-dispatch op ``bcsr_spmv``
    (kernels/dispatch.py) so the Pallas block kernel runs inside shard_map
    on the pallas/interpret backends. All formats return the same (R,)
    vector within fp tolerance.
    """
    if isinstance(interior, ELLBlock):
        return ell_matvec(interior.data, interior.col, x_own)
    if isinstance(interior, HYBBlock):
        return hyb_matvec(interior, x_own)
    if isinstance(interior, BCSRBlock):
        from repro.kernels import dispatch as kd

        op = kd.ops_for(None)
        fn = op.bcsr_spmm if x_own.ndim == 2 else op.bcsr_spmv
        return fn(
            interior.blocks,
            interior.bcol,
            x_own,
            n_brows=interior.n_brows,
            bpr=interior.bpr,
            n_out=x_own.shape[0],
        )
    raise TypeError(f"unknown interior block type {type(interior).__name__}")


def boundary_matvec(
    data_bnd: jax.Array,
    col_bnd: jax.Array,
    x_ext: jax.Array,
    *,
    src_elems: int | None = None,
) -> jax.Array:
    """Compact boundary-block matvec: ``yb[j] = sum_k data[j,k]*x_ext[col[j,k]]``.

    ``data_bnd/col_bnd`` are the (B, k_ext) ghost-entry rows of the shard
    (``DistMat.data_ext``); the caller scatter-adds ``yb`` into the interior
    result at ``bnd_rows``. Padded slots carry zero data, so their adds are
    exact zeros.

    ``src_elems`` is the number of distinct gatherable source elements the
    block can touch (units: elements of ``x_ext``) — the halo length for the
    ring layouts, where ``col_bnd`` indexes only the received buffers. The
    default bounds it by the entry count: a (B, k_ext) gather reads at most
    ``B*k_ext`` elements, NOT the whole ``x_ext`` stream — charging the full
    gathered vector would inflate the boundary block's memory time (and with
    it the comm-hiding credit of the overlap region).
    """
    b = data_bnd.dtype.itemsize
    B = data_bnd.shape[0]
    r = _nrhs(x_ext)
    if src_elems is None:
        src_elems = min(x_ext.shape[0], data_bnd.size)
    # entries + 4B indices streamed once, the touched source elements read
    # once, and the scatter-add's read-modify-write of the B result rows.
    mat_bytes = float(data_bnd.size * (b + col_bnd.dtype.itemsize))
    trace.record_op(
        "bnd_matvec" if r == 1 else "bnd_spmm",
        OpCounts(
            flops=2.0 * data_bnd.size * r,
            hbm_bytes=mat_bytes
            + float(
                min(int(src_elems), data_bnd.size) * r * b
                + B * (2 * b * r + 4)
            ),
            hbm_matrix_bytes=mat_bytes,
        ),
    )
    if x_ext.ndim == 2:
        return jnp.einsum("bk,bkc->bc", data_bnd, x_ext[col_bnd])
    return jnp.einsum("bk,bk->b", data_bnd, x_ext[col_bnd])


# ---------------------------------------------------------------------------
# Halo exchange
# ---------------------------------------------------------------------------


def _halo_exchange(
    x_own: jax.Array, send_sel: jax.Array, plan: HaloPlan, axis
) -> jax.Array:
    """Ring/grid halo exchange body (records counts in the *caller's* region).

    For an (R, r) column block the exchanged rows are r-wide, so the ICI
    payload scales with the RHS count (same number of ppermute launches).

    With a :class:`~repro.core.partition.GridPlan` (``axis`` is the
    ``(rows, cols)`` tuple of mesh axis names) each shift runs as
    per-dimension sub-axis ppermutes: the column hop first, then the row
    hop forwards the received buffer — a corner shift therefore launches
    two collectives and its payload crosses two links, which is exactly
    how ``GridPlan.collective_bytes_per_shard``/``n_launches`` price it.
    """
    grid = getattr(plan, "mode", None) == "grid"
    row_bytes = x_own.dtype.itemsize * _nrhs(x_own)
    trace.record_op(
        "halo_exchange",
        OpCounts(
            ici_bytes=float(plan.collective_bytes_per_shard(row_bytes)),
            n_collectives=float(
                plan.n_launches if grid else len(plan.shifts)
            ),
        ),
    )
    bufs = []
    off = 0
    for k, w in enumerate(plan.widths):
        sel = lax.slice_in_dim(send_sel, off, off + w)
        buf = x_own[sel]
        if grid:
            di, dj = plan.shifts[k]
            if dj:
                buf = lax.ppermute(buf, axis[1], plan.perm_cols(k))
            if di:
                buf = lax.ppermute(buf, axis[0], plan.perm_rows(k))
            bufs.append(buf)
        else:
            bufs.append(lax.ppermute(buf, axis, plan.perm(k)))
        off += w
    if not bufs:
        return jnp.zeros((0,) + x_own.shape[1:], x_own.dtype)
    return jnp.concatenate(bufs)


def halo_exchange(
    x_own: jax.Array, send_sel: jax.Array, plan: HaloPlan, axis
) -> jax.Array:
    """Ring/grid halo exchange: returns the concatenated receive buffers.

    ``send_sel`` is the local (W,) selector row; buffer k is sent to shard
    ``j - shifts[k]`` and received from ``j + shifts[k]`` (zeros at edges).
    Attributed to the ``"halo"`` energy region (the serialized path); the
    overlapped SpMV calls :func:`_halo_exchange` directly so the exchange
    lands in its ``"overlap"`` region instead.
    """
    with trace.region("halo"):
        return _halo_exchange(x_own, send_sel, plan, axis)


def gather_ext(mat: DistMat, x_own: jax.Array, axis) -> jax.Array:
    """Produce the external-vector buffer ``x_ext`` for this shard's rows."""
    if mat.plan.mode in ("ring", "grid"):
        halo = halo_exchange(x_own, mat.send_sel, mat.plan, axis)
        return jnp.concatenate([x_own, halo])
    # allgather mode: padded-global layout owner*R + local — exactly the
    # tiled all_gather of the padded shard vectors.
    with trace.region("halo"):
        trace.record_op(
            "allgather",
            OpCounts(
                ici_bytes=float(
                    mat.plan.collective_bytes_per_shard(
                        x_own.dtype.itemsize * _nrhs(x_own)
                    )
                ),
                n_collectives=1.0,
            ),
        )
        return lax.all_gather(x_own, axis, tiled=True)


# ---------------------------------------------------------------------------
# Distributed SpMV
# ---------------------------------------------------------------------------


# Trace-time default for spmv_shard's overlap flag. Solver factories set it
# for the whole body trace (``with overlap_default(flag)``), so call sites
# that don't thread the flag explicitly — the AMG V-cycle's level SpMVs,
# the Jacobi smoother residuals — follow the solver's schedule instead of
# silently staying overlapped under ``--no-overlap``.
_OVERLAP_DEFAULT = True


@contextlib.contextmanager
def overlap_default(on: bool):
    """Scoped default for :func:`spmv_shard`'s ``overlap`` (trace time)."""
    global _OVERLAP_DEFAULT
    prev = _OVERLAP_DEFAULT
    _OVERLAP_DEFAULT = bool(on)
    try:
        yield
    finally:
        _OVERLAP_DEFAULT = prev


def spmv_shard(
    mat: DistMat, x_own: jax.Array, axis: str, *, overlap: bool | None = None
) -> jax.Array:
    """y_own = (A @ x)_own via the interior/boundary row-block split.

    ``mat`` is the *local* DistMat block (leading shard axis squeezed; see
    ``local_block``); ``x_own`` the local (R,) vector shard or an (R, r)
    multi-RHS column block (the SpMM sweep: same schedule, matrix streamed
    once, vector traffic and halo payload scaled by ``r``). ``overlap=None``
    resolves the scoped :func:`overlap_default` (True unless a solver set
    otherwise).

    ``overlap=True`` (ring layouts with a real exchange): the halo
    ``ppermute`` is issued first, the interior block — every locally-indexed
    entry — is multiplied while the exchange is in flight, and the compact
    boundary block is scatter-added on arrival. The whole phase lands in the
    ``"overlap"`` energy region, modeled with the communication hidden
    behind the interior matvec. ``overlap=False`` (and the allgather /
    single-shard layouts): the serialized order — gather ``x_ext`` fully
    (region ``"halo"``), then multiply both blocks.

    Both orders compute bitwise-identical results; only the schedule and the
    energy-region attribution differ.

    The device ops carry the step names ``interior``, ``boundary`` and
    ``halo`` in their ``op_name`` in both orders (plain
    ``jax.named_scope``s, which leave the energy regions as they are).
    """
    if overlap is None:
        overlap = _OVERLAP_DEFAULT
    ring = mat.plan.mode in ("ring", "grid") and len(mat.plan.shifts) > 0
    if overlap and ring:
        with trace.region(trace.OVERLAP):
            with jax.named_scope("halo"):
                halo = _halo_exchange(x_own, mat.send_sel, mat.plan, axis)
            with jax.named_scope("interior"):
                y = interior_matvec(mat.interior, x_own)
            with jax.named_scope("boundary"):
                x_ext = jnp.concatenate([x_own, halo])
                yb = boundary_matvec(
                    mat.data_ext, mat.col_ext, x_ext, src_elems=halo.shape[0]
                )
                return y.at[mat.bnd_rows].add(yb)
    x_ext = gather_ext(mat, x_own, axis)
    with jax.named_scope("interior"):
        y = interior_matvec(mat.interior, x_own)
    # ring: the boundary gathers touch only the received halo buffers
    src = x_ext.shape[0] - x_own.shape[0] if ring else None
    with jax.named_scope("boundary"):
        yb = boundary_matvec(mat.data_ext, mat.col_ext, x_ext, src_elems=src)
        return y.at[mat.bnd_rows].add(yb)


# ---------------------------------------------------------------------------
# Matrix-powers SpMV (communication-avoiding s-step interiors)
# ---------------------------------------------------------------------------


def ghost_matvec(
    ghost_data: jax.Array, ghost_col: jax.Array, x_ext: jax.Array
) -> jax.Array:
    """Redundant ghost-row matvec: ``yg[j] = sum_k data[j,k]*x_ext[col[j,k]]``.

    The deep-halo replicated rows (``DistMat.ghost_data``) recompute the
    halo region between chained applications instead of re-exchanging —
    the matrix-powers redundancy. Recorded under its own op name so the
    executed ledger prices the redundant flops/bytes honestly rather than
    folding them into the interior matvec.
    """
    b = ghost_data.dtype.itemsize
    G = ghost_data.shape[0]
    mat_bytes = float(ghost_data.size * (b + ghost_col.dtype.itemsize))
    trace.record_op(
        "ghost_matvec",
        OpCounts(
            flops=2.0 * ghost_data.size,
            hbm_bytes=mat_bytes
            + float(
                min(x_ext.shape[0], ghost_data.size) * b + G * (b + 4)
            ),
            hbm_matrix_bytes=mat_bytes,
        ),
    )
    return jnp.einsum("gk,gk->g", ghost_data, x_ext[ghost_col])


def matrix_powers(
    mat: DistMat, p: jax.Array, s: int, axis, *, overlap: bool | None = None
) -> jax.Array:
    """[A p, A² p, …, Aˢ p] (own rows, stacked (s, R)) from ONE exchange.

    The communication-avoiding kernel of the s-step CG body: a single
    *widened* halo exchange (``halo_depth >= s`` partition) delivers the
    depth-s transitive closure of the boundary coupling, after which the
    whole monomial block chains locally — each application multiplies the
    interior + boundary blocks for the own rows AND redundantly recomputes
    every replicated ghost row (depth < s), scattering the results back
    into the halo slots so the next application reads refreshed ghosts.
    Validity is inductive: application ``j`` is exact on own rows and on
    ghosts of depth ``<= s - j``; deeper slots decay to garbage that the
    valid region never reads (they are zero-filled, staying finite).

    One ppermute round and 1/s of the launch latency per SpMV, at the
    price of the ghost-row redundancy — both sides of the trade recorded
    honestly (``halo_exchange`` once, ``ghost_matvec`` per application).
    ``overlap=True`` wraps the whole block in a single ``overlap`` region:
    the one exchange hides behind s interior matvecs' compute.
    """
    if mat.plan.mode not in ("ring", "grid"):
        raise ValueError(
            "matrix_powers needs a ring/grid halo plan (allgather layouts "
            "re-gather the full vector every application)"
        )
    has_halo = len(mat.plan.shifts) > 0
    if has_halo and mat.halo_depth < s:
        raise ValueError(
            f"matrix_powers with s={s} needs a halo_depth >= {s} partition "
            f"(got halo_depth={mat.halo_depth}); rebuild with "
            f"partition_csr(..., halo_depth=s)"
        )
    if overlap is None:
        overlap = _OVERLAP_DEFAULT

    R = p.shape[0]

    def _chain(x_ext: jax.Array) -> jax.Array:
        halo_len = x_ext.shape[0] - R
        outs = []
        for j in range(s):
            x_own = x_ext[:R]
            y = interior_matvec(mat.interior, x_own)
            yb = boundary_matvec(
                mat.data_ext, mat.col_ext, x_ext, src_elems=halo_len or None
            )
            y_own = y.at[mat.bnd_rows].add(yb)
            outs.append(y_own)
            if j + 1 == s:
                break  # the last application's ghosts are never read
            if mat.ghost_data is not None and mat.ghost_data.size:
                yg = ghost_matvec(mat.ghost_data, mat.ghost_col, x_ext)
                halo_next = (
                    jnp.zeros((halo_len,), x_ext.dtype)
                    .at[mat.ghost_pos - R]
                    .set(yg, mode="drop")
                )
            else:
                halo_next = jnp.zeros((halo_len,), x_ext.dtype)
            x_ext = jnp.concatenate([y_own, halo_next])
        return jnp.stack(outs)

    if overlap and has_halo:
        with trace.region(trace.OVERLAP):
            halo = _halo_exchange(p, mat.send_sel, mat.plan, axis)
            return _chain(jnp.concatenate([p, halo]))
    return _chain(gather_ext(mat, p, axis))


# ---------------------------------------------------------------------------
# shard_map plumbing
# ---------------------------------------------------------------------------


def local_block(mat: DistMat) -> DistMat:
    """Squeeze the leading shard axis from every data leaf (inside shard_map)."""
    return jax.tree.map(lambda a: a[0] if a.ndim > 0 else a, mat)


def dist_specs(mat: DistMat, axis="shards"):
    """PartitionSpec pytree for a DistMat sharded over the shard axis.

    ``axis`` may be a single mesh axis name or a tuple of names (2-D
    grid meshes shard the flat leading dimension over both axes,
    row-major — flat shard ``s = i * C + j``).
    """
    return jax.tree.map(
        lambda a: P(axis, *([None] * (a.ndim - 1))), mat
    )


def vec_spec(axis="shards"):
    return P(axis)


def matrix_axis(mat: DistMat):
    """Mesh axis (name or tuple of names) this DistMat's plan shards over."""
    if getattr(mat.plan, "mode", None) == "grid":
        return tuple(mat.plan.axes)
    return "shards"


def shard_vector(mesh, xp, axis="shards") -> jax.Array:
    """(S, R[, r]) padded host vector or RHS block -> device array sharded
    over the shard axis (all trailing axes replicated). Host input goes
    straight to its shards, never through one device."""
    with jax.profiler.TraceAnnotation("repro.shard_vector"):
        if not isinstance(xp, jax.Array):
            xp = np.asarray(xp)
            xp = xp.astype(jax.dtypes.canonicalize_dtype(xp.dtype), copy=False)
        sh = jax.sharding.NamedSharding(
            mesh, P(axis, *([None] * (xp.ndim - 1)))
        )
        return jax.device_put(xp, sh)


def shard_matrix(mesh, mat: DistMat, axis=None) -> DistMat:
    if axis is None:
        axis = matrix_axis(mat)
    specs = dist_specs(mat, axis)
    return jax.tree.map(
        lambda a, s: jax.device_put(a, jax.sharding.NamedSharding(mesh, s)),
        mat,
        specs,
    )


def make_spmv(mesh, mat: DistMat, axis="shards", *, overlap: bool = True):
    """Jitted end-to-end distributed SpMV: (S,R) -> (S,R) sharded arrays.

    ``overlap`` selects the communication-hiding schedule (see
    :func:`spmv_shard`). ``axis`` is the mesh axis name — or the
    ``(rows, cols)`` tuple for 2-D grid meshes.
    """
    specs = dist_specs(mat, axis)

    def fn(m, x):
        mb = local_block(m)
        y = spmv_shard(mb, x[0], axis, overlap=overlap)
        return y[None]

    mapped = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(specs, P(axis, None)),
        out_specs=P(axis, None),
        check_vma=False,  # pallas_call outputs carry no varying annotation
    )
    return jax.jit(mapped)
