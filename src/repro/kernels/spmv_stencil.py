"""Matrix-free stencil SpMV Pallas kernel (7pt / 27pt, Dirichlet).

TPU adaptation of the paper's CSR SpMV hot spot: the benchmark matrices are
structured stencils, and on TPU the roofline-optimal formulation is
**matrix-free shift-and-add** on the 3-D grid held in VMEM — no matrix
values, no column indices, no gathers. Per-row HBM traffic (f64 values,
int32 column indices, read x + write y once):

    format        matrix bytes/row     vector bytes/row   total    vs matfree
    CSR/ELL 7pt   7*(8+4) = 84         ~16                ~100     ~6x
    CSR/ELL 27pt  27*(8+4) = 324       ~16                ~340     ~21x
    matrix-free   0                    ~16                ~16      1x

(f32 halves the vector term again.) The distributed shard_map form of this
operator lives in core/stencil_solver.py; backend selection between this
kernel, interpret mode, and the jnp reference is kernels/dispatch.py.

Tiling: grid over z-slabs of ``bz`` planes. The kernel reads its own
(bz, ny, nx) block plus ONE boundary plane from each z-neighbor (passed as
two extra (1, ny, nx) views of the same array, clamped at the edges and
masked by program_id) — HBM reads are bz+2 planes per bz planes of output,
i.e. within 2/bz of the minimum. x/y-direction neighbors live inside the
block; their shifted reads are VMEM-local. Lane dim = nx (pad to a multiple
of 128 for hardware alignment); sublane = ny.

``stencil_spmv_halo`` is the distributed variant: instead of zero Dirichlet
planes at the z-edges it takes explicit boundary planes (the halo received
from the slab neighbors via ppermute), so a shard_map solver can run the
whole local SpMV as one kernel call.

``stencil_spmv_boundary`` is the communication-hiding companion: it
recomputes ONLY the slab's first and last output planes from the received
halo planes. The overlapped distributed SpMV (core/stencil_solver.py) runs
the full slab with zero halos while the ppermute is in flight — every
interior plane is already final — and patches the two edge planes with this
kernel on arrival. Both kernels share ``_stencil_core``, which computes
every output plane by the same expression on explicit neighbour planes, so
the patched planes are bitwise identical to the serialized single-call
result.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.x32 import pallas_call


def _shift_yx(x: jax.Array, dy: int, dx: int) -> jax.Array:
    """Zero-fill shift within (z, y, x) block along y/x only."""
    z, ny, nx = x.shape
    out = x
    if dy:
        pad = ((0, 0), (dy, 0), (0, 0)) if dy > 0 else ((0, 0), (0, -dy), (0, 0))
        out = jnp.pad(out, pad)
        out = out[:, : ny, :] if dy > 0 else out[:, -dy : ny - dy, :]
    if dx:
        pad = ((0, 0), (0, 0), (dx, 0)) if dx > 0 else ((0, 0), (0, 0), (0, -dx))
        out = jnp.pad(out, pad)
        out = out[:, :, : nx] if dx > 0 else out[:, :, -dx : nx - dx]
    return out


def _stencil_core(y_ref, c, prev_plane, next_plane, *, stencil, aniso):
    """Shared 7pt/27pt arithmetic: y_ref <- A (block c + boundary planes).

    Output plane k reads only planes k-1, k, k+1. Every plane runs the same
    (1, ny, nx) expression on explicit neighbour planes and is stored on its
    own, so a one-plane call (the boundary kernel) is bitwise a plane of the
    slab kernel. The diagonal product comes last, so a compiler that fuses
    it with the subtraction (an FMA) does so in every plane alike.
    """
    planes = [prev_plane] + [c[k : k + 1] for k in range(c.shape[0])]
    planes.append(next_plane)
    if stencil == "7pt":
        ax, ay, az = aniso
        out = []
        for below, cur, above in zip(planes, planes[1:], planes[2:]):
            nb = ax * (_shift_yx(cur, 0, 1) + _shift_yx(cur, 0, -1))
            nb = nb + ay * (_shift_yx(cur, 1, 0) + _shift_yx(cur, -1, 0))
            nb = nb + az * (below + above)
            out.append((2.0 * (ax + ay + az)) * cur - nb)
    else:  # 27pt
        s9 = []
        for pl_ in planes:
            acc = jnp.zeros_like(pl_)
            for dy in (-1, 0, 1):
                for dx in (-1, 0, 1):
                    acc = acc + _shift_yx(pl_, dy, dx)
            s9.append(acc)
        out = [27.0 * cur - (s9[k] + s9[k + 1] + s9[k + 2])
               for k, cur in enumerate(planes[1:-1])]
    for k, y in enumerate(out):
        y_ref[k : k + 1] = y


def _stencil_kernel(prev_ref, cur_ref, next_ref, y_ref, *, stencil, aniso, nzb):
    i = pl.program_id(0)
    c = cur_ref[...]  # (bz, ny, nx)
    dt = c.dtype
    # Boundary planes from neighbor blocks; zero at the global z edges.
    pmask = jnp.where(i > 0, 1, 0).astype(dt)
    nmask = jnp.where(i < nzb - 1, 1, 0).astype(dt)
    prev_plane = prev_ref[...] * pmask  # (1, ny, nx)
    next_plane = next_ref[...] * nmask
    _stencil_core(
        y_ref, c, prev_plane, next_plane, stencil=stencil, aniso=aniso
    )


def _stencil_boundary_kernel(
    hp_ref, below_ref, cur_ref, above_ref, hn_ref, y_ref, *, stencil, aniso
):
    """Program 0 computes output plane 0 (needs prev_halo, x[0], x[1]);
    program 1 computes plane nz-1 (needs x[nz-2], x[nz-1], next_halo)."""
    i = pl.program_id(0)
    c = cur_ref[...]  # (1, ny, nx): plane 0 or nz-1
    prev_plane = jnp.where(i == 0, hp_ref[...], below_ref[...])
    next_plane = jnp.where(i == 0, above_ref[...], hn_ref[...])
    _stencil_core(
        y_ref, c, prev_plane, next_plane, stencil=stencil, aniso=aniso
    )


def _stencil_halo_kernel(
    hp_ref, prev_ref, cur_ref, next_ref, hn_ref, y_ref, *, stencil, aniso, nzb
):
    i = pl.program_id(0)
    c = cur_ref[...]  # (bz, ny, nx)
    # Boundary planes: the clamped self-views interior, the supplied halo
    # planes at the slab edges (zeros arrive there for global-edge shards).
    prev_plane = jnp.where(i == 0, hp_ref[...], prev_ref[...])
    next_plane = jnp.where(i == nzb - 1, hn_ref[...], next_ref[...])
    _stencil_core(
        y_ref, c, prev_plane, next_plane, stencil=stencil, aniso=aniso
    )


@functools.partial(
    jax.jit,
    static_argnames=("stencil", "aniso", "bz", "interpret"),
)
def stencil_spmv(
    x: jax.Array,
    *,
    stencil: str = "7pt",
    aniso: tuple = (1.0, 1.0, 1.0),
    bz: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """y = A_stencil @ x for x of shape (nz, ny, nx); nz % bz == 0."""
    nz, ny, nx = x.shape
    assert nz % bz == 0, f"nz={nz} must be a multiple of bz={bz}"
    nzb = nz // bz
    kernel = functools.partial(
        _stencil_kernel, stencil=stencil, aniso=aniso, nzb=nzb
    )
    # Plane views: block index along z is in *plane* units ((1, ny, nx)
    # blocks); clamped at the global edges (masked inside the kernel).
    prev_spec = pl.BlockSpec(
        (1, ny, nx), lambda i: (jnp.maximum(i * bz - 1, 0), 0, 0)
    )
    next_spec = pl.BlockSpec(
        (1, ny, nx), lambda i: (jnp.minimum(i * bz + bz, nz - 1), 0, 0)
    )
    cur_spec = pl.BlockSpec((bz, ny, nx), lambda i: (i, 0, 0))
    return pallas_call(
        kernel,
        grid=(nzb,),
        in_specs=[prev_spec, cur_spec, next_spec],
        out_specs=pl.BlockSpec((bz, ny, nx), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nz, ny, nx), x.dtype),
        interpret=interpret,
    )(x, x, x)


def pick_bz(nz: int, target: int = 8) -> int:
    """Largest z-block size <= target that divides nz (>= 1 always works)."""
    for bz in range(min(target, nz), 0, -1):
        if nz % bz == 0:
            return bz
    return 1


@functools.partial(
    jax.jit,
    static_argnames=("stencil", "aniso", "bz", "interpret"),
)
def stencil_spmv_halo(
    x: jax.Array,
    prev_halo: jax.Array,
    next_halo: jax.Array,
    *,
    stencil: str = "7pt",
    aniso: tuple = (1.0, 1.0, 1.0),
    bz: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """Local-slab SpMV with explicit z-boundary planes (distributed form).

    ``x`` is the shard's (nz_loc, ny, nx) slab; ``prev_halo``/``next_halo``
    are the (ny, nx) boundary planes received from the z-neighbors (zeros at
    the global edges). nz_loc % bz == 0 (use ``pick_bz``).
    """
    nz, ny, nx = x.shape
    assert nz % bz == 0, f"nz={nz} must be a multiple of bz={bz}"
    nzb = nz // bz
    kernel = functools.partial(
        _stencil_halo_kernel, stencil=stencil, aniso=aniso, nzb=nzb
    )
    plane = pl.BlockSpec((1, ny, nx), lambda i: (0, 0, 0))
    prev_spec = pl.BlockSpec(
        (1, ny, nx), lambda i: (jnp.maximum(i * bz - 1, 0), 0, 0)
    )
    next_spec = pl.BlockSpec(
        (1, ny, nx), lambda i: (jnp.minimum(i * bz + bz, nz - 1), 0, 0)
    )
    cur_spec = pl.BlockSpec((bz, ny, nx), lambda i: (i, 0, 0))
    return pallas_call(
        kernel,
        grid=(nzb,),
        in_specs=[plane, prev_spec, cur_spec, next_spec, plane],
        out_specs=pl.BlockSpec((bz, ny, nx), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nz, ny, nx), x.dtype),
        interpret=interpret,
    )(prev_halo[None], x, x, x, next_halo[None])


@functools.partial(
    jax.jit,
    static_argnames=("stencil", "aniso", "interpret"),
)
def stencil_spmv_boundary(
    x: jax.Array,
    prev_halo: jax.Array,
    next_halo: jax.Array,
    *,
    stencil: str = "7pt",
    aniso: tuple = (1.0, 1.0, 1.0),
    interpret: bool = False,
) -> jax.Array:
    """The slab's first + last output planes only (communication-hiding form).

    ``x`` is the shard's (nz_loc, ny, nx) slab (nz_loc >= 2);
    ``prev_halo``/``next_halo`` the (ny, nx) planes received from the
    z-neighbors. Returns a (2, ny, nx) array: row 0 is output plane 0, row 1
    is output plane nz_loc-1 — bitwise equal to the corresponding planes of
    :func:`stencil_spmv_halo`. Grid of exactly two programs, so the
    on-arrival boundary fix-up costs two plane-sized kernel launches of
    work, independent of nz_loc.
    """
    nz, ny, nx = x.shape
    assert nz >= 2, "boundary split needs at least 2 local z-planes"
    kernel = functools.partial(
        _stencil_boundary_kernel, stencil=stencil, aniso=aniso
    )
    plane = pl.BlockSpec((1, ny, nx), lambda i: (0, 0, 0))
    cur = pl.BlockSpec((1, ny, nx), lambda i: (i * (nz - 1), 0, 0))
    below = pl.BlockSpec(
        (1, ny, nx), lambda i: (jnp.maximum(i * (nz - 1) - 1, 0), 0, 0)
    )
    above = pl.BlockSpec(
        (1, ny, nx), lambda i: (jnp.minimum(i * (nz - 1) + 1, nz - 1), 0, 0)
    )
    return pallas_call(
        kernel,
        grid=(2,),
        in_specs=[plane, below, cur, above, plane],
        out_specs=pl.BlockSpec((1, ny, nx), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((2, ny, nx), x.dtype),
        interpret=interpret,
    )(prev_halo[None], x, x, x, next_halo[None])
