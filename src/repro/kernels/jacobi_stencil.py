"""Fused stencil l1-Jacobi sweep Pallas kernel.

One V-cycle smoothing sweep is x <- x + omega * dinv * (b - A x). Composed
from separate ops it streams x twice (SpMV read + update read) plus b, dinv,
and writes y and x_new. This kernel fuses the whole sweep into one pass:
reads x (+2 boundary planes), b, dinv; writes x_new. For the 7-point stencil
that cuts HBM traffic per sweep from ~6 arrays to ~4 — directly shrinking
the memory-roofline term of the PCG smoother, which dominates V-cycle cost.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.spmv_stencil import _shift_yx
from repro.kernels.x32 import pallas_call


def _jacobi_kernel(
    prev_ref, cur_ref, next_ref, b_ref, dinv_ref, o_ref,
    *, stencil, aniso, omega, nzb,
):
    i = pl.program_id(0)
    c = cur_ref[...]
    dt = c.dtype
    pmask = jnp.where(i > 0, 1, 0).astype(dt)
    nmask = jnp.where(i < nzb - 1, 1, 0).astype(dt)
    prev_plane = prev_ref[...] * pmask
    next_plane = next_ref[...] * nmask

    if stencil == "7pt":
        ax, ay, az = aniso
        zm = jnp.concatenate([prev_plane, c[:-1]], axis=0)
        zp = jnp.concatenate([c[1:], next_plane], axis=0)
        y = (2.0 * (ax + ay + az)) * c
        y = y - ax * (_shift_yx(c, 0, 1) + _shift_yx(c, 0, -1))
        y = y - ay * (_shift_yx(c, 1, 0) + _shift_yx(c, -1, 0))
        y = y - az * (zm + zp)
    else:
        ext = jnp.concatenate([prev_plane, c, next_plane], axis=0)
        s9 = jnp.zeros_like(ext)
        for dy in (-1, 0, 1):
            for dx in (-1, 0, 1):
                s9 = s9 + _shift_yx(ext, dy, dx)
        y = 27.0 * c - (s9[:-2] + s9[1:-1] + s9[2:])

    o_ref[...] = c + omega * dinv_ref[...] * (b_ref[...] - y)


@functools.partial(
    jax.jit, static_argnames=("stencil", "aniso", "omega", "bz", "interpret")
)
def jacobi_stencil_sweep(
    x: jax.Array,
    b: jax.Array,
    dinv: jax.Array,
    *,
    stencil: str = "7pt",
    aniso: tuple = (1.0, 1.0, 1.0),
    omega: float = 1.0,
    bz: int = 8,
    interpret: bool = False,
) -> jax.Array:
    nz, ny, nx = x.shape
    assert nz % bz == 0
    nzb = nz // bz
    kernel = functools.partial(
        _jacobi_kernel, stencil=stencil, aniso=aniso, omega=omega, nzb=nzb
    )
    plane = lambda f: pl.BlockSpec((1, ny, nx), f)
    blk = pl.BlockSpec((bz, ny, nx), lambda i: (i, 0, 0))
    return pallas_call(
        kernel,
        grid=(nzb,),
        in_specs=[
            plane(lambda i: (jnp.maximum(i * bz - 1, 0), 0, 0)),
            blk,
            plane(lambda i: (jnp.minimum(i * bz + bz, nz - 1), 0, 0)),
            blk,
            blk,
        ],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct((nz, ny, nx), x.dtype),
        interpret=interpret,
    )(x, x, x, b, dinv)
