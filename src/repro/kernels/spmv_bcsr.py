"""Block-CSR SpMV Pallas kernel with scalar-prefetched column indices.

The TPU-native analog of the paper's CSR warp-per-row SpMV for *unstructured*
matrices: TPUs have no efficient per-element gather, so the sparse structure
is blocked into dense (br, bc) tiles; the block-column indices are
**scalar-prefetched** (``PrefetchScalarGridSpec``) so the pipeline can issue
the HBM->VMEM copy of the right x tile ahead of compute — the TPU equivalent
of the GPU kernel's latency hiding via massive thread parallelism.

Layout: every block-row is padded to a uniform ``bpr`` blocks (padding blocks
are all-zero with bcol=0, contributing nothing). Grid = (n_brows, bpr),
j-fastest; the output tile for block-row i is revisited across j and
accumulated in place (sequential TPU grid semantics). The prefetched ids
live in SMEM (1 MiB per core), so a matrix of more than ``_IDS_PER_CALL``
blocks runs as one call per range of block rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.x32 import pallas_call


# Block ids one pallas_call may scalar-prefetch: half of a TPU core's 1 MiB
# SMEM. Larger matrices run as several calls over ranges of block rows.
_IDS_PER_CALL = 1 << 17


def _bcsr_calls(kernel, blocks, bcol, x, *, n_brows, bpr, x_tile, y_tile,
                interpret):
    """Run ``kernel`` over the (block row, slot) grid, one pallas_call per
    range of block rows whose block ids fit in SMEM; returns the
    (n_brows, *y_tile) result. Each call reads the full ``blocks`` and
    ``x`` arrays in place (its block-row offset lives in the index map)."""
    _, br, bc = blocks.shape
    x0, y0 = (0,) * len(x_tile), (0,) * len(y_tile)
    step = max(_IDS_PER_CALL // bpr, 1)
    outs = []
    for r0 in range(0, n_brows, step):
        r1 = min(r0 + step, n_brows)
        off = r0 * bpr
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(r1 - r0, bpr),
            in_specs=[
                pl.BlockSpec(
                    (1, br, bc),
                    lambda i, j, ids, off=off: (off + i * bpr + j, 0, 0),
                ),
                pl.BlockSpec(
                    (1, *x_tile), lambda i, j, ids: (ids[i * bpr + j], *x0)
                ),
            ],
            out_specs=pl.BlockSpec((1, *y_tile), lambda i, j, ids: (i, *y0)),
        )
        outs.append(pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((r1 - r0, *y_tile), x.dtype),
            interpret=interpret,
        )(bcol[off:r1 * bpr], blocks, x))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs)


def _bcsr_kernel(bcol_ref, blocks_ref, x_ref, y_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    blk = blocks_ref[0]  # (br, bc)
    xv = x_ref[0]  # (1, bc)
    # (1, bc) . (br, bc)^T -> (1, br): the row-vector form of blk @ x
    y_ref[0] += lax.dot_general(
        xv, blk, (((1,), (1,)), ((), ())), preferred_element_type=y_ref.dtype
    )


@functools.partial(jax.jit, static_argnames=("n_brows", "bpr", "interpret"))
def bcsr_spmv(
    blocks: jax.Array,  # (n_brows * bpr, br, bc)
    bcol: jax.Array,  # (n_brows * bpr,) int32
    x: jax.Array,  # (n_bcols, bc)
    *,
    n_brows: int,
    bpr: int,
    interpret: bool = False,
) -> jax.Array:
    _, br, bc = blocks.shape
    # x/y tiles travel as (1, 1, bc)/(1, 1, br) blocks of 3-D views: a
    # (1, bc) block of the 2-D array would break the TPU's (8, 128) block
    # rule, while a block whose last two dims span the array's is legal.
    y = _bcsr_calls(
        _bcsr_kernel, blocks, bcol, x.reshape(x.shape[0], 1, bc),
        n_brows=n_brows, bpr=bpr, x_tile=(1, bc), y_tile=(1, br),
        interpret=interpret,
    )
    return y.reshape(n_brows, br)


def _bcsr_spmm_kernel(bcol_ref, blocks_ref, x_ref, y_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    blk = blocks_ref[0]  # (br, bc)
    xv = x_ref[0]  # (bc, r)
    y_ref[0] += jnp.dot(blk, xv, preferred_element_type=y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n_brows", "bpr", "interpret"))
def bcsr_spmm(
    blocks: jax.Array,  # (n_brows * bpr, br, bc)
    bcol: jax.Array,  # (n_brows * bpr,) int32
    x: jax.Array,  # (n_bcols, bc, r) RHS block
    *,
    n_brows: int,
    bpr: int,
    interpret: bool = False,
) -> jax.Array:
    """Multi-RHS sibling of :func:`bcsr_spmv`: each (br, bc) matrix tile is
    fetched ONCE and contracted against the full (bc, r) RHS tile, so matrix
    traffic is amortized across the batch while the grid/prefetch schedule
    stays identical to the SpMV kernel."""
    _, br, bc = blocks.shape
    r = x.shape[2]
    return _bcsr_calls(
        _bcsr_spmm_kernel, blocks, bcol, x, n_brows=n_brows, bpr=bpr,
        x_tile=(bc, r), y_tile=(br, r), interpret=interpret,
    )


def bcsr_prepare_x(blocks, x, *, n_brows: int, bpr: int, n_out: int | None):
    """Shared ragged-size guard for the uniform-layout BCSR SpMV callers.

    Validates the packing (``blocks.shape[0] == n_brows * bpr``) and, for a
    flat ``(n,)`` vector with ``n % bc != 0``, zero-pads the trailing block
    column up to the tile grid. Returns ``(x2, flat, n_out)`` where ``x2``
    is the kernel's native (n_bcols, bc) layout and ``n_out`` the length to
    trim the flattened result to (None for native-layout inputs). Both
    ``kernels/ops.bcsr_spmv`` and the dispatch ``OpSet.bcsr_spmv`` go
    through here, so the two entry points cannot drift apart.
    """
    _, br, bc = blocks.shape
    if blocks.shape[0] != n_brows * bpr:
        raise ValueError(
            f"blocks leading dim {blocks.shape[0]} != n_brows*bpr "
            f"({n_brows}*{bpr}); pack with core.sparse.pack_bcsr"
        )
    flat = x.ndim == 1
    if flat:
        n = x.shape[0]
        n_bcols = -(-n // bc)
        pad = n_bcols * bc - n
        if pad:
            x = jnp.concatenate([x, jnp.zeros((pad,), x.dtype)])
        x = x.reshape(n_bcols, bc)
        if n_out is None:
            n_out = min(n, n_brows * br)
    return x, flat, n_out


def bcsr_finish_y(y, flat: bool, n_out: int | None):
    """Inverse of :func:`bcsr_prepare_x`'s flat handling: flatten and trim
    the (n_brows, br) kernel result back to the caller's vector length."""
    return y.reshape(-1)[:n_out] if flat else y


def bcsr_prepare_xb(blocks, x, *, n_brows: int, bpr: int, n_out: int | None):
    """:func:`bcsr_prepare_x` for (n, r) RHS blocks: zero-pads the row
    dimension to the tile grid and reshapes to the kernel's native
    (n_bcols, bc, r) layout. Native 3-D inputs pass through untouched."""
    _, br, bc = blocks.shape
    if blocks.shape[0] != n_brows * bpr:
        raise ValueError(
            f"blocks leading dim {blocks.shape[0]} != n_brows*bpr "
            f"({n_brows}*{bpr}); pack with core.sparse.pack_bcsr"
        )
    flat = x.ndim == 2
    if flat:
        n, r = x.shape
        n_bcols = -(-n // bc)
        pad = n_bcols * bc - n
        if pad:
            x = jnp.concatenate([x, jnp.zeros((pad, r), x.dtype)], axis=0)
        x = x.reshape(n_bcols, bc, r)
        if n_out is None:
            n_out = min(n, n_brows * br)
    return x, flat, n_out


def bcsr_finish_yb(y, flat: bool, n_out: int | None):
    """Flatten/trim the (n_brows, br, r) SpMM result to (n_out, r)."""
    return y.reshape(-1, y.shape[-1])[:n_out] if flat else y


# Host-side packing lives with the other format conversions in
# core/sparse.py (one block-packing implementation); re-exported here for
# the kernel-facing import path.
from repro.core.sparse import pack_bcsr  # noqa: E402, F401
