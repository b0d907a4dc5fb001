"""Public jit'd wrappers for the Pallas kernels.

``interpret`` defaults to True on CPU backends and False on TPU, where
``interpret=True`` is refused — kernels are *written for* TPU (explicit
BlockSpec VMEM tiling) and *validated* in interpret mode against the
pure-jnp oracles in ref.py.
"""

from __future__ import annotations

import jax

from repro.kernels import ref  # noqa: F401  (re-exported oracle module)
from repro.kernels.fused_reductions import fused_axpy as _fused_axpy
from repro.kernels.fused_reductions import fused_axpy2 as _fused_axpy2
from repro.kernels.fused_reductions import fused_axpy2_dots as _fused_axpy2_dots
from repro.kernels.fused_reductions import fused_dots3 as _fused_dots3
from repro.kernels.fused_reductions import fused_dots_n as _fused_dots_n
from repro.kernels.jacobi_stencil import jacobi_stencil_sweep as _jacobi
from repro.kernels.spmv_bcsr import bcsr_spmv as _bcsr_spmv
from repro.kernels.spmv_bcsr import pack_bcsr  # noqa: F401
from repro.kernels.spmv_stencil import pick_bz  # noqa: F401
from repro.kernels.spmv_stencil import stencil_spmv as _stencil_spmv
from repro.kernels.spmv_stencil import (
    stencil_spmv_boundary as _stencil_spmv_boundary,
)
from repro.kernels.spmv_stencil import stencil_spmv_halo as _stencil_spmv_halo


def _interpret(flag: bool | None) -> bool:
    """Interpret mode: the default off the TPU, refused on it."""
    on_tpu = jax.default_backend() == "tpu"
    if flag and on_tpu:
        raise ValueError("interpret mode does not run on a TPU backend")
    return (not on_tpu) if flag is None else flag


def stencil_spmv(x, *, stencil="7pt", aniso=(1.0, 1.0, 1.0), bz=8, interpret=None):
    interpret = _interpret(interpret)
    return _stencil_spmv(x, stencil=stencil, aniso=aniso, bz=bz, interpret=interpret)


def bcsr_spmv(blocks, bcol, x, *, n_brows, bpr, n_out=None, interpret=None):
    """Uniform-layout BCSR SpMV with ragged-size guarding.

    ``x`` may be the kernel's native ``(n_bcols, bc)`` tile layout or a flat
    ``(n,)`` vector with ``n % bc != 0`` — flat inputs are zero-padded up to
    the block grid (the trailing block-row/column is padded, not rejected)
    and the result comes back flat, trimmed to ``n_out`` (default: the
    input length capped at ``n_brows * br``).
    """
    from repro.kernels.spmv_bcsr import bcsr_finish_y, bcsr_prepare_x

    interpret = _interpret(interpret)
    x, flat, n_out = bcsr_prepare_x(
        blocks, x, n_brows=n_brows, bpr=bpr, n_out=n_out
    )
    y = _bcsr_spmv(
        blocks, bcol, x, n_brows=n_brows, bpr=bpr, interpret=interpret
    )
    return bcsr_finish_y(y, flat, n_out)


def stencil_spmv_halo(
    x, prev_halo, next_halo, *, stencil="7pt", aniso=(1.0, 1.0, 1.0), bz=8,
    interpret=None,
):
    interpret = _interpret(interpret)
    return _stencil_spmv_halo(
        x, prev_halo, next_halo, stencil=stencil, aniso=aniso, bz=bz,
        interpret=interpret,
    )


def stencil_spmv_boundary(
    x, prev_halo, next_halo, *, stencil="7pt", aniso=(1.0, 1.0, 1.0),
    interpret=None,
):
    interpret = _interpret(interpret)
    return _stencil_spmv_boundary(
        x, prev_halo, next_halo, stencil=stencil, aniso=aniso,
        interpret=interpret,
    )


def fused_dots3(p, w, r, *, chunk=65536, interpret=None):
    interpret = _interpret(interpret)
    return _fused_dots3(p, w, r, chunk=chunk, interpret=interpret)


def fused_dots_n(pairs, *, chunk=65536, interpret=None):
    interpret = _interpret(interpret)
    return _fused_dots_n(pairs, chunk=chunk, interpret=interpret)


def fused_axpy(a, x, y, *, chunk=65536, interpret=None):
    interpret = _interpret(interpret)
    return _fused_axpy(a, x, y, chunk=chunk, interpret=interpret)


def fused_axpy2(a1, x1, y1, a2, x2, y2, *, chunk=65536, interpret=None):
    interpret = _interpret(interpret)
    return _fused_axpy2(
        a1, x1, y1, a2, x2, y2, chunk=chunk, interpret=interpret
    )


def fused_axpy2_dots(a1, x1, y1, a2, x2, y2, *, chunk=65536, interpret=None):
    interpret = _interpret(interpret)
    return _fused_axpy2_dots(
        a1, x1, y1, a2, x2, y2, chunk=chunk, interpret=interpret
    )


def jacobi_stencil_sweep(
    x, b, dinv, *, stencil="7pt", aniso=(1.0, 1.0, 1.0), omega=1.0, bz=8,
    interpret=None,
):
    interpret = _interpret(interpret)
    return _jacobi(
        x, b, dinv, stencil=stencil, aniso=aniso, omega=omega, bz=bz,
        interpret=interpret,
    )
