"""Poisson on a box with homogeneous Dirichlet boundaries, 7- or 27-point.

7-point: the finite-difference Laplacian, diagonal 6 and -1 on the six
face neighbours. 27-point: the HPCG stencil, diagonal 26 and -1 on all 26
neighbours. Rows are ordered x fastest, then y, then z, so contiguous row
blocks are z-slabs. Weak scaling extrudes the per-chip grid along z.
"""

from __future__ import annotations

import numpy as np
import scipy.sparse as sp

from chipbench.problems import Problem


def global_grid(config: dict, chips: int) -> tuple[int, int, int]:
    nx, ny, nz = (int(v) for v in config["local_grid"])
    if config["extrude"] != "z":
        raise ValueError(f"extrude {config['extrude']!r}: only 'z' is built")
    return nx, ny, nz * chips


def _band(m: int, diag: float, off: float):
    return sp.diags([off, diag, off], [-1, 0, 1], shape=(m, m), format="csr")


def matrix(stencil: str, grid: tuple[int, int, int]):
    """The global CSR, float64 values and int32 indices."""
    nx, ny, nz = grid
    if stencil == "7pt":
        ix, iy, iz = (sp.identity(m, format="csr") for m in (nx, ny, nz))
        lx, ly, lz = (_band(m, 2.0, -1.0) for m in (nx, ny, nz))
        a = (sp.kron(iz, sp.kron(iy, lx)) + sp.kron(iz, sp.kron(ly, ix))
             + sp.kron(lz, sp.kron(iy, ix)))
    elif stencil == "27pt":
        tx, ty, tz = (_band(m, 1.0, 1.0) for m in (nx, ny, nz))
        a = 27.0 * sp.identity(nx * ny * nz) - sp.kron(tz, sp.kron(ty, tx))
    else:
        raise ValueError(f"unknown stencil {stencil!r}")
    a = sp.csr_matrix(a, dtype=np.float64)
    a.sort_indices()
    a.indices = a.indices.astype(np.int32)
    a.indptr = a.indptr.astype(np.int32)
    return a


def build(config: dict, chips: int) -> Problem:
    return Problem(a=matrix(config["stencil"], global_grid(config, chips)),
                   chips=chips, dtype=config["dtype"])
