"""Pallas kernels: shape/dtype sweeps, allclose vs the ref.py oracles.

All kernels run in interpret mode on CPU (the TPU lowering is exercised by
construction: pl.pallas_call + explicit BlockSpecs).
"""

import numpy as np
import jax.numpy as jnp
import pytest
import scipy.sparse as sp

from repro.kernels import ops, ref
from repro.kernels.spmv_bcsr import pack_bcsr
from repro.matrices.poisson import PoissonProblem, poisson_scipy


@pytest.mark.parametrize("stencil", ["7pt", "27pt"])
@pytest.mark.parametrize(
    "shape,bz",
    [((8, 8, 8), 4), ((16, 12, 16), 8), ((8, 5, 9), 2), ((24, 16, 32), 8)],
)
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_stencil_kernel_sweep(stencil, shape, bz, dtype):
    nz, ny, nx = shape
    rng = np.random.default_rng(nz * ny * nx)
    x = rng.standard_normal(shape).astype(dtype)
    y_ker = np.asarray(ops.stencil_spmv(x, stencil=stencil, bz=bz))
    y_ref = np.asarray(
        ref.stencil7_ref(x) if stencil == "7pt" else ref.stencil27_ref(x)
    )
    # no-x64 main process computes f64 inputs in f32; tol follows actual dtype
    tol = 1e-12 if y_ker.dtype == np.float64 else 1e-4
    np.testing.assert_allclose(y_ker, y_ref, rtol=tol, atol=tol)


def test_stencil_kernel_matches_assembled_matrix():
    for stencil in ("7pt", "27pt"):
        p = PoissonProblem(10, 6, 8, stencil)
        a = poisson_scipy(p, dtype=np.float64)
        x = np.random.default_rng(0).standard_normal((8, 6, 10))
        y = np.asarray(ops.stencil_spmv(x.astype(np.float64), stencil=stencil, bz=4))
        tol = 1e-12 if y.dtype == np.float64 else 2e-4
        np.testing.assert_allclose(
            y.reshape(-1), a @ x.reshape(-1), rtol=tol, atol=tol
        )


def test_stencil_kernel_anisotropic():
    p = PoissonProblem(8, 8, 8, "7pt", aniso=(1.0, 2.5, 7.0))
    a = poisson_scipy(p, dtype=np.float64)
    x = np.random.default_rng(1).standard_normal((8, 8, 8))
    y = np.asarray(ops.stencil_spmv(x, stencil="7pt", aniso=(1.0, 2.5, 7.0), bz=4))
    tol = 1e-12 if y.dtype == np.float64 else 2e-4
    np.testing.assert_allclose(y.reshape(-1), a @ x.reshape(-1), rtol=tol, atol=tol)


@pytest.mark.parametrize("br,bc", [(8, 8), (8, 16), (16, 8)])
@pytest.mark.parametrize("n,m,density", [(120, 96, 0.05), (64, 64, 0.2), (33, 57, 0.1)])
def test_bcsr_kernel_sweep(br, bc, n, m, density):
    a = sp.random(n, m, density=density, format="csr", random_state=n + m)
    blocks, bcol, n_brows, bpr, n_bcols = pack_bcsr(a, br, bc, dtype=np.float32)
    x = np.random.default_rng(0).standard_normal(n_bcols * bc).astype(np.float32)
    y = np.asarray(
        ops.bcsr_spmv(
            jnp.asarray(blocks), jnp.asarray(bcol),
            jnp.asarray(x.reshape(n_bcols, bc)), n_brows=n_brows, bpr=bpr,
        )
    ).reshape(-1)[:n]
    y_ref = a @ x[:m]
    np.testing.assert_allclose(y, y_ref, rtol=3e-5, atol=3e-5)
    # oracle agreement
    y_o = np.asarray(
        ref.bcsr_spmv_ref(
            jnp.asarray(blocks), jnp.asarray(bcol),
            jnp.asarray(x.reshape(n_bcols, bc)), n_brows, bpr,
        )
    )
    np.testing.assert_allclose(
        np.asarray(y).reshape(-1),
        y_o.reshape(-1)[: len(np.asarray(y).reshape(-1))],
        rtol=3e-5, atol=3e-5,
    )


@pytest.mark.parametrize("multi_rhs", [False, True])
def test_bcsr_kernel_split_calls(monkeypatch, multi_rhs):
    # a matrix with more block ids than one call's SMEM holds runs as
    # several calls over block-row ranges (a ragged last one included)
    from repro.kernels import spmv_bcsr

    monkeypatch.setattr(spmv_bcsr, "_IDS_PER_CALL", 10)
    a = sp.random(52, 44, density=0.15, format="csr", random_state=3)
    blocks, bcol, n_brows, bpr, n_bcols = pack_bcsr(a, 4, 4, dtype=np.float32)
    assert n_brows * bpr > 10
    rng = np.random.default_rng(1)
    if multi_rhs:
        x = rng.standard_normal((n_bcols * 4, 3)).astype(np.float32)
        y = spmv_bcsr.bcsr_spmm(
            jnp.asarray(blocks), jnp.asarray(bcol),
            jnp.asarray(x.reshape(n_bcols, 4, 3)), n_brows=n_brows, bpr=bpr,
            interpret=True,
        )
        y = np.asarray(y).reshape(-1, 3)[:52]
    else:
        x = rng.standard_normal(n_bcols * 4).astype(np.float32)
        y = spmv_bcsr.bcsr_spmv(
            jnp.asarray(blocks), jnp.asarray(bcol),
            jnp.asarray(x.reshape(n_bcols, 4)), n_brows=n_brows, bpr=bpr,
            interpret=True,
        )
        y = np.asarray(y).reshape(-1)[:52]
    np.testing.assert_allclose(y, a @ x[:44], rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("n,chunk", [(2048, 512), (8192, 1024), (1024, 1024)])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_fused_dots_sweep(n, chunk, dtype):
    rng = np.random.default_rng(n)
    p, w, r = (rng.standard_normal(n).astype(dtype) for _ in range(3))
    d = np.asarray(ops.fused_dots3(jnp.asarray(p), jnp.asarray(w), jnp.asarray(r), chunk=chunk))
    d_ref = np.asarray(ref.fused_dots3_ref(jnp.asarray(p), jnp.asarray(w), jnp.asarray(r)))
    tol = 1e-12 if d.dtype == np.float64 else 2e-4
    np.testing.assert_allclose(d, d_ref, rtol=tol, atol=tol * n)


@pytest.mark.parametrize("stencil", ["7pt", "27pt"])
@pytest.mark.parametrize("shape,bz", [((8, 8, 8), 4), ((12, 10, 14), 4)])
def test_jacobi_fused_kernel(stencil, shape, bz):
    rng = np.random.default_rng(7)
    x = rng.standard_normal(shape).astype(np.float32)
    b = rng.standard_normal(shape).astype(np.float32)
    dinv = (1.0 / (12.0 if stencil == "7pt" else 52.0)) * np.ones(shape, np.float32)
    y = np.asarray(
        ops.jacobi_stencil_sweep(x, b, jnp.asarray(dinv), stencil=stencil, bz=bz)
    )
    y_ref = np.asarray(
        ref.jacobi_stencil_ref(x, b, jnp.asarray(dinv), stencil=stencil)
    )
    np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-5)


def test_jacobi_kernel_converges_on_poisson():
    """Fused sweeps actually smooth: residual decreases monotonically."""
    p = PoissonProblem(8, 8, 8, "7pt")
    a = poisson_scipy(p, dtype=np.float64)
    b3 = np.ones((8, 8, 8))
    dinv = np.asarray(1.0 / (a.diagonal() + (np.abs(a).sum(axis=1).A1 - np.abs(a.diagonal())))).reshape(8, 8, 8)
    x = np.zeros((8, 8, 8))
    res_prev = np.inf
    for _ in range(10):
        x = np.asarray(ops.jacobi_stencil_sweep(x, b3, jnp.asarray(dinv), stencil="7pt", bz=4))
        res = np.linalg.norm(b3.reshape(-1) - a @ x.reshape(-1))
        assert res < res_prev
        res_prev = res
