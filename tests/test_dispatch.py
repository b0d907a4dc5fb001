"""Kernel dispatch layer + fused hot-path kernels.

Interpret-mode Pallas vs the kernels/ref.py oracles in f32/f64 (including
non-multiple-of-chunk lengths), backend resolution, sweep-ledger
accounting, and kernels-on vs kernels-off end-to-end solves.
"""

import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import dispatch as kd
from repro.kernels import ops, ref


# ---------------------------------------------------------------------------
# Backend resolution
# ---------------------------------------------------------------------------


def test_backend_resolution_auto():
    # CPU container: auto resolves to jnp; pallas only on TPU backends.
    assert kd.backend() in kd.BACKENDS
    assert kd.available_backend() == (
        "pallas" if jax.default_backend() == "tpu" else "jnp"
    )


def test_backend_override_and_env(monkeypatch):
    with kd.use_backend("interpret"):
        assert kd.backend() == "interpret"
        assert kd.ops_for(None).backend == "interpret"
        # explicit choice beats the override; 'auto' defers to it
        assert kd.ops_for("jnp").backend == "jnp"
        assert kd.ops_for("auto").backend == "interpret"
    monkeypatch.setenv(kd.ENV_VAR, "interpret")
    assert kd.backend() == "interpret"
    monkeypatch.setenv(kd.ENV_VAR, "auto")
    assert kd.backend() == kd.available_backend()
    monkeypatch.setenv(kd.ENV_VAR, "bogus")
    with pytest.raises(ValueError):
        kd.backend()


def test_set_backend_validation():
    with pytest.raises(ValueError):
        kd.set_backend("nope")
    kd.set_backend("jnp")
    try:
        assert kd.backend() == "jnp"
    finally:
        kd.set_backend(None)


@pytest.mark.parametrize("explicit", [True, False])
def test_f64_pallas_is_refused_or_reported(explicit):
    # Mosaic has no 64-bit types: an explicit 'pallas' choice raises on f64
    # operands; only auto resolution may run the jnp reference, and the
    # energy trace records that choice
    from repro.energy import trace

    ops_ = kd.OpSet("pallas", auto=not explicit)
    f64 = np.dtype("float64")
    if explicit:
        with pytest.raises(ValueError, match="64-bit"):
            ops_._mode("fused_dots_n", f64)
        return
    with trace.capture() as tr:
        assert ops_._mode("fused_dots_n", f64) == "jnp"
        assert ops_._mode("axpy", np.dtype("float32")) == "pallas"
    assert trace.kernels_by_backend(tr) == {
        "jnp": ["fused_dots_n"], "pallas": ["axpy"],
    }


def test_interpret_refused_on_tpu(monkeypatch):
    # nothing runs interpreted on the TPU path
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="interpret"):
        kd.ops_for("interpret")
    assert kd.ops_for(None).backend == "pallas"
    x = jnp.ones(8)
    with pytest.raises(ValueError, match="interpret"):
        ops.fused_axpy(2.0, x, x, interpret=True)



# (variant, dtype, kernels, refused): what a v5e computed wrong is refused
# on a TPU backend, what it computed right still builds
TPU_SOLVERS = [
    ("block", np.float64, None, True),
    ("block", np.float32, "jnp", True),
    ("block", np.float32, None, False),
    ("sstep", np.float32, None, True),
    ("sstep", np.float64, None, False),
]


@pytest.mark.parametrize("variant,dtype,kernels,refused", TPU_SOLVERS)
def test_unverified_solvers_refused_on_tpu(monkeypatch, single_mesh, variant,
                                           dtype, kernels, refused):
    from repro.core.cg import make_block_solver, make_solver
    from repro.core.partition import partition_csr
    from repro.matrices.poisson import cube, poisson_scipy

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with jax.enable_x64(dtype == np.float64):
        mat = partition_csr(poisson_scipy(cube(4)), 1, dtype=dtype,
                            halo_depth=2 if variant == "sstep" else 1)
        if variant == "block":
            build = lambda: make_block_solver(single_mesh, mat,
                                              kernels=kernels)
        else:
            build = lambda: make_solver(single_mesh, mat, variant="sstep",
                                        kernels=kernels)
        if refused:
            with pytest.raises(ValueError, match="does not .* on a TPU"):
                build()
        else:
            assert callable(build())


# ---------------------------------------------------------------------------
# Fused kernels vs oracles (interpret mode), incl. ragged lengths
# ---------------------------------------------------------------------------

LENGTHS = [(2048, 512), (1000, 512), (100, 65536), (513, 128)]


def _tol(dtype, n):
    # no-x64 main process computes f64 inputs in f32; tol follows ACTUAL dtype
    return (1e-12, 1e-12 * max(n, 1)) if dtype == np.float64 else (2e-4, 2e-4 * n)


@pytest.mark.parametrize("n,chunk", LENGTHS)
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_fused_dots3_any_length(n, chunk, dtype):
    rng = np.random.default_rng(n)
    p, w, r = (jnp.asarray(rng.standard_normal(n).astype(dtype)) for _ in range(3))
    d = np.asarray(ops.fused_dots3(p, w, r, chunk=chunk, interpret=True))
    d_ref = np.asarray(ref.fused_dots3_ref(p, w, r))
    rtol, atol = _tol(d.dtype, n)
    np.testing.assert_allclose(d, d_ref, rtol=rtol, atol=atol)


@pytest.mark.parametrize("n,chunk", LENGTHS)
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_fused_dots_n_dedup(n, chunk, dtype):
    rng = np.random.default_rng(n + 1)
    r, w = (jnp.asarray(rng.standard_normal(n).astype(dtype)) for _ in range(2))
    u = r  # identity-preconditioner aliasing: {r, w} read once, (r,r) once
    d = np.asarray(ops.fused_dots_n([(r, u), (w, u), (r, r)], chunk=chunk,
                                    interpret=True))
    d_ref = np.asarray(ref.fused_dots_n_ref([(r, u), (w, u), (r, r)]))
    rtol, atol = _tol(d.dtype, n)
    np.testing.assert_allclose(d, d_ref, rtol=rtol, atol=atol)
    assert abs(d[0] - d[2]) == 0.0  # deduped pair computed once


@pytest.mark.parametrize("n,chunk", LENGTHS)
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_fused_axpy_family(n, chunk, dtype):
    rng = np.random.default_rng(n + 2)
    x1, y1, x2, y2 = (
        jnp.asarray(rng.standard_normal(n).astype(dtype)) for _ in range(4)
    )
    a1, a2 = dtype(0.37), dtype(-1.1)
    rtol, atol = _tol(np.asarray(x1).dtype, n)

    o = np.asarray(ops.fused_axpy(a1, x1, y1, chunk=chunk, interpret=True))
    np.testing.assert_allclose(o, np.asarray(ref.fused_axpy_ref(a1, x1, y1)),
                               rtol=rtol, atol=1e-5)

    o1, o2 = ops.fused_axpy2(a1, x1, y1, a2, x2, y2, chunk=chunk, interpret=True)
    r1, r2 = ref.fused_axpy2_ref(a1, x1, y1, a2, x2, y2)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(r1), rtol=rtol, atol=1e-5)
    np.testing.assert_allclose(np.asarray(o2), np.asarray(r2), rtol=rtol, atol=1e-5)

    o1, o2, d = ops.fused_axpy2_dots(a1, x1, y1, a2, x2, y2, chunk=chunk,
                                     interpret=True)
    r1, r2, dr = ref.fused_axpy2_dots_ref(a1, x1, y1, a2, x2, y2)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(r1), rtol=rtol, atol=1e-5)
    np.testing.assert_allclose(np.asarray(o2), np.asarray(r2), rtol=rtol, atol=1e-5)
    np.testing.assert_allclose(np.asarray(d), np.asarray(dr), rtol=rtol, atol=atol)


def test_fused_axpy_traced_scalar():
    f = jax.jit(lambda a, x, y: ops.fused_axpy(a, x, y, interpret=True))
    x = jnp.arange(300.0, dtype=jnp.float32)
    np.testing.assert_allclose(
        np.asarray(f(2.0, x, x)), 3.0 * np.arange(300.0), rtol=1e-6
    )


# ---------------------------------------------------------------------------
# Halo stencil kernel vs oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("stencil", ["7pt", "27pt"])
@pytest.mark.parametrize("shape,bz", [((8, 6, 10), 4), ((6, 5, 9), 3), ((4, 8, 8), 4)])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_stencil_halo_kernel(stencil, shape, bz, dtype):
    rng = np.random.default_rng(sum(shape))
    x = rng.standard_normal(shape).astype(dtype)
    prev = rng.standard_normal(shape[1:]).astype(dtype)
    nxt = rng.standard_normal(shape[1:]).astype(dtype)
    y = np.asarray(ops.stencil_spmv_halo(x, prev, nxt, stencil=stencil, bz=bz,
                                         interpret=True))
    y_ref = np.asarray(ref.stencil_halo_ref(x, prev, nxt, stencil=stencil))
    tol = 1e-12 if y.dtype == np.float64 else 2e-4
    np.testing.assert_allclose(y, y_ref, rtol=tol, atol=tol)


def test_stencil_halo_zero_halo_matches_dirichlet():
    x = np.random.default_rng(0).standard_normal((8, 7, 11))
    z = np.zeros((7, 11))
    y = np.asarray(ops.stencil_spmv_halo(x, z, z, stencil="7pt", bz=4,
                                         interpret=True))
    tol = 1e-10 if y.dtype == np.float64 else 2e-4
    np.testing.assert_allclose(y, np.asarray(ref.stencil7_ref(x)),
                               rtol=tol, atol=tol)


def test_pick_bz():
    from repro.kernels.spmv_stencil import pick_bz

    assert pick_bz(16) == 8
    assert pick_bz(12) == 6
    assert pick_bz(7) == 7
    assert pick_bz(13) == 1


@pytest.mark.parametrize("n", [1000, 513])
@pytest.mark.parametrize("s", [2, 4])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_sstep_ops_family(n, s, dtype):
    # the three fused s-step ops (basis A-conjugation, one-pass Gram
    # reduction operands, blocked x/r update) vs the ref.py oracles,
    # jnp and interpret backends
    rng = np.random.default_rng(11)
    mk = lambda *shape: jnp.asarray(rng.standard_normal(shape), dtype)
    pb, wb, wp, qp = (mk(n, s) for _ in range(4))
    r, x = mk(n), mk(n)
    bmat, dinv, a = mk(s, s), mk(s), mk(s)
    rtol, atol = _tol(pb.dtype, n)
    g_ref = np.asarray(ref.sstep_gram_ref(pb, wb, wp, r))
    p_ref, w_ref = ref.sstep_basis_ref(bmat, dinv, qp, pb, wp, wb)
    x_ref, r_ref = ref.sstep_update_ref(a, qp, wp, x, r)
    assert g_ref.shape == (2 * s * s + s + 1,)
    for b in ("jnp", "interpret"):
        o = kd.ops_for(b)
        np.testing.assert_allclose(
            np.asarray(o.sstep_gram(pb, wb, wp, r)), g_ref,
            rtol=rtol, atol=atol)
        p_out, w_out = o.sstep_basis(bmat, dinv, qp, pb, wp, wb)
        np.testing.assert_allclose(np.asarray(p_out), np.asarray(p_ref),
                                   rtol=rtol, atol=atol)
        np.testing.assert_allclose(np.asarray(w_out), np.asarray(w_ref),
                                   rtol=rtol, atol=atol)
        x_out, r_out = o.sstep_update(a, qp, wp, x, r)
        np.testing.assert_allclose(np.asarray(x_out), np.asarray(x_ref),
                                   rtol=rtol, atol=atol)
        np.testing.assert_allclose(np.asarray(r_out), np.asarray(r_ref),
                                   rtol=rtol, atol=atol)


# ---------------------------------------------------------------------------
# OpSet dispatch + sweep ledger
# ---------------------------------------------------------------------------


def test_opset_backends_agree():
    rng = np.random.default_rng(3)
    n = 777
    x, y = (jnp.asarray(rng.standard_normal(n), jnp.float32) for _ in range(2))
    outs = {
        b: np.asarray(kd.ops_for(b).axpy(jnp.float32(0.5), x, y))
        for b in ("jnp", "interpret")
    }
    np.testing.assert_allclose(outs["jnp"], outs["interpret"], rtol=1e-6)


def test_ledger_counts_iteration_ops():
    ops_set = kd.ops_for("jnp")
    x = jnp.ones((64,))
    with kd.record_sweeps() as led:
        with kd.ledger_section("iteration"):
            ops_set.axpy(1.0, x, x)
            ops_set.fused_dots_n([(x, x)])
            ops_set.stencil_matvec(
                jnp.ones((4, 4, 4)), jnp.zeros((4, 4)), jnp.zeros((4, 4))
            )
    assert led.vector_sweeps("iteration") == 2
    assert led.spmv_calls("iteration") == 1
    # outside the recording context nothing is counted
    ops_set.axpy(1.0, x, x)
    assert led.vector_sweeps("iteration") == 2


@pytest.mark.parametrize("variant", ["hs", "fcg"])
def test_solver_hot_loop_sweep_bound(variant):
    """Acceptance: <= 3 full-vector HBM sweeps/iter outside the SpMV."""
    from repro.core.stencil_solver import make_stencil_solver_fn
    from repro.matrices.poisson import PoissonProblem

    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("shards",))
    p = PoissonProblem(8, 8, 8, "7pt")
    vec = jax.ShapeDtypeStruct((1, p.n), "float64")
    with kd.record_sweeps() as led:
        solve = make_stencil_solver_fn(mesh, p, 1, variant=variant)
        solve.lower(vec, vec)
    assert led.vector_sweeps("iteration") <= 3
    assert led.spmv_calls("iteration") == 1


# ---------------------------------------------------------------------------
# End-to-end: kernels on vs off, identical convergence
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("stencil", ["7pt", "27pt"])
@pytest.mark.parametrize("variant", ["hs", "fcg"])
def test_stencil_solver_kernels_on_off(stencil, variant):
    code = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.matrices.poisson import PoissonProblem, poisson_scipy, default_rhs
from repro.core.stencil_solver import make_stencil_solver_fn
import scipy.sparse.linalg as spla

S = 4
mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:S]), ("shards",))
p = PoissonProblem(10, 9, 16, "%(stencil)s")
a = poisson_scipy(p, dtype=np.float64)
b = default_rhs(p.n)
R = p.n // S
bv = jnp.asarray(b).reshape(S, R); x0 = jnp.zeros_like(bv)
x_ref = spla.spsolve(a.tocsc(), b)
got = {}
for backend in ("jnp", "interpret"):
    solve = make_stencil_solver_fn(mesh, p, S, variant="%(variant)s",
                                   tol=1e-10, maxiter=500, kernels=backend)
    res = solve(bv, x0)
    xs = np.asarray(res.x).reshape(-1)
    assert np.abs(xs - x_ref).max() < 1e-8, backend
    got[backend] = (int(res.iters), float(res.rel_residual))
j, i = got["jnp"], got["interpret"]
assert j[0] == i[0], (j, i)                 # identical iteration count
assert abs(j[1] - i[1]) < 1e-10, (j, i)     # identical relative residual
print("ONOFF_OK", j)
"""
    from tests.conftest import run_multidevice

    out = run_multidevice(
        code % {"stencil": stencil, "variant": variant}, n_devices=4
    )
    assert "ONOFF_OK" in out


def test_hotpath_fusion_benchmark_smoke():
    """The sweep-accounting benchmark itself must keep running."""
    import subprocess
    import sys

    from tests.conftest import REPO, SRC

    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-c",
         "import benchmarks.hotpath_fusion as h; h.main(smoke=True)"],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO,
    )
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "Measured (traced) HBM sweeps" in r.stdout
