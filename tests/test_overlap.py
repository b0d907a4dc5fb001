"""Communication-hiding layer: split SpMV, pipelined CG, overlap ledger.

Acceptance coverage for the overlap subsystem:

* the interior/boundary row split reproduces the unsplit (full-row ext
  block) SpMV **bitwise** on 1 and 4 shards, for the ring, stencil, and
  allgather layouts;
* the boundary-plane stencil kernel equals the corresponding planes of the
  single-call slab kernel bitwise, per backend;
* ``pipecg`` converges to the same residual as ``hs`` on the Poisson smoke
  problem (and within its 4-sweep hot-loop bound);
* the ledger region-sum invariant still holds with the ``overlap`` region
  active, and overlap strictly reduces ``totals.comm_exposed_s``.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from tests.conftest import run_multidevice


# ---------------------------------------------------------------------------
# Interior/boundary split == unsplit SpMV, bitwise
# ---------------------------------------------------------------------------


def _unsplit_spmv(mesh, mat, de_full, ce_full, xp):
    """The pre-split formulation: full-row ext block, y = A_loc x + A_ext x."""
    from jax.sharding import PartitionSpec as P

    from repro.core.spmv import dist_specs, ell_matvec, gather_ext, local_block

    specs = dist_specs(mat)

    def fn(m, d, c, xv):
        mb = local_block(m)
        x_ext = gather_ext(mb, xv[0], "shards")
        y = ell_matvec(mb.data_loc, mb.col_loc, xv[0])
        y = y + ell_matvec(d[0], c[0], x_ext)
        return y[None]

    f = jax.jit(jax.shard_map(
        fn, mesh=mesh,
        in_specs=(specs, P("shards", None, None), P("shards", None, None),
                  P("shards", None)),
        out_specs=P("shards", None),
    ))
    return np.asarray(f(mat, de_full, ce_full, xp))


def test_split_spmv_bitwise_single_shard(single_mesh):
    from repro.core.partition import expand_boundary, pad_vector, partition_csr
    from repro.core.spmv import make_spmv, shard_matrix, shard_vector
    from repro.matrices.poisson import cube, poisson_scipy

    p = cube(8, "7pt")
    a = poisson_scipy(p)
    mat = shard_matrix(single_mesh, partition_csr(a, 1))
    assert mat.n_bnd == (0,)  # one shard: no ghost-touching rows
    x = np.random.default_rng(0).standard_normal(p.n)
    xp = shard_vector(single_mesh, pad_vector(x, mat))
    y_split = np.asarray(make_spmv(single_mesh, mat)(mat, xp))
    de, ce = expand_boundary(mat)
    y_ref = _unsplit_spmv(single_mesh, mat, jnp.asarray(de), jnp.asarray(ce), xp)
    np.testing.assert_array_equal(y_split, y_ref)


SPLIT_SNIPPET = r"""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.matrices.poisson import cube, poisson_scipy
from repro.core.partition import (partition_csr, partition_stencil,
                                  pad_vector, expand_boundary)
from repro.core.spmv import (dist_specs, ell_matvec, gather_ext, local_block,
                             make_spmv, shard_matrix, shard_vector)
from repro.launch.mesh import make_solver_mesh

S = 4
p = cube(12, "7pt")
A = poisson_scipy(p)
x = np.random.default_rng(0).standard_normal(p.n)
mesh = make_solver_mesh(S)

for name, build in (("csr", lambda: partition_csr(A, S)),
                    ("stencil", lambda: partition_stencil(p, S)),
                    ("allgather",
                     lambda: partition_csr(A, S, force_allgather=True))):
    mat = shard_matrix(mesh, build())
    de, ce = expand_boundary(mat)
    de, ce = jnp.asarray(de), jnp.asarray(ce)
    xp = shard_vector(mesh, pad_vector(x, mat))
    for overlap in (True, False):
        y_split = np.asarray(make_spmv(mesh, mat, overlap=overlap)(mat, xp))
        specs = dist_specs(mat)
        def unsplit(m, d, c, xv):
            mb = local_block(m)
            x_ext = gather_ext(mb, xv[0], "shards")
            y = ell_matvec(mb.data_loc, mb.col_loc, xv[0])
            return (y + ell_matvec(d[0], c[0], x_ext))[None]
        f = jax.jit(jax.shard_map(unsplit, mesh=mesh,
            in_specs=(specs, P("shards", None, None), P("shards", None, None),
                      P("shards", None)),
            out_specs=P("shards", None)))
        y_ref = np.asarray(f(mat, de, ce, xp))
        assert np.array_equal(y_split, y_ref), (name, overlap)
print("SPLIT_OK")
"""


def test_split_spmv_bitwise_4_shards():
    out = run_multidevice(SPLIT_SNIPPET, n_devices=4)
    assert "SPLIT_OK" in out


# ---------------------------------------------------------------------------
# Boundary-plane stencil kernel (the overlap fix-up) vs the slab kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("stencil", ["7pt", "27pt"])
@pytest.mark.parametrize("shape", [(8, 6, 10), (2, 5, 9)])
def test_stencil_boundary_matches_slab_planes(stencil, shape):
    from repro.kernels import ref
    from repro.kernels.spmv_stencil import (
        pick_bz,
        stencil_spmv_boundary,
        stencil_spmv_halo,
    )

    rng = np.random.default_rng(sum(shape))
    x = rng.standard_normal(shape)
    prev = rng.standard_normal(shape[1:])
    nxt = rng.standard_normal(shape[1:])
    # interpret-mode kernel vs the full interpret-mode slab kernel: bitwise
    full_k = np.asarray(stencil_spmv_halo(
        x, prev, nxt, stencil=stencil, bz=pick_bz(shape[0]), interpret=True
    ))
    bd_k = np.asarray(stencil_spmv_boundary(
        x, prev, nxt, stencil=stencil, interpret=True
    ))
    np.testing.assert_array_equal(bd_k[0], full_k[0])
    np.testing.assert_array_equal(bd_k[1], full_k[-1])
    # jnp oracle vs the full jnp oracle: bitwise
    full_r = np.asarray(ref.stencil_halo_ref(x, prev, nxt, stencil=stencil))
    bd_r = np.asarray(ref.stencil_boundary_ref(x, prev, nxt, stencil=stencil))
    np.testing.assert_array_equal(bd_r[0], full_r[0])
    np.testing.assert_array_equal(bd_r[1], full_r[-1])


# ---------------------------------------------------------------------------
# pipecg: convergence + hot-loop sweep bound
# ---------------------------------------------------------------------------


def test_pipecg_matches_hs_residual(single_mesh):
    from repro.core.cg import solve_cg
    from repro.core.partition import partition_csr, unpad_vector
    from repro.core.spmv import shard_matrix
    from repro.matrices.poisson import cube, default_rhs, poisson_scipy

    p = cube(8, "7pt")
    a = poisson_scipy(p, dtype=np.float64)
    b = default_rhs(p.n)
    mat = shard_matrix(single_mesh, partition_csr(a, 1))
    got = {}
    for variant in ("hs", "pipecg"):
        res = solve_cg(
            single_mesh, mat, b.astype(np.float32), variant=variant,
            tol=1e-6, maxiter=300,
        )
        got[variant] = res
        x = unpad_vector(np.asarray(res.x), mat)
        np.testing.assert_allclose(a @ x, b, rtol=1e-3, atol=1e-3)
    # same math, same tolerance: residuals agree (pipecg may run one extra
    # iteration — its convergence check lags the update by one reduction)
    hs, pipe = got["hs"], got["pipecg"]
    assert float(pipe.rel_residual) < 1e-5
    assert abs(int(pipe.iters) - int(hs.iters)) <= 2
    assert float(pipe.rel_residual) == pytest.approx(
        float(hs.rel_residual), rel=1.0
    )


def test_pipecg_sweep_bound():
    """pipecg: <= 4 full-vector HBM sweeps/iter outside the SpMV (the +1 vs
    hs/fcg buys the hidden all-reduce), exactly one SpMV per iteration."""
    from repro.core.stencil_solver import make_stencil_solver_fn
    from repro.kernels import dispatch as kd
    from repro.matrices.poisson import PoissonProblem
    from repro.roofline.analysis import CG_HOTPATH

    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("shards",))
    p = PoissonProblem(8, 8, 8, "7pt")
    vec = jax.ShapeDtypeStruct((1, p.n), "float64")
    with kd.record_sweeps() as led:
        solve = make_stencil_solver_fn(mesh, p, 1, variant="pipecg")
        solve.lower(vec, vec)
    sweeps = led.vector_sweeps("iteration")
    assert sweeps <= 4
    assert led.spmv_calls("iteration") == 1
    # the traced count is what the roofline hot-path model declares
    assert sweeps == CG_HOTPATH["pipecg"]["fused"][1]


PIPECG_MULTI_SNIPPET = r"""
import numpy as np
from repro.matrices.poisson import cube, poisson_scipy, default_rhs
from repro.core.partition import partition_stencil, unpad_vector
from repro.core.spmv import shard_matrix
from repro.core.cg import solve_cg
from repro.launch.mesh import make_solver_mesh
import scipy.sparse.linalg as spla

S = 8
p = cube(16, "7pt")
A = poisson_scipy(p)
b = default_rhs(p.n)
mesh = make_solver_mesh(S)
mat = shard_matrix(mesh, partition_stencil(p, S))
x_ref = spla.spsolve(A.tocsc(), b)
iters = {}
for variant in ("hs", "pipecg"):
    res = solve_cg(mesh, mat, b, variant=variant, tol=1e-10, maxiter=500)
    xs = unpad_vector(np.asarray(res.x), mat)
    assert np.abs(xs - x_ref).max() < 1e-6, variant
    iters[variant] = int(res.iters)
assert abs(iters["pipecg"] - iters["hs"]) <= 2, iters
print("PIPECG_MULTI_OK", iters)
"""


def test_pipecg_multidevice():
    out = run_multidevice(PIPECG_MULTI_SNIPPET, n_devices=8)
    assert "PIPECG_MULTI_OK" in out


# ---------------------------------------------------------------------------
# Ledger: overlap region active, region-sum invariant, exposed-comm ordering
# ---------------------------------------------------------------------------


def _solve_ledger(overlap: bool, *, amg: bool = False) -> dict:
    import json
    import os
    import subprocess
    import sys
    import tempfile

    from tests.conftest import REPO, SRC

    fd, path = tempfile.mkstemp(suffix=".json")
    os.close(fd)
    try:
        env = dict(os.environ)
        env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
        cmd = [sys.executable, "-m", "repro.launch.solve", "--devices", "2",
               "--problem", "poisson7", "--side", "8", "--tol", "1e-6",
               "--maxiter", "60", "--ledger", path]
        if amg:
            cmd.append("--amg")
        if not overlap:
            cmd.append("--no-overlap")
        r = subprocess.run(cmd, capture_output=True, text=True, timeout=900,
                           env=env, cwd=REPO)
        assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
        return json.load(open(path))
    finally:
        os.unlink(path)


def test_overlap_ledger_invariants():
    led_on = _solve_ledger(overlap=True)
    led_off = _solve_ledger(overlap=False)
    on = led_on["solvers"]["BCMGX-analog"]
    off = led_off["solvers"]["BCMGX-analog"]
    # overlap region active; serialized run keeps the spmv/halo pair
    assert "overlap" in on["regions"]
    assert {"spmv", "halo"} <= set(off["regions"])
    # region-sum invariant holds with the overlap region active
    for s in (on, off):
        total = s["totals"]["de_total"]
        region_sum = sum(r["de_j"] for r in s["regions"].values())
        assert abs(region_sum - total) <= 0.01 * total
    # identical algorithm: same iterations either way
    assert on["iters"] == off["iters"]
    # the acceptance ordering: same total comm, strictly less exposed
    assert on["totals"]["comm_s"] == pytest.approx(off["totals"]["comm_s"])
    assert on["totals"]["comm_exposed_s"] < off["totals"]["comm_exposed_s"]
    assert on["totals"]["comm_hidden_s"] > 0 == off["totals"]["comm_hidden_s"]


def test_no_overlap_serializes_the_vcycle_spmvs():
    """--amg --no-overlap must serialize the preconditioner's level SpMVs
    too (the overlap_default plumbing): no overlap region anywhere, the
    halo back in its own region."""
    led = _solve_ledger(overlap=False, amg=True)
    s = led["solvers"]["BCMGX-analog"]
    assert "overlap" not in s["regions"]
    assert {"halo", "spmv", "vcycle", "reductions"} <= set(s["regions"])
    assert s["totals"]["comm_hidden_s"] == 0.0
