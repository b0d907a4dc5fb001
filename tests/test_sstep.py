"""s-step CG building blocks: deep ghost zones + the matrix-powers SpMV.

Host-side property tests pin the partition-layer invariants of
``partition_csr(..., halo_depth=k)`` (format-agnostic ghost plans, nested
widening, depth-1 bit-identity); the 8-device subprocess tests prove the
value-level equivalence that makes the communication-avoiding trade
legal: ONE widened exchange + redundant ghost recompute
(``matrix_powers``) computes exactly what k serial depth-1 exchanges
(``spmv_shard`` chained) compute — on the 1-D ring, on the 2x2 grid, and
for every interior format.
"""

import numpy as np
import pytest
import scipy.sparse as sp
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.partition import partition_csr
from tests.conftest import run_multidevice


def _banded_spd(n: int, bw: int, seed: int) -> sp.csr_matrix:
    """Random symmetric positive-definite band matrix (ring-partitionable)."""
    rng = np.random.default_rng(seed)
    diags = [rng.standard_normal(n - d) * 0.3 for d in range(1, bw + 1)]
    a = sp.diags(diags, range(1, bw + 1), shape=(n, n))
    a = a + a.T
    a = a + sp.eye(n) * (2.0 * bw + 1.0)
    return a.tocsr()


@settings(max_examples=15, deadline=None)
@given(
    n=st.integers(48, 96),
    bw=st.integers(1, 3),
    n_shards=st.sampled_from([2, 3, 4]),
    k=st.sampled_from([2, 3, 4]),
    seed=st.integers(0, 1000),
)
def test_deep_halo_partition_invariants(n, bw, n_shards, k, seed):
    """halo_depth=k ghost zones: nested, bounded, format-agnostic.

    * the depth-k exchange carries at most k times the depth-1 rows (the
      transitive closure of a banded coupling widens by at most one
      depth-1 halo per step) and at least the depth-1 rows;
    * ghost rows replicate only depth < k ghosts, so depth 1 has none;
    * the ghost plan is a property of the PARTITION, not the interior
      format — ell/hyb/bcsr share the identical plan and ghost block.
    """
    a = _banded_spd(n, bw, seed)
    m1 = partition_csr(a, n_shards)
    mk = partition_csr(a, n_shards, halo_depth=k)
    if m1.plan.mode != "ring":
        return  # degenerate draw (single shard owns everything)
    assert mk.plan.mode == "ring"
    assert mk.halo_depth == k and m1.halo_depth == 1
    w1 = sum(m1.plan.widths)
    wk = sum(mk.plan.widths)
    assert w1 <= wk <= k * w1, (w1, wk, k)
    # depth 1 carries no replicated ghost rows; depth k replicates the
    # depth < k ghosts it must recompute between chained applications
    assert m1.n_ghost_rows == 0 and m1.ghost_slots == 0
    if wk > w1:
        assert mk.n_ghost_rows > 0
    for fmt in ("hyb", "bcsr"):
        mf = partition_csr(a, n_shards, fmt=fmt, halo_depth=k)
        assert mf.plan == mk.plan, fmt
        np.testing.assert_array_equal(
            np.asarray(mf.ghost_col), np.asarray(mk.ghost_col)
        )
        np.testing.assert_array_equal(
            np.asarray(mf.ghost_pos), np.asarray(mk.ghost_pos)
        )
        np.testing.assert_allclose(
            np.asarray(mf.ghost_data), np.asarray(mk.ghost_data)
        )


@settings(max_examples=10, deadline=None)
@given(
    n=st.integers(48, 96),
    bw=st.integers(1, 2),
    n_shards=st.sampled_from([2, 4]),
    seed=st.integers(0, 1000),
)
def test_depth1_is_bit_identical_to_historical_build(n, bw, n_shards, seed):
    """halo_depth=1 must reproduce the historical partition exactly —
    every gated baseline rests on this."""
    a = _banded_spd(n, bw, seed)
    m0 = partition_csr(a, n_shards)
    m1 = partition_csr(a, n_shards, halo_depth=1)
    assert m0.plan == m1.plan
    for field in ("data_loc", "col_loc", "data_ext", "col_ext",
                  "bnd_rows", "send_sel"):
        np.testing.assert_array_equal(
            np.asarray(getattr(m0, field)), np.asarray(getattr(m1, field))
        )
    assert m1.ghost_slots == 0 and m1.halo_depth == 1


MP_RING_SNIPPET = r"""
import numpy as np
import jax
from jax.sharding import PartitionSpec as P
import scipy.sparse as sp
from repro.core.partition import pad_vector, partition_csr, unpad_vector
from repro.core.spmv import (
    dist_specs, local_block, matrix_powers, shard_matrix, shard_vector,
    spmv_shard,
)
from repro.launch.mesh import make_solver_mesh
from repro.matrices.poisson import cube, poisson_scipy

S = 8
mesh = make_solver_mesh(S)


def banded_spd(n, bw, seed):
    rng = np.random.default_rng(seed)
    diags = [rng.standard_normal(n - d) * 0.3 for d in range(1, bw + 1)]
    a = sp.diags(diags, range(1, bw + 1), shape=(n, n))
    a = a + a.T + sp.eye(n) * (2.0 * bw + 1.0)
    return a.tocsr()


def powers(mesh, mat, p, s, axis="shards"):
    specs = dist_specs(mat, axis)

    def fn(m, x):
        return matrix_powers(local_block(m), x[0], s, axis)[None]

    return jax.shard_map(
        fn, mesh=mesh, in_specs=(specs, P(axis, None)),
        out_specs=P(axis, None, None), check_vma=False,
    )(mat, p)


def serial(mesh, mat, p, s, axis="shards"):
    specs = dist_specs(mat, axis)

    def fn(m, x):
        mb = local_block(m)
        outs = []
        for _ in range(s):
            x = spmv_shard(mb, x[0], axis, overlap=False)[None]
            outs.append(x[0])
        return jax.numpy.stack(outs)[None]

    return jax.shard_map(
        fn, mesh=mesh, in_specs=(specs, P(axis, None)),
        out_specs=P(axis, None, None), check_vma=False,
    )(mat, p)


cases = [poisson_scipy(cube(12, "7pt"))]
cases += [banded_spd(512, bw, seed) for bw, seed in ((1, 0), (2, 1), (3, 2))]
for a in cases:
    n = a.shape[0]
    x = np.random.default_rng(7).standard_normal(n)
    for fmt in ("ell", "hyb", "bcsr"):
        for s in (2, 3, 4):
            deep = shard_matrix(mesh, partition_csr(a, S, fmt=fmt, halo_depth=s))
            flat = shard_matrix(mesh, partition_csr(a, S, fmt=fmt))
            xp = shard_vector(mesh, pad_vector(x, deep))
            got = np.asarray(powers(mesh, deep, xp, s))
            ref = np.asarray(serial(mesh, flat, shard_vector(mesh, pad_vector(x, flat)), s))
            err = np.abs(got - ref).max() / max(np.abs(ref).max(), 1.0)
            assert err <= 1e-12, (fmt, s, err)
            # ground truth: the actual monomial basis
            acc = x.copy()
            for j in range(s):
                acc = a @ acc
                gj = unpad_vector(got[:, j], deep)
                ej = np.abs(gj - acc).max() / max(np.abs(acc).max(), 1.0)
                assert ej <= 1e-11, (fmt, s, j, ej)
print("MP_RING_OK")
"""


def test_matrix_powers_matches_serial_exchanges_ring():
    """ONE widened exchange == s serial depth-1 exchanges, to 1e-12,
    for every interior format on the 8-shard ring."""
    out = run_multidevice(MP_RING_SNIPPET, n_devices=8)
    assert "MP_RING_OK" in out


MP_GRID_SNIPPET = r"""
import numpy as np
import jax
from jax.sharding import PartitionSpec as P
from repro.core.partition import (
    pad_vector, partition_csr, pencil_partition, unpad_vector,
)
from repro.core.spmv import (
    dist_specs, local_block, matrix_powers, shard_matrix, shard_vector,
    spmv_shard,
)
from repro.launch.mesh import make_grid_mesh
from repro.matrices.poisson import cube, poisson_scipy

grid = (2, 2)
S = 4
mesh = make_grid_mesh(*grid)
axis = ("rows", "cols")
p = cube(12, "7pt")
a = poisson_scipy(p)
perm, part = pencil_partition(p, grid)
ag = a[perm][:, perm].tocsr()
x = np.random.default_rng(3).standard_normal(a.shape[0])


def powers(mat, xp, s):
    specs = dist_specs(mat, axis)

    def fn(m, v):
        return matrix_powers(local_block(m), v[0], s, axis)[None]

    return jax.shard_map(
        fn, mesh=mesh, in_specs=(specs, P(axis, None)),
        out_specs=P(axis, None, None), check_vma=False,
    )(mat, xp)


def serial(mat, xp, s):
    specs = dist_specs(mat, axis)

    def fn(m, v):
        mb = local_block(m)
        outs = []
        for _ in range(s):
            v = spmv_shard(mb, v[0], axis, overlap=False)[None]
            outs.append(v[0])
        return jax.numpy.stack(outs)[None]

    return jax.shard_map(
        fn, mesh=mesh, in_specs=(specs, P(axis, None)),
        out_specs=P(axis, None, None), check_vma=False,
    )(mat, xp)


for s in (2, 3):
    deep = shard_matrix(
        mesh, partition_csr(ag, S, grid=grid, partition=part, halo_depth=s)
    )
    assert deep.plan.mode == "grid"
    flat = shard_matrix(
        mesh, partition_csr(ag, S, grid=grid, partition=part)
    )
    xp = shard_vector(mesh, pad_vector(x, deep), axis)
    got = np.asarray(powers(deep, xp, s))
    ref = np.asarray(serial(flat, shard_vector(mesh, pad_vector(x, flat), axis), s))
    err = np.abs(got - ref).max() / max(np.abs(ref).max(), 1.0)
    assert err <= 1e-12, (s, err)
    acc = x.copy()
    for j in range(s):
        acc = ag @ acc
        gj = unpad_vector(got[:, j], deep)
        ej = np.abs(gj - acc).max() / max(np.abs(acc).max(), 1.0)
        assert ej <= 1e-11, (s, j, ej)
print("MP_GRID_OK")
"""


def test_matrix_powers_matches_serial_exchanges_grid():
    """Same equivalence on the 2x2 process grid (two-hop corner halos)."""
    out = run_multidevice(MP_GRID_SNIPPET, n_devices=4)
    assert "MP_GRID_OK" in out


ILL_COND_SNIPPET = r"""
import numpy as np
import scipy.sparse as sp
from repro.core.cg import solve_cg
from repro.core.partition import partition_csr, unpad_vector
from repro.core.spmv import shard_matrix
from repro.launch.mesh import make_solver_mesh

S = 4
n = 256
# 1-D Laplacian, symmetrically scaled by a 2-decade diagonal:
# cond ~ 4e5 — raw monomial bases lose independence here without the
# A-norm column scaling in the s-step body.  The attainable accuracy
# of the monomial basis degrades with s (the Gram system conditioning
# grows like cond(A)^s), so the agreement bound is per-s: 1e-8 at
# s=2 (the comm-avoiding gate's setting), 1e-7 at s=4.
lap = sp.diags([-np.ones(n - 1), 2.0 * np.ones(n), -np.ones(n - 1)],
               [-1, 0, 1]).tocsr()
d = np.logspace(0, 1, n)
D = sp.diags(d)
a = (D @ lap @ D).tocsr()
b = np.ones(n)
mesh = make_solver_mesh(S)

res_h = solve_cg(
    mesh, shard_matrix(mesh, partition_csr(a, S)), b,
    variant="hs", tol=1e-10, maxiter=8000,
)
assert float(res_h.rel_residual) < 1e-9, float(res_h.rel_residual)
for s, agree_tol in ((2, 1e-8), (4, 1e-7)):
    mat = shard_matrix(mesh, partition_csr(a, S, halo_depth=s))
    res_s = solve_cg(
        mesh, mat, b, variant="sstep", s=s, tol=1e-10, maxiter=8000,
    )
    assert float(res_s.rel_residual) < 1e-9, (s, float(res_s.rel_residual))
    xh = unpad_vector(np.asarray(res_h.x), mat)
    xs = unpad_vector(np.asarray(res_s.x), mat)
    err = np.abs(xs - xh).max() / np.abs(xh).max()
    assert err <= agree_tol, (s, err)
print("ILL_OK")
"""


def test_sstep_ill_conditioned_matches_hs():
    """The A-norm basis scaling keeps s-step CG convergent on a
    ~4e5-condition system; the solution agrees with hs to 1e-8 at s=2."""
    out = run_multidevice(ILL_COND_SNIPPET, n_devices=4)
    assert "ILL_OK" in out
