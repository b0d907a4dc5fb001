"""Compile the main path's kernels for a described TPU v5e, no chip needed.

Interpret-mode tests check what the kernels compute; only the TPU's own
compiler checks what it accepts (block tiling, SMEM/VMEM budgets, 64-bit
types, layouts). Every case here lowers and compiles at real widths
(n = 128^3) for one chip of a described ``v5e:2x2`` topology. The f32
kernels compile both with x64 off and with x64 on, since the solver entry
points turn x64 on for the whole process. The solver cases compile the
default f64 ``hs`` solver, the f64 block-HS solver that batched serving
runs (8 right-hand sides) and the f64 ``sstep`` solver, and check that
their buffers fit one chip's 16 GB.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

SIDE = 128
N = SIDE**3
HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # compiles for a described chip are written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _vec_ops(sd):
    from repro.kernels.dispatch import OpSet

    ops = OpSet("pallas")
    v = sd((N,))
    a = sd(())
    return {
        "fused_dots_n": (lambda x, y: ops.fused_dots_n([(x, y), (x, x)]),
                         [v, v]),
        "axpy": (ops.axpy, [a, v, v]),
        "fused_axpy2": (lambda a, x, y: ops.fused_axpy2(a, x, y, a, y, x),
                        [a, v, v]),
        "fused_axpy2_dots": (
            lambda a, x, y: ops.fused_axpy2_dots(a, x, y, a, y, x),
            [a, v, v],
        ),
    }


def _spmv_ops(sd):
    from repro.kernels.dispatch import OpSet
    from repro.kernels.spmv_stencil import stencil_spmv

    ops = OpSet("pallas")
    slab, plane = sd((SIDE, SIDE, SIDE)), sd((SIDE, SIDE))
    # BCSR of a 7-point matrix in 4x4 tiles: 7 tiles per block row
    nb, bpr = N // 4, 7
    bcsr = [sd((nb * bpr, 4, 4)), sd((nb * bpr,), jnp.int32), sd((N,))]
    return {
        "stencil_spmv_7pt": (lambda x: stencil_spmv(x, stencil="7pt"),
                             [slab]),
        "stencil_spmv_27pt": (lambda x: stencil_spmv(x, stencil="27pt"),
                              [slab]),
        "stencil_spmv_halo": (ops.stencil_matvec, [slab, plane, plane]),
        "stencil_spmv_boundary": (ops.stencil_boundary,
                                  [slab, plane, plane]),
        "bcsr_spmv": (
            lambda b, c, x: ops.bcsr_spmv(b, c, x, n_brows=nb, bpr=bpr),
            bcsr,
        ),
    }


def _block_ops(sd):
    from repro.kernels.dispatch import OpSet

    ops = OpSet("pallas")
    blk, m, mask = sd((N, 8)), sd((8, 8)), sd((8,))
    return {
        "block_gram": (lambda x, y: ops.block_gram([(x, y), (x, x)]),
                       [blk, blk]),
        "block_update": (ops.block_update, [m, blk, blk, mask]),
    }


def _kernel_case(name, sd):
    for table in (_vec_ops, _spmv_ops, _block_ops):
        cases = table(sd)
        if name in cases:
            return cases[name]
    raise KeyError(name)


KERNELS = (
    "fused_dots_n", "axpy", "fused_axpy2", "fused_axpy2_dots",
    "stencil_spmv_7pt", "stencil_spmv_27pt", "stencil_spmv_halo",
    "stencil_spmv_boundary", "bcsr_spmv", "block_gram", "block_update",
)
CASES = [(k, x64) for k in KERNELS for x64 in (False, True)]
SOLVERS = ("hs_solver_f64", "block_hs_solver_f64", "sstep_solver_f64")
CASES += [(k, True) for k in SOLVERS]
NRHS = 8  # the serving engine's default batch width


def _solver_f64(topo, case):
    """One chip, 7-point Poisson at 128^3, ELL, f64, auto kernels (the jnp
    reference for f64): the default ``hs`` solve, block-HS on an (n, 8)
    block, or ``sstep``."""
    from repro.core.cg import (
        abstract_stencil_dist,
        make_block_solver,
        make_solver_fn,
    )
    from repro.core.spmv import dist_specs
    from repro.matrices.poisson import cube

    mesh = jax.sharding.Mesh(np.asarray(topo.devices[:1]), ("shards",))
    mat = abstract_stencil_dist(cube(SIDE, "7pt"), 1, dtype="float64")
    mat_s = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, s)
        ),
        mat, dist_specs(mat, "shards"),
    )
    if case == "block_hs_solver_f64":
        blk = jax.ShapeDtypeStruct(
            (1, N, NRHS), jnp.float64,
            sharding=NamedSharding(mesh, P("shards", None, None)),
        )
        solve = make_block_solver(mesh, mat, kernels="jnp", maxiter=2000)
        solve = solve.func
        return solve.lower(mat_s, blk, blk).compile()
    vec = jax.ShapeDtypeStruct(
        (1, N), jnp.float64, sharding=NamedSharding(mesh, P("shards", None))
    )
    variant = case.split("_")[0]
    solve = make_solver_fn(mesh, mat, variant=variant, kernels="jnp",
                           maxiter=2000)
    return solve.lower(mat_s, vec, vec).compile()


@pytest.mark.parametrize("case,x64", CASES)
def test_compiles_for_v5e(topo, one_chip, case, x64):
    with jax.enable_x64(x64):
        if case in SOLVERS:
            compiled = _solver_f64(topo, case)
            mem = compiled.memory_analysis()
            used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                    + mem.temp_size_in_bytes)
            assert used < HBM_BYTES, f"{used / 1e9:.2f} GB > 16 GB"
            return

        def sd(shape, dtype=jnp.float32):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        fn, args = _kernel_case(case, sd)
        compiled = jax.jit(fn).lower(*args).compile()
    # the Pallas kernel itself was compiled, not a reference in its place
    assert "tpu_custom_call" in compiled.as_text()
