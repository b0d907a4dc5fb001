"""``pallas_call`` whose kernel traces in 32-bit mode.

The solver entry points enable x64 for the whole process, but Mosaic (the
TPU kernel compiler) has no 64-bit types: under x64, Python-int literals in
index maps, ref indices and ``jnp.where`` branches trace as i64 and the
compile fails ("failed to legalize func.return" / "tpu.sitofp"). The
kernels here only ever see f32/i32 operands on the TPU, so their bodies
and index maps are traced with x64 off. Calls with a 64-bit operand (f64
interpret-mode runs on the CPU) keep the process setting, so their
in-kernel constants stay 64-bit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def pallas_call(kernel, **kwargs):
    """Drop-in for :func:`pl.pallas_call` (same arguments)."""
    call = pl.pallas_call(kernel, **kwargs)

    def run(*args):
        if any(jnp.dtype(a.dtype).itemsize == 8
               for a in jax.tree_util.tree_leaves(args)):
            return call(*args)
        with jax.enable_x64(False):
            return call(*args)

    return run
