"""Device milliseconds per CG iteration in the program's ``reductions``
scope (layer: vector ops): the dot products, the fused vector updates and
the ``psum``s of ``core/cg.py``'s ``region("reductions")``. Moves
``solve_s``. Busiest device.

Rule: the op's ``op`` has a step ``reductions`` (``chipbench/scopes.py``).
"""

from chipbench import scopes

is_reduction = scopes.in_scope("reductions")


def read(ctx):
    return scopes.ms_per_iter(ctx, is_reduction)
