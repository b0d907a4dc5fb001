"""Device milliseconds per CG iteration in the program's ``spmv`` scope
(layer: SpMV): every op traced inside ``region("spmv")`` of ``core/cg.py``,
whatever kernel implements it (on a ring the halo exchange and boundary
scatter-add too). Moves ``solve_s``. Busiest device.

Rule: the op's ``op`` has a step ``spmv`` (``chipbench/scopes.py``).
"""

from chipbench import scopes

is_spmv = scopes.in_scope("spmv")


def read(ctx):
    return scopes.ms_per_iter(ctx, is_spmv)
