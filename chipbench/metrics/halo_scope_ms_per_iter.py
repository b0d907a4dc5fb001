"""Device milliseconds per CG iteration in the program's ``halo`` scope
(layer: collectives): the halo exchange of ``core/spmv.py``, its send
buffers and ``ppermute``s, in either SpMV schedule. Moves ``solve_s``.
Busiest device.

Rule: the op's ``op`` has a step ``halo`` (``chipbench/scopes.py``).
"""

from chipbench import scopes

is_halo = scopes.in_scope("halo")


def read(ctx):
    return scopes.ms_per_iter(ctx, is_halo)
