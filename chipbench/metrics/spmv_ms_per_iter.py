"""Device milliseconds per CG iteration in the SpMV (layer: SpMV,
``core/spmv.py``: the ELL gather ``x[col]`` and the einsum that reduces
it, and on a ring the boundary block's gather, einsum and scatter-add).
Moves ``solve_s``. Busiest device.

Rule: an op is the SpMV's when the JAX op it came from (the event's
``op``, e.g. ``jit(solve)/while/body/rk,rk->r/dot_general``) has a
``gather`` or ``scatter-add`` step or one of the SpMV's einsums
(``rk,rk->r``, ``bk,bk->b`` and their multi-RHS forms): nothing else on
the CG path gathers, scatters or contracts over the nonzeros of a row.
"""

import re

_STEP = re.compile(r"^(gather|scatter|scatter-add|[rb]k,[rb]kc?->[rb]c?)$")


def is_spmv(ev) -> bool:
    return any(_STEP.match(step) for step in ev.op.split("/"))


def read(ctx):
    if ctx.trace is None or ctx.iterations == 0:
        return None
    busiest = max(ctx.trace.time_ns(is_spmv).values())
    if busiest <= 0:
        return None
    return busiest / 1e6 / ctx.iterations
