"""Device milliseconds per CG iteration in vector ops and local
reductions (layer: ``kernels/dispatch.py``, ``kernels/fused_reductions.py``
or their jnp forms): every op that is neither the SpMV's nor a collective.
Moves ``solve_s``. Busiest device.

It reads only where the SpMV's rule finds the SpMV: where it does not, the
SpMV's time would count here unseen, so this stays silent with it.
"""

from chipbench.metrics.collective_ms_per_iter import is_collective
from chipbench.metrics.spmv_ms_per_iter import is_spmv


def is_vecop(ev) -> bool:
    return not is_spmv(ev) and not is_collective(ev)


def read(ctx):
    if ctx.trace is None or ctx.iterations == 0:
        return None
    if max(ctx.trace.time_ns(is_spmv).values()) <= 0:
        return None
    busiest = max(ctx.trace.time_ns(is_vecop).values())
    if busiest <= 0:
        return None
    return busiest / 1e6 / ctx.iterations
