"""Device milliseconds per CG iteration in collectives (layer:
collectives, the halo ``ppermute`` of ``core/spmv.py`` and the ``psum``
of ``core/vectors.py``). Moves ``solve_s``. Busiest device.

Rule: an op is a collective when its HLO instruction is one of XLA's
collectives (all-reduce, collective-permute, all-gather, reduce-scatter,
all-to-all, in their sync, start and done forms) or it came from a JAX
collective (``psum``, ``ppermute``, ``all_gather``).
"""

import re

_INST = re.compile(
    r"all-reduce|collective-permute|all-gather|reduce-scatter|all-to-all"
)
_STEP = {"psum", "ppermute", "all_gather", "psum_scatter", "all_to_all"}


def is_collective(ev) -> bool:
    return bool(_INST.search(ev.name)) or any(
        step in _STEP for step in ev.op.split("/"))


def read(ctx):
    if ctx.trace is None or ctx.iterations == 0:
        return None
    busiest = max(ctx.trace.time_ns(is_collective).values())
    if busiest <= 0:
        return None
    return busiest / 1e6 / ctx.iterations
