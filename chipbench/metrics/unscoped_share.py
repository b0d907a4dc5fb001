"""Share of the device's op time in ops outside every layer scope, in %
(layer: device): the loop's condition and counter, compiler-made copies,
the slicing of the arguments. Moves ``solve_s``. Busiest device by this
share.

Rule: the op's ``op`` has none of the steps ``chipbench.scopes.LAYERS``.
Silent where no op has one (a program without the scopes).
"""

from chipbench import scopes


def read(ctx):
    if ctx.trace is None or not ctx.trace.events:
        return None
    if all(scopes.unscoped(ev) for ev in ctx.trace.events):
        return None
    ops = ctx.trace.time_ns(lambda ev: True)
    out = ctx.trace.time_ns(scopes.unscoped)
    return 100.0 * max(out[d] / ops[d] if ops[d] else 0.0 for d in ops)
