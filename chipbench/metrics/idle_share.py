"""Share of the traced window in which no op runs on the device, in %
(layer: device): ``1 - busy union / window``. Moves ``solve_s``. Busiest
device, the one with the least idle time."""


def read(ctx):
    if ctx.trace is None or ctx.trace.window_ns <= 0:
        return None
    busy = max(ctx.trace.busy_ns.values())
    return 100.0 * (1.0 - busy / ctx.trace.window_ns)
