"""Share of the device's op time in ops with no JAX op behind them, in %
(layer: device): compiler-made copies and the like, which the layer rules
cannot place and count as vector ops. Moves ``solve_s``. Busiest device
by this share. A traced run above ``trace.MAX_UNATTRIBUTED`` fails."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.events:
        return None
    return 100.0 * ctx.trace.unattributed_share()
