"""CG iterations per solve, as the program reports them (``res.iters``;
layer: solver loop, ``core/cg.py`` ``hs``). Moves ``solve_s``."""


def read(ctx):
    if not ctx.answers:
        return None
    return ctx.iterations / len(ctx.answers)
