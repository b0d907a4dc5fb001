"""Host seconds of the set-up call with ``b = 0``: compiling the solve
(``core/cg.solver_handle``) or loading it from the persistent cache, plus
a loop that exits at once (layer: compile). Moves ``setup_s``."""


def read(ctx):
    return ctx.spans.get("compile_s")
