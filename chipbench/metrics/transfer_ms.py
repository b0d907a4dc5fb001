"""Host milliseconds per solve in pad + shard of ``b`` and unpad of ``x``
(layer: entry, the ``SolverSession`` handle call). Moves ``solve_s``."""


def read(ctx):
    if not ctx.answers:
        return None
    return 1e3 * sum(a.transfer_s for a in ctx.answers) / len(ctx.answers)
